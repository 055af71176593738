"""Numerics observability plane: gradient health + divergence digests.

The telemetry plane (utils/metrics.py) answers "how much / how fast",
the tracing plane (utils/tracing.py) answers "*why* is rank 7 stalled";
this module answers "is the math still *right*" — the failure mode that
today surfaces only as a bad loss curve hours later.  Two layers:

**Per-tensor gradient health.**  ``tensor_stats`` computes L2 norm,
max-abs, nan/inf count, zero fraction and a cheap checksum in one pass
over an already-materialized buffer, entirely on device (pure jnp —
jit-safe per hvdlint HVD007; ad-hoc ``jnp.isnan`` checks elsewhere in
the tree are flagged by HVD009).  The eager flush path feeds each
flush's allreduce tensors through ``NumericsMonitor.observe`` as a
side-product of execution: stats kernels dispatched asynchronously
behind the gradients themselves, ONE host transfer per shape group
once the device catches up, then gauges
(``hvd_grad_norm{tensor}``, EMA-drift), the ``hvd_nonfinite_total``
counter, and the anomaly policy — nan/inf anywhere, or L2 norm more
than ``HOROVOD_NUMERICS_EMA_K`` times its own exponential moving
average.  An anomaly escalates through the standard path:
``numerics_anomaly`` event → trace-id-tagged warning → one flight-
recorder dump (tools/hvd_postmortem.py ranks this evidence above
enqueue asymmetry).

**Cross-rank divergence sentinel.**  Replicas of one logical collective
must hold identical post-allreduce state; silent divergence (bitflips,
a desynced replica, a miscompiled kernel) is invisible to every
existing plane.  Each cycle's per-tensor records fold into a compact
digest (bucketed norms + checksum, ``fold_digest``) that piggybacks on
``CycleRequest.digest`` — same transport pattern as the metrics
snapshot — and the coordinator (ops/negotiation.py ``_numerics_scan``)
compares records across ranks: disagreement beyond
``HOROVOD_NUMERICS_TOLERANCE`` names the divergent rank (the one whose
*local* pre-reduce contribution is the cross-rank outlier — the
reduced copies are redundant, so the outlier's own input is the
evidence), the tensor, and the first bad cycle.

Default-on: the per-tensor statistics are a side-product of the fused
allreduce (a few reductions over a buffer already resident), and the
digest rides a message that is sent anyway; ``HVD_NUMERICS=0`` lands
every call on a shared null object.  Knobs and the verdict
runbook: docs/numerics.md.
"""

import collections
import functools
import threading

from ..common import hvd_logging as log
from ..common.config import env_bool, env_float, env_int
from . import lockdep
from . import metrics as metrics_mod
from . import tracing as tracing_mod

DIGEST_VERSION = 1

# stats_vector layout (one float32 row per tensor; index constants are
# the contract between the device pass and the host-side consumers)
S_L2, S_MAX_ABS, S_NONFINITE, S_ZERO_FRAC, S_CHECKSUM = range(5)

# per-tensor digest record, as it rides the (plain-pickle) CycleRequest
# wire: reduced (post-allreduce) stats first, local (pre-reduce) second.
# Tuples, not dicts: compact under pickle, and the layout is versioned
# by DIGEST_VERSION.
R_RED_L2, R_RED_MAX, R_RED_NONFINITE, R_RED_SUM, \
    R_LOC_L2, R_LOC_MAX, R_LOC_NONFINITE = range(7)

ANOMALY_NONFINITE = "nonfinite"
ANOMALY_NORM_SPIKE = "norm_spike"
ANOMALY_DIVERGENCE = "divergence"

# EMA floor below which the norm-spike policy stays disarmed: an
# all-zero warmup (frozen layers, cleared grads) must not flag the
# first real gradient as an explosion
_EMA_FLOOR = 1e-12


def numerics_enabled():
    """Master gate (HVD_NUMERICS; default on)."""
    return env_bool("NUMERICS", True)


def tolerance():
    """Relative cross-rank disagreement tolerance for digest records
    (HVD_NUMERICS_TOLERANCE). Post-allreduce replicas of one collective
    are normally bit-identical; the tolerance absorbs representation
    rounding in the digest itself."""
    return env_float("NUMERICS_TOLERANCE", 1e-4)


def digest_window():
    """How many recent cycles the coordinator retains digests for
    (HVD_NUMERICS_DIGEST_CYCLES)."""
    return max(1, env_int("NUMERICS_DIGEST_CYCLES", 32))


def tensor_stats(x):
    """One-pass gradient-health stats of one array, on device.

    Pure jnp — traces cleanly under jit (HVD007), so the same helper
    serves the eager flush path and any traced caller. Returns a dict
    of 0-d device arrays: ``l2``/``max_abs``/``checksum`` over the
    *finite* values (a NaN burst must not wipe out the norm gauges that
    describe it), ``nonfinite`` the nan/inf count, ``zero_frac`` the
    exact-zero fraction. Integer inputs have nonfinite == 0 by
    construction."""
    import jax.numpy as jnp
    x = jnp.asarray(x)
    f = x.astype(jnp.float32).reshape(-1)
    if f.size == 0:
        z = jnp.zeros((), jnp.float32)
        return {"l2": z, "max_abs": z, "nonfinite": z, "zero_frac": z,
                "checksum": z}
    finite = jnp.isfinite(f)
    safe = jnp.where(finite, f, 0.0)
    return {
        "l2": jnp.sqrt(jnp.sum(safe * safe)),
        "max_abs": jnp.max(jnp.abs(safe)),
        "nonfinite": (f.size - jnp.sum(finite)).astype(jnp.float32),
        "zero_frac": jnp.mean((f == 0.0).astype(jnp.float32)),
        "checksum": jnp.sum(safe),
    }


def stats_vector(x):
    """``tensor_stats`` packed as one [5] float32 device array (S_*
    layout) so a whole fusion bucket's stats cross the host boundary in
    a single transfer."""
    import jax.numpy as jnp
    s = tensor_stats(x)
    return jnp.stack([s["l2"], s["max_abs"], s["nonfinite"],
                      s["zero_frac"], s["checksum"]])


def _segment_impl(sizes):
    """Build the (pure, traceable) [N] flat -> [n, 5] S_* pass for one
    fixed slice layout.

    XLA-CPU scatter (jax.ops.segment_*) costs ~1 ms per op at bench
    scale, which alone would blow the ≤2% overhead contract; instead,
    when padding is affordable the buffer is gathered into a dense
    [n, max_size] matrix with a static index map and every stat is an
    axis-1 reduction (~50x faster). Degenerate layouts (one huge slice
    beside many tiny ones, where padding would explode memory) fall
    back to cumsum-difference sums plus one sorted segment_max."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = len(sizes)
    counts = np.asarray(sizes, np.float32)
    total = int(sum(sizes))
    max_s = max(sizes) if sizes else 0
    ends = np.cumsum(sizes)
    starts = ends - np.asarray(sizes)

    def _rows(g, mask):
        # mask is None when every row is fully valid (uniform layout)
        finite = (jnp.isfinite(g) if mask is None
                  else jnp.isfinite(g) & mask)
        zero = (g == 0.0) if mask is None else (g == 0.0) & mask
        safe = jnp.where(finite, g, 0.0)
        return jnp.stack([
            jnp.sqrt(jnp.sum(safe * safe, axis=1)),
            jnp.max(jnp.abs(safe), axis=1) if max_s else
            jnp.zeros((n,), jnp.float32),
            counts - jnp.sum(finite.astype(jnp.float32), axis=1),
            jnp.sum(zero, axis=1).astype(jnp.float32) /
            jnp.maximum(counts, 1.0),
            jnp.sum(safe, axis=1),
        ], axis=1)

    if n and max_s and min(sizes) == max_s:
        # uniform layout (the common case: one model's equally-shaped
        # gradient shards): a plain reshape views the buffer as the
        # dense matrix — no gather copy, no padding mask
        def impl(flat):
            f = jnp.reshape(flat, (-1,)).astype(jnp.float32)
            return _rows(f.reshape(n, max_s), None)

        return impl

    if n * max_s <= max(4 * total, 4096):
        idx = np.minimum(starts[:, None] + np.arange(max_s)[None, :],
                         max(total - 1, 0))
        mask = np.arange(max_s)[None, :] < np.asarray(sizes)[:, None]

        def impl(flat):
            f = jnp.reshape(flat, (-1,)).astype(jnp.float32)
            return _rows(f[idx], mask)

        return impl

    ids = np.repeat(np.arange(n), sizes)

    def impl(flat):
        f = jnp.reshape(flat, (-1,)).astype(jnp.float32)
        finite = jnp.isfinite(f)
        safe = jnp.where(finite, f, 0.0)

        def seg_sum(v):
            c = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                                 jnp.cumsum(v)])
            return c[ends] - c[starts]

        max_abs = jax.ops.segment_max(jnp.abs(safe), ids, num_segments=n,
                                      indices_are_sorted=True)
        return jnp.stack([
            jnp.sqrt(jnp.maximum(seg_sum(safe * safe), 0.0)),
            # empty segments reduce to -inf under segment_max
            jnp.where(jnp.isfinite(max_abs), max_abs, 0.0),
            counts - seg_sum(finite.astype(jnp.float32)),
            seg_sum((f == 0.0).astype(jnp.float32)) /
            jnp.maximum(counts, 1.0),
            seg_sum(safe),
        ], axis=1)

    return impl


@functools.lru_cache(maxsize=256)
def _segment_stats_fn(sizes):
    """Compiled ``_segment_impl`` for one slice layout. The flush path
    sees the SAME fusion plan every step, so steady state is one cached
    XLA call per bucket side."""
    import jax
    return jax.jit(_segment_impl(sizes))


def segment_stats(flat, sizes):
    """Per-slice S_* stats of an already-materialized fused buffer.

    ONE pass over the whole bucket instead of one reduction per slice —
    the fused side-product the flush path wants
    (``ops/fusion.bucket_stats`` is the fusion-plane entry). ``sizes``
    are the static per-tensor element counts in buffer order; returns
    an [n, 5] float32 device matrix (rows follow ``sizes``). Compiled
    per slice layout; calling it inside a traced function inlines."""
    import jax.numpy as jnp
    return _segment_stats_fn(
        tuple(int(s) for s in sizes))(jnp.asarray(flat))


@functools.lru_cache(maxsize=64)
def _group_stats_fn(nargs, shape):
    """Compiled fixed-arity kernel: ``nargs`` same-shape arrays in,
    [nargs, 5] S_* rows out. Keyed on (arity, shape) only — never on a
    batch composition — because the local flush path's batch splits
    are nondeterministic (the background drain races the enqueue
    burst): keying a kernel on the per-flush layout compiles a fresh
    XLA program for nearly every flush, ~100 ms each, which is how a
    "cheap" stats pass becomes 25x the step it observes. Fixed arity
    also keeps the whole stack+stats inside ONE dispatch: an eager
    ``jnp.stack`` over k operands costs a device op per operand, ~4 ms
    where this call costs ~1."""
    import jax
    import jax.numpy as jnp
    size = _size_of(shape)
    counts = float(size)

    def impl(*xs):
        g = jnp.stack([jnp.reshape(x, (-1,)).astype(jnp.float32)
                       for x in xs])
        finite = jnp.isfinite(g)
        safe = jnp.where(finite, g, 0.0)
        return jnp.stack([
            jnp.sqrt(jnp.sum(safe * safe, axis=1)),
            jnp.max(jnp.abs(safe), axis=1) if size else
            jnp.zeros((nargs,), jnp.float32),
            counts - jnp.sum(finite.astype(jnp.float32), axis=1),
            jnp.sum(g == 0.0, axis=1).astype(jnp.float32) /
            max(counts, 1.0),
            jnp.sum(safe, axis=1),
        ], axis=1)

    return jax.jit(impl)


@functools.lru_cache(maxsize=64)
def _zero_of(shape):
    import jax.numpy as jnp
    return jnp.zeros(shape, jnp.float32)


def _batch_stats_groups(arrays):
    """Dispatch the stats kernels for one side of an observed batch:
    yields ``(idxs, k, dev_mat)`` per (shape, dtype) group, where
    ``dev_mat`` is an UNFORCED [pow2, 5] device array (the kernel runs
    asynchronously behind whatever compute produced the gradients).

    Arrays are grouped by (shape, dtype); each group calls the
    fixed-arity kernel for the next power-of-two arity, padding the
    argument list with a cached zero array. However the racy flush
    splits a step's tensors across batches, the process compiles a
    bounded set of kernels (one per tensor shape x pow2 group size)
    instead of one per split; the all-zero padding rows are sliced off
    before any policy sees them."""
    groups = {}
    for i, a in enumerate(arrays):
        groups.setdefault((a.shape, a.dtype.num), []).append(i)
    for (shape, _), idxs in groups.items():
        k = len(idxs)
        pow2 = 1 << (k - 1).bit_length()
        args = [arrays[i] for i in idxs]
        if pow2 != k:
            args.extend([_zero_of(shape)] * (pow2 - k))
        yield idxs, k, _group_stats_fn(pow2, shape)(*args)


def _batch_stats(arrays):
    """[n, 5] S_* host matrix for one side of an observed batch
    (blocking form of ``_batch_stats_groups``)."""
    import numpy as np
    out = np.empty((len(arrays), 5), np.float32)
    for idxs, k, dev in _batch_stats_groups(arrays):
        out[idxs] = np.asarray(dev)[:k]
    return out


def _dev_ready(a):
    """Has this device array's async computation completed?"""
    try:
        return a.is_ready()
    except AttributeError:  # plain numpy / older jax
        return True


def _size_of(shape):
    size = 1
    for d in shape:
        size *= int(d)
    return size


def _round(v):
    # digest stability: two ranks computing the same value must encode
    # the same number, so records are rounded to 6 significant digits
    # before they touch the wire (well inside the default tolerance)
    return float(f"{float(v):.6g}")


def make_record(reduced_row, local_row):
    """One wire digest record (R_* layout) from two S_* stats rows."""
    return (_round(reduced_row[S_L2]), _round(reduced_row[S_MAX_ABS]),
            int(reduced_row[S_NONFINITE]), _round(reduced_row[S_CHECKSUM]),
            _round(local_row[S_L2]), _round(local_row[S_MAX_ABS]),
            int(local_row[S_NONFINITE]))


def fold_digest(pending, cycle, records, rank=None):
    """Fold one cycle's records into the digest awaiting piggyback.
    Several response seqs can execute between two negotiation cycles;
    they all ride the next CycleRequest as one payload."""
    if not records:
        return pending
    if pending is None:
        pending = {"v": DIGEST_VERSION, "rank": rank, "cycles": {}}
    pending["cycles"].setdefault(int(cycle), {}).update(records)
    return pending


def records_disagree(a, b, tol=None):
    """Do two ranks' records for the same (cycle, tensor) describe
    different post-allreduce states? Relative comparison on the reduced
    norm, max-abs and checksum; any nonfinite-count mismatch is an
    immediate disagreement."""
    if tol is None:
        tol = tolerance()
    if int(a[R_RED_NONFINITE]) != int(b[R_RED_NONFINITE]):
        return True
    for idx in (R_RED_L2, R_RED_MAX, R_RED_SUM):
        x, y = float(a[idx]), float(b[idx])
        if abs(x - y) > tol * max(abs(x), abs(y), 1.0):
            return True
    return False


def blame_rank(records_by_rank):
    """Name the divergent rank among disagreeing replicas.

    Post-allreduce copies are redundant, so the outlier's own *local*
    contribution is the evidence: a rank whose pre-reduce stats carry
    nonfinites is blamed outright; otherwise the rank whose local L2
    deviates most from the cross-rank median. Deterministic (sorted
    ranks, first-wins tie break) so every consumer names the same
    culprit."""
    ranks = sorted(records_by_rank)
    if not ranks:
        return None
    for r in ranks:
        if int(records_by_rank[r][R_LOC_NONFINITE]) > 0:
            return r
    locs = sorted(float(records_by_rank[r][R_LOC_L2]) for r in ranks)
    mid = len(locs) // 2
    med = locs[mid] if len(locs) % 2 else (locs[mid - 1] + locs[mid]) / 2.0
    best, best_dev = ranks[0], -1.0
    for r in ranks:
        dev = abs(float(records_by_rank[r][R_LOC_L2]) - med)
        if dev > best_dev:
            best, best_dev = r, dev
    return best


class NumericsMonitor:
    """Per-rank gradient-health observer + digest builder.

    Mirrors the metrics/tracing lifecycle: module singleton via
    ``get_monitor()``/``reset()``, null object when HVD_NUMERICS=0.
    ``observe`` is the only hot-path entry point — called once per
    executed flush from the eager background thread, never from traced
    code (the device passes themselves, ``stats_vector`` /
    ``segment_stats`` / ``_group_stats_fn``, are the jit-safe part)."""

    def __init__(self, ema_beta=None, ema_k=None, warmup=None):
        self._beta = (ema_beta if ema_beta is not None
                      else env_float("NUMERICS_EMA_BETA", 0.9))
        self._ema_k = (ema_k if ema_k is not None
                       else env_float("NUMERICS_EMA_K", 8.0))
        self._warmup = (warmup if warmup is not None
                        else env_int("NUMERICS_WARMUP", 5))
        self._lock = lockdep.lock("NumericsMonitor._lock")
        self._ema = {}        # guarded_by: _lock; tensor -> EMA of L2 norm
        self._obs = {}        # guarded_by: _lock; tensor -> observation count
        self._children = {}   # guarded_by: _lock; tensor -> gauge children
        # parked async results: (names, k, unforced device [pow2, 5])
        self._pending_lock = lockdep.lock("NumericsMonitor._pending_lock")
        self._parked = collections.deque()  # guarded_by: _pending_lock
        self._flagged = set()  # guarded_by: _lock; one event per (tensor, kind)
        self._dumped = False   # guarded_by: _lock; one flight dump per process
        reg = metrics_mod.get_registry()
        self._m_norm = reg.gauge(
            "hvd_grad_norm",
            "L2 norm of this rank's latest pre-reduce contribution, by "
            "tensor.", labels=("tensor",))
        self._m_max = reg.gauge(
            "hvd_grad_max_abs",
            "Max |value| of the latest pre-reduce contribution, by "
            "tensor.", labels=("tensor",))
        self._m_zero = reg.gauge(
            "hvd_grad_zero_fraction",
            "Exact-zero fraction of the latest pre-reduce contribution, "
            "by tensor.", labels=("tensor",))
        self._m_ema = reg.gauge(
            "hvd_grad_norm_ema",
            "Exponential moving average of hvd_grad_norm (the norm-spike "
            "policy baseline).", labels=("tensor",))
        self._m_drift = reg.gauge(
            "hvd_grad_norm_drift",
            "hvd_grad_norm / its EMA — the spike policy trips past "
            "HVD_NUMERICS_EMA_K.", labels=("tensor",))
        self._m_nonfinite = reg.counter(
            "hvd_nonfinite_total",
            "NaN/Inf values seen in gradient buffers, by tensor and "
            "side (local = this rank's contribution, reduced = "
            "post-allreduce).", labels=("tensor", "where"))
        self._m_anomalies = reg.counter(
            "hvd_numerics_anomalies_total",
            "Numerics anomalies flagged by the health policy or the "
            "divergence sentinel, by kind.", labels=("kind",))
        self._m_observed = reg.counter(
            "hvd_numerics_tensors_observed_total",
            "Tensors that went through the gradient-health pass.")

    @property
    def enabled(self):
        return True

    def observe(self, items, cycle=None):
        """Gradient-health pass over one executed flush.

        ``items``: [(name, local, reduced-or-None)] — the pre-reduce
        contribution and the post-allreduce result. Computes every
        stats row on device (one fixed-arity kernel per shape group),
        updates the gauges/EMA policy, and returns the wire records
        dict {name: R_* tuple} for ``fold_digest``. The local path
        (``cycle=None``, all reduced ``None``) is fully asynchronous
        and returns ``{}`` immediately; the digest path blocks, because
        its records must describe this cycle."""
        if not items:
            return {}
        import numpy as np
        names = [name for name, _, _ in items]
        if cycle is None and all(r is None for _, _, r in items):
            # local flush (no digest, no distinct reduced side: the
            # single-process reduced copy IS the local contribution).
            # Forcing the stats here would park the flush thread behind
            # whatever device compute produced the gradients — so the
            # kernels are dispatched asynchronously and the results
            # ingest on a later observe, once the device has caught up.
            # Gauges and the anomaly policy lag the flush by one drain;
            # ``drain()`` forces the tail.
            locs = [l for _, l, _ in items]
            parked = [([names[i] for i in idxs], k, dev)
                      for idxs, k, dev in _batch_stats_groups(locs)]
            with self._pending_lock:
                self._parked.extend(parked)
                backlog = len(self._parked)
            # bounded parking: a device that never catches up must not
            # grow the queue without limit
            self._drain(block=backlog > 64)
            return {}
        # digest path: the records must describe THIS cycle, so force
        # parked work first (EMA order), then block on this batch
        self._drain(block=True)
        loc = _batch_stats([l for _, l, _ in items])
        if all(r is None for _, _, r in items):
            return self.ingest(names, loc, cycle=cycle)
        # a missing reduced side on an otherwise-reduced bucket reuses
        # the local array: rv == lv by construction
        red = _batch_stats([r if r is not None else l
                            for _, l, r in items])
        return self.ingest(names, np.concatenate([red, loc], axis=1),
                           cycle=cycle)

    def drain(self):
        """Force-ingest every parked async stats result (tests, clean
        shutdown, and anyone about to read the gauges)."""
        self._drain(block=True)

    def _drain(self, block):
        import numpy as np
        while True:
            with self._pending_lock:
                if not self._parked:
                    return
                gnames, k, dev = self._parked[0]
                # FIFO readiness: later entries were dispatched later,
                # so the head not being ready means nothing after it is
                if not block and not _dev_ready(dev):
                    return
                self._parked.popleft()
            self.ingest(gnames, np.asarray(dev)[:k])

    def ingest(self, names, mat, cycle=None):
        """Policy half of ``observe``: ``mat`` is an [n, 10] stats
        matrix — reduced S_* columns then local S_* columns, e.g. two
        ``segment_stats`` halves from an already-fused buffer
        (ops/fusion.bucket_stats) — or [n, 5] when the two sides are
        one and the same (single-process flush). Crosses the host
        boundary here, once per bucket. Wire records are built only
        when a ``cycle`` key is given: nothing folds a digest without
        one, and the rounding pass is pure waste on the local path."""
        import numpy as np
        # one host transfer per bucket, then tolist(): the loop below is
        # on the flush path and indexing a Python list row is ~10x
        # cheaper than pulling np scalars out one float at a time
        rows = np.asarray(mat).tolist()
        want_records = cycle is not None
        records = {}
        anomalies = []
        with self._lock:
            for name, row in zip(names, rows):
                red = row[:5]
                loc = row[5:] if len(row) > 5 else red
                if want_records:
                    records[name] = make_record(red, loc)
                loc_l2 = loc[S_L2]
                ch = self._children.get(name)
                if ch is None:
                    ch = (self._m_norm.labels(tensor=name),
                          self._m_max.labels(tensor=name),
                          self._m_zero.labels(tensor=name),
                          self._m_ema.labels(tensor=name),
                          self._m_drift.labels(tensor=name))
                    self._children[name] = ch
                ch[0].set(loc_l2)
                ch[1].set(loc[S_MAX_ABS])
                ch[2].set(loc[S_ZERO_FRAC])
                nf_loc = int(loc[S_NONFINITE])
                nf_red = int(red[S_NONFINITE])
                if nf_loc:
                    self._m_nonfinite.labels(
                        tensor=name, where="local").inc(nf_loc)
                if nf_red:
                    self._m_nonfinite.labels(
                        tensor=name, where="reduced").inc(nf_red)
                ema = self._ema.get(name)
                seen = self._obs.get(name, 0)
                if nf_loc or nf_red:
                    anomalies.append((ANOMALY_NONFINITE, name, {
                        "nonfinite_local": nf_loc,
                        "nonfinite_reduced": nf_red}))
                elif (ema is not None and seen >= self._warmup and
                        ema > _EMA_FLOOR and loc_l2 > self._ema_k * ema):
                    anomalies.append((ANOMALY_NORM_SPIKE, name, {
                        "l2": loc_l2, "ema": _round(ema),
                        "k": self._ema_k}))
                ema = (loc_l2 if ema is None
                       else self._beta * ema + (1.0 - self._beta) * loc_l2)
                self._ema[name] = ema
                self._obs[name] = seen + 1
                ch[3].set(ema)
                ch[4].set(loc_l2 / ema if ema > _EMA_FLOOR else 1.0)
            self._m_observed.inc(len(names))
        for kind, name, detail in anomalies:
            self._flag(kind, name, cycle, detail)
        return records

    def observe_compression(self, name, before, after, compressor):
        """Pre/post-compression norm delta (the error-feedback dashboard
        the quantized-collectives work will A/B against). Host-side only
        — the compressor's compress() itself must stay jit-pure."""
        import numpy as np
        # both rows as ONE program. Op by op they are some thirty programs
        # over a stacked buffer (a row a device), ten of them cross-device
        # reductions, all in flight at once, and under load XLA's CPU
        # client then stops for good, every thread of the process waiting:
        # a worker that is alive and never reports (ROADMAP D13 (i); plain
        # JAX on eight virtual devices does the same, and neither one
        # program nor a wait after each reduction does)
        row = np.asarray(_group_stats_fn(2, tuple(np.shape(before)))(
            before, after.astype(before.dtype)))
        pre, post = float(row[0][S_L2]), float(row[1][S_L2])
        reg = metrics_mod.get_registry()
        reg.gauge(
            "hvd_compression_norm_delta",
            "Relative L2 norm lost to wire compression "
            "(|pre - post| / pre), by tensor and compressor.",
            labels=("tensor", "compressor")).labels(
            tensor=name, compressor=compressor).set(
            abs(pre - post) / pre if pre > 0.0 else 0.0)
        reg.counter(
            "hvd_compressed_tensors_total",
            "Tensors that went through a lossy wire compressor.",
            labels=("compressor",)).labels(compressor=compressor).inc()

    def _flag(self, kind, tensor, cycle, detail):
        """Escalate one anomaly through the standard path: structured
        event → trace-id-tagged warning → one flight dump. Deduped per
        (tensor, kind) so a persistent condition cannot flood the event
        ring the postmortem reads."""
        with self._lock:
            if (tensor, kind) in self._flagged:
                return
            self._flagged.add((tensor, kind))
            first_dump = not self._dumped
            self._dumped = True
        reg = metrics_mod.get_registry()
        tracer = tracing_mod.get_tracer()
        trace_id = tracer.trace_id_for(tensor)
        self._m_anomalies.labels(kind=kind).inc()
        reg.event("numerics_anomaly", anomaly=kind, tensor=tensor,
                  rank=reg.rank, cycle=cycle, trace_id=trace_id, **detail)
        log.warning(
            "numerics: %s anomaly on tensor '%s' (rank %s, cycle %s, "
            "trace %s): %s", kind, tensor, reg.rank, cycle, trace_id,
            detail)
        if first_dump:
            tracer.dump("numerics_anomaly")


class NullMonitor:
    """HVD_NUMERICS=0: every call is a no-op."""

    enabled = False

    def observe(self, items, cycle=None):
        return {}

    def ingest(self, names, mat, cycle=None):
        return {}

    def drain(self):
        return None

    def observe_compression(self, name, before, after, compressor):
        return None


_monitor = None  # guarded_by: _monitor_lock
_monitor_lock = lockdep.lock("numerics._monitor_lock")


def get_monitor():
    """The process-wide monitor (created on first use; HVD_NUMERICS=0
    yields a no-op monitor)."""
    global _monitor
    # hvdlint: disable=HVD021(double-checked init fast path; the slow path re-reads under _monitor_lock before publishing)
    m = _monitor
    if m is None:
        with _monitor_lock:
            if _monitor is None:
                _monitor = (NumericsMonitor() if numerics_enabled()
                            else NullMonitor())
            m = _monitor
    return m


def reset(enabled=None, **knobs):
    """Replace the process monitor (tests; re-init after env changes).
    ``enabled``: None re-reads HVD_NUMERICS, True/False forces."""
    global _monitor
    with _monitor_lock:
        if enabled is None:
            _monitor = None
        else:
            _monitor = (NumericsMonitor(**knobs) if enabled
                        else NullMonitor())
            return _monitor
    return get_monitor()
