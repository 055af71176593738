"""Memory & compile observability plane (docs/memory.md).

PR 17 made per-chip HBM — not step time — the binding constraint, and
this module is the repo's one answer to the three questions the mesh
era makes routine:

  * **where did the bytes go** — an :class:`HBMLedger` attributing
    per-chip device bytes by component (params, optimizer state,
    gradients, KV-cache blocks, activation estimate), published as
    ``hvd_hbm_bytes{component}`` / ``hvd_hbm_headroom_bytes`` gauges,
    snapshotted into flight dumps and rendered by hvd_top;
  * **why did this step recompile** — a :class:`CompileTracker` that
    turns every instrumented jit call into a cache hit/miss with the
    abstract-shape key that missed, plus an EMA recompile-storm
    detector escalating event → warning → flight dump (deduped per
    site) so a leaking shape polymorphism is *named*, not felt;
  * **did GSPMD silently reshard a param** — :func:`scan_resharding`,
    an HLO-text sentinel that flags all-gather / collective-permute
    ops whose shapes match a *parameter leaf* being undone against its
    declared spec, and names the leaf and the mesh axis.

Attribution is host-side math over tree metadata and declared specs —
the same philosophy as the serving BlockLedger: the accountant never
touches the device. The only sanctioned device probes
(``device.memory_stats``, ``jax.live_arrays``) live here, enforced by
hvdlint HVD020 everywhere else in trainer/serving/ops.

``tools/hvd_mem.py`` fronts the pre-flight planner
(:func:`plan_memory` — "does this model fit at dp=2,tp=4 on v5e?"
from the costmodel ChipSpec table) and a CI selftest.
"""

import logging
import math
import re
import threading

from ..common.config import env_bool, env_float, env_int
from . import lockdep

log = logging.getLogger("horovod_tpu.memory")

# Ledger component keys, in the order panes render them.
COMPONENTS = ("params", "opt_state", "grads", "kv_cache", "activations",
              "other")

_lock = lockdep.rlock("memory._lock")
_enabled = None  # guarded_by: _lock; cached HVD_MEM switch
_ledger = None   # guarded_by: _lock
_tracker = None  # guarded_by: _lock


def enabled():
    """Master switch (HVD_MEM, default on). Cached; reset() re-reads."""
    global _enabled
    with _lock:
        if _enabled is None:
            _enabled = env_bool("MEM", True)
        return _enabled


def reset(enabled=None):
    """Drop the process ledger/tracker singletons (tests).

    ``enabled`` forces the plane on/off regardless of HVD_MEM; None
    re-reads the environment on next use.
    """
    global _enabled, _ledger, _tracker
    with _lock:
        _enabled = enabled
        _ledger = None
        _tracker = None


def get_ledger():
    global _ledger
    with _lock:
        if _ledger is None:
            _ledger = HBMLedger()
        return _ledger


def get_tracker():
    global _tracker
    with _lock:
        if _tracker is None:
            _tracker = CompileTracker()
        return _tracker


# ---------------------------------------------------------------------------
# device probes — the ONLY sanctioned call sites (hvdlint HVD020)
# ---------------------------------------------------------------------------

def device_memory_stats(device=None):
    """``device.memory_stats()`` for one device, or None when the
    backend doesn't expose it (CPU, some forwarded runtimes)."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = getattr(device, "memory_stats", None)
        if stats is None:
            return None
        return stats() or None
    # hvdlint: disable=HVD006(probe is best-effort telemetry; absence of stats is the None contract, never an error)
    except Exception:  # noqa: BLE001
        return None


def step_peak_bytes(device=None):
    """Peak device bytes: ``peak_bytes_in_use + peak_bytes_reserved``, or
    None on backends without allocator stats — the trainer nulls its
    ``hvd_step_peak_hbm_bytes`` gauge exactly like the CPU MFU gauge.
    On the v5e runtime the first field counts arrays (arguments,
    results) and leaves out the scratch that loaded programs reserve
    for their temporaries, which the second counts: the LM step reads
    7.29 GB + 3.58 GB where ``memory_analysis()`` declares 7.27 GB of
    arguments and 3.64 GB of temporaries (chip run, PR 23; PERF.md §6).
    ``benchmarks/run.py:peak_bytes`` sums the same two."""
    stats = device_memory_stats(device)
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
    if peak is None:
        return None
    return int(peak) + int(stats.get("peak_bytes_reserved", 0))


def live_array_bytes():
    """Total bytes of live jax arrays on this process's default device,
    per-shard (what this chip actually holds). None if unavailable."""
    try:
        import jax
        total = 0
        for arr in jax.live_arrays():
            total += _per_chip_nbytes(arr)
        return total
    # hvdlint: disable=HVD006(best-effort telemetry probe; a backend without live_arrays reports None, never raises)
    except Exception:  # noqa: BLE001
        return None


# ---------------------------------------------------------------------------
# byte attribution (host-side math, no device traffic)
# ---------------------------------------------------------------------------

def _per_chip_nbytes(leaf):
    """Bytes one chip holds for a leaf: the shard shape when sharded
    (same contract as KVCache.per_chip_bytes), the full shape else."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return 0
    dtype = getattr(leaf, "dtype", None)
    itemsize = getattr(dtype, "itemsize", None)
    if itemsize is None:
        try:
            import numpy as np
            itemsize = np.dtype(dtype).itemsize
        # hvdlint: disable=HVD006(unsizeable leaf contributes 0 bytes by contract; the ledger is an estimate, not an allocator)
        except Exception:  # noqa: BLE001
            return 0
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None and hasattr(sharding, "shard_shape"):
        try:
            shape = sharding.shard_shape(tuple(shape))
        # hvdlint: disable=HVD006(abstract leaves have no committed layout; full-shape bytes are the documented fallback)
        except Exception:  # noqa: BLE001
            pass
    return int(math.prod(shape)) * int(itemsize)


def spec_shard_shape(shape, spec, mesh):
    """Shard shape of ``shape`` under a PartitionSpec on ``mesh`` —
    delegates to the mesh module's axis-size math (the one home for
    mesh arithmetic, HVD019 spirit) so abstract (eval_shape) leaves
    shard exactly like committed arrays."""
    if spec is None or mesh is None:
        return tuple(shape)
    from ..parallel import mesh as mesh_lib
    return mesh_lib.spec_shard_shape(shape, spec, mesh)


def tree_per_chip_bytes(tree, spec_tree=None, mesh=None):
    """Per-chip bytes of a pytree. Concrete arrays use their committed
    sharding; abstract leaves (ShapeDtypeStruct) use ``spec_tree`` +
    ``mesh`` math; leaves with neither count their full shape."""
    import jax

    if spec_tree is None:
        return sum(_per_chip_nbytes(leaf)
                   for leaf in jax.tree_util.tree_leaves(tree))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = treedef.flatten_up_to(spec_tree)
    total = 0
    for leaf, spec in zip(leaves, specs):
        shape = getattr(leaf, "shape", None)
        if shape is None:
            continue
        dtype = getattr(leaf, "dtype", None)
        itemsize = getattr(dtype, "itemsize", 4)
        shard = spec_shard_shape(tuple(shape), spec, mesh)
        total += int(math.prod(shard)) * int(itemsize)
    return total


# ---------------------------------------------------------------------------
# the per-chip HBM ledger
# ---------------------------------------------------------------------------

class HBMLedger:
    """Attributes per-chip device bytes by component and publishes the
    ``hvd_hbm_bytes{component}`` / ``hvd_hbm_headroom_bytes`` gauges.

    Components are *absolute* (account() overwrites, it does not
    accumulate): each plane re-states what it holds — params on
    placement and on every weight swap, kv_cache at engine build,
    activations whenever the costmodel estimate changes. Capacity comes
    from the costmodel ChipSpec table (per-generation HBM GiB; the cpu
    row carries a stand-in so the whole path exercises on CPU CI).
    """

    def __init__(self, capacity_bytes=None):
        self._components = {}
        self._capacity = (capacity_bytes if capacity_bytes is not None
                          else self._detect_capacity())

    @staticmethod
    def _detect_capacity():
        import jax

        from . import costmodel
        device = jax.devices()[0]
        spec = costmodel.chip_spec(device)
        if spec is None:
            # a ledger without capacity still attributes bytes, only
            # headroom is absent — but an unknown chip is said out loud
            log.warning(
                "costmodel.CHIP_SPECS has no row for device_kind %r: the "
                "HBM ledger publishes no capacity or headroom",
                device.device_kind)
            return None
        return spec.hbm_capacity_bytes

    @property
    def capacity_bytes(self):
        return self._capacity

    def account(self, component, nbytes):
        """State the per-chip bytes a component currently holds."""
        with _lock:
            self._components[str(component)] = max(0, int(nbytes))
        self.publish()

    def account_tree(self, component, tree, spec_tree=None, mesh=None):
        self.account(component,
                     tree_per_chip_bytes(tree, spec_tree, mesh))

    def account_kv(self, kv_cache):
        """Ride KVCache.per_chip_bytes() — the serving plane's own
        shard-aware accountant."""
        self.account("kv_cache", kv_cache.per_chip_bytes())

    def set_activation_estimate(self, nbytes):
        self.account("activations", nbytes)

    def total_bytes(self):
        with _lock:
            return sum(self._components.values())

    def headroom_bytes(self):
        if self._capacity is None:
            return None
        return self._capacity - self.total_bytes()

    def snapshot(self):
        """Flight-dump / hvd_mem section: components + capacity math +
        the measured allocator view (None off-TPU) for validation."""
        with _lock:
            components = dict(self._components)
        stats = device_memory_stats()
        return {
            "components": components,
            "total_bytes": sum(components.values()),
            "capacity_bytes": self._capacity,
            "headroom_bytes": self.headroom_bytes(),
            "measured_bytes_in_use": (stats or {}).get("bytes_in_use"),
            "measured_peak_bytes": (stats or {}).get("peak_bytes_in_use"),
        }

    def publish(self):
        """Refresh the gauges; a no-op under NullRegistry."""
        from . import metrics as hvd_metrics
        reg = hvd_metrics.get_registry()
        if not reg.enabled:
            return
        g = reg.gauge("hvd_hbm_bytes",
                      "Attributed per-chip HBM bytes by component",
                      labels=("component",))
        with _lock:
            items = sorted(self._components.items())
        for component, nbytes in items:
            g.labels(component=component).set(nbytes)
        if self._capacity is not None:
            reg.gauge("hvd_hbm_capacity_bytes",
                      "Per-chip HBM capacity (ChipSpec table)").set(
                          self._capacity)
            reg.gauge("hvd_hbm_headroom_bytes",
                      "Capacity minus attributed bytes").set(
                          self.headroom_bytes())


# ---------------------------------------------------------------------------
# compile observability: hit/miss tracking + recompile-storm escalation
# ---------------------------------------------------------------------------

def abstract_key(args):
    """The abstract-shape key a jit cache would miss on: every leaf's
    dtype+shape, in tree order. Returns (hashable, human) — the
    hashable tuple is computed on every call (cheap: no string work),
    the human string only renders on a miss."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return tuple(
        (str(getattr(leaf, "dtype", type(leaf).__name__)),
         tuple(getattr(leaf, "shape", ())))
        for leaf in leaves)


def format_key(key, max_leaves=8):
    parts = [f"{dt}[{','.join(str(d) for d in shape)}]"
             for dt, shape in key[:max_leaves]]
    if len(key) > max_leaves:
        parts.append(f"...+{len(key) - max_leaves}")
    return " ".join(parts) or "()"


class CompileTracker:
    """Per-site jit cache hit/miss accounting with an EMA storm ladder.

    Each instrumented site (``train:<loop>``, ``serve_prefill``,
    ``serve_decode``) reports its call's abstract-shape key; a key this
    site has never seen is a presumed compile miss. Misses feed a
    per-site EMA of the miss rate (decay HVD_MEM_STORM_DECAY); when the
    EMA crosses HVD_MEM_STORM_EMA with at least HVD_MEM_STORM_MIN
    misses, the site is in a *recompile storm* and the ladder fires
    once per site: ``recompile_storm`` event + warning naming the site
    and the churning key, then a flight dump tagged
    ``recompile_storm`` (deduped — one dump per site per process).
    The first miss at a site is free: one compile is what jit costs.
    """

    def __init__(self, decay=None, threshold=None, min_misses=None):
        self._decay = (decay if decay is not None
                       else env_float("MEM_STORM_DECAY", 0.8))
        self._threshold = (threshold if threshold is not None
                           else env_float("MEM_STORM_EMA", 0.5))
        self._min_misses = (min_misses if min_misses is not None
                            else env_int("MEM_STORM_MIN", 3))
        self._sites = {}

    def _site(self, site):
        entry = self._sites.get(site)
        if entry is None:
            entry = {"keys": set(), "hits": 0, "misses": 0, "ema": 0.0,
                     "storming": False, "dumped": False, "last_key": None}
            self._sites[site] = entry
        return entry

    def observe(self, site, args):
        """Record one call at a jit site; returns 'hit' or 'miss'."""
        key = abstract_key(args)
        from . import metrics as hvd_metrics
        reg = hvd_metrics.get_registry()
        with _lock:
            entry = self._site(site)
            miss = key not in entry["keys"]
            if miss:
                entry["keys"].add(key)
                entry["misses"] += 1
                entry["last_key"] = format_key(key)
            else:
                entry["hits"] += 1
            # First compile is jit working as designed — it doesn't
            # feed the storm signal.
            signal = 1.0 if (miss and entry["misses"] > 1) else 0.0
            entry["ema"] = (self._decay * entry["ema"]
                            + (1.0 - self._decay) * signal)
            storm = (entry["misses"] >= self._min_misses
                     and entry["ema"] > self._threshold)
            first_storm = storm and not entry["storming"]
            entry["storming"] = storm
            misses, key_str = entry["misses"], entry["last_key"]
            need_dump = first_storm and not entry["dumped"]
            if need_dump:
                entry["dumped"] = True
        outcome = "miss" if miss else "hit"
        if reg.enabled:
            reg.counter("hvd_compile_total",
                        "Instrumented jit-site calls by cache outcome",
                        labels=("site", "outcome")).labels(
                            site=site, outcome=outcome).inc()
            if miss:
                reg.event("compile_miss", site=site, key=format_key(key))
        if first_storm:
            self._escalate(site, misses, key_str, need_dump, reg)
        return outcome

    def _escalate(self, site, misses, key_str, need_dump, reg):
        # event → trace-tagged warning → flight dump, the PR 7 ladder
        log.warning(
            "recompile storm at jit site %r: %d distinct abstract-shape "
            "keys, last missed key %s — a shape polymorphism is leaking "
            "into this site (docs/memory.md)", site, misses, key_str)
        if reg.enabled:
            reg.counter("hvd_recompile_storms_total",
                        "Recompile storms detected, by jit site",
                        labels=("site",)).labels(site=site).inc()
            reg.event("recompile_storm", site=site, misses=misses,
                      key=key_str)
        if need_dump:
            try:
                from . import tracing as hvd_tracing
                hvd_tracing.get_tracer().dump("recompile_storm")
            # hvdlint: disable=HVD006(the dump is the last rung of a telemetry ladder; a disabled tracer must not break the step that triggered it)
            except Exception:  # noqa: BLE001
                pass

    def site_summary(self):
        with _lock:
            return {
                site: {"hits": e["hits"], "misses": e["misses"],
                       "ema": round(e["ema"], 4),
                       "storming": e["storming"],
                       "last_key": e["last_key"]}
                for site, e in sorted(self._sites.items())}


class instrument_compiles:
    """Wrap a jitted callable so every call reports hit/miss at
    ``site``; attribute access (``.lower`` etc.) passes through."""

    def __init__(self, fn, site):
        self._fn = fn
        self._site = site

    def __call__(self, *args, **kwargs):
        if enabled():
            get_tracker().observe(self._site, (args, kwargs))
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


# ---------------------------------------------------------------------------
# GSPMD resharding sentinel
# ---------------------------------------------------------------------------

# `%all-gather.5 = f32[8,128]{1,0} all-gather(%p), ..., dimensions={0}`
# — post-optimization HLO text, which names its operands without their
# shapes: those come from the operands' own definition lines. We keep
# the parse deliberately dumb: op kind, result shape, operand shapes,
# gather dim.
_HLO_SHAPED_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?([a-z][a-z0-9]*)\[([0-9,]*)\][^=]*?"
    r"\b(all-gather|collective-permute)\(")
_HLO_DEF_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*[a-z][a-z0-9]*\[([0-9,]*)\]")
_HLO_NAME_RE = re.compile(r"%([\w.\-]+)")
_HLO_DIMS_RE = re.compile(r"dimensions=\{(\d+)\}")


def _parse_shape(text):
    return tuple(int(d) for d in text.split(",") if d) if text else ()


def _iter_hlo_collectives(hlo_text):
    lines = hlo_text.splitlines()
    shapes = {m.group(1): _parse_shape(m.group(2))
              for m in map(_HLO_DEF_RE.search, lines) if m}
    for line in lines:
        m = _HLO_SHAPED_OP_RE.search(line)
        if not m:
            continue
        result_shape = _parse_shape(m.group(2))
        op = m.group(3)
        args = line[m.end():].split(")", 1)[0]
        operands = [shapes[name] for name in _HLO_NAME_RE.findall(args)
                    if name in shapes]
        dims = _HLO_DIMS_RE.search(line)
        yield {"op": op, "result_shape": result_shape,
               "operand_shapes": operands,
               "dim": int(dims.group(1)) if dims else None,
               "line": line.strip()}


def _leaf_table(params, spec_tree, mesh):
    """(name, full_shape, declared_shard_shape, spec) per param leaf."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = jax.tree_util.tree_flatten(spec_tree)[0] if spec_tree else []
    if len(specs) != len(leaves):
        specs = treedef.flatten_up_to(spec_tree) if spec_tree else \
            [None] * len(leaves)
    table = []
    for (path, leaf), spec in zip(leaves, specs):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        if not shape:
            continue
        name = jax.tree_util.keystr(path)
        table.append((name, shape,
                      spec_shard_shape(shape, spec, mesh), spec))
    return table


def _axis_for(spec, dim, ratio, mesh):
    """Name the mesh axis a gather undoes: the axis the declared spec
    put on that dim, else any mesh axis whose size matches the ratio."""
    entries = tuple(spec) if spec is not None else ()
    if dim is not None and dim < len(entries) and entries[dim] is not None:
        part = entries[dim]
        names = part if isinstance(part, (tuple, list)) else (part,)
        return "+".join(str(n) for n in names)
    for name, size in (getattr(mesh, "shape", {}) or {}).items():
        if int(size) == ratio:
            return str(name)
    return None


def scan_resharding(hlo_text, params, spec_tree, mesh, site="gspmd_step"):
    """Scan compiled HLO for resharding collectives that undo a declared
    param sharding, and name the offending leaf and mesh axis.

    Precision contract (the clean-spec negative arm): only collectives
    whose *result* shape equals a param leaf's full shape while an
    *operand* shape equals that leaf's declared shard shape are
    flagged — a full-shape gather of something you declared sharded is
    GSPMD undoing your spec every step. Activation collectives
    (all-reduce, batch-shaped gathers) never match a param leaf's
    (full, shard) shape pair and stay silent. A result shape that ALSO
    matches a leaf declared *replicated* is ambiguous — GSPMD
    legitimately gathers such a leaf's sharded update math back to its
    declared replicated layout (the embedding's adam update does
    exactly this) — and ambiguity resolves to silence: the sentinel is
    precision-first, a missed shape-twin beats a false alarm on every
    clean step.
    """
    full_table = _leaf_table(params, spec_tree, mesh)
    table = [row for row in full_table
             if row[1] != row[2]]  # only leaves actually declared sharded
    # full shapes of replicated-by-spec leaves: gathers producing these
    # are explainable as materializing that declared layout
    replicated_fulls = {row[1] for row in full_table if row[1] == row[2]}
    findings = []
    for coll in _iter_hlo_collectives(hlo_text):
        if coll["result_shape"] in replicated_fulls:
            continue
        for name, full, shard, spec in table:
            if coll["result_shape"] != full:
                continue
            if shard not in coll["operand_shapes"]:
                continue
            dim = coll["dim"]
            if dim is None:
                # collective-permute keeps shapes; infer the resharded
                # dim as the first one the declared shard splits
                dim = next((i for i, (f, s) in enumerate(zip(full, shard))
                            if f != s), None)
            ratio = (full[dim] // max(1, shard[dim])
                     if dim is not None and dim < len(full) else 0)
            findings.append({
                "leaf": name, "op": coll["op"],
                "axis": _axis_for(spec, dim, ratio, mesh),
                "dim": dim, "full_shape": list(full),
                "shard_shape": list(shard), "hlo": coll["line"][:200],
            })
            break
    _report_findings(site, findings)
    return findings


def scan_jit_resharding(jitted, args, params, spec_tree, mesh,
                        site="gspmd_step"):
    """Lower+compile a jitted callable and run :func:`scan_resharding`
    on its optimized HLO (``make_gspmd_step`` output, the decode step)."""
    compiled = jitted.lower(*args).compile()
    texts = getattr(compiled, "as_text", None)
    hlo = compiled.as_text() if texts else ""
    return scan_resharding(hlo, params, spec_tree, mesh, site=site)


def _report_findings(site, findings):
    if not findings:
        return
    from . import metrics as hvd_metrics
    reg = hvd_metrics.get_registry()
    for f in findings:
        log.warning(
            "GSPMD resharding sentinel: %s of param %s (axis %s, dim %s)"
            " at site %r — the compiled step gathers a leaf the spec "
            "tree declared sharded (docs/memory.md)", f["op"], f["leaf"],
            f["axis"], f["dim"], site)
        if reg.enabled:
            reg.event("resharding_finding", site=site, leaf=f["leaf"],
                      op=f["op"], axis=f["axis"])
    if reg.enabled:
        reg.counter("hvd_resharding_findings_total",
                    "Param-resharding collectives found in compiled HLO",
                    labels=("site",)).labels(site=site).inc(len(findings))


# ---------------------------------------------------------------------------
# pre-flight planner (tools/hvd_mem --plan)
# ---------------------------------------------------------------------------

def _kv_plan_bytes(cfg, slots, max_len, tp):
    if not slots or not max_len:
        return 0
    import jax.numpy as jnp
    head_dim = cfg.d_model // cfg.num_heads
    heads = cfg.num_heads // tp if tp and cfg.num_heads % tp == 0 \
        else cfg.num_heads  # kv_cache_spec: indivisible heads replicate
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (2 * cfg.num_layers * slots * max_len * heads * head_dim
            * itemsize)


def plan_memory(cfg, *, dp=1, tp=1, sp=1, batch_per_chip=1, seq=None,
                chip=None, optimizer="adam", kv_slots=0, kv_max_len=0):
    """Pre-flight per-chip HBM estimate for a TransformerConfig at a
    dp×tp×sp layout — pure math from the model config, the declared
    param specs, and the ChipSpec table; no devices touched.

    Params/grads per chip come from the abstract param tree sharded by
    ``models.transformer.param_specs`` math; optimizer state is the
    adam 2× (mu+nu, param dtype — the factor ``optimizer='sgd'`` drops
    to 1×); activations ride the costmodel estimate; KV the serving
    dense-cache shape. Validated against the measured ledger in
    tests/test_memory.py (≤15%).
    """
    import jax
    import jax.numpy as jnp

    from ..models import transformer as tr
    from . import costmodel

    seq = seq or min(cfg.max_seq_len, 128)
    abstract = jax.eval_shape(
        lambda rng: tr.TransformerLM(cfg).init(
            rng, jnp.zeros((1, seq), jnp.int32))["params"],
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    specs = tr.param_specs(abstract)
    axis_sizes = {"dp": dp, "tp": tp, "sp": sp}
    mesh = _PlanMesh(axis_sizes)
    params_b = tree_per_chip_bytes(abstract, specs, mesh)
    opt_factor = {"adam": 2, "adamw": 2, "sgd": 1, "none": 0}.get(
        str(optimizer).lower(), 2)
    act_b = costmodel.lm_activation_bytes(cfg, seq, batch_per_chip)
    kv_b = _kv_plan_bytes(cfg, kv_slots, kv_max_len, tp)
    components = {
        "params": params_b,
        "grads": params_b,
        "opt_state": opt_factor * params_b,
        "activations": act_b,
        "kv_cache": kv_b,
    }
    total = sum(components.values())
    spec = costmodel.chip_spec(chip) if chip else None
    capacity = getattr(spec, "hbm_capacity_bytes", None) if spec else None
    return {
        "config": type(cfg).__name__,
        "layout": {"dp": dp, "tp": tp, "sp": sp},
        "batch_per_chip": batch_per_chip, "seq": seq,
        "chip": spec.kind if spec else None,
        "components": components,
        "total_bytes": total,
        "capacity_bytes": capacity,
        "headroom_bytes": capacity - total if capacity else None,
        "fits": (capacity - total > 0) if capacity else None,
    }


class _PlanMesh:
    """Duck-typed stand-in carrying only ``.shape`` (axis sizes) so the
    planner reuses spec_shard_shape without building a device mesh."""

    def __init__(self, axis_sizes):
        self.shape = dict(axis_sizes)


# ---------------------------------------------------------------------------
# flight-dump section
# ---------------------------------------------------------------------------

def flight_section():
    """The ``memory`` section of a flight dump: ledger snapshot +
    per-site compile summary. Never raises; None when the plane is off
    or nothing has been accounted yet."""
    try:
        if not enabled():
            return None
        with _lock:
            # capture the singletons under the lock: a concurrent
            # reset() must not null them between the emptiness check
            # and the snapshot calls below
            ledger, tracker = _ledger, _tracker
            have = (ledger is not None and ledger._components) or \
                (tracker is not None and tracker._sites)
        if not have:
            return None
        section = {}
        if ledger is not None:
            section["hbm"] = ledger.snapshot()
        if tracker is not None:
            section["compile"] = tracker.site_summary()
        return section or None
    # hvdlint: disable=HVD006(flight dumps must land even when the memory plane is mid-teardown; the section is simply absent)
    except Exception:  # noqa: BLE001
        return None
