"""The continuous-batching step loop (docs/serving.md).

One ``step()`` = admit joins, one fused decode over every batch slot,
retire finishers; everything the step runs is launched before anything
of it is read. The device work is shape-static by construction:

  * decode always runs all ``num_slots`` rows — inactive rows compute
    garbage that the host ignores and write garbage into their own
    (inactive) cache rows, which the next prefill overwrites, every
    kind of state whole. Occupancy is data, not shape, so join/retire
    never recompiles.
  * prefill pads each prompt to a KV-block multiple, bounding compile
    variants at max_len / block; causal masking makes the pads inert
    for attention, and a model with a recurrent state is told the true
    length (decode.prefill).
  * exactly ONE host readback per decode pass (the sampled token ids,
    ``_read_unread``) and one per prefill (the first token,
    ``_read_first_tokens``) — the contract hvdlint HVD011 enforces over
    this package; both sites carry the sanctioned disable marker.

The decode step does not wait for the host. The engine has no stop
token: a row ends by its count, its deadline or the block ledger, all
host state that no sampled id can change. So the host knows before it
launches a pass which rows the pass advances, where, and which of them
it finishes; only the ids are unknown, and the next pass takes those
from the device:

  * what a pass feeds on lives on the device. ``_decode_jit`` returns
    the ids it sampled and the positions moved on by one, and both are
    the next call's arguments as they are; temperatures and the set of
    rows stay where they were put. The host writes positions,
    temperatures and rows again only in a step where a row joined or
    left (``_place_rows``); a prefill's first token goes into the ids on
    the device, with its slot write. The sampling key is folded inside
    the program from the engine's key and the host's step count
    (prefills and passes share the count, one each). A step that only
    decodes uploads that count and nothing else. A prefill's key is
    the same fold made on the host (host_key.py): it goes up with the
    dispatch as a host value, as prompt, index and temperature do.
  * a step that admits launches first and reads after. ``_admit``
    dispatches the prefill and the slot write of every request the step
    admits, back to back; ``_decode`` launches its pass over all active
    rows, the new ones among them; only then are the first tokens read,
    in admission order, each followed by its bookkeeping (``ttft_s``
    stamped when the host HAS the token). At most two admissions are
    ever unread (``_UNREAD_ADMISSIONS``): a third reads the first one
    before it launches, with the second one's prefill queued behind, so
    a burst holds two prefills' output rows on the device and not one a
    free slot, and the chip still never waits; and a dispatch that
    compiles (the first prefill of a padded length, the first pass)
    reads them all first, so no first token waits seconds for another
    request's program. The device's order is the
    synchronous one (prefill, slot write, pass), so every token is the
    same token; the host walks the pass's launch path while the chip
    runs the prefill, and wakes up and books the admission while it
    runs the pass, where the chip used to wait for all three. A request
    that asks for one token takes no part in the pass and is retired at
    its read.
  * the read of a pass's ids happens where it delays nothing. After
    launching pass k, ``_decode`` reads it at once if something at the
    boundary waits for it (``_due``: a row gets its last token, a free
    slot could admit a request at the next boundary, two weight
    generations are live) and otherwise returns with it in flight; the
    next ``step()`` launches pass k+1 FIRST and then reads and books
    pass k while the chip runs k+1 (``_read_unread``, one statement
    either way). At most one pass is ever unread. A request therefore
    never finds a second program queued ahead of its prefill: its first
    token comes when the synchronous order would have given it.
  * what is known at launch is booked at launch: a row's count of
    tokens (``_Active.given``), its position and its cache length
    (``kv.ledger.grow``, which can refuse BEFORE the launch: the row
    then goes ``kv_exhausted`` without that pass). The values are
    appended at the read, and a deadline is seen there: a row that blew
    it while a pass was in flight has wasted one row of that pass,
    never got a wrong or an extra token.

The drain policy turns the same engine into the static-batch baseline
(admit only into an idle batch, run the wave to completion) — one code
path, one flag, no drift between the system and its baseline
(examples/serve_lm.py --baseline; tests/test_serving.py holds
continuous batching to at most 2/3 of its steps on the same requests).
"""

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..common import config
from ..common.exceptions import RanksLostError
from ..utils import alerts as hvd_alerts
from ..utils import history as hvd_history
from ..utils import memory as hvd_memory
from ..utils import metrics as hvd_metrics
from ..utils import tracing as hvd_tracing
from . import host_key, tracing as serve_tracing
from .decode import (ROUTED_COUNTS, decode, passes, prefill,
                     prefill_extents)
from .kv_cache import KVCache
from .queue import AdmissionQueue, RequestResult
from .sampling import sample_tokens
from .scheduler import SlotScheduler


log = logging.getLogger("horovod_tpu.serving")


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill_jit(cfg, params, tokens, last_index, temperature, rng):
    """Prefill + first-token sample; returns (token, state): every kind
    of state the model keeps for the row (decode.prefill), K/V as
    [layers, 1, s_pad, h, d]."""
    row, state = prefill(cfg, params, tokens, last_index)  # [1, vocab]
    tok = sample_tokens(rng, row, temperature[None])[0]
    return tok, state


# The two programs that rewrite the cache DONATE it (kv_cache.KVCache),
# every kind of state in it: their cache outputs alias the inputs, so the
# one-row-a-slot scatter, the state update and the one-slot write land in
# place, not behind a copy of each array.
@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
def _decode_jit(cfg, params, tokens, positions, state, temps, rows, key,
                count):
    """One decode pass over the rows in ``rows`` ([slots] bool), and what
    the NEXT pass over the same rows feeds on, so that nothing of it has
    to come back through the host: (ids, positions, state). ``ids`` is
    ``tokens`` with the pass's rows replaced by what they sampled (the
    other rows keep theirs: another cohort's, or an idle slot's junk);
    ``positions`` moved on by one for the pass's rows. Neither is donated:
    the host reads ``ids``, possibly a step later. The sampling key is
    folded here from the engine's ``key`` and the host's step ``count``
    (the same bits as ``fold_in`` on the host, without its dispatch).
    ``rows`` is the model's mask: a recurrent state of a row outside it
    is kept bit for bit; K/V need no such care (the host parks the other
    rows' positions at max_len - 1), but attention reads nothing of such a
    row. ``more``: [] or, of a model with experts, [decode.ROUTED_COUNTS]."""
    logits, state, *more = decode(cfg, params, tokens, positions, state, rows)
    rng = jax.random.fold_in(key, count)
    ids = jnp.where(rows, sample_tokens(rng, logits, temps), tokens)
    return ids, positions + rows.astype(positions.dtype), state, more


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(state, row, slot, ids, token):
    """Write what a prefill left (``row``: {kind: [layers, 1, ...]}) into
    cache row ``slot`` (dynamic index) of every kind, and its first
    ``token`` into the device's ``ids`` at that slot: (state, ids). The
    extent along the axis after the slot is the row's own (static): the
    padded prefix for K/V (of a ring, its window at most), the whole of it
    for a kind that is no sequence: nothing of the last occupant is left."""
    state = {kind: arr.at[:, slot, :row[kind].shape[2]].set(row[kind][:, 0])
             for kind, arr in state.items()}
    return state, ids.at[slot].set(token)


# Admissions a step may have launched and not yet read. Two: the chip has
# the next prefill queued behind the one whose token the host reads, so it
# never waits for the host between them, and a burst of admissions holds
# two prefills' output rows on the device, not one for every free slot
# (a row lives until its slot write has run: 0.2 GB of K/V for a 1024-token
# prompt of the Ouro cell's model; PERF.md §6, PR 38).
_UNREAD_ADMISSIONS = 2


class _Active:
    """Host-side per-slot decode state."""

    __slots__ = ("request", "generated", "next_pos", "last_token_ts",
                 "ttft_s", "generation")

    def __init__(self, request, prompt_len, generation=0):
        self.request = request
        # the token VALUES the host has read, none until the prefill's
        # first token is (``_read_first_tokens``); the last of them, or
        # the one a program in flight sampled, is in the device's ids
        self.generated = []
        # cache position the next pass writes; moved on when a pass is
        # LAUNCHED, so it counts the tokens the row has been given
        self.next_pos = prompt_len
        # stamped when the host HAS the first token
        self.last_token_ts = self.ttft_s = None
        # weight generation that admitted this request: it decodes on
        # these weights to the end, across any hot swap (docs/fleet.md)
        self.generation = generation

    @property
    def given(self):
        """Tokens the row has been given, the launch-time truth:
        len(generated), plus one while its prefill's first token or a
        pass over it is unread."""
        return self.next_pos - len(self.request.prompt) + 1


class ServeEngine:
    """Continuous-batching engine over one model replica.

    ``policy="drain"`` is the static-batch baseline; everything else
    about the engine (kernels, cache, sampling, metrics) is identical.
    ``replica`` (serving.replica.ReplicaGroup) plugs the engine into
    the control plane's liveness ledger: each step heartbeats, and a
    declared-lost peer triggers the failover callback + a flight dump
    instead of a hang.
    """

    def __init__(self, cfg, params, num_slots=None, max_len=None,
                 kv_block=None, total_blocks=None, policy="continuous",
                 queue=None, seed=0, replica=None, on_ranks_lost=None,
                 subscriber=None, generation=None, clock=time.monotonic,
                 swap_gate=None, mesh=None):
        self.cfg = cfg
        # Tensor-parallel serving (docs/mesh.md): with a mesh whose tp
        # axis is >1, params are placed by the model's spec tree
        # (Megatron column/row split) and the KV cache is head-sharded;
        # GSPMD then shards prefill/decode over the same mesh. mesh=None
        # is the unsharded single-chip engine, byte-identical to before.
        self.mesh = mesh
        params = self._place_params(params)
        self.params = params
        # fleet plane (docs/fleet.md): the subscriber feeds armed weight
        # generations; swaps happen at step boundaries in _maybe_swap.
        # params is always the CURRENT generation's tree (what prefill
        # uses); _params_by_gen keeps older generations alive exactly as
        # long as a request admitted under them is still decoding.
        if subscriber is None and replica is not None:
            subscriber = getattr(replica, "subscriber", None)
        self._subscriber = subscriber
        if generation is None:
            generation = 0
            if subscriber is not None and \
                    subscriber.current_generation is not None:
                generation = subscriber.current_generation
        self._generation = int(generation)
        self._params_by_gen = {self._generation: params}
        self.last_swap = None  # latency phases of the most recent swap
        num_slots = (config.env_int("SERVE_SLOTS", 8)
                     if num_slots is None else num_slots)
        self.kv = KVCache(cfg, num_slots, max_len=max_len,
                          block_size=kv_block, total_blocks=total_blocks,
                          mesh=mesh)
        # Memory plane (docs/memory.md): state what this engine holds —
        # the placed weight tree and the dense KV arrays — so the
        # per-chip HBM ledger attributes serving bytes from tree
        # metadata alone (device probes stay inside utils/memory.py,
        # hvdlint HVD020).
        if hvd_memory.enabled():
            mem_ledger = hvd_memory.get_ledger()
            mem_ledger.account_tree("params", params)
            mem_ledger.account_kv(self.kv)
        self.scheduler = SlotScheduler(num_slots, policy=policy)
        self.queue = queue if queue is not None else AdmissionQueue()
        self._clock = clock
        self._rng = jax.random.PRNGKey(seed)
        self._step_count = 0
        self._replica = replica
        self._on_ranks_lost = on_ranks_lost
        self._key_words = np.asarray(self._rng)  # read once (host_key.py)
        # router/canary hook (horovod_tpu/router/canary.py): called with the
        # armed generation before a swap; returning False holds this replica on
        # its current weights (the generation stays armed and is re-offered
        # next step). None = swap whenever armed, the pre-router behavior.
        self._swap_gate = swap_gate
        # elasticity plane (docs/elasticity.md): a draining engine
        # refuses new submissions but keeps admitting ITS OWN queue and
        # stepping until the router retires it — planned scale-down
        # finishes the work it already accepted, it never drops it
        self._draining = False
        self._active = {}  # slot -> _Active
        self._finished = []
        # What a decode pass feeds on stays on the device (docs/serving.md,
        # "The step's order"). ``_ids``: the last token of every row, a
        # prefill's first token or what the last pass sampled; it never
        # comes from the host. ``_feed``: (positions, temperatures, rows)
        # as the last pass left them for the next one, or None when the
        # host has to write them again: a row joined or left, or the pass
        # was one cohort's of several. ``_unread``: the ONE pass that may
        # be in flight with its ids not read yet, (ids, [(slot, _Active)],
        # its launch's number in the step record, what a model with experts
        # counted in it). ``_joined``: the admissions of the step in progress
        # whose first token is still to be read, [(slot, _Active, token, the
        # prefill's launch number)]; empty between steps.
        self._ids = self._put(np.zeros(num_slots, np.int32))
        self._feed = None
        self._unread = None
        self._joined = []
        self._tick = None  # the decode-tick span of the step in progress
        # what this engine has dispatched: the padded prompt lengths it has
        # prefilled, and "decode". The first dispatch of each traces,
        # lowers and compiles (or loads) its program: seconds on the host
        # that no unread first token is left behind (``_compiles``)
        self._dispatched = set()
        reg = self._metrics = hvd_metrics.get_registry()
        self._m_requests = reg.counter(
            "hvd_serve_requests_total",
            "Serving requests by terminal outcome "
            "(completed/rejected/failed).", labels=("outcome",))
        self._m_tokens = reg.counter(
            "hvd_serve_tokens_total",
            "Tokens processed by the serving engine, by phase.",
            labels=("phase",))
        self._m_ttft = reg.histogram(
            "hvd_serve_ttft_seconds",
            "Time to first token: request arrival to the prefill "
            "sample.")
        self._m_intertoken = reg.histogram(
            "hvd_serve_intertoken_seconds",
            "Gap between consecutive decode tokens of one request.")
        self._m_active = reg.gauge(
            "hvd_serve_active_slots",
            "Batch slots currently decoding a request.")
        self._m_blocks = reg.gauge(
            "hvd_serve_kv_blocks_in_use",
            "KV-cache blocks currently claimed by active slots.")
        self._m_in_place = reg.gauge(
            "hvd_serve_kv_in_place",
            "1 once an engine's first slot write and first decode step "
            "both consumed the cache arrays they were given, every kind "
            "of state among them (the cache is updated in place); 0 "
            "when a donation was dropped and every call copies that "
            "array; no value before either.")
        self._m_ahead = reg.counter(
            "hvd_serve_steps_ahead_total",
            "Engine steps that returned with their decode pass in flight "
            "and its ids unread (every slot busy, no row finishing, one "
            "weight generation): the next step launches its pass first "
            "and reads this one while the chip runs.")
        self._m_admitted_ahead = reg.counter(
            "hvd_serve_admissions_ahead_total",
            "Admissions whose first token was read with a program of "
            "their step queued behind their prefill (the step's decode "
            "pass, or a later admission's prefill): the chip ran on "
            "while the host read and booked it.")
        self._m_drew = reg.counter(
            "hvd_serve_decode_draw_steps_total",
            "Engine steps whose decode pass held a row with a "
            "temperature above 0, so the sampler ran its categorical "
            "draw over rows x vocabulary; a pass of greedy rows alone "
            "draws nothing (serving/sampling.py).")
        state_bytes = reg.gauge(
            "hvd_serve_state_bytes",
            "Bytes of per-slot serving state resident on one chip, by "
            "kind (k, v, or the one latent of latent attention; ssm and "
            "conv of a recurrent mixer), over all its planes (one a layer, "
            "times the passes of a looped stack).", labels=("kind",))
        for kind, nbytes in self.kv.bytes_by_kind().items():
            state_bytes.labels(kind=kind).set(nbytes)
        # bytes of recurrent state one row holds over all layers: what a
        # decode pass reads and writes again per row it advances
        self._row_state_bytes = self.kv.row_state_bytes()
        # the step record's ``kv_bytes`` (what decode attention has to
        # stream of each decoding row, under the blocking its kernel reads
        # a row in) is the cache's to count, from its arrays' own shapes and
        # for each class of positional state by its own row length and
        # planes: ``KVCache.count_reads``, at the launch in ``_decode``
        # cache-writing programs whose first call on this engine has
        # yet to show that it consumed its arrays (_note_in_place)
        self._in_place_unchecked = {"write_slot", "decode"}
        # SLO goodput accounting (docs/serving.md): a token only counts
        # as goodput when its request completed within its deadline;
        # everything else — deadline-blown, kv-exhausted, evicted — is
        # wasted device work, labeled by why.
        self._m_goodput = reg.counter(
            "hvd_serve_goodput_tokens_total",
            "Tokens (prefill + decode) of requests that completed "
            "within their SLO deadline.")
        self._m_wasted = reg.counter(
            "hvd_serve_wasted_tokens_total",
            "Tokens (prefill + decode) whose request ended without "
            "meeting its SLO, by why the work was wasted.",
            labels=("reason",))
        self._m_goodput_ratio = reg.gauge(
            "hvd_serve_goodput_ratio",
            "goodput / (goodput + wasted) tokens over the engine's "
            "life; 1.0 until the first wasted token.")
        self._goodput_tokens = 0
        self._wasted_tokens = 0
        if self._subscriber is not None:
            rep = str(self._subscriber.replica)
            self._m_gen = reg.gauge(
                "hvd_fleet_generation",
                "Weight generation this replica is currently serving.",
                labels=("replica",)).labels(replica=rep)
            self._m_gen.set(self._generation)
            self._m_swaps = reg.counter(
                "hvd_fleet_swaps_total",
                "Zero-drain weight swaps completed by serving engines.")
            self._m_last_swap = reg.gauge(
                "hvd_fleet_last_swap_seconds",
                "Detect->swapped latency of this replica's most recent "
                "weight swap.", labels=("replica",)).labels(replica=rep)
            self._m_swap_s = reg.histogram(
                "hvd_fleet_swap_seconds",
                "Weight-swap latency decomposition "
                "(detect_to_loaded/loaded_to_armed/armed_to_swapped/"
                "total).", labels=("phase",))
        serve_tracing.phase_histogram(reg)
        self._gauge_interval = config.env_float(
            "SERVE_METRICS_INTERVAL_S", 1.0)
        self._last_gauge_ts = -1e30
        self._slow_tick_us = serve_tracing.slow_tick_us()
        # the record of the step in progress (serving/tracing.py
        # StepTrace); the shared null object between steps
        self._rec = serve_tracing.NULL_STEP

    # -- submission -----------------------------------------------------

    def submit(self, request):
        if self._draining:
            return False
        return self.queue.submit(request)

    # -- graceful drain (docs/elasticity.md) ----------------------------

    @property
    def draining(self):
        return self._draining

    def begin_drain(self):
        """Enter drain mode: no new admissions from outside, existing
        queue + in-flight work runs to completion under the router's
        drain deadline. Idempotent."""
        if self._draining:
            return
        self._draining = True
        self._metrics.event("serve_drain_begin",
                            inflight=len(self._active),
                            queued=len(self.queue))

    # -- the step loop --------------------------------------------------

    def step(self):
        """One scheduler iteration. Returns the requests that finished
        during it (as RequestResults, also kept on self.results).

        The step writes its own record (serving/tracing.py StepTrace):
        every statement below runs under one of STEP_PHASES, and the
        timing itself stays in that module (hvdlint HVD014)."""
        rec = self._rec = serve_tracing.begin_step()
        try:
            with rec.phase("control"):
                self._heartbeat()
                self._maybe_swap()
            dirty = self._admit()
            self.scheduler.begin_wave()
            dirty |= self._decode()
            with rec.phase("telemetry"):
                self._refresh_gauges(force=dirty)
                # Alerting + durable history ride the serve tick too
                # (docs/alerts.md) — interval-throttled clock compares,
                # on the engine's clock so drills with virtual time
                # drive them.
                now = self._clock()
                hvd_history.poke(now)
                hvd_alerts.tick(now)
            done, self._finished = self._finished, []
            return done
        finally:
            self._rec, self._tick = serve_tracing.NULL_STEP, None
            rec.finish()

    def run_to_completion(self, max_steps=100000):
        """Drive step() until queue and batch are empty; the engine's
        synchronous-driver mode (examples/serve_lm.py, the tests)."""
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self._active and not len(self.queue):
                break
        return out

    @property
    def active_count(self):
        return len(self._active)

    @property
    def generation(self):
        """The weight generation newly admitted requests decode on."""
        return self._generation

    def load_snapshot(self):
        """Compact live-load summary — what the router scores dispatch
        on (docs/routing.md). Rides every heartbeat as the ``load``
        piggyback, so keep it a few plain ints: queue depth, busy/free
        slots, outstanding decode work in tokens (queued + remaining
        on active slots — the term that makes least-loaded cost-aware
        under bimodal lengths), free KV blocks, and the current +
        armed weight generations (the canary controller reads cohorts
        off these)."""
        ledger = self.kv.ledger
        sub = self._subscriber
        work = sum(max(st.request.max_new_tokens - st.given, 0)
                   for st in self._active.values())
        queued_tokens = (self.queue.queued_work_tokens()
                         if hasattr(self.queue, "queued_work_tokens")
                         else 0)
        work += queued_tokens
        snap = {
            "queue_depth": len(self.queue),
            "active_slots": len(self._active),
            "work_tokens": work,
            "free_slots": self.kv.num_slots - len(self._active),
            "free_blocks": ledger.total_blocks - ledger.blocks_in_use,
            "total_blocks": ledger.total_blocks,
            # OOM forecast (docs/memory.md): free blocks after the
            # queue drains — the elasticity pressure signal and the
            # router's kv_forecast shed read this field
            "predicted_free_blocks": ledger.predicted_free_blocks(
                queued_tokens),
            "generation": self._generation,
            "armed_generation": (getattr(sub, "armed_generation", None)
                                 if sub is not None else None),
        }
        if self._draining:
            snap["draining"] = True
        return snap

    def resharding_report(self):
        """GSPMD resharding sentinel over the decode step
        (docs/memory.md): lower + compile ``_decode_jit`` at this
        engine's real shapes and scan the optimized HLO for collectives
        that gather a param leaf the spec tree declared sharded. Empty
        on a clean spec tree (and always on an unsharded engine, where
        nothing is declared sharded)."""
        from ..models.transformer import param_specs
        positions, temps, rows = self._place_rows(())
        lowered = _decode_jit.lower(
            self.cfg, self.params, self._ids, positions, self.kv.arrays,
            temps, rows, self._rng, np.int32(0))
        hlo = lowered.compile().as_text()
        return hvd_memory.scan_resharding(
            hlo, self.params, param_specs(self.params), self.mesh,
            site="serve_decode")

    # -- internals ------------------------------------------------------

    def _place_params(self, params):
        """Place a weight tree on the engine's mesh through the model's
        spec tree — every path params enter the engine (__init__ and
        hot swaps) goes through here so a swapped-in generation shards
        exactly like the one it replaces."""
        if self.mesh is None:
            return params
        from ..models.transformer import TransformerConfig, param_specs
        from ..parallel import mesh as mesh_lib
        if not isinstance(self.cfg, TransformerConfig):
            raise NotImplementedError(
                "serving over a mesh places a TransformerLM's parameter "
                f"tree only; {type(self.cfg).__name__} serves on one chip "
                "(ROADMAP R2)")
        return mesh_lib.device_put_tree(params, param_specs(params),
                                        self.mesh)

    def _maybe_swap(self):
        """Zero-drain hot swap at the step boundary (docs/fleet.md):
        poll the subscriber (cheap: one stat, rate-limited), and if a
        fully loaded + verified generation is armed, make it current.
        In-flight requests keep their admit-time generation — the
        cohort decode in _decode() finishes them on the old weights —
        so nothing drains and no half-loaded tree is ever visible."""
        sub = self._subscriber
        if sub is None:
            return
        sub.poll()
        if self._swap_gate is not None:
            armed = getattr(sub, "armed_generation", None)
            if armed is not None and not self._swap_gate(armed):
                return  # held by the canary gate; re-offered next step
        rec = sub.take_armed()
        if rec is None:
            return
        old_gen, gen = self._generation, rec.generation
        new_params = self._place_params(rec.params)
        self.params = new_params
        self._params_by_gen[gen] = new_params
        self._generation = gen
        self._prune_params()
        # re-state the params component: a swapped-in generation may
        # differ in dtype/shape from the tree it replaces
        if hvd_memory.enabled():
            hvd_memory.get_ledger().account_tree("params", new_params)
        now = sub.clock()  # the subscriber's clock stamped rec
        d2l = max(rec.loaded_ts - rec.detect_ts, 0.0)
        l2a = max(rec.armed_ts - rec.loaded_ts, 0.0)
        a2s = max(now - rec.armed_ts, 0.0)
        total = d2l + l2a + a2s
        for phase, dt in (("detect_to_loaded", d2l),
                          ("loaded_to_armed", l2a),
                          ("armed_to_swapped", a2s), ("total", total)):
            self._m_swap_s.labels(phase=phase).observe(dt)
        self._m_swaps.inc()
        self._m_gen.set(gen)
        self._m_last_swap.set(total)
        self.last_swap = {
            "generation": gen, "from_generation": old_gen,
            "step": rec.step,
            "detect_to_loaded_ms": round(d2l * 1e3, 3),
            "loaded_to_armed_ms": round(l2a * 1e3, 3),
            "armed_to_swapped_ms": round(a2s * 1e3, 3),
            "total_ms": round(total * 1e3, 3),
        }
        self._metrics.event(
            "fleet_swap", replica=sub.replica,
            inflight=len(self._active), **self.last_swap)

    def _prune_params(self):
        """Drop weight generations no active request decodes on. The
        single-generation steady state short-circuits for free."""
        if len(self._params_by_gen) == 1:
            return
        live = {st.generation for st in self._active.values()}
        live.add(self._generation)
        for gen in [g for g in self._params_by_gen if g not in live]:
            del self._params_by_gen[gen]

    def _heartbeat(self):
        if self._replica is None:
            return
        try:
            self._replica.heartbeat(load=self.load_snapshot())
        except RanksLostError as err:
            lost = tuple(int(r) for r in err.ranks)
            # name the in-flight requests in the event: their spans are
            # still open, so the dump below carries them and
            # hvd_postmortem / hvd_slo can tell whose work died here
            inflight = sorted(st.request.request_id
                              for st in self._active.values())
            self._metrics.event("serve_failover", lost_ranks=list(lost),
                                inflight=inflight)
            hvd_tracing.get_tracer().dump("serve_ranks_lost")
            replica, self._replica = self._replica, None
            replica.close()
            if self._on_ranks_lost is not None:
                self._on_ranks_lost(lost)

    def _pad_len(self, n):
        block = self.kv.ledger.block_size
        return min(-(-n // block) * block, self.kv.max_len)

    def _admit(self):
        """Launch the prefill and the slot write of every request this
        step can admit, back to back. The first tokens are read after the
        step's decode launch (``_read_first_tokens``); a third admission
        and each one after it first reads the oldest unread one, whose
        successor's prefill is queued behind it (``_UNREAD_ADMISSIONS``),
        and the first admission of a padded length reads them all: its
        dispatch compiles, and a first token is not made to wait seconds
        for another request's program."""
        admitted = False
        while self.scheduler.can_join():
            with self._rec.phase("admit"):
                req = self._pop_admissible()
            if req is None:
                break
            if self._compiles(self._pad_len(len(req.prompt))):
                self._read_first_tokens()
            elif len(self._joined) == _UNREAD_ADMISSIONS:
                self._read_first_tokens(keep=_UNREAD_ADMISSIONS - 1)
            self._prefill(req)
            admitted = True
        return admitted

    def _pop_admissible(self):
        """The next queued request the cache can hold for its whole
        life, or None: the queue is empty, or its head has to wait for
        retirements (requeued). Requests that can never fit are failed
        on the way."""
        while True:
            req = self.queue.pop()
            if req is None:
                return None
            prompt_len = len(req.prompt)
            final_len = self._final_len(req)
            if (prompt_len == 0 or final_len > self.kv.max_len or
                    self.kv.ledger._blocks_for(final_len) >
                    self.kv.ledger.total_blocks):
                self._m_requests.labels(outcome="failed").inc()
                trace = serve_tracing.trace_of(req)
                phases = trace.on_reject("too_long")
                self._metrics.event(
                    "serve_reject", request_id=req.request_id,
                    reason="too_long", trace_id=trace.trace_id)
                self._finished.append(RequestResult(
                    req.request_id, (), "failed", reason="too_long",
                    finish_ts=self._clock(), trace_id=trace.trace_id,
                    phase_ms=phases or None,
                    generation=self._generation))
                continue
            if not self.kv.ledger.can_alloc(final_len):
                # cache pressure, not impossibility: wait for retirements.
                # Gate on the WHOLE-life need, not just the prompt — an
                # optimistic admit would decode for a while and then die
                # kv_exhausted when a later joiner took the headroom.
                self.queue.requeue(req)
                return None
            return req

    @staticmethod
    def _final_len(req):
        # cache rows needed over the request's whole life: the final
        # generated token is sampled but never written back
        return len(req.prompt) + max(req.max_new_tokens - 1, 0)

    def _compiles(self, program):
        """Is this the engine's first dispatch of ``program`` (a padded
        prompt length's prefill, or "decode")? Noted as dispatched."""
        if program in self._dispatched:
            return False
        self._dispatched.add(program)
        return True

    def _prefill(self, req):
        """Launch one admission: its prefill program as soon as the slot,
        the padded prompt and the key are there (prompt, index and
        temperature go up with the dispatch as host values), then the
        rest of the host state that says the row is there (all known
        without the token's value), then the slot write. The row joins
        this step's pass unless the prefill's token is all it asked for."""
        rec = self._rec
        with rec.phase("prefill"):
            prompt_len = len(req.prompt)
            slot = self.scheduler.join(req.request_id)
            # the request's prefill span opens before the dispatch: a
            # compile or a slow launch is the prefill's, not a stall
            serve_tracing.trace_of(req).on_prefill_start(slot, prompt_len)
            tokens = np.zeros((1, self._pad_len(prompt_len)), np.int32)
            tokens[0, :prompt_len] = req.prompt
            # the key is folded on the host: no device call before the prefill
            rng = host_key.fold_in(self._key_words, self._step_count)
            self._step_count += 1
            with rec.launch("_prefill_jit") as n:
                tok, row = _prefill_jit(
                    self.cfg, self.params, tokens,
                    np.int32(prompt_len - 1), np.float32(req.temperature),
                    rng)
            self.kv.ledger.alloc_at(slot, prompt_len,
                                    reserve=self._final_len(req))
            # compile observability: each distinct padded prompt length
            # is a real prefill recompile; a churn of them is the storm
            # the tracker names (docs/memory.md)
            if hvd_memory.enabled():
                hvd_memory.get_tracker().observe("serve_prefill",
                                                 (tokens,))
            kv = self.kv
            went_in = kv.arrays
            # the first token joins the device's ids where it is: the
            # next decode pass takes it from there, not from the host
            with rec.launch("_write_slot"):
                kv.arrays, self._ids = _write_slot(
                    went_in, row, np.int32(slot), self._ids, tok)
            self._feed = None  # a row joined
            if "write_slot" in self._in_place_unchecked:
                self._note_in_place("write_slot", went_in)
            st = _Active(req, prompt_len, generation=self._generation)
            if req.max_new_tokens > 1:
                self._active[slot] = st
            self._joined.append((slot, st, tok, n))
            rec.count("admitted")
            rec.count("prompt_tokens", prompt_len)
            rec.count("state_bytes", self._row_state_bytes)
            # a prefill of two token extents says both (models/sambay.py)
            for name, n in prefill_extents(self.cfg, tokens.shape[1]).items():
                rec.count(name, n)

    def _read_first_tokens(self, keep=0, launched=False):
        """Read the first token of this step's admissions, in admission
        order and all but the newest ``keep``, and book each: the ONE
        place a prefill's token crosses to the host. ``launched``: the
        step's decode pass is. An admission is read AHEAD when a program
        of the step is queued behind its prefill, that pass or a later
        admission's prefill: the chip runs on while the host reads and
        books."""
        joined = self._joined
        if not joined:
            return
        rec = self._rec
        last, n = len(joined) - 1, len(joined) - keep
        self._joined = joined[n:]
        for i, (slot, st, tok, launch) in enumerate(joined[:n]):
            with rec.phase("prefill_readback"):
                # the one sanctioned per-prefill readback: the first token
                # hvdlint: disable=HVD011(first-token sample is the prefill's output)
                first = int(jax.device_get(tok))
            rec.read(launch)
            with rec.phase("bookkeeping"):
                req = st.request
                st.generated.append(first)
                st.last_token_ts = now = self._clock()
                st.ttft_s = now - req.arrival_ts
                trace = serve_tracing.trace_of(req)
                trace.on_prefill_end(ttft_s=st.ttft_s)
                trace.annotate(generation=st.generation)
                self._m_tokens.labels(phase="prefill").inc(len(req.prompt))
                self._m_tokens.labels(phase="decode").inc()
                self._m_ttft.observe(st.ttft_s)
                if launched or i < last:
                    rec.count("admitted_ahead")
                    self._m_admitted_ahead.inc()
                self._metrics.event(
                    "serve_admit", request_id=req.request_id, slot=slot,
                    prompt_len=len(req.prompt), trace_id=trace.trace_id,
                    generation=st.generation,
                    ttft_s=round(st.ttft_s, 6))
                if req.max_new_tokens <= 1:  # took no part in the pass
                    self._active[slot] = st
                    self._retire(slot, "completed")

    def _put(self, array):
        """Small host arrays (a tree of them) beside the cache, COMMITTED
        there (replicated over a mesh) like everything a program hands
        back: jit tells an argument that is committed from one that is not,
        and a pass fed by the host must be the same call as one fed by the
        pass before it."""
        return jax.device_put(array, self.kv.vector_sharding)

    def _place_rows(self, slots):
        """(positions, temperatures, rows) on the device for a pass over
        ``slots``, written from the host's own state. Every other row
        parks its K/V write at max_len - 1."""
        S = self.kv.num_slots
        positions = np.full(S, self.kv.max_len - 1, np.int32)
        temps = np.zeros(S, np.float32)
        rows = np.zeros(S, bool)
        for slot in slots:
            st = self._active[slot]
            positions[slot] = st.next_pos
            temps[slot] = st.request.temperature
            rows[slot] = True
        return self._put((positions, temps, rows))

    def _decode(self):
        """Launch one decode pass over the active rows, the ones this
        step admitted among them, then read what is due: the pass a step
        ago that is still unread, the first tokens of this step's
        admissions, and this pass too unless nothing at the boundary
        waits for it (``_due``). The module docstring has the order and
        why."""
        if not self._active:
            # nothing unread either (a last row is read at once); a
            # request that asked for its prefill's token alone ends here
            self._read_first_tokens()
            return False
        rec = self._rec
        with rec.phase("decode_prepare"):
            # one span per fused step, its duration attributed to every
            # request active during the tick (serving/tracing.py). In a
            # step that admitted it opens once the first tokens are read:
            # the wait for them is the prefills', and no row's decode.
            if not self._joined:
                self._open_tick()
            # Cohort-partitioned decode (docs/fleet.md): a request
            # decodes on the weights that admitted it, across any hot
            # swap, so each live generation runs its own fused pass over
            # ALL slots with its own params. Non-cohort rows park their
            # K/V write at max_len-1, where the length mask hides the
            # garbage until the row's own pass overwrites it with the
            # real value — each pass writes then attends, so even a
            # final-token write at max_len-1 is read only after it
            # lands. A recurrent state has nowhere to park: a model
            # that keeps one is told the pass's rows and leaves every
            # other row's state bit for bit. Between swaps there is
            # exactly one cohort and this is the same single fused call
            # as always. (The generations are taken before a row can be
            # refused below: a pass consumes its step count, and so its
            # key, even if its last row has just gone.)
            gens = sorted({st.generation for st in self._active.values()})
        # Everything that decides which rows a pass advances is host state
        # and known before the launch: the ledger says so here, a count
        # says which rows the pass finishes, and no sampled id can change
        # either. A row the ledger refuses goes without the pass, with the
        # tokens it has, the last of them possibly still in flight.
        for slot in list(self._active):
            st = self._active.get(slot)
            if st is not None and \
                    not self.kv.ledger.grow(slot, st.next_pos + 1):
                self._read_unread()
                self._read_first_tokens()
                if self._active.get(slot) is st:
                    with rec.phase("bookkeeping"):
                        self._retire(slot, "failed", reason="kv_exhausted")
        if self._compiles("decode"):  # this engine's first pass, likewise
            self._read_first_tokens()
        launched, routed = [], []
        one = len(gens) == 1
        # what one cohort of several left is of no use to this pass
        feed, self._feed = self._feed if one else None, None
        for gen in gens:
            with rec.phase("decode_prepare"):
                slots = [slot for slot, st in self._active.items()
                         if st.generation == gen]
                count = np.int32(self._step_count)
                self._step_count += 1
                if not slots:
                    continue
                positions, temps, rows = feed or self._place_rows(slots)
                lengths = []
                for slot in slots:
                    st = self._active[slot]
                    st.next_pos += 1
                    launched.append((slot, st))
                    lengths.append(st.next_pos)
                # what attention has to stream: each decoding row's K/V in
                # whole blocks up to its length (a ring's: to its window)
                self.kv.count_reads(rec, lengths)
                if self.kv.recurrent:
                    rec.count("state_rows", len(slots))
                    rec.count("state_bytes",
                              2 * len(slots) * self._row_state_bytes)
                # decode is shape-static by construction: one miss at
                # the first step, hits forever — a second miss here IS
                # the bug
                if hvd_memory.enabled():
                    hvd_memory.get_tracker().observe(
                        "serve_decode", (self._ids, positions, temps))
            with rec.phase("decode_dispatch"):
                kv = self.kv
                went_in = kv.arrays
                with rec.launch("_decode_jit") as n:
                    self._ids, positions, kv.arrays, more = _decode_jit(
                        self.cfg, self._params_by_gen[gen], self._ids,
                        positions, went_in, temps, rows, self._rng, count)
                routed += more  # what a model with experts counted
                if one:  # what the next pass over the same rows feeds on
                    self._feed = (positions, temps, rows)
                if "decode" in self._in_place_unchecked:
                    self._note_in_place("decode", went_in)
        rec.count("active", len(launched))
        rec.count("cohorts", len(gens))
        # the program's sampler draws only where a row of its pass asks
        if any(st.request.temperature > 0.0 for _, st in launched):
            rec.count("drew")
            self._m_drew.inc()
        if launched:  # stack passes the launched program runs each row
            rec.count("passes", passes(self.cfg))
        # everything is launched: now what came before it, in the chip's
        # order and while the chip runs on. The pass before this one, ...
        self._read_unread()
        # ... this step's prefills ...
        self._read_first_tokens(launched=bool(launched))
        self._open_tick()
        # ... and this pass, if anything waits for it
        if launched:
            self._unread = (self._ids, launched, n, routed)
            if self._due(launched, len(gens)):
                self._read_unread()
            else:
                rec.count("ahead")
                self._m_ahead.inc()
        self._close_tick()
        return True

    def _due(self, launched, cohorts):
        """Does anything at this step's boundary wait for the pass just
        launched? Then it is read before the step returns, today's order;
        otherwise the step returns with it in flight and the next step
        launches its pass before it reads this one. Decided from what the
        engine sees, at every step:

          * a row gets its last token in this pass: its result is due and
            its slot frees, which in a closed loop is what brings the next
            request;
          * a request could join at the next boundary (a free slot, under
            either policy): it must not find a second program queued
            ahead of its prefill, so first-token latency stays what the
            synchronous order gives;
          * more than one weight generation is live (a hot swap's
            cohorts): one pass each, read together.
        """
        return (cohorts > 1 or self.scheduler.can_join() or
                any(st.given >= st.request.max_new_tokens
                    for _, st in launched))

    def _read_unread(self):
        """Read the ids of the pass in flight, if there is one, and book
        them: the ONE place a decode pass's ids cross to the host, at the
        end of the step that launched it or after the next launch."""
        if self._unread is None:
            return
        rec = self._rec
        (ids, launched, n, routed), self._unread = self._unread, None
        with rec.phase("decode_readback"):
            # the one sanctioned readback per pass (during a swap
            # transition one for the cohorts' passes together): the ids,
            # and WITH them what a model with experts counted in the pass
            # (``routed``: empty for every other model)
            # hvdlint: disable=HVD011(the per-step batched token readback)
            ids, routed = jax.device_get((ids, routed))
            ids = np.asarray(ids)
        rec.read(n)
        for counts in routed:  # in the record of the step that READS them
            for name, value in zip(ROUTED_COUNTS, counts):
                rec.peak(name, int(value))
        self._close_tick()
        with rec.phase("bookkeeping"):
            now = self._clock()
            for slot, st in launched:
                if self._active.get(slot) is not st:
                    continue  # left at an earlier read: its pass is waste
                st.generated.append(int(ids[slot]))
                self._m_intertoken.observe(now - st.last_token_ts)
                st.last_token_ts = now
                self._m_tokens.labels(phase="decode").inc()
                req = st.request
                if len(st.generated) >= req.max_new_tokens:
                    self._retire(slot, "completed")
                elif (req.deadline_s is not None and
                        now - req.arrival_ts > req.deadline_s):
                    self._retire(slot, "failed", reason="deadline")

    def _open_tick(self):
        """Open the step's one decode-tick span, unless it has one."""
        if self._tick is None:
            self._tick = self._rec.tick_span(**self.scheduler.snapshot())

    def _close_tick(self):
        """Close the step's decode-tick span at its first call in a step
        (after the first readback of a decode pass, or at the end of a
        step that read none) and attribute it to the requests still
        decoding."""
        tick = self._tick
        if tick is None or not tick.open:
            return
        with self._rec.phase("telemetry"):
            tick_us = serve_tracing.finish_tick(
                tick, len(self._active), self._slow_tick_us, self._rec)
            for st in self._active.values():
                serve_tracing.trace_of(st.request).on_decode_tick(tick_us)

    def _note_in_place(self, program, went_in):
        """After the first call of a cache-writing program on this
        engine: were the arrays that went in consumed, every kind of
        them? ``is_deleted`` is a host flag (no sync). A dropped donation
        (JAX drops one whose output is laid out or sharded unlike the
        input) leaves the results right and copies that array whole on
        every call."""
        self._in_place_unchecked.discard(program)
        kept = sorted(kind for kind, arr in went_in.items()
                      if not arr.is_deleted())
        if kept:
            log.warning(
                "serving: %s did not consume the KV cache it was given "
                "(%s); the cache is copied on every call instead of "
                "updated in place (hvd_serve_kv_in_place = 0)", program,
                ", ".join(kept))
            self._m_in_place.set(0)
            self._in_place_unchecked.clear()  # the verdict is in
        elif not self._in_place_unchecked:
            self._m_in_place.set(1)

    def _retire(self, slot, outcome, reason=""):
        self._rec.count("retired")
        st = self._active.pop(slot)
        self._feed = None  # a row left
        self.kv.ledger.free(slot)
        self.scheduler.retire(slot)
        self._m_requests.labels(outcome=outcome).inc()
        now = self._clock()
        req = st.request
        trace = serve_tracing.trace_of(req)
        phases = trace.on_retire(outcome, reason,
                                 tokens=len(st.generated))
        # SLO goodput: every token this request cost the device counts
        # as goodput only if it completed inside its deadline —
        # otherwise the whole request was wasted work, by reason
        tokens = len(req.prompt) + len(st.generated)
        met = (outcome == "completed" and
               (req.deadline_s is None or
                now - req.arrival_ts <= req.deadline_s))
        if met:
            self._goodput_tokens += tokens
            self._m_goodput.inc(tokens)
        else:
            waste = reason or ("deadline_miss" if outcome == "completed"
                               else outcome)
            self._wasted_tokens += tokens
            self._m_wasted.labels(reason=waste).inc(tokens)
        total = self._goodput_tokens + self._wasted_tokens
        if total:
            self._m_goodput_ratio.set(self._goodput_tokens / total)
        # phase_ms/ttft_s ride the event so hvd_slo --history can
        # rebuild the tail decomposition from history segments alone
        # (runs that degrade without ever producing a flight dump).
        self._metrics.event("serve_retire",
                            request_id=req.request_id, slot=slot,
                            outcome=outcome, reason=reason,
                            tokens=len(st.generated),
                            generation=st.generation,
                            trace_id=trace.trace_id,
                            phase_ms=phases or None,
                            ttft_s=st.ttft_s)
        self._finished.append(RequestResult(
            req.request_id, tuple(st.generated), outcome,
            ttft_s=st.ttft_s, finish_ts=now, reason=reason,
            trace_id=trace.trace_id, phase_ms=phases or None,
            generation=st.generation))
        self._prune_params()

    def _refresh_gauges(self, force=False):
        now = self._clock()
        if not force and now - self._last_gauge_ts < self._gauge_interval:
            return
        self._last_gauge_ts = now
        self._m_active.set(len(self._active))
        self._m_blocks.set(self.kv.ledger.blocks_in_use)
