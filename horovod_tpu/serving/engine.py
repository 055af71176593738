"""The continuous-batching step loop (docs/serving.md).

One ``step()`` = admit joins, one fused decode over every batch slot,
retire finishers. The device work is shape-static by construction:

  * decode always runs all ``num_slots`` rows — inactive rows compute
    garbage that the host ignores and write garbage into their own
    (inactive) cache rows, which the next prefill overwrites, every
    kind of state whole. Occupancy is data, not shape, so join/retire
    never recompiles.
  * prefill pads each prompt to a KV-block multiple, bounding compile
    variants at max_len / block; causal masking makes the pads inert
    for attention, and a model with a recurrent state is told the true
    length (decode.prefill).
  * exactly ONE host readback per decode step (the sampled token ids)
    and one per prefill (the first token) — the contract hvdlint HVD011
    enforces over this package; both sites carry the sanctioned
    disable marker.

The drain policy turns the same engine into the static-batch baseline
(admit only into an idle batch, run the wave to completion) that
bench.py's HVD_BENCH_SERVE leg compares against — one code path, one
flag, no drift between the system and its baseline.
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
from . import tracing as serve_tracing
from .decode import decode, prefill
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
def _decode_jit(cfg, params, tokens, positions, state, temps, rng,
                mask=None):
    logits, state = decode(cfg, params, tokens, positions, state, mask)
    return sample_tokens(rng, logits, temps), state


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_slot(state, row, slot):
    """Write what a prefill left (``row``: {kind: [layers, 1, ...]}) into
    cache row ``slot`` (dynamic index) of every kind. The extent along
    the axis after the slot is the row's own (static): the padded prefix
    for K/V, the whole of it for a kind that is no sequence — nothing of
    the slot's last occupant is left in those."""
    return {kind: arr.at[:, slot, :row[kind].shape[2]].set(row[kind][:, 0])
            for kind, arr in state.items()}


class _Active:
    """Host-side per-slot decode state."""

    __slots__ = ("request", "generated", "next_token", "next_pos",
                 "last_token_ts", "ttft_s", "generation")

    def __init__(self, request, first_token, prompt_len, now,
                 generation=0):
        self.request = request
        self.generated = [first_token]
        self.next_token = first_token  # fed to the next decode step
        self.next_pos = prompt_len  # cache position it will occupy
        self.last_token_ts = now
        self.ttft_s = now - request.arrival_ts
        # weight generation that admitted this request: it decodes on
        # these weights to the end, across any hot swap (docs/fleet.md)
        self.generation = generation


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
        # router/canary hook (horovod_tpu/router/canary.py): called with
        # the armed generation before a swap; returning False holds this
        # replica on its current weights (the generation stays armed and
        # is re-offered next step). None = swap whenever armed, the
        # pre-router behavior.
        self._swap_gate = swap_gate
        # elasticity plane (docs/elasticity.md): a draining engine
        # refuses new submissions but keeps admitting ITS OWN queue and
        # stepping until the router retires it — planned scale-down
        # finishes the work it already accepted, it never drops it
        self._draining = False
        self._active = {}  # slot -> _Active
        self._finished = []
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
        state_bytes = reg.gauge(
            "hvd_serve_state_bytes",
            "Bytes of per-slot serving state resident on one chip, by "
            "kind (k, v; ssm and conv where the model has a recurrent "
            "mixer).", labels=("kind",))
        for kind, nbytes in self.kv.bytes_by_kind().items():
            state_bytes.labels(kind=kind).set(nbytes)
        # bytes of recurrent state one row holds over all layers: what a
        # decode pass reads and writes again per row it advances
        self._row_state_bytes = self.kv.row_state_bytes()
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
            self._rec = serve_tracing.NULL_STEP
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
        work = sum(max(st.request.max_new_tokens - len(st.generated), 0)
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
        S = self.kv.num_slots
        tokens = jnp.zeros(S, jnp.int32)
        positions = jnp.zeros(S, jnp.int32)
        temps = jnp.zeros(S, jnp.float32)
        lowered = _decode_jit.lower(
            self.cfg, self.params, tokens, positions, self.kv.arrays,
            temps, jax.random.PRNGKey(0),
            jnp.ones(S, bool) if self.kv.recurrent else None)
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
        admitted = False
        while self.scheduler.can_join():
            with self._rec.phase("admit"):
                req = self._pop_admissible()
            if req is None:
                break
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

    def _prefill(self, req):
        rec = self._rec
        with rec.phase("prefill"):
            prompt_len = len(req.prompt)
            slot = self.scheduler.join(req.request_id)
            trace = serve_tracing.trace_of(req)
            trace.on_prefill_start(slot, prompt_len)
            self.kv.ledger.alloc_at(slot, prompt_len,
                                    reserve=self._final_len(req))
            s_pad = self._pad_len(prompt_len)
            tokens = np.zeros((1, s_pad), np.int32)
            tokens[0, :prompt_len] = req.prompt
            rng = jax.random.fold_in(self._rng, self._step_count)
            self._step_count += 1
            # compile observability: each distinct padded prompt length
            # is a real prefill recompile; a churn of them is the storm
            # the tracker names (docs/memory.md)
            if hvd_memory.enabled():
                hvd_memory.get_tracker().observe("serve_prefill",
                                                 (tokens,))
            tok, row = _prefill_jit(
                self.cfg, self.params, jnp.asarray(tokens),
                jnp.int32(prompt_len - 1), jnp.float32(req.temperature),
                rng)
            kv = self.kv
            went_in = kv.arrays
            kv.arrays = _write_slot(went_in, row, jnp.int32(slot))
            if "write_slot" in self._in_place_unchecked:
                self._note_in_place("write_slot", went_in)
            rec.count("admitted")
            rec.count("prompt_tokens", prompt_len)
            rec.count("state_bytes", self._row_state_bytes)
        with rec.phase("prefill_readback"):
            # the one sanctioned per-prefill readback: the first token
            # hvdlint: disable=HVD011(first-token sample is the prefill's output)
            first = int(jax.device_get(tok))
        with rec.phase("bookkeeping"):
            now = self._clock()
            self._active[slot] = _Active(req, first, prompt_len, now,
                                         generation=self._generation)
            trace.on_prefill_end(ttft_s=self._active[slot].ttft_s)
            trace.annotate(generation=self._generation)
            self._m_tokens.labels(phase="prefill").inc(prompt_len)
            self._m_tokens.labels(phase="decode").inc()
            self._m_ttft.observe(self._active[slot].ttft_s)
            self._metrics.event(
                "serve_admit", request_id=req.request_id, slot=slot,
                prompt_len=prompt_len, trace_id=trace.trace_id,
                generation=self._generation,
                ttft_s=round(self._active[slot].ttft_s, 6))
            if req.max_new_tokens <= 1:
                self._retire(slot, "completed")

    def _decode(self):
        if not self._active:
            return False
        rec = self._rec
        with rec.phase("decode_prepare"):
            # one span per fused step, its duration attributed to every
            # request active during the tick (serving/tracing.py)
            tick = rec.tick_span(**self.scheduler.snapshot())
            in_tick = list(self._active.values())
            S = self.kv.num_slots
            # Cohort-partitioned decode (docs/fleet.md): a request
            # decodes on the weights that admitted it, across any hot
            # swap, so each live generation runs its own fused pass over
            # ALL slots with its own params. Non-cohort rows park their
            # K/V write at max_len-1, where the length mask hides the
            # garbage until the row's own pass overwrites it with the
            # real value — each pass writes then attends, so even a
            # final-token write at max_len-1 is read only after it
            # lands. A recurrent state has nowhere to park: a model
            # that keeps one is told the pass's rows (``mask``) and
            # leaves every other row's state bit for bit. Between swaps
            # there is exactly one cohort and this is the same single
            # fused call as always.
            cohorts = {}
            for slot, st in self._active.items():
                cohorts.setdefault(st.generation, []).append(slot)
            rec.count("active", len(in_tick))
            rec.count("cohorts", len(cohorts))
        ids = {}  # generation -> that pass's sampled ids, every slot
        for gen in sorted(cohorts):
            with rec.phase("decode_prepare"):
                tokens = np.zeros(S, np.int32)
                positions = np.full(S, self.kv.max_len - 1, np.int32)
                temps = np.zeros(S, np.float32)
                for slot in cohorts[gen]:
                    st = self._active[slot]
                    tokens[slot] = st.next_token
                    positions[slot] = st.next_pos
                    temps[slot] = st.request.temperature
                mask = None
                if self.kv.recurrent:
                    mask = np.zeros(S, bool)
                    mask[cohorts[gen]] = True
                    mask = jnp.asarray(mask)
                    rec.count("state_rows", len(cohorts[gen]))
                    rec.count("state_bytes", 2 * len(cohorts[gen])
                              * self._row_state_bytes)
                rng = jax.random.fold_in(self._rng, self._step_count)
                self._step_count += 1
                # decode is shape-static by construction: one miss at
                # the first step, hits forever — a second miss here IS
                # the bug
                if hvd_memory.enabled():
                    hvd_memory.get_tracker().observe(
                        "serve_decode", (tokens, positions, temps))
                tokens, positions, temps = (
                    jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(temps))
            with rec.phase("decode_dispatch"):
                kv = self.kv
                went_in = kv.arrays
                nxt, kv.arrays = _decode_jit(
                    self.cfg, self._params_by_gen[gen], tokens,
                    positions, went_in, temps, rng, mask)
                if "decode" in self._in_place_unchecked:
                    self._note_in_place("decode", went_in)
            with rec.phase("decode_readback"):
                # the one sanctioned per-step readback (one per cohort
                # during a swap transition): this pass's sampled ids
                # hvdlint: disable=HVD011(the per-step batched token readback)
                ids[gen] = np.asarray(jax.device_get(nxt))
        with rec.phase("telemetry"):
            tick_us = serve_tracing.finish_tick(tick, len(in_tick),
                                                self._slow_tick_us)
            for st in in_tick:
                serve_tracing.trace_of(st.request).on_decode_tick(tick_us)
        with rec.phase("bookkeeping"):
            now = self._clock()
            for slot in list(self._active):
                st = self._active[slot]
                # the fed token's K/V landed at next_pos this step
                if not self.kv.ledger.grow(slot, st.next_pos + 1):
                    self._retire(slot, "failed", reason="kv_exhausted")
                    continue
                tok = int(ids[st.generation][slot])
                st.generated.append(tok)
                st.next_token = tok
                st.next_pos += 1
                self._m_intertoken.observe(now - st.last_token_ts)
                st.last_token_ts = now
                self._m_tokens.labels(phase="decode").inc()
                req = st.request
                if len(st.generated) >= req.max_new_tokens:
                    self._retire(slot, "completed")
                elif (req.deadline_s is not None and
                        now - req.arrival_ts > req.deadline_s):
                    self._retire(slot, "failed", reason="deadline")
        return True

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
