"""Eager coordination core: queue → fuse → execute → callback.

TPU-native replacement for the reference's background thread + rank-0
negotiation (BackgroundThreadLoop operations.cc:857, RunLoopOnce
operations.cc:1246, protocol comment operations.cc:1217-1245).

Why it is different on TPU: the reference's per-step wire negotiation exists
because eager GPU frameworks submit tensors in nondeterministic order across
ranks (operations.cc:852-855). Single-controller JAX has no such problem —
every process runs the same Python program, so submission order is already
identical everywhere. What survives is the *local* machinery, which this
module provides with full parity:

  * tensor table keyed by name, duplicate-name detection
    (DUPLICATE_NAME_ERROR, operations.cc:121; EnqueueTensorAllreduce
    operations.cc:1654)
  * a paced background flush loop (HOROVOD_CYCLE_TIME, default 5 ms,
    operations.cc:1013)
  * tensor fusion into bucketed collectives (HOROVOD_FUSION_THRESHOLD,
    FuseResponses operations.cc:450-573)
  * an LRU plan cache, the analogue of the response cache + bypass fast path
    (response_cache.h:43-92, RunBypass operations.cc:1168-1215)
  * integer handles with poll/synchronize semantics
    (torch/handle_manager.h:30-41, torch/mpi_ops.py:406-438)
  * stall detection with warning/shutdown deadlines
    (CheckForStalledTensors operations.cc:688-769)
  * timeline spans (NEGOTIATE_*, MEMCPY_IN_FUSION_BUFFER, ALLREDUCE, ...)

Eager input conventions (single-controller SPMD):

  * An array whose leading dim equals ``size()`` is **stacked**: row i is
    worker i's tensor (the pmap convention). Collectives run on-device over
    the mesh; the result keeps the stacked shape.
  * A list of arrays is per-local-worker input with possibly different
    first dims — the allgatherv case (MPI_Allgatherv,
    mpi_operations.cc:86-173).
  * Any other array is **replicated**: this process's single contribution.
    Participants are the host processes; with one process an allreduce is
    the identity, exactly like a 1-rank Horovod run.
"""

import collections
import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..common import hvd_logging as log
from ..common import state as state_mod
from ..parallel import mesh as mesh_lib
from ..common.exceptions import (DuplicateNameError, MismatchError,
                                 RanksLostError, ShutdownError,
                                 StalledError)
from ..utils import lockdep
from ..utils import metrics as hvd_metrics
from ..utils import numerics as hvd_numerics
from ..utils import timeline as timeline_mod
from ..utils import tracing as hvd_tracing
from . import compression as compression_mod
from . import quantization as quant_mod

ALLREDUCE = "allreduce"
ALLGATHER = "allgather"
BROADCAST = "broadcast"
REDUCESCATTER = "reducescatter"
ALLTOALL = "alltoall"


def _entry_nbytes(entry):
    from .fusion import _nbytes
    if entry.kind == "list":
        return sum(_nbytes(t) for t in entry.tensor)
    return _nbytes(entry.tensor)


class TensorTableEntry:
    """Parity: TensorTableEntry (common.h:167-184)."""

    __slots__ = ("name", "op", "tensor", "root_rank", "average", "kind",
                 "handle", "result", "status", "event", "enqueue_time",
                 "prescale", "postscale", "trace_id", "span")

    def __init__(self, name, op, tensor, root_rank=0, average=False,
                 kind="replicated", handle=None):
        self.name = name
        self.op = op
        self.tensor = tensor
        self.root_rank = root_rank
        self.average = average
        self.kind = kind
        self.handle = handle
        self.result = None
        self.status = None  # None = pending, True = ok, Exception = error
        self.event = threading.Event()
        self.enqueue_time = time.monotonic()
        # tracing plane (utils/tracing.py): the tensor's trace id and its
        # open negotiation-wait span, closed when the coordinator orders
        # execution (or aborted on the failure paths)
        self.trace_id = None
        self.span = None

    def signature(self):
        if self.kind == "list":
            shapes = tuple(tuple(t.shape) for t in self.tensor)
            dtypes = tuple(str(t.dtype) for t in self.tensor)
        else:
            shapes = tuple(self.tensor.shape)
            dtypes = str(self.tensor.dtype)
        return (self.op, self.name, shapes, dtypes, self.root_rank,
                self.average, self.kind)


class HandleManager:
    """Integer async handles (torch/handle_manager.h:30-41)."""

    def __init__(self):
        self._lock = lockdep.lock("HandleManager._lock")
        self._next = 0      # guarded_by: _lock
        self._entries = {}  # guarded_by: _lock

    def allocate(self, entry):
        with self._lock:
            h = self._next
            self._next += 1
            self._entries[h] = entry
            entry.handle = h
            return h

    def get(self, handle):
        with self._lock:
            entry = self._entries.get(handle)
        if entry is None:
            raise ValueError(f"Handle {handle} was not created or has "
                             f"already been released.")
        return entry

    def poll(self, handle):
        return self.get(handle).event.is_set()

    def release(self, handle):
        with self._lock:
            self._entries.pop(handle, None)


class PlanCache:
    """LRU plan cache — response-cache analogue (response_cache.h:43-92).

    Maps the signature of a drained batch to its fusion plan so repeat
    iterations skip planning entirely (the RunBypass fast path,
    operations.cc:1168-1215). Hit/miss counters feed tests and the
    autotuner.
    """

    def __init__(self, capacity):
        self.capacity = capacity
        self._cache = collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        reg = hvd_metrics.get_registry()
        self._m_hits = reg.counter(
            "hvd_plan_cache_hits_total",
            "Fusion-plan cache hits (batch signature seen before).")
        self._m_misses = reg.counter(
            "hvd_plan_cache_misses_total",
            "Fusion-plan cache misses (plan computed fresh).")

    def get(self, key):
        plan = self._cache.get(key)
        if plan is not None:
            self._cache.move_to_end(key)
            self.hits += 1
            self._m_hits.inc()
        else:
            self.misses += 1
            self._m_misses.inc()
        return plan

    def put(self, key, plan):
        if self.capacity <= 0:
            return
        self._cache[key] = plan
        self._cache.move_to_end(key)
        while len(self._cache) > self.capacity:
            self._cache.popitem(last=False)

    def clear(self):
        self._cache.clear()


class EagerCoordinator:
    """The per-process coordination core (BackgroundThreadLoop analogue)."""

    # how long the control plane must stay unreachable (with >=3 failed
    # attempts under exponential backoff) before this worker declares it
    # lost and fails pending work — transient coordinator pauses or TCP
    # resets must not tear the job down at cycle cadence
    POISON_GRACE_S = 5.0

    def __init__(self, state):
        self._state = state
        self._config = state.config
        self._mesh = state.mesh
        self._axis = state.mesh.axis_names[0]
        self._world = int(state.mesh.devices.size)
        self._queue = collections.deque()  # guarded_by: _queue_lock
        self._queue_lock = lockdep.lock("EagerCoordinator._queue_lock")
        self._tensor_table = {}  # guarded_by: _queue_lock; name -> entry
        self._flush_lock = lockdep.lock("EagerCoordinator._flush_lock")
        self.handles = HandleManager()
        self.plan_cache = PlanCache(self._config.cache_capacity)
        self._shutdown = False
        # coordinator-lost deadline: config override, else the class
        # default (tests patch the class attribute before init)
        self._poison_grace_s = (
            getattr(self._config, "coordinator_lost_timeout_seconds", 0.0)
            or self.POISON_GRACE_S)
        self._paused = False  # test hook: lets stall detection be exercised
        # Overlap plane (docs/tensor-fusion.md): flush_ready() drains
        # fusion buckets that filled while the caller is still enqueuing
        # later tensors; the event makes the background cycle's pacing
        # interruptible so a filled bucket dispatches now instead of
        # waiting out the cycle sleep.
        self._ready_event = threading.Event()
        self._stall_warned = set()
        self._verified_sigs = set()  # cross-process checks done (signature)
        self.timeline = timeline_mod.create_from_env(
            self._config, jax.process_index() == 0)
        # Multi-process control plane: rank-0 coordinator negotiation over
        # the launch layer's TCP protocol (ops/negotiation.py — the
        # reference's Request/Response protocol, operations.cc:1217-1245).
        # With it, processes may submit collectives in any order; without
        # a resolvable control address, fall back to the strict
        # same-program-order contract with cross-process checking.
        self._negotiator = None
        self._negotiated_pending = {}  # name -> entry awaiting a response
        self._applied_seq = -1
        self._cycle_failures = 0
        self._cycle_fail_since = None   # first failure of current streak
        self._cycle_backoff_until = 0.0
        self._cycle_req_id = 0
        self._negotiation_dead = False
        # (metas, hit_ids) not yet delivered to the coordinator, or None
        self._unannounced = None
        # worker half of the response cache (response_cache.h:43-92):
        # a name resubmitted with an unchanged signature rides the wire
        # as one bit (its coordinator-assigned cache id) instead of a
        # full EntryMeta — the RunBypass steady-state fast path
        self._neg_cache = {}      # name -> (cache_id, signature)
        self._neg_cache_ids = {}  # cache_id -> name
        self._reannounce = set()  # names whose ids came back unknown
        self._neg_hit_count = 0   # tensors announced as cache bits
        if jax.process_count() > 1:
            from . import negotiation as neg
            addrs = neg.control_addresses()
            key = neg.control_key()
            if addrs is None or key is None:
                from ..run.secret import HVD_SECRET_KEY as _SECRET_ENV
                missing = ("HVD_CONTROL_ADDR/HVD_COORDINATOR_ADDR"
                           if addrs is None else _SECRET_ENV)
                log.warning(
                    "no %s; the multi-process eager API runs WITHOUT "
                    "rank-0 negotiation — every process must submit "
                    "collectives in the same order", missing)
            else:
                self._negotiator = neg.NegotiationWorker(
                    jax.process_index(), jax.process_count(),
                    self._config, addrs, key)
        self.autotuner = None
        # Multi-process without negotiation: per-process tuning would
        # diverge the fusion plans across processes (multi-controller SPMD
        # needs identical collective order everywhere), so only process 0
        # measures+tunes and every process — including 0 — adopts tuned
        # values at the same agreed point in the replicated-collective
        # order via _sync_tuned_params (the reference coordinator's
        # parameter broadcast, parameter_manager.cc:66-81). Under
        # negotiation none of that is needed: fusion happens at the
        # coordinator with rank 0's live config, and tuned values ride
        # every CycleResponse for the other processes to mirror.
        self._autotune_defer = (self._config.autotune and
                                jax.process_count() > 1 and
                                self._negotiator is None)
        if (self._autotune_defer and
                self._config.autotune_sync_collectives <= 0):
            raise ValueError(
                "HOROVOD_AUTOTUNE_SYNC_COLLECTIVES must be >= 1 (got "
                f"{self._config.autotune_sync_collectives}); a non-positive "
                "interval would silently sync on every collective — to "
                "disable autotuning, unset HOROVOD_AUTOTUNE instead")
        self._autotune_sync_every = (
            self._config.autotune_sync_collectives
            if self._autotune_defer else 0)
        self._replicated_count = 0
        self._proposed_params = None
        # set by _sync_tuned_params: the adoption flush must not be scored
        # (it ran under the old plan and paid the sync-allgather latency)
        self._adopted_this_flush = False
        # True between staging a suggestion and its adoption at the sync
        # point: measurement pauses in that window, or cycles run under
        # the OLD config would be scored against the NEW knobs
        self._autotune_pending_adoption = False
        # Passive scoring state: (flush timestamp, batch bytes) of the
        # previous non-empty flush. Throughput is scored as
        # prev_bytes / (this flush's start - prev flush's start) — wall
        # time the loop measures anyway, the reference ParameterManager's
        # approach (operations.cc:1553-1555 feeding Update() from cycle
        # timestamps, no extra synchronization). Under async dispatch
        # this is exact in steady state: callers block on their handles,
        # so the inter-flush period IS the time the device (plus the
        # fixed dispatch path) took for the previous batch. Crucially
        # the scored regime and the frozen regime are now the SAME
        # regime — the r3 tuner forced a device sync per scored cycle
        # and tuned for a world that stopped existing at freeze.
        self._at_prev_flush = None
        if self._config.autotune and (jax.process_index() == 0):
            from ..utils import autotune as autotune_mod
            self.autotuner = autotune_mod.Autotuner(
                self._config, log_path=self._config.autotune_log or None)
        # Telemetry plane (utils/metrics.py): instruments bound once here
        # so the per-cycle cost is an inc/observe, the exposition server
        # (HVD_METRICS_PORT + rank) runs off the hot path, and the
        # snapshot piggyback rides the negotiation cycle every
        # metrics_interval seconds.
        reg = self._metrics = hvd_metrics.get_registry()
        if reg.enabled and reg.rank is None:
            reg.rank = jax.process_index()
        # Tracing plane (utils/tracing.py): per-tensor lifecycle spans and
        # the always-on flight recorder. The recorder auto-dumps from the
        # failure paths below; the SIGTERM hook catches external kills.
        self._tracer = hvd_tracing.get_tracer()
        hvd_tracing.set_rank(jax.process_index())
        hvd_tracing.install_signal_dump()
        # dump-solicitation protocol: the coordinator sets dump_requested
        # on CycleResponses when it escalates; this worker attaches ONE
        # flight snapshot to its next CycleRequest in reply
        self._flight_send_pending = False
        self._flight_sent = False
        # Numerics plane (utils/numerics.py): gradient-health stats as a
        # side-product of allreduce execution, folded into a per-cycle
        # digest that rides the next CycleRequest so the coordinator's
        # divergence sentinel can compare replicas. The monitor is read
        # through get_monitor() at each use so numerics.reset(enabled=)
        # toggles a live engine.
        self._numerics_pending = None  # digest awaiting piggyback
        self._numerics_cycle = None    # seq being executed (None: local)
        self._numerics_staged = None   # fused-bucket stats matrix
        # Error-feedback residuals for the quantized wire codecs
        # (ops/quantization.py): per fused bucket, keyed by member names
        self._ef = quant_mod.ErrorFeedback()
        # validate HVD_COMPRESSION at init, not mid-step: an unknown or
        # unavailable codec name must raise here — never silently fall
        # back to full width (the negotiation fingerprint would still
        # agree, but the operator asked for bytes they aren't getting)
        compression_mod.Compression.from_name(
            getattr(self._config, "compression", "none"))
        self._m_neg_cycles = reg.counter(
            "hvd_negotiation_cycles_total",
            "Negotiation cycle RPCs completed by this worker.")
        self._m_neg_cycle_s = reg.histogram(
            "hvd_negotiation_cycle_seconds",
            "Latency of one negotiation cycle RPC (request to response, "
            "excluding response application).")
        self._m_neg_failures = reg.counter(
            "hvd_negotiation_cycle_failures_total",
            "Cycle RPC failures (transient transport errors; backoff "
            "applies between retries).")
        self._m_flush_s = reg.histogram(
            "hvd_flush_seconds",
            "Duration of one non-negotiated flush (plan + execute).")
        self._m_flush_tensors = reg.histogram(
            "hvd_flush_tensors",
            "Tensors drained per non-negotiated flush.",
            buckets=hvd_metrics.COUNT_BUCKETS)
        self._m_coll_bytes = reg.counter(
            "hvd_collective_bytes_total",
            "Payload bytes executed through the eager data plane, by "
            "op class.", labels=("op",))
        self._m_coll_s = reg.histogram(
            "hvd_collective_seconds",
            "Dispatch latency of one eager collective execution "
            "(async: completion happens on device), by op class.",
            labels=("op",))
        self._m_overlap_flushes = reg.counter(
            "hvd_overlap_ready_flushes_total",
            "Ready-bucket drains dispatched while the caller was still "
            "enqueuing later tensors (overlap plane).")
        self._m_overlap_tensors = reg.counter(
            "hvd_overlap_ready_tensors_total",
            "Tensors dispatched by ready-bucket drains ahead of the "
            "whole-tree barrier.")
        self._m_overlap_wakes = reg.counter(
            "hvd_overlap_wakes_total",
            "Early background-cycle wakes requested by flush_ready "
            "(negotiated path: a bucket's worth of bytes is queued).")
        self._m_stalled_tensors = reg.gauge(
            "hvd_stalled_tensors",
            "Pending tensors on this worker past the stall warning "
            "deadline (0 = healthy).")
        self._m_stall_kills = reg.counter(
            "hvd_stall_kills_total",
            "Tensors failed by the stall shutdown deadline.")
        self._metrics_next_push = 0.0
        self._metrics_server = None
        if reg.enabled and getattr(self._config, "metrics_port", 0):
            try:
                self._metrics_server = hvd_metrics.MetricsServer(
                    int(self._config.metrics_port) + jax.process_index(),
                    reg.snapshot,
                    remote_snapshots_fn=self._remote_metrics_snapshots)
            except OSError as exc:
                log.warning("metrics server failed to bind port %s: %s",
                            self._config.metrics_port, exc)
        self._thread = threading.Thread(
            target=self._background_loop, daemon=True, name="hvd-background")
        self._thread.start()

    # -- enqueue API (EnqueueTensorAllreduce/..., operations.cc:1654-1770) --

    def enqueue(self, name, op, tensor, root_rank=0, average=False,
                kind=None):
        if self._shutdown:
            raise ShutdownError()
        if self._negotiation_dead:
            raise ShutdownError("negotiation control plane lost")
        if op == BROADCAST and not 0 <= root_rank < self._world:
            raise MismatchError(
                f"Invalid root_rank {root_rank} for broadcast '{name}': "
                f"must be in [0, {self._world}).")
        # kind overrides the shape heuristic for callers that know their
        # tensor's semantics (e.g. sparse values whose nnz happens to equal
        # the world size must not be reinterpreted as stacked).
        entry_kind = kind if kind is not None else self._classify(tensor)
        trace_id = self._tracer.new_trace_id(name)
        with self._tracer.span(hvd_tracing.ENQUEUE, tensor=name,
                               trace_id=trace_id, op=op, kind=entry_kind):
            with self._queue_lock:
                if name in self._tensor_table:
                    raise DuplicateNameError(name)
                entry = TensorTableEntry(name, op, tensor,
                                         root_rank=root_rank,
                                         average=average, kind=entry_kind)
                entry.trace_id = trace_id
                # the negotiation-wait span stays open until the
                # coordinator orders execution (_apply_cycle_response) or
                # the queue drains locally (non-negotiated flush)
                entry.span = self._tracer.span(
                    hvd_tracing.NEGOTIATE, tensor=name, trace_id=trace_id,
                    op=op, enqueue_req=self._cycle_req_id)
                self._tensor_table[name] = entry
                self._queue.append(entry)
        handle = self.handles.allocate(entry)
        if self.timeline:
            self.timeline.negotiate_start(name, op)
        return handle

    def _classify(self, tensor):
        if isinstance(tensor, (list, tuple)):
            return "list"
        # The stacked convention (row i = worker i, the pmap idiom) only
        # exists single-controller. Multi-controller SPMD contributions are
        # always per-process — a rank whose first dim happens to equal the
        # world size must not silently diverge onto the stacked path while
        # its peers run the replicated one.
        if jax.process_count() > 1:
            return "replicated"
        if (hasattr(tensor, "ndim") and tensor.ndim >= 1 and
                tensor.shape[0] == self._world):
            return "stacked"
        return "replicated"

    # -- handle API --

    def poll(self, handle):
        return self.handles.poll(handle)

    @contextlib.contextmanager
    def hold_cycle(self):
        """Public burst hook: while held, no cycle runs (background loop
        and synchronize-side flushes pause), so every collective enqueued
        inside lands in ONE fused cycle on the next flush. What a
        backward pass's dispatch order gives training steps naturally,
        benchmarks get explicitly (examples/allreduce_benchmark.py)."""
        prev = self._paused
        self._paused = True
        try:
            yield
        finally:
            self._paused = prev

    def synchronize(self, handle):
        """Block until the handle's collective completes and return its
        output (torch/mpi_ops.py:422-438)."""
        entry = self.handles.get(handle)
        deadline = None
        if self._config.stall_shutdown_time_seconds > 0:
            deadline = (entry.enqueue_time +
                        self._config.stall_shutdown_time_seconds)
        while not entry.event.is_set():
            if not self._paused and self._negotiator is None:
                # non-blocking: if another thread's flush is stuck inside a
                # hung transport collective, waiting on its lock here would
                # also swallow the stall deadline below. Under negotiation
                # ONLY the background thread may run the cycle — a
                # user-thread flush would break the single-origin ordering
                # of data-plane collectives.
                self.flush(blocking=False)
            if entry.event.wait(timeout=self._config.cycle_time_ms / 1000.0):
                break
            if deadline is not None and time.monotonic() > deadline:
                raise StalledError(
                    f"Collective '{entry.name}' stalled for more than "
                    f"{self._config.stall_shutdown_time_seconds}s.")
        self.handles.release(handle)
        if isinstance(entry.status, Exception):
            raise entry.status
        return entry.result

    # -- the cycle loop (RunLoopOnce, operations.cc:1246) --

    def _background_loop(self):
        while not self._shutdown:
            # interruptible pacing: flush_ready() sets the event when a
            # fusion bucket fills, so its collective dispatches now
            # instead of waiting out the rest of the cycle sleep
            self._ready_event.wait(self._config.cycle_time_ms / 1000.0)
            self._ready_event.clear()
            if self._paused:
                continue
            try:
                self.flush()
            except Exception as exc:  # never kill the loop
                log.error("background flush failed: %s", exc)
            self._check_stalled()

    def flush(self, blocking=True):
        """Drain the queue and execute everything in it (one cycle)."""
        if not self._flush_lock.acquire(blocking):
            return
        try:
            self._flush_locked()
        finally:
            self._flush_lock.release()

    def _flush_locked(self):
        if self._negotiator is not None:
            self._negotiated_flush_locked()
            return
        with self._queue_lock:
            batch = list(self._queue)
            self._queue.clear()
        if not batch:
            return
        t0 = self._run_batch(batch)
        if (self.autotuner is not None
                and not self.autotuner.frozen
                and not self._autotune_pending_adoption):
            total = sum(_entry_nbytes(e) for e in batch)
            prev = self._at_prev_flush
            self._at_prev_flush = (t0, total)
            # a pause in traffic is not collective time: a window much
            # longer than the cycle pacing means the app went idle
            # between flushes, and scoring it would punish whatever
            # knobs happened to be live
            idle_cap = max(10 * self._config.cycle_time_ms / 1000.0, 1.0)
            if self._adopted_this_flush:
                # adoption mid-flush: the interval straddles two knob
                # settings and belongs to neither — restart the window
                self._at_prev_flush = None
            elif prev is not None and (t0 - prev[0]) < idle_cap:
                if self.autotuner.record_cycle(prev[1], t0 - prev[0]):
                    # knobs move now: the next interval runs under new
                    # values, so the window restarts
                    self._at_prev_flush = None
                    if self._autotune_defer:
                        # multi-process: don't apply locally — stage the
                        # suggestion for the next agreed sync point, or
                        # the processes' fusion plans would diverge
                        # mid-stream
                        self._proposed_params = (
                            self.autotuner.threshold,
                            self.autotuner.cycle_time_ms)
                        self._autotune_pending_adoption = True
                    else:
                        # apply the next suggestion
                        # (ParameterManager::Tune)
                        self._config.fusion_threshold = int(
                            self.autotuner.threshold)
                        self._config.cycle_time_ms = float(
                            self.autotuner.cycle_time_ms)

    def _run_batch(self, batch):
        """Plan + execute one drained batch — the body of a
        non-negotiated cycle, shared by the whole-queue flush and the
        overlap plane's ready-bucket drains. Returns the flush start
        time (the autotune scorer's window anchor). Caller holds
        _flush_lock."""
        if self.timeline:
            self.timeline.mark_cycle_start()
            for e in batch:
                self.timeline.negotiate_end(e.name)
        for e in batch:
            # single-process: negotiation is a local queue wait
            if e.span is not None:
                e.span.close(local=True)
        t0 = time.perf_counter()
        # the plan depends on the (possibly autotuned) fusion threshold
        # and on the codec knobs (which may be toggled on a live engine)
        key = (int(self._config.fusion_threshold),
               quant_mod.config_fingerprint(self._config),
               tuple(e.signature() for e in batch))
        plan = self.plan_cache.get(key)
        if plan is None:
            plan = self._make_plan(batch)
            self.plan_cache.put(key, plan)
        self._adopted_this_flush = False
        self._execute(batch, plan)
        self._m_flush_s.observe(time.perf_counter() - t0)
        self._m_flush_tensors.observe(len(batch))
        return t0

    def flush_ready(self):
        """Overlap plane: dispatch every fusion bucket that has FILLED,
        without waiting for the whole-tree barrier or the cycle pacing.
        Callers (optim's reverse-order gradient enqueue) invoke this
        between enqueues so a full bucket's collective starts while
        later (earlier-layer) grads are still being submitted. Partial
        groups always stay queued for the normal cycle. Under
        negotiation only the background thread may originate data-plane
        collectives (single-origin ordering), so this wakes its cycle
        immediately instead of draining inline. No-op unless
        HOROVOD_OVERLAP_EAGER is on."""
        if self._shutdown or self._paused:
            return
        if not getattr(self._config, "overlap_eager", False):
            return
        if self._negotiator is not None:
            threshold = int(self._config.fusion_threshold)
            with self._queue_lock:
                queued = sum(_entry_nbytes(e) for e in self._queue
                             if e.op == ALLREDUCE)
            if queued and (threshold <= 0 or queued >= threshold):
                self._m_overlap_wakes.inc()
                self._ready_event.set()
            return
        if not self._flush_lock.acquire(False):
            return  # a cycle is already draining; it takes the queue
        try:
            with self._queue_lock:
                batch = self._take_ready_locked()
            if not batch:
                return
            self._m_overlap_flushes.inc()
            self._m_overlap_tensors.inc(len(batch))
            self._run_batch(batch)
        finally:
            self._flush_lock.release()

    def _take_ready_locked(self):
        """Remove and return every queued entry belonging to a fusion
        group whose accumulated bytes crossed the fusion threshold.
        Groups are keyed exactly like _make_plan's bucketing, so a
        drained group plans into at least one full bucket; partial
        groups and non-allreduce ops stay queued in submission order.
        Deterministic given the same program + config, so multi-process
        (non-negotiated) drains stay matched across ranks. Caller holds
        _queue_lock."""
        threshold = int(self._config.fusion_threshold)
        if threshold <= 0 or not self._queue:
            return []
        world = max(self._world, 1)
        group_bytes = {}
        keys = []
        for e in self._queue:
            if e.op != ALLREDUCE or e.kind == "list":
                keys.append(None)
                continue
            nb = _entry_nbytes(e)
            per_rank = nb // world if e.kind == "stacked" else nb
            codec = quant_mod.select_codec(
                self._config, getattr(e.tensor, "dtype", None), per_rank)
            key = (e.kind, str(getattr(e.tensor, "dtype", None)),
                   e.average, codec)
            keys.append(key)
            group_bytes[key] = group_bytes.get(key, 0) + nb
        ready = {k for k, b in group_bytes.items() if b >= threshold}
        if not ready:
            return []
        batch = []
        keep = collections.deque()
        for e, key in zip(self._queue, keys):
            (batch if key in ready else keep).append(e)
        self._queue.clear()
        self._queue.extend(keep)
        return batch

    def _make_plan(self, batch):
        """Group fusable entries (stacked allreduces by dtype/average), one
        group per other entry — FuseResponses parity."""
        from . import fusion as fusion_mod
        groups = []
        fusable = [i for i, e in enumerate(batch)
                   if e.op == ALLREDUCE and e.kind == "stacked"]
        if fusable:
            leaves = [batch[i].tensor for i in fusable]
            # bucket per (dtype, average, wire codec) in submission
            # order — codec selection mirrors the coordinator's
            # (quantization.select_codec on per-rank tensor bytes)
            world = max(self._world, 1)
            by_key = collections.OrderedDict()
            for i in fusable:
                e = batch[i]
                codec = quant_mod.select_codec(
                    self._config, e.tensor.dtype,
                    _entry_nbytes(e) // world)
                by_key.setdefault(
                    (str(e.tensor.dtype), e.average, codec), []).append(i)
            for (_, average, codec), idxs in by_key.items():
                buckets = fusion_mod.plan_buckets(
                    [batch[i].tensor for i in idxs],
                    self._config.fusion_threshold)
                for b in buckets:
                    groups.append(("fused_allreduce",
                                   [idxs[j] for j in b.indices], average,
                                   codec))
        for i, e in enumerate(batch):
            if e.op == ALLREDUCE and e.kind == "stacked":
                continue
            codec = None
            if e.op == ALLREDUCE and e.kind == "replicated":
                codec = quant_mod.select_codec(
                    self._config, getattr(e.tensor, "dtype", None),
                    _entry_nbytes(e))
            groups.append((e.op + ":" + e.kind, [i], e.average, codec))
        return groups

    def _execute(self, batch, plan):
        mon = hvd_numerics.get_monitor()
        observed = []
        for kind, idxs, average, codec in plan:
            entries = [batch[i] for i in idxs]
            t0 = time.perf_counter()
            lead = entries[0]
            ex_span = self._tracer.span(
                hvd_tracing.EXECUTE, tensor=lead.name,
                trace_id=lead.trace_id, op=lead.op, fused=len(entries))
            try:
                if kind == "fused_allreduce":
                    self._exec_fused_stacked_allreduce(entries, average,
                                                       codec)
                else:
                    op, entry_kind = kind.split(":")
                    self._exec_single(entries[0], op, entry_kind, codec)
                for e in entries:
                    e.status = True
                op_class = entries[0].op
                nbytes = sum(_entry_nbytes(e) for e in entries)
                self._m_coll_bytes.labels(op=op_class).inc(nbytes)
                self._m_coll_s.labels(op=op_class).observe(
                    time.perf_counter() - t0)
                if op_class == ALLREDUCE and mon.enabled:
                    # reduced side None on purpose: a single-process
                    # allreduce returns the contribution itself, so one
                    # stats half serves both digest sides
                    observed.extend(
                        (e.name, e.tensor, None) for e in entries)
                ex_span.close(bytes=nbytes)
            # hvdlint: disable=HVD006(status carries the fault to every waiter)
            except Exception as exc:
                ex_span.abort(exc)
                for e in entries:
                    e.status = exc
            finally:
                with self._tracer.span(
                        hvd_tracing.CALLBACK, tensor=lead.name,
                        trace_id=lead.trace_id, parent=ex_span,
                        n_tensors=len(entries)):
                    with self._queue_lock:
                        for e in entries:
                            self._tensor_table.pop(e.name, None)
                            e.event.set()
        # gradient health ONCE per flush (not per plan group: an
        # unfusable batch plans into singleton groups, and per-group
        # observation would pay the host-boundary cost |batch| times).
        # Runs after every waiter above is released — jax arrays are
        # immutable, so observing off the critical path is safe. No
        # cycle key on the local path, so no cross-rank digest to fold.
        if observed:
            try:
                mon.observe(observed)
            except Exception as exc:
                log.error("numerics observe failed: %s", exc)

    # -- negotiated multi-process cycle (RunLoopOnce's coordinator
    # protocol, operations.cc:1246-1551, over the TCP control plane) --

    def _negotiated_flush_locked(self):
        """One negotiation round: announce newly queued entries, apply
        every response the coordinator has ordered since our last ack.
        Runs ONLY on the background thread — all data-plane collectives
        originate here, in response-seq order, so they match across
        processes no matter how entries were submitted."""
        from . import negotiation as neg
        if self._negotiation_dead:
            # the control plane was declared lost: anything newly queued
            # fails fast instead of waiting on negotiation forever
            self._fail_pending_negotiated(ShutdownError(
                "negotiation control plane lost"))
            return
        if time.monotonic() < self._cycle_backoff_until:
            return  # exponential backoff after control-plane failures
        # Announcements survive transient control-plane failures: a retry
        # resends the SAME request id + metas/hits, and the coordinator
        # dedupes on the id — a response lost after the server processed
        # it must not cause a re-submit (the names were already negotiated
        # away; re-submitting would plant ghost table rows no rank
        # completes). While a retry is outstanding, new queue entries
        # wait their turn.
        if self._unannounced is not None:
            metas, hit_ids = self._unannounced
        else:
            with self._queue_lock:
                batch = list(self._queue)
                self._queue.clear()
            if self.timeline and batch:
                self.timeline.mark_cycle_start()
            metas = []
            hit_ids = []
            for e in batch:
                if e.kind == "list":  # local-only op: no cross-process leg
                    if self.timeline:
                        self.timeline.negotiate_end(e.name)
                    if e.span is not None:
                        e.span.close(local=True)
                    self._finish_entries([e], lambda es: self._exec_single(
                        es[0], es[0].op, "list"))
                    continue
                self._negotiated_pending[e.name] = e
                cached = self._neg_cache.get(e.name)
                if cached is not None:
                    if cached[1] == e.signature():
                        hit_ids.append(cached[0])  # steady-state bypass
                        self._neg_hit_count += 1
                        if e.span is not None:
                            e.span.annotate(cache_hit=True)
                        continue
                    # signature changed: full meta (which also makes the
                    # coordinator invalidate the id for every peer)
                    del self._neg_cache[e.name]
                    self._neg_cache_ids.pop(cached[0], None)
                metas.append(self._meta_of(e, neg))
                if e.span is not None:
                    e.span.annotate(cache_hit=False)
            # names whose cache ids came back unknown (evicted or
            # invalidated at the coordinator): re-announce in full
            for name in sorted(self._reannounce):
                e = self._negotiated_pending.get(name)
                if e is not None and all(m.name != name for m in metas):
                    metas.append(self._meta_of(e, neg))
            self._reannounce.clear()
            self._cycle_req_id += 1
        # low-rate metrics piggyback: rank 0's registry is already local
        # to the aggregating server, so only workers push snapshots
        push = None
        if self._metrics.enabled and jax.process_index() != 0:
            now = time.monotonic()
            if now >= self._metrics_next_push:
                self._metrics_next_push = now + (
                    getattr(self._config, "metrics_interval", 5.0) or 5.0)
                push = self._metrics.snapshot(max_events=32)
        # dump solicitation: the coordinator asked for this worker's
        # flight recorder (dump_requested flag on a prior response) —
        # attach one snapshot and clear the request
        flight = None
        if self._flight_send_pending:
            self._flight_send_pending = False
            flight = self._tracer.flight_snapshot("coordinator_request")
        # numerics digest piggyback: every bucket executed since the last
        # cycle rides this request for the coordinator's sentinel
        digest, self._numerics_pending = self._numerics_pending, None
        t0 = time.perf_counter()
        try:
            resp = self._negotiator.cycle(
                metas, self._applied_seq,
                req_id=self._cycle_req_id,
                hits=neg.encode_hits(hit_ids),
                metrics=push, flight=flight, digest=digest,
                codec_fp=quant_mod.config_fingerprint(self._config))
        # hvdlint: disable=HVD006(retried next cycle; counted in hvd_negotiation_failures and escalated by liveness fail-fast)
        except Exception as exc:  # noqa: BLE001 — transient TCP hiccups
            self._unannounced = (metas, hit_ids)
            if digest is not None:
                # don't lose the digest to a transient transport failure;
                # the retry cycle carries it instead
                self._numerics_pending = digest
            self._m_neg_failures.inc()
            now = time.monotonic()
            self._cycle_failures += 1
            if self._cycle_fail_since is None:
                self._cycle_fail_since = now
            # exponential backoff between retries (50 ms → 1.6 s): three
            # instant connection-resets at the 5 ms cycle cadence must
            # not tear the job down within ~15 ms
            self._cycle_backoff_until = now + min(
                0.05 * (2 ** min(self._cycle_failures - 1, 5)), 1.6)
            if (self._cycle_failures >= 3 and
                    now - self._cycle_fail_since >= self._poison_grace_s):
                # The coordinator is gone (rank 0 exited/crashed), and has
                # been for a real time window — not just a transient pause:
                # fail pending work with a clear error instead of hanging,
                # try to tell the control plane so peers are released
                # rather than left blocked in matching collectives, and
                # poison this coordinator — continuing to negotiate after
                # dropping state would diverge from the peers anyway.
                # RanksLostError: the coordinator IS rank 0's process, so
                # losing the plane is losing rank 0 — supervisors key
                # their auto-shrink on this type's exit code.
                # first-class telemetry before the dump: the flight
                # recorder snapshots the event ring, so the postmortem
                # sees this rank's own verdict alongside its open spans
                self._metrics.event(
                    "ranks_lost", ranks=[0],
                    reason="control plane unreachable",
                    trace_id=self._blocking_trace_id())
                self._tracer.dump("coordinator_lost")
                self._fail_pending_negotiated(RanksLostError(
                    [0], reason="negotiation control plane unreachable: "
                                f"{exc}",
                    trace_id=self._blocking_trace_id()))
                self._unannounced = None
                self._negotiation_dead = True
                try:
                    self._cycle_req_id += 1
                    self._negotiator.cycle([], self._applied_seq,
                                           shutdown=True,
                                           req_id=self._cycle_req_id)
                # hvdlint: disable=HVD006(shutdown farewell; control plane already gone)
                except Exception:  # noqa: BLE001 — plane truly gone
                    pass
            return
        self._m_neg_cycles.inc()
        self._m_neg_cycle_s.observe(time.perf_counter() - t0)
        self._tracer.record_cycle(
            req_id=self._cycle_req_id, ack=self._applied_seq,
            n_metas=len(metas), n_hits=len(hit_ids),
            rtt_ms=(time.perf_counter() - t0) * 1000.0)
        if getattr(resp, "dump_requested", False) and not self._flight_sent:
            self._flight_sent = True
            self._flight_send_pending = True
            self._tracer.dump("coordinator_request")
        self._unannounced = None
        self._cycle_failures = 0
        self._cycle_fail_since = None
        self._cycle_backoff_until = 0.0
        executed_bytes = self._apply_cycle_response(resp)
        if self.autotuner is not None and executed_bytes > 0:
            if self.autotuner.record_cycle(executed_bytes,
                                           time.perf_counter() - t0):
                # rank 0 applies directly: coordinator fusion reads this
                # config live, and workers mirror it off the responses
                self._config.fusion_threshold = int(
                    self.autotuner.threshold)
                self._config.cycle_time_ms = float(
                    self.autotuner.cycle_time_ms)

    def _remote_metrics_snapshots(self):
        """Rank 0 only: the peers' piggybacked snapshots held by the
        coordinator service (the MetricsServer's aggregation source).
        Runs on the metrics HTTP server thread while the handler thread
        mutates the ledger, so it must go through the locked accessor —
        the bare ``dict(svc.metrics_snapshots)`` it replaced could die
        with "dictionary changed size during iteration" (HVD021)."""
        neg = self._negotiator
        svc = getattr(neg, "service", None) if neg is not None else None
        return svc.metrics_snapshot_view() if svc is not None else {}

    @staticmethod
    def _meta_of(e, neg):
        t = e.tensor
        dtype = getattr(t, "dtype", None) or np.result_type(t)
        return neg.EntryMeta(e.name, e.op, dtype, np.shape(t),
                             e.root_rank, e.average)

    def _finish_entries(self, entries, exec_fn):
        """Run exec_fn over entries, then complete them (status, table
        removal, event) — the bookkeeping half of _execute."""
        t0 = time.perf_counter()
        lead = entries[0]
        ex_span = self._tracer.span(
            hvd_tracing.EXECUTE, tensor=lead.name, trace_id=lead.trace_id,
            op=lead.op, fused=len(entries))
        try:
            exec_fn(entries)
            for e in entries:
                e.status = True
            op = entries[0].op
            nbytes = sum(_entry_nbytes(e) for e in entries)
            self._m_coll_bytes.labels(op=op).inc(nbytes)
            self._m_coll_s.labels(op=op).observe(time.perf_counter() - t0)
            # gradient-health side pass (utils/numerics.py): one stacked
            # host transfer over the just-executed bucket; records fold
            # into the digest the next CycleRequest piggybacks so the
            # coordinator's sentinel can compare replicas
            mon = hvd_numerics.get_monitor()
            if op == ALLREDUCE and mon.enabled:
                cyc = self._numerics_cycle
                staged, self._numerics_staged = self._numerics_staged, None
                if staged is not None:
                    recs = mon.ingest(staged[0], staged[1], cycle=cyc)
                else:
                    recs = mon.observe(
                        [(e.name, e.tensor, e.result) for e in entries],
                        cycle=cyc)
                if recs and cyc is not None:
                    self._numerics_pending = hvd_numerics.fold_digest(
                        self._numerics_pending, cyc, recs,
                        rank=jax.process_index())
                lead_rec = recs.get(lead.name)
                if lead_rec is not None:
                    ex_span.annotate(
                        grad_l2=lead_rec[hvd_numerics.R_RED_L2],
                        nonfinite=lead_rec[hvd_numerics.R_RED_NONFINITE])
            ex_span.close(bytes=nbytes)
        # hvdlint: disable=HVD006(status carries the fault to every waiter)
        except Exception as exc:  # noqa: BLE001 — status carries it
            ex_span.abort(exc)
            for e in entries:
                e.status = exc
        finally:
            with self._tracer.span(
                    hvd_tracing.CALLBACK, tensor=lead.name,
                    trace_id=lead.trace_id, parent=ex_span,
                    n_tensors=len(entries)):
                with self._queue_lock:
                    for e in entries:
                        self._tensor_table.pop(e.name, None)
                        e.event.set()

    def _apply_cycle_response(self, resp):
        """Apply coordinator responses strictly in seq order; returns the
        payload bytes executed (the autotuner's numerator)."""
        executed_bytes = 0
        try:
            # liveness fail-fast: the coordinator's ledger declared ranks
            # dead — pending work can never complete, so fail it all
            # within one cycle of the declaration instead of hanging
            from . import negotiation as neg
            neg.raise_if_ranks_lost(resp,
                                    trace_id=self._blocking_trace_id())
        except RanksLostError as exc:
            self._tracer.dump("ranks_lost")
            self._fail_pending_negotiated(exc)
            self._negotiation_dead = True
            return 0
        if getattr(resp, "stale_ack", False):
            # this rank fell behind the coordinator's bounded response
            # log (negotiation.py MAX_RESPONSE_LOG): the missed responses
            # are unrecoverable, so pending work must fail, not hang —
            # and the peers must hear shutdown, or their matching
            # collectives (and never-completing table rows) hang forever
            self._tracer.dump("stale_ack")
            self._fail_pending_negotiated(ShutdownError(
                "negotiation response log overflow: this rank fell "
                "behind the coordinator's retained window"))
            self._negotiation_dead = True
            try:
                self._cycle_req_id += 1
                self._negotiator.cycle([], self._applied_seq,
                                       shutdown=True,
                                       req_id=self._cycle_req_id)
            # hvdlint: disable=HVD006(shutdown farewell; control plane already gone)
            except Exception:  # noqa: BLE001 — plane gone too
                pass
            return 0
        for off, r in enumerate(resp.responses):
            seq = resp.base_seq + off
            if seq <= self._applied_seq:
                continue
            entries = [self._negotiated_pending.pop(n)
                       for n in r.names if n in self._negotiated_pending]
            if len(entries) != len(r.names):
                # control-plane state diverged (e.g. pending was failed
                # after transient unreachability but the coordinator was
                # actually alive and later ordered the tensors). Raising
                # here would wedge the loop — the background thread logs
                # and retries the same seqs forever while the popped
                # entries' synchronize() hangs. Fail cleanly instead.
                missing = [n for n in r.names
                           if all(e.name != n for e in entries)]
                exc = ShutdownError(
                    f"control-plane state diverged: coordinator ordered "
                    f"{r.names} but {missing} are not pending here")
                for e in entries:
                    if e.span is not None:
                        e.span.abort(exc)
                    e.status = exc
                with self._queue_lock:
                    for e in entries:
                        self._tensor_table.pop(e.name, None)
                        e.event.set()
                self._fail_pending_negotiated(exc)
                self._applied_seq = seq
                continue
            if self.timeline:
                for e in entries:
                    self.timeline.negotiate_end(e.name)
            for e in entries:
                # close the negotiation-wait span: the coordinator has
                # ordered this tensor (or errored it). ``cycle`` (=seq) is
                # globally consistent, so it is the cross-rank stitch key.
                if e.span is None:
                    continue
                if r.kind == r.ERROR:
                    e.span.abort(r.error)
                else:
                    waited = self._cycle_req_id - int(
                        e.span.attrs.get("enqueue_req",
                                         self._cycle_req_id))
                    e.span.close(cycle=seq, cycles_waited=waited)
            if r.kind == r.EXECUTE and getattr(r, "cache_ids", None):
                # learn coordinator-assigned cache ids; riding the
                # seq-ordered log makes every rank's mapping identical
                for e, cid in zip(entries, r.cache_ids):
                    old = self._neg_cache.get(e.name)
                    if old is not None and old[0] != cid:
                        self._neg_cache_ids.pop(old[0], None)
                    self._neg_cache[e.name] = (cid, e.signature())
                    self._neg_cache_ids[cid] = e.name
            # digest key for the bucket about to execute: seq is globally
            # consistent, so the sentinel lines it up across ranks
            self._numerics_cycle = seq
            if r.kind == r.ERROR:
                exc = MismatchError(r.error)
                for e in entries:
                    e.status = exc
                with self._queue_lock:
                    for e in entries:
                        self._tensor_table.pop(e.name, None)
                        e.event.set()
            elif r.op == ALLREDUCE and (
                    len(entries) > 1 or getattr(r, "codec", None)):
                # singles with a negotiated wire codec also route through
                # the fused path: it owns the encode/EF machinery and is
                # the identity concat for one entry
                executed_bytes += sum(_entry_nbytes(e) for e in entries)
                codec = getattr(r, "codec", None)
                self._finish_entries(
                    entries,
                    lambda es, c=codec: self._exec_fused_replicated_allreduce(
                        es, es[0].average, c))
            elif r.op == ALLGATHER and len(entries) > 1:
                executed_bytes += sum(_entry_nbytes(e) for e in entries)
                self._finish_entries(
                    entries, self._exec_fused_replicated_allgather)
            else:
                executed_bytes += _entry_nbytes(entries[0])
                self._finish_entries(
                    entries, lambda es: self._exec_single(es[0], r.op,
                                                          "replicated"))
            self._applied_seq = seq
        self._numerics_cycle = None
        for cid in getattr(resp, "unknown_ids", ()):
            # the coordinator no longer holds this id (evicted, or a peer
            # invalidated it with a changed signature): drop the mapping
            # and re-announce the tensor in full next cycle
            name = self._neg_cache_ids.pop(cid, None)
            if name is not None:
                self._neg_cache.pop(name, None)
                if name in self._negotiated_pending:
                    self._reannounce.add(name)
        if resp.params and jax.process_index() != 0:
            # mirror rank 0's (possibly autotuned) knobs; fusion decisions
            # happen at the coordinator, so adoption timing is free
            self._config.fusion_threshold = int(resp.params[0])
            self._config.cycle_time_ms = float(resp.params[1])
        if resp.shutdown:
            self._fail_pending_negotiated(ShutdownError())
        return executed_bytes

    def _fail_pending_negotiated(self, exc):
        self._reannounce.clear()
        with self._queue_lock:
            pending = list(self._negotiated_pending.values()) + \
                list(self._queue)
            self._negotiated_pending.clear()
            self._queue.clear()
            for e in pending:
                self._tensor_table.pop(e.name, None)
        for e in pending:
            if e.span is not None:
                e.span.abort(exc)
            e.status = exc
            e.event.set()

    def _blocking_trace_id(self):
        """Trace id of the oldest tensor still waiting on negotiation —
        the one a RanksLostError names so the flight dump can be read
        starting from the span that was actually blocked."""
        for e in self._negotiated_pending.values():
            if e.trace_id:
                return e.trace_id
        return None

    @functools.cached_property
    def _proc_engine(self):
        """Device-side cross-process collective engine (one bandwidth-
        optimal XLA collective per op — ops/process_collectives.py)."""
        from .process_collectives import ProcessCollectiveEngine
        return ProcessCollectiveEngine()

    @functools.cached_property
    def _hier_engine(self):
        """Two-level [hosts, local] engine for eager fused allreduces,
        or None when the split is off or degenerate. Eligible when the
        knob is on, the world is multi-process, local_size (config, or
        the launcher's HVD_LOCAL_SIZE) divides it, and more than one
        host remains — a single-host "split" is the flat engine with
        extra steps. local_size=1 is legal: every process is its own
        host and the codec rides the full inter-host exchange, which is
        how 2-process tests exercise the hierarchy."""
        if not getattr(self._config, "overlap_hierarchical", False):
            return None
        nproc = jax.process_count()
        if nproc <= 1:
            return None
        local = int(getattr(self._config, "overlap_local_size", 0)) or \
            state_mod.process_local_size()
        if local < 1 or nproc % local or nproc // local <= 1:
            log.warning(
                "hierarchical reduction disabled: local_size %d gives "
                "no multi-host split of %d processes", local, nproc)
            return None
        from .process_collectives import HierarchicalProcessEngine
        try:
            return HierarchicalProcessEngine(local)
        except Exception as exc:  # topology probe, not control flow
            log.warning("hierarchical engine unavailable, falling back "
                        "flat: %s", exc)
            return None

    def _exec_fused_replicated_allreduce(self, entries, average,
                                         codec=None):
        """Coordinator-fused multi-process allreduce: one flattened
        buffer, ONE cross-process device-side collective for the whole
        bucket (MPIAllreduce's fusion-buffer memcpy-in/allreduce/
        memcpy-out, mpi_operations.cc:25-66, on the process axis).
        Concat, psum, and un-fuse slicing all happen on device — the
        host never stages the payload. ``codec`` is the negotiated wire
        codec from the CycleResponse plan (ops/quantization.py): a
        quantized codec runs the two-phase encoded collective with
        error feedback; a cast codec narrows the buffer for the psum."""
        tl = self.timeline
        names = [e.name for e in entries]
        if tl:
            for n in names:
                tl.start_activity(n, timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        flats = [jnp.reshape(jnp.asarray(e.tensor), (-1,)) for e in entries]
        fused = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.ALLREDUCE)
        if codec is not None and quant_mod.is_quantized(codec):
            block = int(getattr(self._config, "quant_block",
                                quant_mod.BLOCK_DEFAULT))
            ef_on = bool(getattr(self._config, "quant_ef", True))
            total = int(fused.shape[0])
            hier = self._hier_engine
            if hier is not None:
                # Two-level path: the intra-host legs (reduce-scatter
                # in, all-gather out) stay full-width; only this
                # process's 1/local_size shard crosses hosts encoded.
                # EF is keyed per-shard (#hier suffix) because the
                # residual lives at shard, not buffer, length.
                key = "|".join(names) + "#hier"
                shard_len = quant_mod.pad_to(
                    total, block * hier.nproc) // hier.local_size
                residual = (self._ef.peek(key, (shard_len,))
                            if ef_on else None)
                with jax.profiler.TraceAnnotation(
                        f"hvd.hier_allreduce.{codec}.x{len(entries)}"):
                    full, comp, dec_own = hier.allreduce_quantized(
                        fused, codec, block, average=average,
                        residual=residual)
                summed = full[:total].astype(fused.dtype)
                if ef_on:
                    self._ef.update(key, comp, dec_own, block,
                                    anchor=names[0])
                wire_inter = quant_mod.encoded_nbytes(
                    shard_len, codec, block)
                quant_mod.account(codec, fused.nbytes, wire_inter)
                quant_mod.account_leg("intra", None, fused.nbytes)
                quant_mod.account_leg("inter", codec, wire_inter)
                mon = hvd_numerics.get_monitor()
                if mon.enabled:
                    mon.observe_compression(names[0], comp, dec_own,
                                            codec)
            else:
                key = "|".join(names)
                comp = self._ef.compensate(key, fused) if ef_on else fused
                nproc = jax.process_count()
                payload, scales = quant_mod.encode(
                    comp, block, codec, multiple=block * nproc)
                with jax.profiler.TraceAnnotation(
                        f"hvd.quantized_allreduce.{codec}.x{len(entries)}"):
                    summed = self._proc_engine.allreduce_quantized(
                        payload, scales, codec, block,
                        average=average)[:total].astype(fused.dtype)
                # this rank's own wire contribution as the peers saw it
                # — the error-feedback reference and the numerics
                # plane's post-compression side
                dec_own = quant_mod.decode(payload, scales, block, total)
                if ef_on:
                    self._ef.update(key, comp, dec_own, block,
                                    anchor=names[0])
                quant_mod.account(codec, fused.nbytes,
                                  quant_mod.wire_nbytes(payload, scales))
                mon = hvd_numerics.get_monitor()
                if mon.enabled:
                    mon.observe_compression(names[0], comp, dec_own,
                                            codec)
        elif codec is not None:
            wire = fused.astype(quant_mod.wire_dtype(codec))
            with jax.profiler.TraceAnnotation(
                    f"hvd.fused_allreduce.{codec}.x{len(entries)}"):
                summed = self._proc_engine.allreduce(
                    wire, average=average).astype(fused.dtype)
            quant_mod.account(codec, fused.nbytes, wire.nbytes)
        else:
            hier = self._hier_engine
            if hier is not None:
                with jax.profiler.TraceAnnotation(
                        f"hvd.hier_allreduce.x{len(entries)}"):
                    summed = hier.allreduce(
                        fused, average=average).astype(fused.dtype)
                quant_mod.account(None, fused.nbytes, fused.nbytes)
                quant_mod.account_leg("intra", None, fused.nbytes)
                # full-width shard per process crosses hosts
                quant_mod.account_leg(
                    "inter", None, fused.nbytes // hier.local_size)
            else:
                with jax.profiler.TraceAnnotation(
                        f"hvd.fused_allreduce.x{len(entries)}"):
                    summed = self._proc_engine.allreduce(fused,
                                                         average=average)
                quant_mod.account(None, fused.nbytes, fused.nbytes)
        if hvd_numerics.get_monitor().enabled:
            # fused side-product: per-slice health stats in one segment
            # pass over the buffers the collective already materialized;
            # _finish_entries picks the staged matrix up (still on
            # device — the host transfer happens in ingest)
            from . import fusion as fusion_mod
            sizes = [int(f.shape[0]) for f in flats]
            self._numerics_staged = (names, jnp.concatenate(
                [fusion_mod.bucket_stats(summed, sizes),
                 fusion_mod.bucket_stats(fused, sizes)], axis=1))
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.MEMCPY_OUT_FUSION_BUFFER)
        offset = 0
        for e, flat in zip(entries, flats):
            n = flat.shape[0]
            e.result = jnp.reshape(summed[offset:offset + n],
                                   np.shape(e.tensor))
            offset += n
        if tl:
            for n in names:
                tl.end_activity(n)

    def _exec_fused_replicated_allgather(self, entries):
        """Coordinator-fused multi-process allgatherv: ONE counts
        exchange and ONE payload collective for the whole bucket
        (Response::add_allgather_response fusion, message.h:172, with
        the per-rank displacement math of
        collective_operations.cc:68-134 / MPI_Allgatherv
        mpi_operations.cc:86-173). Members may have different inner
        shapes (flattened into the buffer) and per-rank first dims;
        every process executes this identically because the bucket
        composition rides the coordinator's seq-ordered response."""
        eng = self._proc_engine
        nproc = jax.process_count()
        tl = self.timeline
        names = [e.name for e in entries]
        if tl:
            for n in names:
                tl.start_activity(n, timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        tensors = [jnp.asarray(e.tensor) for e in entries]
        shapes = [t.shape for t in tensors]
        inners = [s[1:] for s in shapes]
        # scalars gather to [nproc] (rank-1 contract, same as unfused)
        d0s = [s[0] if len(s) else 1 for s in shapes]
        inner_sizes = np.asarray(
            [int(np.prod(i, dtype=np.int64)) if len(i) else 1
             for i in inners], np.int64)
        flats = [jnp.reshape(t, (-1,)) for t in tensors]
        local = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.ALLGATHER)
        # one dim0-counts exchange for the whole bucket (the unfused
        # path pays one per tensor)
        counts = np.asarray(eng.allgather_stacked(
            np.asarray(d0s, np.int32))).astype(np.int64)  # [nproc, k]
        totals = (counts * inner_sizes[None, :]).sum(axis=1)
        maxlen = int(totals.max())
        if local.shape[0] < maxlen:
            local = jnp.concatenate(
                [local, jnp.zeros((maxlen - local.shape[0],), local.dtype)])
        with jax.profiler.TraceAnnotation(
                f"hvd.fused_allgather.x{len(entries)}"):
            gathered = eng.allgather_stacked(local)  # [nproc, maxlen]
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.MEMCPY_OUT_FUSION_BUFFER)
        # un-fuse: rank p's chunk holds member m's rows at displacement
        # sum_{j<m} counts[p,j]*inner_sizes[j]
        for m, e in enumerate(entries):
            pieces = []
            for p in range(nproc):
                off = int((counts[p, :m] * inner_sizes[:m]).sum())
                n_el = int(counts[p, m]) * int(inner_sizes[m])
                seg = gathered[p, off:off + n_el]
                if len(shapes[m]):
                    seg = jnp.reshape(
                        seg, (int(counts[p, m]),) + tuple(inners[m]))
                pieces.append(seg)
            e.result = jnp.concatenate(pieces, axis=0)
        if tl:
            for n in names:
                tl.end_activity(n)

    # -- execution engines --

    def _sharding(self, spec):
        return mesh_lib.named_sharding(spec, self._mesh)

    @functools.cached_property
    def _stacked_psum(self):
        mesh, axis = self._mesh, self._axis

        @jax.jit
        def f(x):
            return jax.shard_map(
                lambda s: lax.psum(s, axis), mesh=mesh,
                in_specs=P(axis), out_specs=P(axis))(x)
        return f

    @functools.cached_property
    def _stacked_bcast(self):
        mesh, axis = self._mesh, self._axis

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, root):
            def shard_fn(s):
                idx = lax.axis_index(axis)
                masked = jnp.where(idx == root, s, jnp.zeros_like(s))
                return lax.psum(masked, axis)
            return jax.shard_map(shard_fn, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis))(x)
        return f

    def _put_stacked(self, arr):
        """Shard a [world, ...] array over the worker axis."""
        spec = P(self._axis, *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, self._sharding(spec))

    @functools.cached_property
    def _replicate(self):
        """Reshard a worker-sharded result to fully replicated. Horovod's
        contract is that every worker holds the complete reduced tensor
        after the op; on >1 process a sharded result would not even be
        readable by the caller (non-addressable shards). XLA lowers this to
        the all-gather leg a ring allreduce ends with anyway."""
        return jax.jit(lambda x: x, out_shardings=self._sharding(P()))

    def _exec_fused_stacked_allreduce(self, entries, average, codec=None):
        """Fuse [world, n_i] tensors into one [world, total] buffer, one
        psum, split back (MPIAllreduce memcpy-in/allreduce/memcpy-out,
        mpi_operations.cc:25-66). ``codec`` is the wire codec from the
        plan (ops/quantization.py): quantized codecs run the simulated
        stacked wire (each row encoded as its own contribution, f32
        accumulation, error feedback) so single-process runs see the
        exact numerics of the cross-process encoded collective."""
        tl = self.timeline
        names = [e.name for e in entries]
        if tl:
            for n in names:
                tl.start_activity(n, timeline_mod.MEMCPY_IN_FUSION_BUFFER)
        flats = [jnp.reshape(jnp.asarray(e.tensor), (self._world, -1))
                 for e in entries]
        fused = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=1)
        fused = self._put_stacked(fused)
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.ALLREDUCE)
        if codec is not None and quant_mod.is_quantized(codec):
            block = int(getattr(self._config, "quant_block",
                                quant_mod.BLOCK_DEFAULT))
            ef_on = bool(getattr(self._config, "quant_ef", True))
            key = "|".join(names)
            total = int(fused.shape[1])
            comp = self._ef.compensate(key, fused) if ef_on else fused
            with jax.profiler.TraceAnnotation(
                    f"hvd.quantized_allreduce.{codec}.x{len(entries)}"):
                summed, dec_rows = quant_mod.stacked_wire_allreduce(
                    comp, block, codec, bool(average), total)
            # rows are identical; replicate for the same output
            # sharding as the psum path
            summed = self._replicate(summed.astype(fused.dtype))
            if ef_on:
                self._ef.update(key, comp, dec_rows, block,
                                anchor=names[0])
            quant_mod.account(
                codec, fused.nbytes,
                self._world * quant_mod.encoded_nbytes(total, codec, block))
            mon = hvd_numerics.get_monitor()
            if mon.enabled:
                mon.observe_compression(names[0], comp, dec_rows, codec)
        elif codec is not None:
            wire = fused.astype(quant_mod.wire_dtype(codec))
            summed = self._replicate(
                self._stacked_psum(wire)).astype(fused.dtype)
            if average:
                summed = summed / self._world
            quant_mod.account(codec, fused.nbytes, wire.nbytes)
        else:
            summed = self._replicate(self._stacked_psum(fused))
            if average:
                summed = summed / self._world
            quant_mod.account(None, fused.nbytes, fused.nbytes)
        if tl:
            for n in names:
                tl.end_activity(n)
                tl.start_activity(n, timeline_mod.MEMCPY_OUT_FUSION_BUFFER)
        offset = 0
        for e, flat in zip(entries, flats):
            n = flat.shape[1]
            e.result = jnp.reshape(summed[:, offset:offset + n],
                                   np.shape(e.tensor))
            offset += n
        if tl:
            for n in names:
                tl.end_activity(n)
        return entries

    def _exec_single(self, entry, op, entry_kind, codec=None):
        tl = self.timeline
        if tl:
            tl.start_activity(entry.name, op.upper())
        # Count replicated executions BEFORE running the op, and sync
        # tuned params in the finally: every process executes the same
        # replicated ops in the same program order (and error paths —
        # verification mismatches — raise on all processes alike), so the
        # counter and therefore the sync schedule stay in lockstep.
        sync_params = False
        if self._autotune_sync_every and entry_kind == "replicated":
            self._replicated_count += 1
            sync_params = (
                self._replicated_count % self._autotune_sync_every == 0)
        try:
            # Verify on the FIRST occurrence of each collective SIGNATURE
            # (op/dtype/shape/root — not name: auto-generated names are
            # fresh per call, which would re-verify every op and grow the
            # seen-set without bound). The skip schedule must be globally
            # agreed because verification is itself a collective;
            # signature-order is deterministic across processes under the
            # same-program SPMD contract, unlike per-process plan-cache
            # hits, which diverge with batch-timing skew. Repeats skip it
            # — response-cache-bypass economics (RunBypass,
            # operations.cc:1168-1215) with a coordinated condition.
            # Under negotiation the coordinator already validated metadata
            # centrally (EntryMeta.agrees_with) before ordering execution.
            if entry_kind == "replicated" and self._negotiator is None:
                vkey = self._verify_key(entry, op)
                if vkey not in self._verified_sigs:
                    self._verify_cross_process(entry, op)
                    if len(self._verified_sigs) >= 65536:
                        self._verified_sigs.clear()
                    self._verified_sigs.add(vkey)
            # TraceAnnotation places this host-side span inline with the
            # XLA device events when a jax.profiler trace is active
            # (utils/timeline.py profile(); SURVEY "timeline fidelity")
            with jax.profiler.TraceAnnotation(f"hvd.{op}.{entry.name}"):
                if op == ALLREDUCE:
                    if codec is not None and entry_kind == "replicated":
                        # wire codec selected for this tensor: the fused
                        # path owns the encode/EF machinery and is the
                        # identity concat for one entry
                        self._exec_fused_replicated_allreduce(
                            [entry], entry.average, codec)
                    else:
                        entry.result = self._allreduce_one(entry,
                                                           entry_kind)
                elif op == ALLGATHER:
                    entry.result = self._allgather_one(entry, entry_kind)
                elif op == BROADCAST:
                    entry.result = self._broadcast_one(entry, entry_kind)
                elif op == REDUCESCATTER:
                    entry.result = self._reducescatter_one(entry,
                                                           entry_kind)
                elif op == ALLTOALL:
                    entry.result = self._alltoall_one(entry, entry_kind)
                else:
                    raise ValueError(f"Unknown op {op}")
        finally:
            if sync_params:
                self._sync_tuned_params()
            if tl:
                tl.end_activity(entry.name)

    def freeze_autotune(self):
        """End the tuning phase: adopt the best scored point into the
        live config and stop per-cycle scoring (the reference
        ParameterManager's converged state). Single/multi-process safe:
        on the deferred (multi-process) path the adopted values still
        travel through the next agreed _sync_tuned_params point rather
        than being applied locally mid-stream. Returns the adopted
        (threshold, cycle_ms, score) or None."""
        if self.autotuner is None:
            return None
        best = self.autotuner.freeze()
        if best is None:
            return None
        if self._autotune_defer:
            self._proposed_params = (self.autotuner.threshold,
                                     self.autotuner.cycle_time_ms)
            self._autotune_pending_adoption = True
        else:
            self._config.fusion_threshold = int(self.autotuner.threshold)
            self._config.cycle_time_ms = float(self.autotuner.cycle_time_ms)
        return best

    def _sync_tuned_params(self):
        """Adopt process 0's (possibly staged) tuned parameters on every
        process, at this agreed point in the replicated-collective order —
        the reference coordinator's parameter broadcast over a custom MPI
        struct (parameter_manager.cc:66-81). A fixed-size int32 allgather:
        EVERY process must reach it (no locally-decided skips), which the
        count-scheduled call site guarantees."""
        from jax.experimental import multihost_utils
        if self._proposed_params is not None:
            thr, ct = self._proposed_params
        else:
            thr, ct = (self._config.fusion_threshold,
                       self._config.cycle_time_ms)
        # int32 triple [threshold-hi, threshold-lo, cycle time µs]: exact
        # through the wire (jax without x64 would truncate int64/float64;
        # a single int32 would overflow for thresholds >= 2 GiB)
        thr_hi, thr_lo = divmod(int(thr), 1 << 31)
        mine = np.array([thr_hi, thr_lo, int(ct * 1000)], np.int32)
        gathered = np.asarray(multihost_utils.process_allgather(mine))
        if gathered.ndim == 1:  # single process: allgather returns [3]
            gathered = gathered[None, :]
        self._config.fusion_threshold = (
            (int(gathered[0, 0]) << 31) + int(gathered[0, 1]))
        self._config.cycle_time_ms = float(gathered[0, 2]) / 1000.0
        self._proposed_params = None
        self._autotune_pending_adoption = False
        self._adopted_this_flush = True

    _META_DIMS = 10

    def _verify_key(self, entry, op):
        """Signature for the verified-set: what _verify_cross_process
        would compare, minus the name."""
        t = entry.tensor
        shape = tuple(np.shape(t))
        vshape = shape[1:] if op == ALLGATHER else shape
        dtype = getattr(t, "dtype", None) or np.result_type(t)
        return (op, str(dtype), len(shape), vshape, int(entry.root_rank))

    def _verify_cross_process(self, entry, op):
        """Cross-process shape/dtype/op agreement before the collective —
        the coordinator's error checking (ConstructResponse,
        operations.cc:209-371) without its negotiation: one fixed-size
        metadata allgather; mismatches raise MismatchError naming the
        tensor instead of hanging or crashing inside the transport.
        Allgather tolerates differing first dims, everything else must
        agree exactly. EVERY branch reaches the same allgather — a
        locally-decided skip would leave peers blocked one-sided in it."""
        if jax.process_count() == 1:
            return
        import zlib
        from jax.experimental import multihost_utils
        t = entry.tensor
        shape = tuple(np.shape(t))
        # crc32 (not hash(): hash randomization differs across processes),
        # masked to 31 bits: jax without x64 truncates int64 through the
        # allgather. np.result_type reads the dtype without materializing
        # a device array on the host.
        dtype = getattr(t, "dtype", None) or np.result_type(t)
        dtype_id = zlib.crc32(str(dtype).encode()) & 0x7FFFFFFF
        ops = [ALLREDUCE, ALLGATHER, BROADCAST, REDUCESCATTER, ALLTOALL]
        meta = np.zeros((self._META_DIMS,), np.int32)
        meta[0] = ops.index(op)
        meta[1] = dtype_id
        meta[2] = int(entry.root_rank)
        meta[3] = len(shape)
        if len(shape) <= self._META_DIMS - 4:
            meta[4:4 + len(shape)] = shape
        else:
            # rank exceeds the descriptor: compare a shape digest instead,
            # in the same fixed-size collective (no one-sided skips)
            vshape = shape[1:] if op == ALLGATHER else shape
            meta[4] = zlib.crc32(str(vshape).encode()) & 0x7FFFFFFF
        all_meta = np.asarray(multihost_utils.process_allgather(meta))
        mine = jax.process_index()
        for p in range(all_meta.shape[0]):
            other = all_meta[p]
            if not (other[:4] == meta[:4]).all():
                same = False
            elif len(shape) > self._META_DIMS - 4:
                same = other[4] == meta[4]  # digest (d0 pre-excluded)
            else:
                start = 5 if op == ALLGATHER else 4
                same = (other[start:] == meta[start:]).all()
            if not same:
                raise MismatchError(
                    f"Mismatched {op} '{entry.name}' across processes: "
                    f"process {mine} submitted op={meta[0]} dtype_id="
                    f"{meta[1]} root={meta[2]} shape={shape}, process {p} "
                    f"submitted op={other[0]} dtype_id={other[1]} "
                    f"root={other[2]} "
                    f"shape={tuple(other[4:4 + other[3]])} "
                    f"(ConstructResponse checks, operations.cc:209-371).")

    def _allreduce_one(self, entry, kind):
        if kind == "stacked":
            x = self._put_stacked(
                jnp.reshape(jnp.asarray(entry.tensor), (self._world, -1)))
            out = self._replicate(self._stacked_psum(x))
            if entry.average:
                out = out / self._world
            return jnp.reshape(out, np.shape(entry.tensor))
        # replicated: participants are host processes.
        if jax.process_count() == 1:
            return jnp.asarray(entry.tensor)
        return self._proc_engine.allreduce(entry.tensor,
                                           average=entry.average)

    def _allgather_one(self, entry, kind):
        if kind == "list":
            tensors = [jnp.asarray(t) for t in entry.tensor]
            self._check_gather_shapes(entry.name, tensors)
            return jnp.concatenate(tensors, axis=0)
        if kind == "stacked":
            # [world, d0, ...] → concat along dim 0 → [world*d0, ...]
            t = jnp.asarray(entry.tensor)
            return jnp.reshape(t, (self._world * t.shape[1],) + t.shape[2:])
        if jax.process_count() == 1:
            return jnp.asarray(entry.tensor)
        # cross-process allgatherv: first dims may differ per rank
        # (MPI_Allgatherv recvcounts/displacements, mpi_operations.cc:142;
        # output math collective_operations.cc:68-105). The device gather
        # needs equal shapes, so exchange dim0 sizes, pad to the max,
        # gather, then slice each rank's true extent back out.
        eng = self._proc_engine
        t = jnp.asarray(entry.tensor)
        if t.ndim == 0:
            return eng.allgather_stacked(t)  # → [nproc]
        counts = np.asarray(eng.allgather_stacked(
            np.asarray([t.shape[0]], np.int32)))[:, 0]
        max0 = int(counts.max())
        if t.shape[0] < max0:
            pad = jnp.zeros((max0 - t.shape[0],) + t.shape[1:], t.dtype)
            t = jnp.concatenate([t, pad], axis=0)
        gathered = eng.allgather_stacked(t)
        if (counts == max0).all():
            return jnp.reshape(gathered, (-1,) + gathered.shape[2:])
        return jnp.concatenate(
            [gathered[p, :int(counts[p])] for p in range(len(counts))],
            axis=0)

    def _broadcast_one(self, entry, kind):
        if kind == "stacked":
            x = self._put_stacked(jnp.asarray(entry.tensor))
            return self._replicate(self._stacked_bcast(x, int(entry.root_rank)))
        if jax.process_count() == 1:
            return jnp.asarray(entry.tensor)
        return self._proc_engine.broadcast(entry.tensor,
                                           int(entry.root_rank))

    def _reducescatter_one(self, entry, kind):
        """Each worker gets its 1/world shard of the elementwise-summed
        tensor (horovod's later-version reducescatter contract; building
        block of the hierarchical path, nccl_operations.cc:269)."""
        world = self._world if kind == "stacked" else jax.process_count()

        def scatter(summed, full_shape):
            d0 = full_shape[0]
            if d0 % world:
                raise MismatchError(
                    f"reducescatter '{entry.name}': first dim {d0} not "
                    f"divisible by world size {world}.")
            return jnp.reshape(summed, (world, d0 // world) + full_shape[1:])

        if kind == "stacked":
            # [world, d0, ...] rows summed; row i of the result is worker
            # i's shard — result [world, d0/world, ...]
            t = jnp.asarray(entry.tensor)
            summed = jnp.sum(t, axis=0)
            if entry.average:
                summed = summed / world
            return scatter(summed, t.shape[1:])
        t = jnp.asarray(entry.tensor)
        if jax.process_count() == 1:
            return t
        # device-side psum_scatter: this process receives ONLY its
        # 1/nproc shard over the wire (the real reducescatter contract,
        # nccl_operations.cc:269 — not a full allgather)
        if t.shape[0] % world:
            raise MismatchError(
                f"reducescatter '{entry.name}': first dim {t.shape[0]} "
                f"not divisible by world size {world}.")
        shard = self._proc_engine.reducescatter(t, average=entry.average)
        return jnp.reshape(shard, (t.shape[0] // world,) + t.shape[1:])

    def _alltoall_one(self, entry, kind):
        """Worker j's chunk i goes to worker i (MPI_Alltoall semantics;
        extension — the reference exposes no alltoall, SURVEY.md §5)."""
        world = self._world if kind == "stacked" else jax.process_count()
        if kind == "stacked":
            # [world, world*k, ...] → out[i] = concat_j input[j]'s chunk i
            t = jnp.asarray(entry.tensor)
            if t.shape[1] % world:
                raise MismatchError(
                    f"alltoall '{entry.name}': dim 1 ({t.shape[1]}) not "
                    f"divisible by world size {world}.")
            k = t.shape[1] // world
            # [w_src, w_dst, k, ...] → transpose → [w_dst, w_src, k, ...]
            chunks = jnp.reshape(t, (world, world, k) + t.shape[2:])
            out = jnp.swapaxes(chunks, 0, 1)
            return jnp.reshape(out, (world, world * k) + t.shape[2:])
        t = jnp.asarray(entry.tensor)
        if jax.process_count() == 1:
            return t
        if t.shape[0] % world:
            raise MismatchError(
                f"alltoall '{entry.name}': first dim ({t.shape[0]}) not "
                f"divisible by world size {world}.")
        # device-side lax.all_to_all: each pairwise chunk crosses the
        # wire exactly once (O(M) per process, not the O(P·M) a full
        # allgather would move)
        return self._proc_engine.alltoall(t)

    def _check_gather_shapes(self, name, tensors):
        """Allgather rank/dim checks (ConstructResponse,
        operations.cc:290-307): ranks may differ in dim 0 only."""
        first = tensors[0]
        for t in tensors[1:]:
            if t.dtype != first.dtype:
                raise MismatchError(
                    f"Mismatched data types for allgather '{name}': "
                    f"{first.dtype} vs {t.dtype}.")
            if t.ndim != first.ndim or t.shape[1:] != first.shape[1:]:
                raise MismatchError(
                    f"Mismatched allgather tensor shapes for '{name}': all "
                    f"dimensions except the first must match "
                    f"({first.shape} vs {t.shape}).")

    # -- stall detection (CheckForStalledTensors, operations.cc:688-769) --

    def _check_stalled(self):
        if self._config.stall_check_disable:
            return
        now = time.monotonic()
        warn = self._config.stall_warning_time_seconds
        kill = self._config.stall_shutdown_time_seconds
        with self._queue_lock:
            pending = list(self._tensor_table.values())
        stalled = [e for e in pending if now - e.enqueue_time > warn]
        # gauge recomputed every scan, so it clears when laggards arrive
        self._m_stalled_tensors.set(len(stalled))
        new = [e for e in stalled if e.name not in self._stall_warned]
        if new:
            names = ", ".join(
                f"{e.name} [trace {e.trace_id}]" if e.trace_id else e.name
                for e in new)
            self._metrics.event(
                "stall", tensors=sorted(e.name for e in new),
                deadline_s=warn,
                trace_ids=sorted(e.trace_id for e in new if e.trace_id))
            log.warning(
                "One or more tensors were submitted to be reduced, gathered "
                "or broadcasted by subset of ranks and are waiting for "
                "remainder of ranks for more than %ss: %s", warn, names)
            self._stall_warned.update(e.name for e in new)
        if kill > 0:
            dead = [e for e in pending if now - e.enqueue_time > kill]
            if dead:
                self._m_stall_kills.inc(len(dead))
                self._metrics.event(
                    "stall_kill", tensors=sorted(e.name for e in dead),
                    deadline_s=kill,
                    trace_ids=sorted(e.trace_id for e in dead
                                     if e.trace_id))
                self._tracer.dump("stall_kill")
                exc = StalledError(
                    f"Collectives stalled past shutdown deadline: "
                    f"{', '.join(e.name for e in dead)} (traces: "
                    f"{', '.join(e.trace_id or '?' for e in dead)})")
                with self._queue_lock:
                    for e in dead:
                        self._tensor_table.pop(e.name, None)
                        try:
                            self._queue.remove(e)
                        except ValueError:
                            pass
                for e in dead:
                    if e.span is not None:
                        e.span.abort(exc)
                    e.status = exc
                    e.event.set()

    # -- shutdown (horovod_shutdown, operations.cc:1101-1122) --

    def shutdown(self):
        self._shutdown = True
        if self._thread.is_alive():
            self._thread.join(timeout=2)
        if self._negotiator is not None and not self._negotiation_dead:
            # Final drain + shutdown announcement in one cycle: apply any
            # responses the coordinator ALREADY ordered (the peers will
            # execute those collectives — skipping them here would strand
            # peers one-sided in the data plane), then the shutdown flag
            # makes the coordinator ERROR anything that becomes ready
            # later, so peers' outstanding work fails instead of hanging
            # (the reference drains outstanding responses before finalize,
            # operations.cc:1101-1122; RequestList.shutdown →
            # ResponseList.shutdown, operations.cc:1442-1478).
            try:
                self._cycle_req_id += 1
                resp = self._negotiator.cycle([], self._applied_seq,
                                              shutdown=True,
                                              req_id=self._cycle_req_id)
                if not self._thread.is_alive():
                    # applying responses mutates _applied_seq/_pending and
                    # runs device collectives — single-origin territory.
                    # If the background thread survived the join (stuck
                    # mid-cycle), announcing shutdown above is all that is
                    # safe to do from this thread.
                    self._apply_cycle_response(resp)
            # hvdlint: disable=HVD006(final drain at shutdown; peer may already be gone)
            except Exception:  # noqa: BLE001 — peer may already be gone
                pass
        with self._queue_lock:
            pending = list(self._tensor_table.values())
            self._tensor_table.clear()
            self._queue.clear()
            self._negotiated_pending.clear()
        exc = ShutdownError()
        for e in pending:
            if e.span is not None:
                e.span.abort(exc)
            e.status = exc
            e.event.set()
        if self._metrics_server is not None:
            self._metrics_server.close()
            self._metrics_server = None
        if self._negotiator is not None:
            self._negotiator.close()
            self._negotiator = None
        if self.timeline:
            self.timeline.close()
            self.timeline = None
        if self.autotuner is not None:
            self.autotuner.close()
            self.autotuner = None
