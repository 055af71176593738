"""Environment-variable configuration surface.

The reference parses all runtime knobs from HOROVOD_* environment variables at
background-thread startup (horovod/common/operations.cc:986-1080, helpers
set_bool_from_env/set_int_from_env at operations.cc:788-801). We keep the same
names (both HOROVOD_* and an HVD_* alias) and the same defaults:

  fusion threshold 64 MB  (operations.cc:1005)
  cycle time 5 ms         (operations.cc:1013)
  cache capacity 1024     (global_state.h:135)
  stall warning 60 s      (global_state.h:67-76)
"""

import dataclasses
import os


def _env(name, default=None):
    """Look up HOROVOD_<name> with HVD_<name> as an alias."""
    for prefix in ("HOROVOD_", "HVD_"):
        val = os.environ.get(prefix + name)
        if val is not None:
            return val
    return default


def env_bool(name, default=False):
    val = _env(name)
    if val is None:
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def env_int(name, default):
    val = _env(name)
    if val is None:
        return default
    try:
        return int(val)
    except ValueError:
        return default


def env_float(name, default):
    val = _env(name)
    if val is None:
        return default
    try:
        return float(val)
    except ValueError:
        return default


def env_str(name, default=None):
    return _env(name, default)


@dataclasses.dataclass
class HorovodConfig:
    """Runtime knobs, parsed once at init (reference operations.cc:986-1080)."""

    # Tensor fusion: bytes of gradient data batched into one collective.
    fusion_threshold: int = 64 * 1024 * 1024
    # Eager coordination cycle time in ms (pacing of the flush loop).
    cycle_time_ms: float = 5.0
    # Response/plan cache capacity (entries).
    cache_capacity: int = 1024
    # Timeline tracing output path (rank-0 only), empty disables.
    timeline_filename: str = ""
    timeline_mark_cycles: bool = False
    # Stall detection.
    stall_check_disable: bool = False
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0  # 0 = never hard-shutdown
    # Liveness: the coordinator declares a rank LOST (fail-fast
    # RanksLostError to every surviving rank) when it has heartbeated at
    # least once and then gone silent for this long. 0 disables the
    # escalation — the legacy warn-only behavior.
    rank_lost_timeout_seconds: float = 0.0
    # Worker-side mirror: how long the coordinator must stay unreachable
    # before a worker fails its pending work. 0 = the engine's built-in
    # default (EagerCoordinator.POISON_GRACE_S).
    coordinator_lost_timeout_seconds: float = 0.0
    # Chaos plane (run/chaos.py): deterministic fault injection on the
    # control-plane transport. Spec grammar:
    #   service:message:fault:prob[:count][;more rules]
    # e.g. "hvd.negotiation:CycleResponse:drop_response:0.2". Empty
    # disables injection entirely (the default — production safe).
    chaos_spec: str = ""
    chaos_seed: int = 0
    chaos_delay_ms: float = 50.0
    # Telemetry plane (utils/metrics.py): base port for the per-rank
    # Prometheus/JSON exposition server (rank r binds metrics_port + r);
    # 0 disables serving. metrics_interval is the seconds between a
    # worker's piggybacked snapshot pushes to rank 0 — the staleness
    # bound of the aggregate view.
    metrics_port: int = 0
    metrics_interval: float = 5.0
    # Autotuning of fusion_threshold / cycle_time.
    autotune: bool = False
    autotune_log: str = ""
    # Multi-process autotune: tuned values are adopted by every process at
    # the same point in the replicated-collective order, synced via a tiny
    # allgather every this-many replicated collectives (the role of the
    # reference coordinator's parameter broadcast,
    # parameter_manager.cc:66-81).
    autotune_sync_collectives: int = 32
    # Quantized wire (ops/quantization.py, docs/compression.md): the
    # codec gradient allreduces cross the wire in. "none" keeps full
    # width; "bf16"/"fp16" cast; "int8"/"fp8" are block-scaled with a
    # per-block max-abs f32 scale. Selection is per tensor (floating
    # dtype, >= quant_min_bytes) and — under negotiation — decided by
    # the coordinator from rank 0's config, with a per-cycle
    # fingerprint check that fails loudly if any rank's knobs differ.
    compression: str = "none"
    # Elements per quantization block (one f32 scale each; the scale
    # overhead is 4/quant_block bytes per element).
    quant_block: int = 256
    # Tensors smaller than this stay full width: the encode + scale
    # overhead beats the wire saving on tiny buffers.
    quant_min_bytes: int = 1024
    # Error feedback: carry each encode's rounding error into the next
    # step's buffer. Leave on — it is what preserves convergence at
    # int8/fp8 width.
    quant_ef: bool = True
    # Checkpoint plane (utils/checkpoint.py, docs/checkpoint.md).
    # ckpt_every is the trainer contract's default save cadence in
    # steps (0 = only explicit/emergency saves); ckpt_keep the
    # retention depth; ckpt_async the double-buffered background
    # writer; ckpt_verify the restore-time checksum pass;
    # ckpt_preemption installs the SIGTERM/SIGINT finish-step +
    # emergency-save + exit-45 handler.
    ckpt_every: int = 0
    ckpt_keep: int = 3
    ckpt_async: bool = True
    ckpt_verify: bool = True
    ckpt_preemption: bool = True
    # Hierarchical (two-level ICI/DCN) collectives.
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Explicit ppermute ring allreduce backend (ops/operation_manager.py).
    ring_allreduce: bool = False
    # Overlap plane (docs/tensor-fusion.md): dispatch fused gradient
    # buckets in readiness order while the backward is still producing
    # later (earlier-layer) grads, instead of one barrier-then-allreduce
    # over the whole tree. Off by default: the barrier path stays the
    # reference behavior.
    overlap_eager: bool = False
    # Two-level eager reduction: intra-host full-width reduce-scatter,
    # inter-host allreduce on the negotiated codec, intra-host
    # broadcast. The quantized wire rides only the inter-host leg.
    overlap_hierarchical: bool = False
    # Processes per host for the two-level split. 0 = take the
    # launcher's HVD_LOCAL_SIZE. Must divide the world size; a split
    # with only one host (or one process per host) falls back flat.
    overlap_local_size: int = 0
    # Logging.
    log_level: str = "WARNING"
    log_timestamp: bool = False

    @classmethod
    def from_env(cls):
        return cls(
            fusion_threshold=env_int("FUSION_THRESHOLD", 64 * 1024 * 1024),
            cycle_time_ms=env_float("CYCLE_TIME", 5.0),
            cache_capacity=env_int("CACHE_CAPACITY", 1024),
            timeline_filename=env_str("TIMELINE", "") or "",
            timeline_mark_cycles=env_bool("TIMELINE_MARK_CYCLES", False),
            stall_check_disable=env_bool("STALL_CHECK_DISABLE", False),
            stall_warning_time_seconds=env_float(
                "STALL_CHECK_TIME_SECONDS", 60.0),
            stall_shutdown_time_seconds=env_float(
                "STALL_SHUTDOWN_TIME_SECONDS", 0.0),
            rank_lost_timeout_seconds=env_float(
                "RANK_LOST_TIMEOUT_SECONDS", 0.0),
            coordinator_lost_timeout_seconds=env_float(
                "COORDINATOR_LOST_TIMEOUT_SECONDS", 0.0),
            chaos_spec=env_str("CHAOS_SPEC", "") or "",
            chaos_seed=env_int("CHAOS_SEED", 0),
            chaos_delay_ms=env_float("CHAOS_DELAY_MS", 50.0),
            compression=(env_str("COMPRESSION", "none") or "none")
            .strip().lower(),
            quant_block=env_int("QUANT_BLOCK", 256),
            quant_min_bytes=env_int("QUANT_MIN_BYTES", 1024),
            quant_ef=env_bool("QUANT_EF", True),
            metrics_port=env_int("METRICS_PORT", 0),
            metrics_interval=env_float("METRICS_INTERVAL", 5.0),
            autotune=env_bool("AUTOTUNE", False),
            autotune_log=env_str("AUTOTUNE_LOG", "") or "",
            autotune_sync_collectives=env_int("AUTOTUNE_SYNC_COLLECTIVES",
                                              32),
            ckpt_every=env_int("CKPT_EVERY", 0),
            ckpt_keep=env_int("CKPT_KEEP", 3),
            ckpt_async=env_bool("CKPT_ASYNC", True),
            ckpt_verify=env_bool("CKPT_VERIFY", True),
            ckpt_preemption=env_bool("CKPT_PREEMPTION", True),
            hierarchical_allreduce=env_bool("HIERARCHICAL_ALLREDUCE", False),
            hierarchical_allgather=env_bool("HIERARCHICAL_ALLGATHER", False),
            ring_allreduce=env_bool("RING_ALLREDUCE", False),
            overlap_eager=env_bool("OVERLAP_EAGER", False),
            overlap_hierarchical=env_bool("OVERLAP_HIERARCHICAL", False),
            overlap_local_size=env_int("OVERLAP_LOCAL_SIZE", 0),
            log_level=env_str("LOG_LEVEL", "WARNING") or "WARNING",
            log_timestamp=env_bool("LOG_TIMESTAMP", False),
        )


# ---------------------------------------------------------------------------
# The environment-variable registry: every HVD_*/HOROVOD_* variable the
# framework reads, in one place. This MUST stay a pure literal — the
# hvdlint HVD005 rule and the docs/envvars.md generator parse it with
# ast.literal_eval (never importing this module, so linting works
# without jax). Rows are (name, aliased, default, owner, description).
#
#   aliased=True: read through the helpers above, which try the
#   HOROVOD_ spelling then HVD_; `name` is the canonical HOROVOD_ form
#   and both spellings are accepted. aliased=False: the exact name is
#   read literally at the owner site.
#
# Adding a variable: add a row here, then regenerate the doc with
#   python -m tools.hvdlint --emit-envdoc
# (CI runs --check-envdoc and HVD005, so unregistered reads and a stale
# doc both fail the lint stage.)
ENV_REGISTRY = (
    # -- config helpers (common/config.py:from_env) --------------------
    ("HOROVOD_ALERT", True, "1", "utils/alerts.py",
     "Set 0 to replace the AlertManager with a no-op (no rule "
     "evaluation, no incidents; the hvd_alert_state gauges never "
     "appear)."),
    ("HOROVOD_ALERT_BREAKER_FLAPS", True, "3", "utils/alerts.py",
     "Default rule pack: breaker trips within the rule window at or "
     "above this count is a breaker-open flap."),
    ("HOROVOD_ALERT_FOR_S", True, "5.0", "utils/alerts.py",
     "Default for-duration hysteresis: a rule's predicate must hold "
     "this many seconds before pending escalates to firing (and hold "
     "clear as long before firing resolves)."),
    ("HOROVOD_ALERT_GOODPUT_BURN", True, "2.0", "utils/alerts.py",
     "Default rule pack: multi-window goodput burn rate (wasted-token "
     "fraction over 1 - HOROVOD_ALERT_GOODPUT_SLO) above this in BOTH "
     "the 60s and 15s windows fires serve_goodput_burn."),
    ("HOROVOD_ALERT_GOODPUT_SLO", True, "0.9", "utils/alerts.py",
     "Serving goodput SLO target (useful-token fraction) the burn-rate "
     "rule's error budget is derived from."),
    ("HOROVOD_ALERT_HBM_HEADROOM_FRAC", True, "0.10", "utils/alerts.py",
     "Default rule pack: HBM headroom below this fraction of capacity "
     "fires hbm_headroom (OOM territory)."),
    ("HOROVOD_ALERT_INTERVAL_S", True, "1.0", "utils/alerts.py",
     "Minimum seconds between AlertManager rule evaluations; ticks "
     "inside the interval are a lock-free no-op on the instrument "
     "path."),
    ("HOROVOD_ALERT_NONFINITE_BURST", True, "3", "utils/alerts.py",
     "Default rule pack: nonfinite gradient observations within the "
     "rule window at or above this count is a nonfinite burst."),
    ("HOROVOD_ALERT_TTFT_SLO_S", True, "2.0", "utils/alerts.py",
     "Serving TTFT SLO (seconds) the rolling-p99 rule compares "
     "against."),
    ("HOROVOD_AUTOTUNE", True, "0", "common/config.py",
     "Enable the online fusion-parameter autotuner."),
    ("HOROVOD_AUTOTUNE_LOG", True, None, "common/config.py",
     "CSV file the autotuner appends sampled points to."),
    ("HOROVOD_AUTOTUNE_SYNC_COLLECTIVES", True, "32", "common/config.py",
     "Adopt tuned values every N replicated collectives (keeps ranks "
     "in lockstep)."),
    ("HOROVOD_CACHE_CAPACITY", True, "1024", "common/config.py",
     "Response-cache capacity of the negotiation client."),
    ("HOROVOD_CHAOS_DELAY_MS", True, "50.0", "common/config.py",
     "Injected delay for chaos delay_request/delay_response rules."),
    ("HOROVOD_CHAOS_SEED", True, "0", "common/config.py",
     "Deterministic seed for chaos-rule sampling."),
    ("HOROVOD_CHAOS_SPEC", True, None, "common/config.py",
     "Chaos-plane fault spec (run/chaos.py grammar); unset disables "
     "injection."),
    ("HOROVOD_CKPT_ASYNC", True, "1", "common/config.py",
     "Checkpoint plane: double-buffered background writer (set 0 for "
     "synchronous saves that block the step loop)."),
    ("HOROVOD_CKPT_EVERY", True, "0", "common/config.py",
     "Trainer checkpoint cadence in steps (0 = only explicit and "
     "preemption-triggered emergency saves)."),
    ("HOROVOD_CKPT_KEEP", True, "3", "common/config.py",
     "Retention: committed checkpoints kept per directory; older ones "
     "and stale crashed partials are garbage-collected at commit."),
    ("HOROVOD_CKPT_PREEMPTION", True, "1", "common/config.py",
     "Install the SIGTERM/SIGINT preemption handler: finish the "
     "in-flight step, force an emergency durable checkpoint, exit 45 "
     "(the supervisor's graceful no-shrink restart code)."),
    ("HOROVOD_CKPT_VERIFY", True, "1", "common/config.py",
     "Verify per-file crc32 checksums on checkpoint restore; "
     "corruption raises CorruptCheckpointError instead of returning a "
     "wrong tree."),
    ("HOROVOD_COMPRESSION", True, "none", "common/config.py",
     "Wire codec for gradient allreduces (none, fp16, bf16, int8, "
     "fp8); quantized codecs are negotiated per tensor."),
    ("HOROVOD_COORDINATOR_LOST_TIMEOUT_SECONDS", True, "0.0",
     "common/config.py",
     "Worker self-terminates after this long without coordinator "
     "contact (0 disables)."),
    ("HOROVOD_CYCLE_TIME", True, "5.0", "common/config.py",
     "Negotiation cycle time in milliseconds."),
    ("HOROVOD_ELASTIC_BREAKER_CLOSE_N", True, "3", "router/elastic.py",
     "Circuit breaker: consecutive successful completions a half-open "
     "replica must serve before its breaker closes again."),
    ("HOROVOD_ELASTIC_BREAKER_FAILS", True, "3", "router/elastic.py",
     "Circuit breaker: consecutive failed dispatches that trip a "
     "replica's breaker open (probe traffic only until it recovers)."),
    ("HOROVOD_ELASTIC_BREAKER_TIMEOUT_S", True, "10.0",
     "router/elastic.py",
     "Circuit breaker: a live replica holding a dispatched request "
     "longer than this without completing is declared wedged and its "
     "breaker trips — catches the heartbeating-but-stuck failure the "
     "liveness ledger cannot see."),
    ("HOROVOD_ELASTIC_COOLDOWN_S", True, "10.0", "router/elastic.py",
     "Elasticity: minimum seconds between executed scale changes; "
     "with the dwell requirement this is the anti-flap hysteresis."),
    ("HOROVOD_ELASTIC_DOWN_UTIL", True, "0.25", "router/elastic.py",
     "Elasticity: scale down when fleet slot utilization stays at or "
     "below this fraction (and the queue is empty) for the dwell "
     "window."),
    ("HOROVOD_ELASTIC_DRAIN_TIMEOUT_S", True, "30.0",
     "router/core.py",
     "Graceful drain: seconds a DRAINING replica gets to finish its "
     "in-flight work before the router force-retires it and reroutes "
     "the remainder through the exactly-once ledger."),
    ("HOROVOD_ELASTIC_DWELL_S", True, "5.0", "router/elastic.py",
     "Elasticity: a pressure or idle signal must hold continuously "
     "this long before a scale decision executes (one blip never "
     "moves the fleet)."),
    ("HOROVOD_ELASTIC_MAX_REPLICAS", True, "0", "router/elastic.py",
     "Elasticity: ceiling on live replicas for scale-up (0 = "
     "unbounded)."),
    ("HOROVOD_ELASTIC_MIN_REPLICAS", True, "1", "router/elastic.py",
     "Elasticity: floor on live replicas — scale-down never drains "
     "below it."),
    ("HOROVOD_ELASTIC_PROBE_S", True, "2.0", "router/elastic.py",
     "Circuit breaker: seconds between single probe requests admitted "
     "to an open replica to test recovery."),
    ("HOROVOD_ELASTIC_SHED_DEPTH", True, "16", "router/core.py",
     "Overload shedding: Router.submit rejects at admission (with a "
     "retry-after derived from the drain rate) when every usable "
     "replica's queue depth reaches this, or all are KV-exhausted "
     "(0 disables shedding)."),
    ("HOROVOD_ELASTIC_TTFT_SLO_S", True, "1.0", "router/elastic.py",
     "Elasticity: rolling-window p99 TTFT above this is scale-up "
     "pressure even when queues look shallow."),
    ("HOROVOD_ELASTIC_UP_DEPTH", True, "4.0", "router/elastic.py",
     "Elasticity: mean queue depth per live replica at or above this "
     "is scale-up pressure."),
    ("HOROVOD_FLEET_POLL_S", True, "0.5", "fleet/subscriber.py",
     "Fleet plane: seconds between publication-pointer polls by a "
     "serving replica's WeightSubscriber (the fast path is one stat)."),
    ("HOROVOD_FLEET_PUBLISH", True, "0", "trainer.py",
     "Fleet plane: publish every committed checkpoint as a weight "
     "generation (trainer.Checkpointer attaches a WeightPublisher on "
     "rank 0)."),
    ("HOROVOD_FLEET_VERIFY", True, "1", "fleet/subscriber.py",
     "Fleet plane: checksum-verify a published generation's files "
     "before arming it for a hot swap (0 trusts the manifest; corrupt "
     "weights would reach decode)."),
    ("HOROVOD_FLIGHT_CYCLES", True, "64", "utils/tracing.py",
     "Flight-recorder ring size for negotiation-cycle records."),
    ("HOROVOD_FLIGHT_DIR", True, None, "utils/tracing.py",
     "Directory flight-recorder dumps are written to (default: "
     "<tmp>/hvd-flight)."),
    ("HOROVOD_FLIGHT_SIGTERM", True, "1", "utils/tracing.py",
     "Set 0 to skip installing the SIGTERM flight-dump handler."),
    ("HOROVOD_FLIGHT_SPANS", True, "2048", "utils/tracing.py",
     "Flight-recorder ring size for finished spans."),
    ("HOROVOD_FUSION_THRESHOLD", True, "67108864", "common/config.py",
     "Fusion-buffer byte threshold for bucketing collectives."),
    ("HOROVOD_HIERARCHICAL_ALLGATHER", True, "0", "common/config.py",
     "Two-level (intra/inter host) allgather."),
    ("HOROVOD_HIERARCHICAL_ALLREDUCE", True, "0", "common/config.py",
     "Two-level (ICI reduce-scatter + DCN allreduce) allreduce."),
    ("HOROVOD_HISTORY", True, "1", "utils/history.py",
     "Set 0 to disable the durable run-history WAL (per-rank "
     "delta-encoded metrics snapshots + the event ring, written by a "
     "background thread; what tools/hvd_replay.py reads)."),
    ("HOROVOD_HISTORY_DIR", True, None, "utils/history.py",
     "Directory history segments and the rank-0 run manifest are "
     "written to (default: <tmp>/hvd-history)."),
    ("HOROVOD_HISTORY_INTERVAL_S", True, "30.0", "utils/history.py",
     "Seconds between history snapshots; pokes inside the interval "
     "are a lock-free no-op on the instrument path."),
    ("HOROVOD_HISTORY_MAX_MB", True, "64.0", "utils/history.py",
     "On-disk budget per rank for history segments; the writer "
     "rotates size-bounded segments and prunes the oldest past it."),
    ("HOROVOD_LOG_LEVEL", True, "WARNING", "common/config.py",
     "Framework log level (TRACE/DEBUG/INFO/WARNING/ERROR/FATAL)."),
    ("HOROVOD_LOG_TIMESTAMP", True, "0", "common/config.py",
     "Prefix log lines with timestamps."),
    ("HOROVOD_MEM", True, "1", "utils/memory.py",
     "Set 0 to disable the memory & compile observability plane (HBM "
     "ledger gauges, jit-site compile tracking, recompile-storm "
     "ladder, resharding sentinel reporting)."),
    ("HOROVOD_MEM_STORM_DECAY", True, "0.8", "utils/memory.py",
     "EMA decay of the per-site compile-miss rate the recompile-storm "
     "detector maintains (closer to 1 = longer memory)."),
    ("HOROVOD_MEM_STORM_EMA", True, "0.5", "utils/memory.py",
     "Miss-rate EMA threshold above which an instrumented jit site is "
     "declared in a recompile storm."),
    ("HOROVOD_MEM_STORM_MIN", True, "3", "utils/memory.py",
     "Minimum distinct compile misses at a site before the storm "
     "ladder may fire (the first compile is always free)."),
    ("HOROVOD_MESH", False, None, "parallel/mesh.py",
     "Full data-plane mesh spec as comma-separated axis=size pairs "
     "(e.g. dp=2,tp=4; dp may be omitted and absorbs the remaining "
     "devices). Wins over the per-axis HOROVOD_MESH_* knobs."),
    ("HOROVOD_MESH_EP", False, "1", "parallel/mesh.py",
     "Expert-parallel axis size for the global mesh (ignored when "
     "HOROVOD_MESH is set)."),
    ("HOROVOD_MESH_PP", False, "1", "parallel/mesh.py",
     "Pipeline-parallel axis size for the global mesh (ignored when "
     "HOROVOD_MESH is set)."),
    ("HOROVOD_MESH_SP", False, "1", "parallel/mesh.py",
     "Sequence-parallel axis size for the global mesh (ignored when "
     "HOROVOD_MESH is set)."),
    ("HOROVOD_MESH_TP", False, "1", "parallel/mesh.py",
     "Tensor-parallel axis size for the global mesh (ignored when "
     "HOROVOD_MESH is set)."),
    ("HOROVOD_METRICS", True, "1", "utils/metrics.py",
     "Set 0 to replace the metrics registry with no-op instruments."),
    ("HOROVOD_METRICS_EVENT_LOG", True, None, "utils/metrics.py",
     "JSONL file the metrics event channel appends to."),
    ("HOROVOD_METRICS_INTERVAL", True, "5.0", "common/config.py",
     "Seconds between rank-0 metrics aggregation pulls."),
    ("HOROVOD_METRICS_PORT", True, "0", "common/config.py",
     "Rank-0 HTTP port for /metrics and /metrics.json (0 disables)."),
    ("HOROVOD_PERF_ATTRIB_EVERY", True, "0", "trainer.py",
     "Capture + attribute every Nth instrumented step (profiler trace "
     "-> per-class hvd_step_breakdown_ms / overlap gauges); 0 (the "
     "default) keeps the capture off the hot path; a cadence of 64 "
     "or more spreads a capture's cost over that many steps."),
    ("HOROVOD_NUMERICS", True, "1", "utils/numerics.py",
     "Set 0 to replace the numerics plane (gradient health stats + "
     "divergence sentinel) with no-ops."),
    ("HOROVOD_NUMERICS_DIGEST_CYCLES", True, "32", "utils/numerics.py",
     "How many recent cycles the coordinator retains cross-rank "
     "digests for."),
    ("HOROVOD_NUMERICS_EMA_BETA", True, "0.9", "utils/numerics.py",
     "Decay of the per-tensor gradient-norm EMA the spike policy "
     "compares against."),
    ("HOROVOD_NUMERICS_EMA_K", True, "8.0", "utils/numerics.py",
     "Flag a norm_spike anomaly when a gradient norm exceeds k times "
     "its EMA."),
    ("HOROVOD_NUMERICS_TOLERANCE", True, "1e-4", "utils/numerics.py",
     "Relative cross-rank disagreement tolerance for post-allreduce "
     "digest records."),
    ("HOROVOD_NUMERICS_WARMUP", True, "5", "utils/numerics.py",
     "Per-tensor observations before the norm-spike policy arms."),
    ("HOROVOD_OVERLAP_EAGER", True, "0", "common/config.py",
     "Overlap plane: dispatch fused gradient buckets in readiness "
     "order while backward still produces later grads, instead of one "
     "barrier-then-allreduce over the whole tree."),
    ("HOROVOD_OVERLAP_HIERARCHICAL", True, "0", "common/config.py",
     "Two-level eager reduction: intra-host full-width reduce-scatter, "
     "inter-host allreduce on the negotiated codec, intra-host "
     "broadcast; the quantized wire rides only the inter-host leg."),
    ("HOROVOD_OVERLAP_LOCAL_SIZE", True, "0", "common/config.py",
     "Processes per host for the two-level reduction split (0 = take "
     "the launcher's HVD_LOCAL_SIZE; must divide the world size)."),
    ("HOROVOD_QUANT_BLOCK", True, "256", "common/config.py",
     "Elements per block-scaled quantization block (one f32 scale "
     "each)."),
    ("HOROVOD_QUANT_EF", True, "1", "common/config.py",
     "Error feedback for quantized codecs: carry encode rounding "
     "error into the next step (set 0 to disable)."),
    ("HOROVOD_QUANT_MIN_BYTES", True, "1024", "common/config.py",
     "Tensors smaller than this many bytes skip the quantized wire "
     "and stay full width."),
    ("HOROVOD_RANK_LOST_TIMEOUT_SECONDS", True, "0.0",
     "common/config.py",
     "Coordinator declares a silent rank lost after this long "
     "(0 disables)."),
    ("HOROVOD_RING_ALLREDUCE", True, "0", "common/config.py",
     "Use the explicit ppermute ring allreduce backend."),
    ("HOROVOD_ROUTE_AFFINITY_PREFIX", True, "8", "router/core.py",
     "Router plane: prompt-prefix length (tokens) hashed for cache-"
     "affinity stickiness; 0 disables affinity routing."),
    ("HOROVOD_ROUTE_CANARY_GOODPUT_DROP", True, "0.10",
     "router/canary.py",
     "Canary rollout: roll back when the canary cohort's goodput "
     "ratio (completed tokens / all tokens) falls more than this "
     "below the baseline cohort's."),
    ("HOROVOD_ROUTE_CANARY_MIN_DELTA_S", True, "0.025",
     "router/canary.py",
     "Canary rollout: a latency breach additionally needs this "
     "absolute p99 gap (seconds) — keeps the verdict above the "
     "histogram buckets' own resolution."),
    ("HOROVOD_ROUTE_CANARY_PCT", True, "10.0", "router/canary.py",
     "Canary rollout: percent of traffic (deterministic request-id "
     "hash) steered to the cohort serving the newly armed weight "
     "generation."),
    ("HOROVOD_ROUTE_CANARY_REPLICAS", True, "1", "router/canary.py",
     "Canary rollout: max replicas admitted to the canary cohort when "
     "several arm the new generation at once; the rest hold as "
     "baseline."),
    ("HOROVOD_ROUTE_CANARY_TTFT_X", True, "1.5", "router/canary.py",
     "Canary rollout: roll back when the canary cohort's p99 TTFT or "
     "inter-token gap exceeds this multiple of the baseline "
     "cohort's."),
    ("HOROVOD_ROUTE_CANARY_WINDOW", True, "24", "router/canary.py",
     "Canary rollout: completed requests each cohort must accumulate "
     "before the promote/rollback verdict is computed."),
    ("HOROVOD_ROUTE_POLICY", True, "least_loaded", "router/policy.py",
     "Router plane: dispatch policy over live replica load snapshots "
     "(least_loaded, round_robin)."),
    ("HOROVOD_ROUTE_REROUTE_WINDOW_S", True, "30.0", "router/core.py",
     "Router plane: max age (seconds since dispatch) a request may be "
     "requeued to a survivor after its replica is lost; older "
     "requests fail loudly instead of resurrecting."),
    ("HOROVOD_ROUTE_STALE_S", True, "5.0", "router/core.py",
     "Router plane: exclude a replica from dispatch once its load "
     "snapshot is older than this — a silent replica ages out instead "
     "of scoring as freshly idle forever (0 disables; never-reported "
     "replicas get this long as a post-add grace window)."),
    ("HOROVOD_SERVE_ADMISSION_TIMEOUT_S", True, "10.0",
     "serving/queue.py",
     "Serving admission control: reject a queued request after waiting "
     "this long without a free slot."),
    ("HOROVOD_SERVE_KV_BLOCK", True, "16", "serving/kv_cache.py",
     "KV-cache allocation granularity in tokens: slots claim cache "
     "capacity in blocks of this many positions."),
    ("HOROVOD_SERVE_METRICS_INTERVAL_S", True, "1.0",
     "serving/engine.py",
     "Seconds between serving-gauge refreshes (queue depth, active "
     "slots, KV blocks in use)."),
    ("HOROVOD_SERVE_QUEUE_DEPTH", True, "64", "serving/queue.py",
     "Admission-queue capacity; requests arriving at a full queue are "
     "rejected immediately."),
    ("HOROVOD_SERVE_SLOTS", True, "8", "serving/engine.py",
     "Device batch slots of the continuous-batching engine (the max "
     "concurrently decoding requests)."),
    ("HOROVOD_SERVE_TRACE", True, "1", "serving/tracing.py",
     "Set 0 to disable request-path tracing (per-request spans, phase "
     "decomposition, goodput accounting) in the serving plane."),
    ("HOROVOD_SERVE_TRACE_SLOW_TICK_MS", True, "250.0",
     "serving/tracing.py",
     "Decode ticks slower than this emit a slow_decode_tick event "
     "into the metrics ring."),
    ("HOROVOD_STALL_CHECK_DISABLE", True, "0", "common/config.py",
     "Disable the coordinator's stalled-rank warnings."),
    ("HOROVOD_STALL_CHECK_TIME_SECONDS", True, "60.0",
     "common/config.py",
     "Warn when an entry waits longer than this for stragglers."),
    ("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", True, "0.0",
     "common/config.py",
     "Escalate a stall to job shutdown after this long (0 disables)."),
    ("HOROVOD_TIMELINE", True, None, "common/config.py",
     "Write a Chrome-trace timeline to this file."),
    ("HOROVOD_TIMELINE_MARK_CYCLES", True, "0", "common/config.py",
     "Mark negotiation cycles in the timeline."),
    ("HOROVOD_TRACE", True, "1", "utils/tracing.py",
     "Set 0 to replace the tracing plane (spans + flight recorder) "
     "with no-ops."),
    ("HOROVOD_TRACE_SLOW_MS", True, "100.0", "utils/tracing.py",
     "Spans slower than this emit a slow_span event into the metrics "
     "ring."),
    # -- launcher / rendezvous (exact names) ---------------------------
    ("HOROVOD_SECRET_KEY", False, None, "run/cli.py",
     "Base64 HMAC key for the run service; generated per job when "
     "unset (HVD_SECRET_KEY also accepted)."),
    ("HVD_SECRET_KEY", False, None, "run/cli.py",
     "Alternate spelling of HOROVOD_SECRET_KEY checked by hvdrun."),
    ("HOROVOD_START_TIMEOUT", False, "600", "run/cli.py",
     "Seconds hvdrun waits for all workers to register."),
    ("HVD_COORDINATOR_ADDR", False, None, "mpi_ops.py",
     "host:port of the jax.distributed coordinator (worker 0)."),
    ("HVD_CONTROL_ADDR", False, None, "ops/negotiation.py",
     "Pin the negotiation control-plane listener to this host:port."),
    ("HVD_NUM_PROC", False, None, "mpi_ops.py",
     "Total worker count; exported by hvdrun, fallback to MPI/PMI "
     "world size."),
    ("HVD_PROCESS_ID", False, None, "mpi_ops.py",
     "This worker's global rank; exported by hvdrun."),
    ("HVD_LOCAL_RANK", False, None, "common/state.py",
     "Rank within the host; exported by hvdrun."),
    ("HVD_LOCAL_SIZE", False, None, "common/state.py",
     "Workers on this host; exported by hvdrun."),
    ("HVD_CROSS_RANK", False, None, "run/cli.py",
     "Host index of this worker; exported by hvdrun."),
    ("HVD_CROSS_SIZE", False, None, "run/cli.py",
     "Number of hosts in the job; exported by hvdrun."),
    ("HVD_HOST_SALT", False, None, "run/hosts.py",
     "Extra entropy mixed into the per-host identity hash."),
    ("HVD_RENDEZVOUS_DIR", False, None, "run/mpi.py",
     "Shared directory for mpirun-mode file rendezvous (default: "
     "system tmp; must be shared across hosts)."),
    ("HVD_SPARK_BIND_ADDR", False, None, "spark/__init__.py",
     "Pin the Spark driver's run-service bind address."),
    ("_HVD_RUN_SERVICE_ADDRS", False, None, "run/launch.py",
     "Internal: codec-encoded service addresses hvdrun hands each "
     "worker."),
    ("_HVD_SECRET_KEY", False, None, "run/secret.py",
     "Internal: per-job base64 HMAC key hvdrun exports to workers."),
    # -- feature gates / integrations (exact names) --------------------
    ("HVD_DISABLE_NATIVE", False, None, "_native/__init__.py",
     "Set 1 to skip loading the C++ native plane and use pure "
     "Python."),
    ("HVD_PLANE_SHM", False, "1", "_native/src/plane.h",
     "Set 0 to force TCP between same-host native planes instead of "
     "shared memory."),
    ("HVD_LOCKDEP", False, "0", "utils/lockdep.py",
     "Set 1 to swap every lockdep.lock() for an instrumented lock that "
     "witnesses acquisition orders and reports deadlock-shaped bugs "
     "(order cycles, rank violations, self-deadlock, hold-while-"
     "blocking) through metrics events, warnings, and flight dumps. "
     "Unset, lock() returns a raw threading lock — zero overhead."),
    ("HVD_LOCKDEP_MAX_FINDINGS", False, "32", "utils/lockdep.py",
     "Cap on stored lockdep findings per process; past it new findings "
     "are counted but dropped (a hot inversion must not grow memory "
     "unboundedly)."),
    ("HVD_LOCKDEP_STALL_S", False, "1.0", "utils/lockdep.py",
     "Seconds a lock-holding thread may block acquiring another lock "
     "before lockdep reports hold_while_blocking."),
    ("HVD_RUN_LABEL", False, None, "utils/provenance.py",
     "Free-form run label stamped into provenance blocks (history "
     "run manifest)."),
    ("HVD_TF_NATIVE", False, "1", "tensorflow/native.py",
     "Set 0 to disable the TensorFlow native bridge."),
    ("HVD_TF_NATIVE_ADDR", False, None, "tensorflow/native.py",
     "host:port rendezvous for the TF native bridge."),
    ("HVD_TF_NATIVE_TIMEOUT", False, "60", "tensorflow/native.py",
     "Seconds to wait on the TF native rendezvous."),
    ("HVD_TORCH_NATIVE", False, "1", "torch/native.py",
     "Set 0 to disable the PyTorch native bridge."),
    ("HVD_TORCH_NATIVE_ADDR", False, None, "torch/native.py",
     "host:port rendezvous for the torch native bridge."),
    ("HVD_TORCH_NATIVE_TIMEOUT", False, "60", "torch/native.py",
     "Seconds to wait on torch native rendezvous/collectives."),
    # -- tools / CI (exact names) -------------------------------------
    ("HVD_SLO_PCT", False, "90", "tools/hvd_slo.py",
     "Tail percentile the hvd_slo analyzer attributes (the slowest "
     "(100-pct)% of completed requests form the tail)."),
    ("HVD_TEST_WORKERS", False, "auto", "ci/run_tests.sh",
     "pytest-xdist worker count for the CI suite."),
)
