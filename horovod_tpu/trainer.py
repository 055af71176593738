"""Training-step builders: the glue between models, DistributedOptimizer and
the mesh.

Two idioms, mirroring the two ways the framework exposes collectives:

  * ``make_data_parallel_step`` — Horovod-style explicit SPMD: shard_map
    over the worker axis, per-worker grads, explicit fused
    ``allreduce_gradients`` (the DistributedOptimizer path; reference
    torch/__init__.py:95-151 semantics in one compiled step).
  * ``make_gspmd_step`` — sharding-annotated jit: parameters and batch carry
    NamedShardings (tp/sp/dp), XLA inserts the collectives. This is the
    multi-axis (tensor/sequence-parallel) path the flagship transformer
    uses.
"""

import functools
import signal
import threading
import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from .common.config import env_bool, env_int
from .common.exceptions import PREEMPTED_EXIT_CODE
from .common import hvd_logging as log
from . import optim
from .parallel import mesh as mesh_lib
from .ops.compression import Compression
from .utils import alerts as hvd_alerts
from .utils import checkpoint as hvd_checkpoint
from .utils import history as hvd_history
from .utils import memory as hvd_memory
from .utils import metrics as hvd_metrics
from .utils import tracing as hvd_tracing


def instrument_step(step_fn, tokens_per_step=None, name="train",
                    flops_per_token=None, attrib_every=None, spec=None):
    """Wrap a compiled train step with step-path telemetry: an
    ``hvd_step_seconds`` histogram, an ``hvd_steps_total`` counter and —
    when ``tokens_per_step`` is given — an ``hvd_tokens_per_second``
    gauge, all labeled by ``name`` so eval/train loops coexist.

    The wrapper blocks on the step's outputs (``block_until_ready``)
    before stamping the end time: without the sync, async dispatch would
    time the enqueue (~µs) instead of the step. That makes it a per-step
    host sync — fine for the per-step host-loop idiom this wraps
    (make_gspmd_step, whose callers read the loss every step anyway).
    Disabled metrics make this a plain passthrough of the original
    function.

    Two optional attribution layers (the perf-attribution plane):

      * ``flops_per_token`` (e.g. ``models.transformer
        .matmul_flops_per_token``) with ``tokens_per_step`` publishes a
        live per-step ``hvd_mfu`` gauge against the chip's peak
        (``spec`` — a ``costmodel.ChipSpec``, auto-detected from the
        local device when omitted; no gauge off-TPU, where the CPU
        spec's placeholder peak would make MFU noise).
      * ``attrib_every=N`` (default ``HOROVOD_PERF_ATTRIB_EVERY``, 0 =
        off) wraps every Nth step in a ``jax.profiler.trace`` capture
        and publishes ``hvd_step_device_busy_frac``, per-class
        ``hvd_step_breakdown_ms`` / ``hvd_step_breakdown_drift`` (EMA
        -relative, hvd_top's "top regressing class"), and the
        exposed/hidden-comm overlap gauges. The first capture happens
        at step N, never step 1 — step 1 is compile. Capture failures
        emit a ``perf_attrib_error`` event and never break the step.
        A capture costs a profiler start and stop and a trace parse on
        the host; the cadence N spreads that over N steps (not
        measured on a chip).

    The memory plane (docs/memory.md, default-on via HVD_MEM) rides the
    same wrapper: every call reports its abstract-shape key to the
    compile tracker under site ``train:<name>`` (the recompile-storm
    signal), and ``hvd_step_peak_hbm_bytes`` tracks the allocator's
    peak (``peak_bytes_in_use + peak_bytes_reserved``) next to
    ``hvd_mfu`` — nulled on CPU the same way, since CPU
    backends expose no allocator stats.

    So does the alerting & run-history plane (docs/alerts.md,
    default-on via ``HVD_HISTORY`` / ``HVD_ALERT``): every step pokes
    the on-disk history writer and ticks the AlertManager — both are
    interval-throttled clock compares that no-op on the vast majority
    of steps.
    """
    reg = hvd_metrics.get_registry()
    if not reg.enabled:
        return step_fn
    step_s = reg.histogram(
        "hvd_step_seconds", "Wall time of one training step (synced).",
        labels=("loop",))
    steps = reg.counter(
        "hvd_steps_total", "Training steps executed.", labels=("loop",))
    tps = reg.gauge(
        "hvd_tokens_per_second",
        "Throughput of the most recent step (tokens_per_step / step "
        "seconds).", labels=("loop",))

    if attrib_every is None:
        attrib_every = env_int("PERF_ATTRIB_EVERY", 0)
    flops_per_step = ((flops_per_token or 0) * (tokens_per_step or 0)) or None
    if flops_per_step and spec is None:
        from .utils import costmodel
        device = jax.devices()[0]
        spec = costmodel.chip_spec(device)
        if spec is None:
            log.warning(
                "costmodel.CHIP_SPECS has no row for device_kind %r: no "
                "hvd_mfu gauge will be published", device.device_kind)
        if spec is not None and spec.kind == "cpu":
            spec = None  # placeholder peak → MFU would be noise
    mfu = reg.gauge(
        "hvd_mfu", "Model FLOPs utilization of the most recent step "
        "(flops_per_step / peak / step seconds).",
        labels=("loop",)) if flops_per_step and spec else None
    # Memory plane (docs/memory.md): peak allocator bytes next to the
    # MFU gauge, nulled the same way on CPU — backends without
    # allocator stats (step_peak_bytes() None) never create the gauge.
    peak_hbm = reg.gauge(
        "hvd_step_peak_hbm_bytes",
        "Peak allocated device bytes on this chip as of the most "
        "recent step (memory plane; absent off-TPU).",
        labels=("loop",)) if hvd_memory.enabled() \
        and hvd_memory.step_peak_bytes() is not None else None
    if attrib_every:
        busy = reg.gauge(
            "hvd_step_device_busy_frac",
            "Device-busy fraction of the last attributed step "
            "(device-op time / wall).", labels=("loop",))
        breakdown = reg.gauge(
            "hvd_step_breakdown_ms",
            "Per-op-class device ms of the last attributed step.",
            labels=("loop", "op_class"))
        drift = reg.gauge(
            "hvd_step_breakdown_drift",
            "Per-op-class ms drift of the last attributed step vs its "
            "running mean (relative; +0.1 = 10% slower than usual).",
            labels=("loop", "op_class"))
        exposed = reg.gauge(
            "hvd_step_exposed_comm_ms",
            "Collective ms NOT hidden under compute in the last "
            "attributed step.", labels=("loop",))
        hidden = reg.gauge(
            "hvd_step_hidden_comm_ms",
            "Collective ms overlapped with compute in the last "
            "attributed step.", labels=("loop",))
        ovl_frac = reg.gauge(
            "hvd_step_overlap_frac",
            "hidden / (hidden + exposed) collective ms of the last "
            "attributed step.", labels=("loop",))
    ema = {}  # op_class -> running-mean ms, for the drift gauge
    counter = [0]

    def _attribute(pdir, dt):
        import shutil

        from .utils import profiling
        try:
            dec = profiling.profile_decomposition(
                pdir, wall_ms=dt * 1e3, steps=1)
        finally:
            shutil.rmtree(pdir, ignore_errors=True)
        if dec.get("device_busy_frac") is not None:
            busy.labels(loop=name).set(dec["device_busy_frac"])
        for c in dec["classes"]:
            cls, ms = c["class"], c["ms_per_step"]
            breakdown.labels(loop=name, op_class=cls).set(ms)
            prev = ema.get(cls)
            if prev:
                drift.labels(loop=name, op_class=cls).set(
                    round(ms / prev - 1.0, 4))
            ema[cls] = ms if prev is None else 0.8 * prev + 0.2 * ms
        ov = dec.get("overlap")
        if ov:
            exposed.labels(loop=name).set(ov["exposed_comm_ms"])
            hidden.labels(loop=name).set(ov["hidden_comm_ms"])
            if ov["overlap_frac"] is not None:
                ovl_frac.labels(loop=name).set(ov["overlap_frac"])

    tracer = hvd_tracing.get_tracer()

    @functools.wraps(step_fn)
    def wrapped(*args, **kwargs):
        counter[0] += 1
        capture = attrib_every and counter[0] % attrib_every == 0 \
            and counter[0] > 1
        pdir = None
        if capture:
            import tempfile
            try:
                pdir = tempfile.mkdtemp(prefix="hvd-perf-attrib-")
                jax.profiler.start_trace(pdir)
            except Exception:
                reg.event("perf_attrib_error", phase="start")
                pdir = None
        # Compile observability (docs/memory.md): this call's abstract-
        # shape key is what the jit cache hits or misses on; a churning
        # key here is the recompile storm the tracker escalates.
        if hvd_memory.enabled():
            hvd_memory.get_tracker().observe(f"train:{name}",
                                             (args, kwargs))
        t0 = time.perf_counter()
        # step span: the root every per-tensor span of this step hangs
        # under in the postmortem timeline (stage="step", one per call)
        with tracer.span(hvd_tracing.STEP, tensor=name) as span:
            out = step_fn(*args, **kwargs)
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            span.annotate(seconds=dt)
        if pdir is not None:
            try:
                jax.profiler.stop_trace()
                _attribute(pdir, dt)
            except Exception as e:
                import shutil
                shutil.rmtree(pdir, ignore_errors=True)
                reg.event("perf_attrib_error", phase="attribute",
                          error=type(e).__name__)
        step_s.labels(loop=name).observe(dt)
        steps.labels(loop=name).inc()
        if tokens_per_step and dt > 0:
            tps.labels(loop=name).set(tokens_per_step / dt)
        if mfu is not None and dt > 0:
            mfu.labels(loop=name).set(
                flops_per_step / (spec.peak_flops * dt))
        if peak_hbm is not None and hvd_memory.enabled():
            pb = hvd_memory.step_peak_bytes()
            if pb is not None:
                peak_hbm.labels(loop=name).set(pb)
        # Alerting + durable history ride the same tick (docs/alerts.md):
        # both are interval-throttled no-ops on the vast majority of
        # steps.
        hvd_history.poke()
        hvd_alerts.tick()
        return out

    return wrapped


class Checkpointer:
    """The train loop's checkpoint contract: periodic async saves,
    auto-resume, and preemption-safe exit, in three calls.

    ::

        ckpt = trainer.Checkpointer(args.checkpoint_dir,
                                    every=args.checkpoint_every)
        state, start_step, extra = ckpt.resume(like=(params, opt_state))
        for i in range(start_step, steps):
            ...one optimizer step...
            if ckpt.step_end(i + 1, (params, opt_state),
                             extra={"data_pos": i + 1}):
                sys.exit(trainer.PREEMPTED_EXIT_CODE)
        ckpt.close()

    ``step_end`` saves every ``every`` steps through the async
    CheckpointManager (the step loop blocks only for the host snapshot)
    and consumes preemption: on SIGTERM/SIGINT it lets the in-flight
    step finish, then forces an emergency BLOCKING save of the state it
    was handed and returns True — the caller exits with
    ``PREEMPTED_EXIT_CODE`` (45), which the elastic supervisor treats
    as a graceful no-shrink restart. ``extra`` carries whatever resume
    needs beyond the tree (RNG key, data position) into the manifest.

    Signal handlers chain to any previously installed callable handler
    (e.g. the tracing plane's SIGTERM flight dump) and are only
    installed from the main thread; ``preemption=False`` or
    HVD_CKPT_PREEMPTION=0 disables them.
    """

    def __init__(self, directory, every=None, keep=None, async_save=None,
                 preemption=None, rank=0, world_size=1, manager=None,
                 verbose=False, publish=None, layout=None):
        self.every = env_int("CKPT_EVERY", 0) if every is None else int(every)
        self.manager = manager or hvd_checkpoint.CheckpointManager(
            directory, rank=rank, world_size=world_size, keep=keep,
            async_save=async_save, layout=layout)
        # fleet plane (docs/fleet.md): publish every commit as a weight
        # generation serving replicas can hot-swap to. The publisher
        # recovers its generation counter from the existing pointer, so
        # a preempted-and-restarted trainer keeps publishing monotonic
        # ids. Rank 0 only — that is the rank whose writer commits.
        if publish is None:
            publish = env_bool("FLEET_PUBLISH", False)
        self.publisher = None
        if publish and self.manager.rank == 0:
            from .fleet import WeightPublisher
            self.publisher = WeightPublisher(self.manager.directory)
            self.manager.on_commit = self.publisher.publish
        self.verbose = verbose
        self._preempt = threading.Event()
        self._signals = []
        if preemption is None:
            preemption = env_bool("CKPT_PREEMPTION", True)
        if preemption:
            self._install_handlers()

    def _install_handlers(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev = signal.getsignal(sig)

                def handler(signum, frame, _prev=prev):
                    self._preempt.set()
                    hvd_metrics.get_registry().event(
                        "ckpt_preempt", signum=int(signum))
                    # chain CUSTOM handlers only (the tracing plane's
                    # flight dump); SIG_DFL/SIG_IGN/the default
                    # KeyboardInterrupt raiser would abort the
                    # in-flight step we promised to finish
                    if callable(_prev) and _prev not in (
                            signal.SIG_IGN, signal.SIG_DFL,
                            signal.default_int_handler):
                        _prev(signum, frame)

                signal.signal(sig, handler)
                self._signals.append(sig)
            except ValueError:
                return  # not the main thread: run without handlers

    @property
    def preempted(self):
        return self._preempt.is_set()

    def resume(self, like=None, mesh=None, spec_tree=None):
        """(state, start_step, extra) — the checkpointed state when one
        exists, else ``(like, 0, {})``. Feed the tree through
        ``broadcast_parameters`` on multi-rank jobs for consistency.

        Pass ``spec_tree`` (PartitionSpec tree matching ``like``) to
        re-place the restored leaves on the mesh — the cross-layout
        restore path: the checkpoint may have been saved under a
        different dp×tp×sp factorization (docs/mesh.md)."""
        if not self.manager.exists():
            return like, 0, {}
        tree, step, extra = self.manager.restore(like=like, mesh=mesh,
                                                 spec_tree=spec_tree)
        if self.verbose:
            print(f"checkpoint: resumed step {step} from "
                  f"{self.manager.directory}")
        return tree, step, extra

    def step_end(self, step, state, extra=None):
        """Call after every completed optimizer step. Returns True when
        the process should exit with PREEMPTED_EXIT_CODE (an emergency
        durable checkpoint of ``state`` has already committed)."""
        if self._preempt.is_set():
            self.manager.save(state, step, extra=extra, block=True,
                              kind="emergency")
            hvd_metrics.get_registry().event("ckpt_emergency_exit",
                                             step=int(step))
            if self.verbose:
                print(f"checkpoint: preempted — emergency save at step "
                      f"{step} committed, exiting "
                      f"{PREEMPTED_EXIT_CODE}")
            self.close()
            return True
        if self.every and step % self.every == 0:
            self.manager.save(state, step, extra=extra)
        return False

    def close(self):
        for sig in self._signals:
            try:
                signal.signal(sig, signal.SIG_DFL)
            except ValueError:
                pass
        self._signals = []
        self.manager.close()


def softmax_cross_entropy(logits, labels, weights=None):
    """Mean token-level cross entropy (labels are int ids). ``weights``
    (same shape as labels) masks positions out of the mean.

    Streaming-logsumexp form: ``nll = lse(logits) - logits[label]``.
    Unlike ``log_softmax + gather`` it never materializes a
    [..., vocab] log-prob array — the exp/sum fuses into one fp32
    -accumulating pass over the logits in whatever dtype they arrive
    (at GPT-2-small bench scale the logp buffer alone is 1.65 GB of
    HBM write+read, ~2 ms/step on v5e). The max is stop_gradient'd:
    its subtraction cancels in the gradient, and detaching it keeps
    autodiff from emitting an argmax scatter."""
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    sumexp = jnp.sum(jnp.exp((logits - m).astype(jnp.float32)), axis=-1)
    lse = m[..., 0].astype(jnp.float32) + jnp.log(sumexp)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - tgt.astype(jnp.float32)
    if weights is None:
        return jnp.mean(nll)
    weights = weights.astype(nll.dtype)
    return jnp.sum(nll * weights) / jnp.sum(weights)


def make_data_parallel_step(loss_fn, tx, mesh, axis_name=None,
                            compression=Compression.none,
                            fusion_threshold=None, donate=True,
                            batch_specs=None):
    """Compiled Horovod-style train step.

    ``loss_fn(params, batch) -> scalar`` is the per-worker loss on the
    worker's shard. Returns ``step(params, opt_state, batch) -> (params,
    opt_state, mean_loss)`` where batch's leading dim is sharded over the
    worker axis and gradients are averaged with one fused psum per fusion
    bucket before the optimizer applies them.
    """
    axis = axis_name or mesh.axis_names[0]

    def per_worker(params, opt_state, batch):
        # Backward pass on a device-varying copy of the params — see
        # ops.collective_ops.ensure_varying for why (replicated params
        # would make autodiff pre-sum the grads, turning the explicit
        # allreduce below into a no-op on an already-summed value).
        from .ops import collective_ops as cops
        vparams = jax.tree_util.tree_map(
            lambda p: cops.ensure_varying(p, axis), params)
        loss, grads = jax.value_and_grad(loss_fn)(vparams, batch)
        grads = optim.allreduce_gradients(
            grads, compression=compression, axis_name=axis,
            fusion_threshold=fusion_threshold)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        mean_loss = jax.lax.pmean(loss, axis)
        return params, opt_state, mean_loss

    # batch_specs: PartitionSpec pytree for the batch argument (per-leaf),
    # default: shard every leaf's leading dim over the worker axis.
    # Replicated leaves (e.g. an rng key) use P().
    batch_spec = batch_specs if batch_specs is not None else P(axis)
    step = jax.shard_map(
        per_worker, mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()))
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def opt_state_specs(tx, params, param_spec_tree):
    """PartitionSpec pytree for ``tx.init(params)``: params-like leaves
    (mu/nu/momentum buffers) inherit the corresponding param's spec; every
    other leaf (step counts, schedule state) is replicated."""
    state_shape = jax.eval_shape(tx.init, params)
    return optax.tree_map_params(
        tx, lambda _, spec: spec, state_shape, param_spec_tree,
        transform_non_params=lambda _: P())


def init_opt_state(tx, params, mesh=None, param_spec_tree=None):
    """``tx.init(params)`` placed on the mesh (the process-global mesh
    when ``mesh`` is None): leaves mirroring a param
    (mu/nu/trace) take that param's sharding, scalars (step counts) are
    replicated. Use this instead of a bare ``tx.init`` with sharded steps —
    a host-created state's scalar avals lack the mesh context, so the first
    step call compiles one program and every later call another (the
    feedback opt_state *does* carry the mesh context), silently doubling
    compile time."""
    if param_spec_tree is None:
        param_spec_tree = jax.tree_util.tree_map(lambda _: P(), params)
    shardings = mesh_lib.tree_shardings(
        opt_state_specs(tx, params, param_spec_tree), mesh)
    return jax.jit(tx.init, out_shardings=shardings)(params)


def _traced_under(mesh, fn):
    """``fn`` traced with ``mesh`` as JAX's ambient abstract mesh, so code
    with no GSPMD partitioning rule of its own (the Pallas attention
    kernels, models/transformer._dispatch_attention) can find the layout
    and shard_map itself over it."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)
    return traced


def make_gspmd_step(loss_fn, tx, mesh, param_spec_tree, batch_spec,
                    donate=True, params=None):
    """Sharding-annotated train step: params placed by ``param_spec_tree``
    (e.g. models.transformer.param_specs), batch by ``batch_spec``; XLA
    (GSPMD) inserts all tp/sp/dp collectives over ICI. ``mesh=None``
    targets the process-global mesh (parallel.mesh.global_mesh).

    Pass ``params`` (the concrete or abstract param tree) so the optimizer
    state's shardings can be derived too and every step argument/result is
    pinned — without it, ``tx.init`` on the host yields SingleDeviceSharding
    scalars whose shardings change after the first step, costing a silent
    second compilation of the whole step.
    """
    param_shardings = mesh_lib.tree_shardings(param_spec_tree, mesh)
    batch_sharding = mesh_lib.named_sharding(batch_spec, mesh)
    if params is not None:
        opt_shardings = mesh_lib.tree_shardings(
            opt_state_specs(tx, params, param_spec_tree), mesh)
        out_shardings = (param_shardings, opt_shardings,
                         mesh_lib.named_sharding(P(), mesh))
    else:
        opt_shardings = None
        out_shardings = None

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(
        _traced_under(batch_sharding.mesh, step),
        in_shardings=(param_shardings, opt_shardings, batch_sharding),
        out_shardings=out_shardings,
        donate_argnums=donate_argnums), param_shardings, batch_sharding


def place(tree, mesh, spec_tree):
    """device_put a pytree according to a PartitionSpec pytree
    (``mesh=None`` targets the process-global mesh)."""
    return mesh_lib.device_put_tree(tree, spec_tree, mesh)


def replicate(tree, mesh=None):
    return mesh_lib.replicate_tree(tree, mesh)
