"""Shared harness for the synthetic image benchmarks
(synthetic_benchmark.py and scaling_benchmark.py): build a data-parallel
train step over the current mesh and time it with the warmup + measured
iterations protocol of the reference harness
(examples/pytorch_synthetic_benchmark.py:24-33 — warmup batches, then
num_iters x num_batches_per_iter timed batches)."""

import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models, trainer


def build_step(model_name, mesh, batch, image_size, fp16_allreduce=False,
               steps_per_call=1):
    """Compiled data-parallel train step + initial (params, opt_state,
    batch data) for a zoo model on synthetic ImageNet-shaped data.
    ``steps_per_call`` runs that many updates on-device per host call
    (trainer.make_data_parallel_step) — the synthetic-loop form."""
    kwargs = {"dropout_rate": 0.0} if model_name.startswith("vgg") else {}
    model = models.build(model_name, num_classes=1000, dtype=jnp.bfloat16,
                         **kwargs)
    images = jnp.zeros((batch, image_size, image_size, 3), jnp.bfloat16)
    labels = jnp.zeros((batch,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=False)
    # replicated ON THE MESH, like the step's outputs: host-initialised
    # params carry a single-device sharding, and the second call (fed the
    # first call's outputs) would compile the whole step again
    params = trainer.replicate(variables["params"], mesh)
    batch_stats = variables.get("batch_stats", {})  # VGG has no BN

    compression = (hvd.Compression.bf16 if fp16_allreduce
                   else hvd.Compression.none)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  compression=compression)
    opt_state = trainer.init_opt_state(tx, params, mesh)

    def loss_fn(p, b):
        imgs, lbls = b
        logits, _ = model.apply(
            {"params": p, "batch_stats": batch_stats}, imgs, train=True,
            mutable=["batch_stats"])
        return trainer.softmax_cross_entropy(logits, lbls)

    step = trainer.make_data_parallel_step(loss_fn, tx, mesh,
                                           compression=compression,
                                           donate=True,
                                           steps_per_call=steps_per_call)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    images = jax.device_put(images, sharding)
    labels = jax.device_put(labels, sharding)
    return step, params, opt_state, (images, labels)


def timed_rates(step, params, opt_state, batch_data, batch,
                num_warmup_batches, num_iters, num_batches_per_iter,
                on_iter=None, updates_per_step=1, return_state=False):
    """Run the reference timing protocol; returns per-iteration total
    img/sec. At least one warmup step always runs so trace+compile of the
    jitted step can never land inside the timed region (a compile-polluted
    first iteration would silently wreck the reported rate). The sync
    barrier is a scalar device-to-host read.

    With return_state=True, returns (rates, params, opt_state) — REQUIRED
    for repeated calls on the same step: the jitted step donates its
    params/opt_state buffers, so re-passing the originals after one call
    is a donated-buffer use error."""
    for _ in range(max(1, num_warmup_batches)):
        params, opt_state, loss = step(params, opt_state, batch_data)
    float(loss)  # scalar transfer: a sync barrier on every backend

    rates = []
    for i in range(num_iters):
        t0 = time.perf_counter()
        for _ in range(num_batches_per_iter):
            params, opt_state, loss = step(params, opt_state, batch_data)
        float(loss)  # scalar transfer: a sync barrier on every backend
        dt = time.perf_counter() - t0
        rate = batch * num_batches_per_iter * updates_per_step / dt
        rates.append(rate)
        if on_iter is not None:
            on_iter(i, rate)
    if return_state:
        return rates, params, opt_state
    return rates


def positive_int(value):
    v = int(value)
    if v < 1:
        raise ValueError(f"expected a positive count, got {value}")
    return v


def transformer_matmul_flops_per_token(cfg, seq):
    """Matmul FLOPs per token — models.transformer.matmul_flops_per_token
    (kept here as the harnesses' historical import point)."""
    from horovod_tpu.models import transformer as tr
    return tr.matmul_flops_per_token(cfg, seq)


def flagship_config(on_tpu=True, **overrides):
    """The canonical flagship bench model: gpt2_small_tpu — GPT-2-small's
    size/FLOPs with the TPU-native 6x128 head shape (head_dim 128 = the
    lane width, so the flash kernels run unpadded; +18% tok/s over 12x64
    measured — see TransformerConfig.gpt2_small_tpu).
    tie_embeddings matches real GPT-2 (shared input/output matrix) and
    is ~3% faster on v5e (no separate [d, vocab] adamw update).
    logits_fp32=False keeps the [B, S, vocab] logits in bf16 —
    trainer.softmax_cross_entropy still accumulates its logsumexp in
    fp32, only the stored logit values round (measured ~4 ms/step at
    this scale; docs/benchmarks.md). ``overrides`` (e.g. flash_variant,
    max_seq_len) go straight into the TransformerConfig — the flash
    ablation leg pins variants through here."""
    from horovod_tpu.models import transformer as tr

    if on_tpu:
        kw = dict(attention_impl="flash", tie_embeddings=True,
                  logits_fp32=False)
        kw.update(overrides)
        return tr.TransformerConfig.gpt2_small_tpu(**kw)
    kw = dict(attention_impl="full")
    kw.update(overrides)
    return tr.TransformerConfig.tiny(**kw)


def build_transformer_step(mesh, batch, seq, cfg=None, on_tpu=True,
                           n_steps=None, vocab_chunk=0):
    """Compiled GSPMD train step + initial state for the flagship
    transformer LM — the ONE setup recipe (model/init/optimizer/token
    generation) shared by bench.py's MFU line and scaling_benchmark
    --model transformer, so the harnesses cannot drift.

    ``n_steps=None`` returns a per-call step (make_gspmd_step) with
    tokens [batch, seq]; ``n_steps=k`` returns the device-side scan
    (make_gspmd_multi_step) with tokens [k, batch, seq].
    Returns (step, params, opt_state, tokens, cfg)."""
    import numpy as np
    import optax

    from horovod_tpu.models import transformer as tr

    if cfg is None:
        cfg = flagship_config(on_tpu)
    model = tr.TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, seq), jnp.int32))["params"]
    # bf16 first moment (PaLM-style): halves the momentum state's HBM
    # traffic through the bandwidth-bound fused grad+AdamW updates —
    # measured -5 ms/step (+7% tok/s) at flagship scale on v5e with
    # loss identical to 3 decimals; second moment stays fp32 (its
    # dynamic range matters, the first moment's doesn't)
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    make = (trainer.make_gspmd_step if n_steps is None
            else trainer.make_gspmd_multi_step)
    step, pshard, bshard = make(
        tr.lm_loss_fn(model, vocab_chunk=vocab_chunk), tx, mesh,
        tr.param_specs(params), tr.batch_spec(), params=params)
    params = jax.tree_util.tree_map(jax.device_put, params, pshard)
    opt_state = trainer.init_opt_state(tx, params, mesh,
                                       tr.param_specs(params))
    rng = np.random.RandomState(0)
    shape = (batch, seq) if n_steps is None else (n_steps, batch, seq)
    toks = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, shape,
                                dtype=np.int64).astype(np.int32)), bshard)
    return step, params, opt_state, toks, cfg


def setup_transformer_lm(on_tpu, seq=None, flash_variant=None,
                         batch_per_chip=None):
    """Build the flagship-transformer bench (the canonical source of the
    tokens/sec/chip + MFU numbers in bench.py's JSON line and
    docs/benchmarks.md — keep single-sourced so harnesses cannot drift).

    Uses the device-side multi-step loop (trainer.make_gspmd_multi_step)
    so host dispatch is amortized out of the measurement; the loop scans
    over a stacked [n_steps, batch, seq] token array, a real optimizer
    update per inner step.

    ``seq`` / ``flash_variant`` / ``batch_per_chip`` override the
    flagship defaults — the flash-ablation leg builds one window per
    (variant, seq) operating point through exactly this recipe, so the
    ablation and the headline number can never measure different setups.

    Returns (window_fn, meta): window_fn() runs one timed window and
    returns seconds/step; the first call includes compile (callers
    treat it as warmup). Exposing windows individually lets bench.py
    INTERLEAVE them with the ResNet windows so session drift is
    common-mode across both headline numbers."""
    from horovod_tpu.parallel import mesh as mesh_mod

    if on_tpu:
        # batch 16 is the measured per-chip sweet spot (r4: 0.632 MFU vs
        # 0.603 at batch 8 and 0.58 at batch 32, docs/benchmarks.md)
        defaults = (16, 1024, 10)
    else:  # CI smoke on CPU: tiny everything, no MFU claim
        defaults = (2, 64, 2)
    batch_per_chip = batch_per_chip or defaults[0]
    seq = seq or defaults[1]
    inner = defaults[2]

    overrides = {}
    if flash_variant is not None:
        overrides["flash_variant"] = flash_variant
    if on_tpu and seq > 1024:
        overrides["max_seq_len"] = seq
    cfg = flagship_config(on_tpu, **overrides)

    n = hvd.size()
    mesh = mesh_mod.build_mesh(dp=n)
    batch = batch_per_chip * n
    step, params, opt_state, toks, cfg = build_transformer_step(
        mesh, batch, seq, cfg=cfg, on_tpu=on_tpu, n_steps=inner)
    live = {"params": params, "opt": opt_state}

    def window():
        t0 = time.perf_counter()
        live["params"], live["opt"], loss = step(live["params"],
                                                 live["opt"], toks)
        float(loss)  # scalar read: the barrier that ends the window
        return (time.perf_counter() - t0) / inner

    meta = {"batch": batch, "batch_per_chip": batch_per_chip, "seq": seq,
            "inner": inner, "cfg": cfg, "n": n,
            "flash_variant": flash_variant or "auto",
            "model": f"gpt2-small-{'tpu-flash' if on_tpu else 'tiny-smoke'}"}
    return window, meta


def transformer_lm_metrics(window_s, meta, peak_flops=None):
    """Fold per-window seconds/step into the bench's metrics dict.
    tokens_per_sec_per_chip/mfu keep the best-window convention (r3/r4
    comparability); the paired-measurement bound rides alongside as
    ms_per_step_mean/pm so cross-round deltas can be judged against
    session drift."""
    best = min(window_s)
    mean = sum(window_s) / len(window_s)
    pm = (max(window_s) - min(window_s)) / 2
    tps_chip = meta["batch"] * meta["seq"] / best / meta["n"]
    flops_per_token = transformer_matmul_flops_per_token(
        meta["cfg"], meta["seq"])
    mfu = (tps_chip * flops_per_token / peak_flops) if peak_flops else None
    return {
        "model": meta["model"],
        "tokens_per_sec_per_chip": round(tps_chip, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "seq_len": meta["seq"],
        "batch_per_chip": meta["batch_per_chip"],
        "ms_per_step": round(best * 1e3, 2),
        "ms_per_step_mean": round(mean * 1e3, 2),
        "ms_per_step_pm": round(pm * 1e3, 2),
        "windows": len(window_s),
    }


def bench_transformer_lm(on_tpu, peak_flops=None):
    """Sequential-windows convenience wrapper over setup/window/metrics
    (bench.py interleaves the windows itself)."""
    window, meta = setup_transformer_lm(on_tpu)
    window()  # compile + warmup
    windows = 3 if on_tpu else 1
    return transformer_lm_metrics([window() for _ in range(windows)],
                                  meta, peak_flops=peak_flops)


# ---------------------------------------------------------------------------
# Eager-allreduce training steps — the autotuner's regime.
#
# The GSPMD steps above average gradients with an in-graph psum, which the
# eager coordination core (and therefore HOROVOD_AUTOTUNE's passive scorer)
# never sees. These builders produce the eager form: per-shard gradients
# computed STACKED — vmap over a [world, per_shard, ...] batch, so every
# gradient leaf has leading dim == hvd.size() and rides the eager core's
# fused stacked-allreduce path (ops/eager.py), the exact path the tuner's
# burst bench exercises — then one optimizer apply on the averaged row.
# Shared by examples/{transformer_lm,synthetic_benchmark}.py
# --eager-allreduce and bench.py's autotune train leg, so the tuner is
# scored on the same step recipe users run.
# ---------------------------------------------------------------------------


def build_eager_lm_step(cfg, world, batch_per_shard, seq, lr=3e-4,
                        tx=None, params=None):
    """Transformer train step with EAGER gradient averaging.
    Returns (step, params, opt_state, toks); step(params, opt_state,
    toks) -> (params, opt_state, loss), toks [world, batch_per_shard,
    seq]. Pass ``tx``/``params`` to reuse a caller's optimizer and
    initialized weights (examples/transformer_lm.py --eager-allreduce)."""
    import numpy as np

    from horovod_tpu.models import transformer as tr

    model = tr.TransformerLM(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((2, seq), jnp.int32))["params"]
    if tx is None:
        tx = optax.adamw(lr, mu_dtype=jnp.bfloat16)
    opt_state = tx.init(params)
    loss_fn = tr.lm_loss_fn(model)
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(
        0, cfg.vocab_size, (world, batch_per_shard, seq),
        dtype=np.int64).astype(np.int32))
    return (_eager_step(loss_fn, tx), params, opt_state, toks)


def build_eager_image_step(model_name, world, batch_per_shard, image_size,
                           compression=None):
    """Image-model (ResNet et al) train step with EAGER gradient
    averaging; batch data is [world, batch_per_shard, H, W, 3]."""
    from horovod_tpu import models, trainer as trainer_mod

    kwargs = {"dropout_rate": 0.0} if model_name.startswith("vgg") else {}
    model = models.build(model_name, num_classes=1000, dtype=jnp.bfloat16,
                         **kwargs)
    images = jnp.zeros((world, batch_per_shard, image_size, image_size, 3),
                       jnp.bfloat16)
    labels = jnp.zeros((world, batch_per_shard), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[0, :2],
                           train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)

    def loss_fn(p, batch):
        imgs, lbls = batch
        logits, _ = model.apply(
            {"params": p, "batch_stats": batch_stats}, imgs, train=True,
            mutable=["batch_stats"])
        return trainer_mod.softmax_cross_entropy(logits, lbls)

    step = _eager_step(loss_fn, tx, compression=compression)
    return step, params, opt_state, (images, labels)


def _eager_step(loss_fn, tx, compression=None):
    """The shared eager-dp step: jitted vmap'd per-shard grads (stacked
    [world, ...] leaves), ONE eager fused allreduce between compute and
    apply, jitted apply on the averaged row-0 grads."""
    grad_fn = jax.jit(jax.vmap(jax.value_and_grad(loss_fn),
                               in_axes=(None, 0)))
    compression = compression or hvd.Compression.none

    @jax.jit
    def apply_fn(params, opt_state, grads):
        g0 = jax.tree_util.tree_map(lambda g: g[0], grads)
        updates, opt_state = tx.update(g0, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def step(params, opt_state, batch):
        losses, grads = grad_fn(params, batch)
        # the eager core: every leaf is [world, ...] -> stacked kind,
        # fused by the live fusion_threshold/cycle_time knobs, scored
        # passively by the autotuner when HOROVOD_AUTOTUNE=1
        grads = hvd.allreduce_gradients(grads, compression=compression)
        params, opt_state = apply_fn(params, opt_state, grads)
        return params, opt_state, jnp.mean(losses)

    return step
