"""Step recipes shared by the Horovod-parity examples
(synthetic_benchmark.py, scaling_benchmark.py, transformer_lm.py
--eager-allreduce) and chip_smoke.py, and the reference harness's timing
protocol (examples/pytorch_synthetic_benchmark.py:24-33 in the reference:
warmup batches, then num_iters x num_batches_per_iter timed batches)."""

import time

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models, trainer


def build_step(model_name, mesh, batch, image_size, fp16_allreduce=False):
    """Compiled data-parallel train step + initial (params, opt_state,
    batch data) for a zoo model on synthetic ImageNet-shaped data."""
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
                                           donate=True)
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    images = jax.device_put(images, sharding)
    labels = jax.device_put(labels, sharding)
    return step, params, opt_state, (images, labels)


def timed_rates(step, params, opt_state, batch_data, batch,
                num_warmup_batches, num_iters, num_batches_per_iter,
                on_iter=None):
    """Run the reference timing protocol; returns per-iteration total
    img/sec. At least one warmup step always runs so trace+compile of the
    jitted step can never land inside the timed region (a compile-polluted
    first iteration would silently wreck the reported rate). The sync
    barrier is a scalar device-to-host read. The jitted step donates
    params/opt_state: they are dead to the caller afterwards."""
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
        rate = batch * num_batches_per_iter / dt
        rates.append(rate)
        if on_iter is not None:
            on_iter(i, rate)
    return rates


def positive_int(value):
    v = int(value)
    if v < 1:
        raise ValueError(f"expected a positive count, got {value}")
    return v


def flagship_config(on_tpu=True, **overrides):
    """The model build_transformer_step trains when given no config:
    gpt2_small_tpu on a chip (GPT-2-small's size with the 6x128 head
    shape: head_dim 128 is the lane width, so the flash kernels run
    unpadded), TransformerConfig.tiny elsewhere. tie_embeddings matches
    GPT-2 (one input/output matrix); logits_fp32=False keeps the
    [B, S, vocab] logits in bf16 (trainer.softmax_cross_entropy still
    accumulates its logsumexp in fp32). ``overrides`` go straight into
    the TransformerConfig."""
    from horovod_tpu.models import transformer as tr

    if on_tpu:
        kw = dict(attention_impl="flash", tie_embeddings=True,
                  logits_fp32=False)
        kw.update(overrides)
        return tr.TransformerConfig.gpt2_small_tpu(**kw)
    kw = dict(attention_impl="full")
    kw.update(overrides)
    return tr.TransformerConfig.tiny(**kw)


def build_transformer_step(mesh, batch, seq, cfg=None, on_tpu=True):
    """Compiled GSPMD train step (trainer.make_gspmd_step) + initial
    state for the transformer LM, tokens [batch, seq]: the one setup
    recipe (model, init, optimizer, tokens) shared by chip_smoke.py and
    scaling_benchmark --model transformer.
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
    # traffic through the bandwidth-bound fused grad+AdamW updates;
    # second moment stays fp32 (its dynamic range matters, the first
    # moment's doesn't)
    tx = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    step, pshard, bshard = trainer.make_gspmd_step(
        tr.lm_loss_fn(model), tx, mesh,
        tr.param_specs(params), tr.batch_spec(), params=params)
    params = jax.tree_util.tree_map(jax.device_put, params, pshard)
    opt_state = trainer.init_opt_state(tx, params, mesh,
                                       tr.param_specs(params))
    rng = np.random.RandomState(0)
    toks = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq),
                                dtype=np.int64).astype(np.int32)), bshard)
    return step, params, opt_state, toks, cfg


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
# --eager-allreduce.
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
