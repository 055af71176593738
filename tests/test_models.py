"""Model-zoo shape/forward tests plus a data-parallel training smoke test
(the 'ONE model running' milestone, SURVEY.md §7 slice 1; parity with the
reference's example-based integration tests, .buildkite/gen-pipeline.sh)."""

import numpy as np
import pytest


def test_mnist_cnn_forward(hvd):
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models.mnist import MnistCNN

    model = MnistCNN()
    x = jnp.zeros((2, 28, 28, 1))
    params = model.init(jax.random.PRNGKey(0), x)["params"]
    out = model.apply({"params": params}, x)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


@pytest.mark.parametrize("name,depth_params", [("resnet18", 11_000_000),
                                               ("resnet50", 25_000_000)])
def test_resnet_forward_and_param_count(hvd, name, depth_params):
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import resnet

    model = resnet.MODELS[name](num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (1, 1000)
    n_params = sum(np.prod(p.shape) for p in
                   jax.tree_util.tree_leaves(variables["params"]))
    # torchvision resnet50 has 25.6M params, resnet18 11.7M — match within 5%
    assert abs(n_params - depth_params) / depth_params < 0.1


def test_vgg16_forward_and_param_count(hvd):
    import jax
    import jax.numpy as jnp
    from horovod_tpu import models

    model = models.build("vgg16", num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 224, 224, 3))
    variables = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(variables, x)
    assert out.shape == (1, 1000)
    n = sum(np.prod(p.shape) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    # torchvision vgg16: 138.4M params — the benchmark table's
    # communication-bound model (docs/benchmarks.md VGG-16 68% row)
    assert abs(n - 138_357_544) / 138_357_544 < 0.01, n


def test_inception3_forward_and_param_count(hvd):
    import jax
    import jax.numpy as jnp
    from horovod_tpu import models

    model = models.build("inception3", num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 299, 299, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (1, 1000)
    n = sum(np.prod(p.shape) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    # torchvision inception_v3 (no aux head): ~23.8M params
    assert abs(n - 23_834_568) / 23_834_568 < 0.02, n


def test_model_registry_rejects_unknown(hvd):
    from horovod_tpu import models
    import pytest as _pytest
    with _pytest.raises(KeyError, match="Unknown model"):
        models.build("alexnet")


def test_transformer_forward(hvd):
    import jax
    from horovod_tpu.models import transformer as tr

    cfg = tr.TransformerConfig.tiny()
    model, params = tr.init_params(cfg, jax.random.PRNGKey(0),
                                   batch_size=2, seq_len=16)
    out = model.apply({"params": params},
                      np.zeros((2, 16), np.int32))
    assert out.shape == (2, 16, cfg.vocab_size)


def test_transformer_param_specs_cover_tp(hvd):
    import jax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.models import transformer as tr

    cfg = tr.TransformerConfig.tiny()
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    specs = tr.param_specs(params)
    flat = jax.tree_util.tree_leaves_with_path(specs)
    tp_sharded = [s for _, s in flat if s != P()]
    # qkv/out/gate/up/down per layer + lm_head + embed rule
    assert len(tp_sharded) >= cfg.num_layers * 5 + 1


def test_data_parallel_training_decreases_loss(hvd):
    """MNIST-shaped end-to-end: DistributedOptimizer + broadcast_parameters
    on the 8-worker mesh; loss must drop (reference examples smoke tests)."""
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu import trainer
    from horovod_tpu.models.mnist import MnistCNN

    model = MnistCNN()
    rng = np.random.RandomState(0)
    # synthetic "digits": class = quadrant with most mass
    X = rng.rand(64, 28, 28, 1).astype(np.float32)
    Y = rng.randint(0, 10, 64)

    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))[
        "params"]
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)
    params = hvd.broadcast_parameters(params)

    def loss_fn(p, batch):
        imgs, labels = batch
        logits = model.apply({"params": p}, imgs)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    step = trainer.make_data_parallel_step(loss_fn, tx, hvd.mesh(),
                                           donate=False)
    batch = (jnp.asarray(X), jnp.asarray(Y))
    losses = []
    for _ in range(30):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.3, losses[:3] + losses[-3:]


def test_gspmd_transformer_step_multi_axis(hvd):
    """Full transformer train step over a dp2 x tp2 x sp2 mesh — the
    multi-axis path dryrun_multichip exercises."""
    import __graft_entry__ as graft
    graft.dryrun_multichip(8)


class TestTiedEmbeddings:
    def test_tied_head_uses_embedding(self, hvd):
        """tie_embeddings=True: no separate lm_head params; logits are
        hidden @ embedding.T; dense and chunked losses agree; gradients
        reach the shared matrix from both uses."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from horovod_tpu.models import transformer as tr

        cfg = tr.TransformerConfig.tiny(tie_embeddings=True)
        model = tr.TransformerLM(cfg)
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16)),
            jnp.int32)
        params = model.init(jax.random.PRNGKey(0), toks)["params"]
        assert "lm_head" not in params
        logits = model.apply({"params": params}, toks)
        assert logits.shape == (2, 16, cfg.vocab_size)
        # logits really are hidden @ embedding.T (fp32 straight from the
        # MXU accumulator — models/transformer.py head path)
        hidden = model.apply({"params": params}, toks, return_hidden=True)
        want = jnp.dot(hidden,
                       params["embed"]["embedding"].T.astype(hidden.dtype),
                       preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(want, np.float32),
                                   rtol=1e-5, atol=1e-5)
        dense = tr.lm_loss_fn(model)(params, toks)
        chunked = tr.lm_loss_fn(model, vocab_chunk=64)(params, toks)
        # dense (streaming-lse over fp32 logits) and chunked (per-chunk
        # online lse) accumulate in different orders — bit-exactness is
        # not part of the contract (2e-4: bf16 activations and rotation
        # leave ~1e-4 of order-dependent slack between the two paths)
        np.testing.assert_allclose(float(dense), float(chunked),
                                   rtol=2e-4)
        g = jax.grad(tr.lm_loss_fn(model))(params, toks)
        emb_g = np.asarray(g["embed"]["embedding"])
        assert np.isfinite(emb_g).all() and np.abs(emb_g).sum() > 0


class TestTpuHeadShape:
    def test_gpt2_small_tpu_same_size_and_flops(self, hvd):
        """gpt2_small_tpu is GPT-2-small with the TPU-native 6x128 head
        shape: identical parameter count and identical matmul FLOPs per
        token (the PaLM MFU formula is head-count independent) — the
        +18% measured on v5e comes from kernel-level padding, not from
        a smaller model."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr

        def n_params(cfg):
            model = tr.TransformerLM(cfg)
            p = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))["params"]
            return sum(x.size for x in jax.tree_util.tree_leaves(p))

        a = tr.TransformerConfig.gpt2_small(tie_embeddings=True)
        b = tr.TransformerConfig.gpt2_small_tpu(tie_embeddings=True)
        assert n_params(a) == n_params(b)
        assert (a.d_model, a.num_layers, a.d_ff, a.vocab_size) == \
               (b.d_model, b.num_layers, b.d_ff, b.vocab_size)
        assert b.d_model // b.num_heads == 128  # the lane width

        assert (tr.matmul_flops_per_token(a, 1024) ==
                tr.matmul_flops_per_token(b, 1024))


def _serving_imports(module):
    """Every name a file of ``horovod_tpu/models/`` imports from
    ``horovod_tpu.serving``, at module level or inside a function."""
    import ast
    import pathlib
    import horovod_tpu.models
    path = pathlib.Path(horovod_tpu.models.__file__).with_name(module + ".py")
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            # level 1 is horovod_tpu.models, level 2 horovod_tpu
            base = ("", "horovod_tpu.models.", "horovod_tpu.")[node.level]
            found += [f"{base}{node.module or ''}.{a.name}".replace("..", ".")
                      for a in node.names]
        elif isinstance(node, ast.Import):
            found += [a.name for a in node.names]
    return [name for name in found
            if name.startswith("horovod_tpu.serving")]


@pytest.mark.parametrize("module, allowed", [
    ("transformer", []), ("moe", []), ("hybrid", []), ("latent_moe", []),
    ("window_moe", []),
    # the looped model's no-cache forward lives in serving/decode.py with
    # ``_stack``; both move when training, prefill and decode share one
    # block (ROADMAP D1 (a)), and this entry goes with them
    ("looped", ["horovod_tpu.serving.decode.hidden_states"])])
def test_models_do_not_import_serving(module, allowed):
    """Imports point one way: ``serving/`` builds on ``models/``. A model
    brings ``state_shapes``, ``prefill`` and ``decode``; the leaves they
    are built of (``_dense``, ``_rmsnorm``, ``_embed``, ``_logits``,
    ``_mlp``) are models/transformer.py's, so that a training path for a
    served family imports no server."""
    assert _serving_imports(module) == allowed
