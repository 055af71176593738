"""Trainer step builders: sharding stability and opt-state spec derivation.

The reference relies on its response cache to make repeat iterations cheap
(response_cache.h:43-92); the jit analogue is *compiling exactly once*. These
tests pin the subtle failure mode where a host-created optimizer state (its
scalar avals carry no mesh context) silently recompiles the whole train step
on the second call.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import trainer
from horovod_tpu.models import transformer as tr
from horovod_tpu.parallel import mesh as mesh_mod


def _tiny_setup(mesh):
    cfg = tr.TransformerConfig.tiny()
    model = tr.TransformerLM(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 64)),
        jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    return model, params, tokens


class TestOptStateSpecs:
    def test_mirrors_param_specs_and_replicates_scalars(self):
        params = {"w": jnp.ones((4, 4)), "b": jnp.ones((4,))}
        specs = {"w": P("tp", None), "b": P()}
        tx = optax.adamw(1e-3)
        out = trainer.opt_state_specs(tx, params, specs)
        adam = out[0]
        assert adam.count == P()
        assert adam.mu["w"] == P("tp", None)
        assert adam.mu["b"] == P()
        assert adam.nu["w"] == P("tp", None)

    def test_works_with_distributed_optimizer(self):
        import horovod_tpu as hvd
        params = {"w": jnp.ones((4, 4))}
        tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        out = trainer.opt_state_specs(
            tx, params, {"w": P()})
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda s: isinstance(s, P))
        assert all(isinstance(s, P) for s in leaves)


class TestGradientScaling:
    def test_data_parallel_update_matches_analytic_gd(self, hvd):
        """The distributed step must equal full-batch GD exactly — guards
        against shard_map autodiff pre-summing grads of replicated params
        (which silently applies size()× gradients)."""
        import horovod_tpu as hvd_mod
        mesh = hvd.mesh()
        axis = mesh.axis_names[0]
        X = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        true_w = np.array([[2.0], [-3.0], [0.5], [1.0]], np.float32)
        Y = X @ true_w

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        tx = hvd_mod.DistributedOptimizer(optax.sgd(0.1))
        params = {"w": jnp.zeros((4, 1))}
        step = trainer.make_data_parallel_step(loss_fn, tx, mesh,
                                               donate=False)
        opt_state = trainer.init_opt_state(tx, params, mesh)
        batch = trainer.place((jnp.asarray(X), jnp.asarray(Y)), mesh,
                              (P(axis), P(axis)))
        p1, _, _ = step(params, opt_state, batch)
        w0 = np.zeros((4, 1), np.float32)
        w1 = w0 - 0.1 * (2.0 / 64.0 * X.T @ (X @ w0 - Y))
        np.testing.assert_allclose(np.asarray(p1["w"]), w1, rtol=1e-5)

    def test_data_parallel_training_converges(self, hvd):
        mesh = hvd.mesh()
        axis = mesh.axis_names[0]
        X = np.random.RandomState(1).randn(64, 4).astype(np.float32)
        true_w = np.array([[2.0], [-3.0], [0.5], [1.0]], np.float32)
        Y = X @ true_w

        def loss_fn(p, batch):
            x, y = batch
            return jnp.mean((x @ p["w"] - y) ** 2)

        tx = optax.sgd(0.1)
        params = {"w": jnp.zeros((4, 1))}
        step = trainer.make_data_parallel_step(loss_fn, tx, mesh,
                                               donate=False)
        opt_state = trainer.init_opt_state(tx, params, mesh)
        batch = trainer.place((jnp.asarray(X), jnp.asarray(Y)), mesh,
                              (P(axis), P(axis)))
        for _ in range(200):
            params, opt_state, loss = step(params, opt_state, batch)
            # block each step: hundreds of in-flight 8-device collective
            # programs can starve the CPU backend's rendezvous (the real
            # TPU path has hardware queues and doesn't need this)
            loss.block_until_ready()
        assert float(loss) < 1e-3
        np.testing.assert_allclose(np.asarray(params["w"]), true_w,
                                   atol=1e-2)


class TestSingleCompile:
    def test_gspmd_step_compiles_once(self, hvd):
        mesh = mesh_mod.build_mesh(dp=2, tp=2, sp=2)
        model, params, tokens = _tiny_setup(mesh)
        loss_fn = tr.lm_loss_fn(model)
        tx = optax.adamw(1e-3)
        specs = tr.param_specs(params)
        step, pshard, bshard = trainer.make_gspmd_step(
            loss_fn, tx, mesh, specs, tr.batch_spec(sp=True), params=params)
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        opt_state = trainer.init_opt_state(tx, params, mesh, specs)
        tokens = jax.device_put(tokens, bshard)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
        assert jnp.isfinite(loss)
        assert step._cache_size() == 1, (
            "train step recompiled: opt_state shardings are not stable "
            "across calls")

    def test_bare_tx_init_would_recompile(self, hvd):
        # documents WHY init_opt_state exists: the naive host-side tx.init
        # costs a second compilation.
        mesh = mesh_mod.build_mesh(dp=2, tp=2, sp=2)
        model, params, tokens = _tiny_setup(mesh)
        loss_fn = tr.lm_loss_fn(model)
        tx = optax.adamw(1e-3)
        specs = tr.param_specs(params)
        step, pshard, bshard = trainer.make_gspmd_step(
            loss_fn, tx, mesh, specs, tr.batch_spec(sp=True), params=params)
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        opt_state = tx.init(params)  # deliberately NOT init_opt_state
        tokens = jax.device_put(tokens, bshard)
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
        assert step._cache_size() >= 1  # smoke: still correct, just slower

    def test_data_parallel_step_compiles_once(self, hvd):
        mesh = hvd.mesh()

        def loss_fn(p, batch):
            x, y = batch
            pred = x @ p["w"]
            return jnp.mean((pred - y) ** 2)

        tx = optax.sgd(0.1, momentum=0.9)
        params = trainer.replicate({"w": jnp.ones((4, 2))}, mesh)
        step = trainer.make_data_parallel_step(loss_fn, tx, mesh,
                                               donate=False)
        opt_state = trainer.init_opt_state(tx, params, mesh)
        axis = mesh.axis_names[0]
        batch = trainer.place((jnp.ones((8, 4)), jnp.zeros((8, 2))), mesh,
                              (P(axis), P(axis)))
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, batch)
        assert step._cache_size() == 1
