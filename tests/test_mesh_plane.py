"""Named-mesh data plane (docs/mesh.md): HOROVOD_MESH parsing, the
process-global mesh lifecycle, spec-tree placement helpers, real
dp×tp×sp training parity against the dp-only path, cross-layout
checkpoint restore (save 2×4, restore 4×2 / 8×1, bit-exact), and the
tensor-parallel ServeEngine (temp-0 token parity + the per-chip KV
byte drop). Runs on the conftest 8-device virtual CPU mesh."""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from horovod_tpu import trainer
from horovod_tpu.models import transformer as tr
from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.utils import checkpoint as ckpt
from horovod_tpu.utils import metrics as hvd_metrics

# the MULTICHIP_r05 contract: sharded vs single-path losses agree to
RTOL = 5e-4

_MESH_ENV = ("HOROVOD_MESH", "HOROVOD_MESH_TP", "HOROVOD_MESH_SP",
             "HOROVOD_MESH_PP", "HOROVOD_MESH_EP")


@pytest.fixture(autouse=True)
def _fresh_global_mesh():
    """Every test starts and ends with no committed mesh and no mesh
    env knobs — layout leakage between tests is exactly the bug
    set_global_mesh exists to make loud."""
    saved = {k: os.environ.pop(k) for k in _MESH_ENV if k in os.environ}
    mesh_lib.reset_global_mesh()
    yield
    mesh_lib.reset_global_mesh()
    os.environ.update(saved)


@pytest.fixture
def reg():
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()


def _layout(mesh):
    return {a: s for a, s in mesh.shape.items() if s > 1}


# ---------------------------------------------------------------------------
# spec parsing + env construction
# ---------------------------------------------------------------------------

class TestMeshSpec:
    def test_parse_full_spec(self):
        assert mesh_lib.parse_mesh_spec("dp=2,tp=4") == {"dp": 2, "tp": 4}
        assert mesh_lib.parse_mesh_spec(" tp=2 , sp=2 ") == \
            {"tp": 2, "sp": 2}
        assert mesh_lib.parse_mesh_spec("") == {}

    @pytest.mark.parametrize("bad", [
        "xp=2",        # unknown axis
        "tp=2,tp=4",   # duplicate
        "tp=two",      # non-int
        "tp=0",        # size < 1
        "tp",          # not axis=size
    ])
    def test_parse_fails_loud(self, bad):
        with pytest.raises(ValueError):
            mesh_lib.parse_mesh_spec(bad)

    def test_env_full_spec_wins_over_knobs(self):
        mesh = mesh_lib.mesh_from_env(environ={
            "HOROVOD_MESH": "dp=2,tp=4", "HOROVOD_MESH_TP": "2"})
        assert _layout(mesh) == {"dp": 2, "tp": 4}

    def test_env_per_axis_knobs_infer_dp(self):
        mesh = mesh_lib.mesh_from_env(
            environ={"HOROVOD_MESH_TP": "2", "HOROVOD_MESH_SP": "2"})
        assert _layout(mesh) == {"dp": 2, "tp": 2, "sp": 2}

    def test_env_empty_is_pure_dp(self):
        mesh = mesh_lib.mesh_from_env(environ={})
        assert _layout(mesh) == {"dp": jax.device_count()}

    def test_indivisible_layout_fails_loud(self):
        with pytest.raises(ValueError):
            mesh_lib.mesh_from_env(environ={"HOROVOD_MESH": "dp=3,tp=4"})


# ---------------------------------------------------------------------------
# process-global mesh lifecycle
# ---------------------------------------------------------------------------

class TestGlobalMesh:
    def test_lazy_build_commits_env_layout(self):
        assert mesh_lib.global_mesh_if_set() is None
        os.environ["HOROVOD_MESH"] = "tp=2"
        mesh = mesh_lib.global_mesh()
        assert _layout(mesh) == {"dp": 4, "tp": 2}
        # committed: later env changes don't re-build
        os.environ["HOROVOD_MESH"] = "tp=4"
        assert mesh_lib.global_mesh() is mesh
        assert mesh_lib.global_mesh_if_set() is mesh

    def test_set_is_idempotent_for_same_shape(self):
        a = mesh_lib.build_mesh(tp=2)
        mesh_lib.set_global_mesh(a)
        mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=2))  # no raise

    def test_replacing_committed_layout_raises(self):
        mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=2))
        with pytest.raises(RuntimeError):
            mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=4))
        mesh_lib.reset_global_mesh()
        mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=4))

    def test_commit_publishes_axis_gauges(self, reg):
        mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=2, sp=2))
        snap = reg.snapshot()
        fam = snap["metrics"]["hvd_mesh_axis_size"]
        sizes = {v["labels"]["axis"]: v["value"] for v in fam["values"]}
        assert sizes == {"dp": 2, "pp": 1, "tp": 2, "sp": 2, "ep": 1}


# ---------------------------------------------------------------------------
# spec-tree placement helpers
# ---------------------------------------------------------------------------

class TestPlacement:
    def test_device_put_tree_places_by_spec(self):
        mesh = mesh_lib.build_mesh(tp=4)
        tree = {"w": jnp.ones((8, 8)), "b": jnp.ones((8,))}
        specs = {"w": P(None, "tp"), "b": P()}
        placed = mesh_lib.device_put_tree(tree, specs, mesh)
        assert placed["w"].sharding.spec == P(None, "tp")
        assert placed["w"].sharding.mesh.shape == mesh.shape
        # sharded dim: each device holds 8/4 columns
        assert placed["w"].sharding.shard_shape((8, 8)) == (8, 2)
        np.testing.assert_array_equal(np.asarray(placed["w"]),
                                      np.ones((8, 8)))

    def test_param_specs_place_tied_lm(self):
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
        mesh = mesh_lib.build_mesh(tp=2)
        placed = mesh_lib.device_put_tree(params, tr.param_specs(params),
                                          mesh)
        qkv = placed["layer_0"]["attn"]["qkv"]["kernel"]
        out = placed["layer_0"]["attn"]["out"]["kernel"]
        assert qkv.sharding.spec == P(None, "tp")   # column-parallel
        assert out.sharding.spec == P("tp", None)   # row-parallel

    def test_replicate_tree(self):
        mesh = mesh_lib.build_mesh(tp=2)
        placed = mesh_lib.replicate_tree({"x": jnp.arange(4.0)}, mesh)
        assert placed["x"].sharding.spec == P()

    def test_kv_cache_spec_follows_tp_divisibility(self):
        assert mesh_lib.kv_cache_spec(
            4, mesh_lib.build_mesh(tp=2)) == P(None, None, None, "tp",
                                               None)
        assert mesh_lib.kv_cache_spec(4, mesh_lib.build_mesh()) == P()
        # tp=8 doesn't divide 4 heads: replicated, never raggedly sharded
        assert mesh_lib.kv_cache_spec(4, mesh_lib.build_mesh(tp=8)) == P()

    def test_decode_head_sharding_needs_committed_tp_mesh(self):
        assert mesh_lib.decode_head_sharding(4) is None  # nothing set
        mesh_lib.set_global_mesh(mesh_lib.build_mesh(tp=2))
        hs = mesh_lib.decode_head_sharding(4)
        assert hs is not None and hs.spec == P(None, None, "tp", None)
        assert mesh_lib.decode_head_sharding(3) is None  # indivisible


# ---------------------------------------------------------------------------
# real dp×tp×sp training vs the dp-only path (MULTICHIP_r05 tolerance)
# ---------------------------------------------------------------------------

def _train_losses(mesh, sp, params, model, steps=3, batch=8, seq=32):
    loss_fn = tr.lm_loss_fn(model)
    specs = tr.param_specs(params)
    tx = optax.adam(1e-3)
    p = trainer.place(params, mesh, specs)
    opt = trainer.init_opt_state(tx, p, mesh, specs)
    step, _, batch_shard = trainer.make_gspmd_step(
        loss_fn, tx, mesh, specs, tr.batch_spec(sp=sp), donate=False,
        params=p)
    toks = np.random.RandomState(0).randint(
        0, model.cfg.vocab_size, size=(steps, batch, seq)).astype(np.int32)
    losses = []
    for t in toks:
        p, opt, loss = step(p, opt, jax.device_put(t, batch_shard))
        losses.append(float(loss))
    return losses


@pytest.mark.slow
def test_dp_tp_sp_training_matches_dp_only():
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    model, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    ref = _train_losses(mesh_lib.build_mesh(), False, params, model)
    got = _train_losses(mesh_lib.build_mesh(dp=2, tp=2, sp=2), True,
                        params, model)
    np.testing.assert_allclose(got, ref, rtol=RTOL)


@pytest.mark.slow
def test_tp2_training_matches_dp_only():
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    model, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    ref = _train_losses(mesh_lib.build_mesh(), False, params, model)
    got = _train_losses(mesh_lib.build_mesh(tp=2), False, params, model)
    np.testing.assert_allclose(got, ref, rtol=RTOL)


# ---------------------------------------------------------------------------
# cross-layout checkpoint restore
# ---------------------------------------------------------------------------

def _state_on(mesh):
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    _, params = tr.init_params(cfg, jax.random.PRNGKey(1))
    specs = tr.param_specs(params)
    tx = optax.adam(1e-3)
    params = trainer.place(params, mesh, specs)
    opt = trainer.init_opt_state(tx, params, mesh, specs)
    return params, opt, specs, trainer.opt_state_specs(tx, params, specs)


def _assert_trees_bit_exact(got, want):
    flat_g, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_w, _ = jax.tree_util.tree_flatten_with_path(want)
    assert len(flat_g) == len(flat_w)
    for (path, g), (_, w) in zip(flat_g, flat_w):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=jax.tree_util.keystr(path))


class TestCrossLayoutRestore:
    EXTRA = {"rng": [7, 11], "data_pos": 12345}

    def _save_2x4(self, tmp_path):
        mesh_a = mesh_lib.build_mesh(dp=2, tp=4)
        params, opt, specs, opt_specs = _state_on(mesh_a)
        mgr = ckpt.CheckpointManager(
            str(tmp_path), async_save=False,
            layout=mesh_lib.mesh_layout(mesh_a))
        mgr.save((params, opt), step=7, extra=dict(self.EXTRA))
        return params, opt, specs, opt_specs

    @pytest.mark.parametrize("layout", [{"dp": 4, "tp": 2}, {"dp": 8}])
    def test_save_2x4_restore_bit_exact(self, tmp_path, layout, reg):
        params, opt, specs, opt_specs = self._save_2x4(tmp_path)
        assert ckpt.saved_layout(str(tmp_path)) == \
            {"dp": 2, "pp": 1, "tp": 4, "sp": 1, "ep": 1}

        mesh_b = mesh_lib.build_mesh(**layout)
        like = jax.tree_util.tree_map(np.zeros_like, (params, opt))
        got, step, extra = ckpt.restore_on_mesh(
            str(tmp_path), like=like, spec_tree=(specs, opt_specs),
            mesh=mesh_b)
        assert step == 7
        assert extra == self.EXTRA
        _assert_trees_bit_exact(got, (params, opt))
        # every leaf landed on the restore-time mesh
        for leaf in jax.tree_util.tree_leaves(got):
            assert dict(leaf.sharding.mesh.shape) == dict(mesh_b.shape)
        # the layout change is announced on the event channel
        events = [e for e in reg.snapshot()["events"]
                  if e["event"] == "ckpt_cross_layout_restore"]
        assert len(events) == 1
        assert events[0]["saved"]["tp"] == 4
        assert events[0]["restored"] == mesh_lib.mesh_layout(mesh_b)

    def test_manager_restore_routes_spec_tree(self, tmp_path):
        params, opt, specs, opt_specs = self._save_2x4(tmp_path)
        mesh_b = mesh_lib.build_mesh(dp=4, tp=2)
        like = jax.tree_util.tree_map(np.zeros_like, (params, opt))
        mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
        got, step, extra = mgr.restore(like=like, mesh=mesh_b,
                                       spec_tree=(specs, opt_specs))
        assert step == 7 and extra == self.EXTRA
        _assert_trees_bit_exact(got, (params, opt))

    def test_same_layout_restore_emits_no_event(self, tmp_path, reg):
        params, opt, specs, opt_specs = self._save_2x4(tmp_path)
        mesh_a = mesh_lib.build_mesh(dp=2, tp=4)
        like = jax.tree_util.tree_map(np.zeros_like, (params, opt))
        got, _, _ = ckpt.restore_on_mesh(
            str(tmp_path), like=like, spec_tree=(specs, opt_specs),
            mesh=mesh_a)
        _assert_trees_bit_exact(got, (params, opt))
        assert not [e for e in reg.snapshot()["events"]
                    if e["event"] == "ckpt_cross_layout_restore"]

    def test_legacy_unstamped_manifest_keeps_mn_path(self, tmp_path):
        # regression arm: a pre-mesh save (no layout=) restores through
        # the plain M->N path and reports no layout
        tree = {"w": jnp.arange(16.0).reshape(4, 4), "step": jnp.ones(())}
        mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(tree, step=3, extra={"pos": 1})
        assert ckpt.saved_layout(str(tmp_path)) is None
        like = jax.tree_util.tree_map(np.zeros_like, tree)
        got, step, extra = ckpt.restore_with_extra(str(tmp_path),
                                                   like=like)
        assert step == 3 and extra == {"pos": 1}
        _assert_trees_bit_exact(got, tree)
        # ...and restore_on_mesh still works on it (placement only)
        got2, _, _ = ckpt.restore_on_mesh(
            str(tmp_path), like=like,
            spec_tree={"w": P(None, "tp"), "step": P()},
            mesh=mesh_lib.build_mesh(tp=2))
        _assert_trees_bit_exact(got2, tree)
        assert got2["w"].sharding.spec == P(None, "tp")


# ---------------------------------------------------------------------------
# tensor-parallel ServeEngine over the same mesh
# ---------------------------------------------------------------------------

def _serve_tokens(cfg, params, mesh):
    from horovod_tpu.serving.engine import ServeEngine
    from horovod_tpu.serving.queue import AdmissionQueue, Request
    engine = ServeEngine(
        cfg, params, num_slots=2, max_len=48, kv_block=8,
        queue=AdmissionQueue(max_depth=64, admission_timeout_s=1e9),
        mesh=mesh)
    prompts = [(5, 9, 17), (4, 8, 15, 16, 23, 42)]
    for i, p in enumerate(prompts):
        engine.submit(Request(f"r{i}", p, max_new_tokens=8,
                              temperature=0.0))
    results = {r.request_id: list(r.tokens)
               for r in engine.run_to_completion()}
    return [results[f"r{i}"] for i in range(len(prompts))], engine


@pytest.mark.slow
def test_tp_engine_token_parity_and_kv_bytes(reg):
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))

    # unsharded reference first (no committed mesh -> dp-only program)
    ref_tokens, ref_engine = _serve_tokens(cfg, params, mesh=None)

    mesh = mesh_lib.build_mesh(tp=2)
    mesh_lib.set_global_mesh(mesh)  # decode head-sharding hint
    tp_tokens, tp_engine = _serve_tokens(cfg, params, mesh=mesh)

    assert tp_tokens == ref_tokens  # temp-0, token for token
    # the point of tp serving: each chip holds heads/tp of the cache
    ratio = ref_engine.kv.per_chip_bytes() / tp_engine.kv.per_chip_bytes()
    assert ratio >= 1.9
    # head axis (index 3) sharded over tp (trailing Nones normalized)
    assert tuple(tp_engine.kv.k.sharding.spec)[:4] == \
        (None, None, None, "tp")


def test_tp_engine_updates_its_cache_in_place(reg):
    """The head-sharded cache is donated too: its outputs keep the
    inputs' sharding, so no donation is dropped (JAX would warn "Some
    donated buffers were not usable" and copy the cache every call)."""
    import warnings
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    mesh = mesh_lib.build_mesh(tp=2)
    mesh_lib.set_global_mesh(mesh)  # decode head-sharding hint
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        tokens, engine = _serve_tokens(cfg, params, mesh=mesh)
    assert all(len(t) == 8 for t in tokens)
    gauge, = reg.snapshot()["metrics"]["hvd_serve_kv_in_place"]["values"]
    assert gauge["value"] == 1
    for arr in (engine.kv.k, engine.kv.v):
        assert not arr.is_deleted()
        assert tuple(arr.sharding.spec)[:4] == (None, None, None, "tp")
