"""Overlap plane (docs/tensor-fusion.md): readiness-ordered bucket
dispatch inside the backward window, two-level hierarchical reduction
with the codec on the inter-host leg only, and bit-for-bit fp32 parity
with the barrier path.

Multi-process arms run through run.launch.run and skip on backends
whose XLA has no cross-process collectives (the CPU test platform) —
on a real pod they execute.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.run.launch import run
from horovod_tpu.utils import metrics as hvd_metrics

_ENV = {"JAX_PLATFORMS": "cpu"}
_CPU_MULTIPROC = "Multiprocess computations aren't implemented"


@pytest.fixture
def reg():
    """Fresh enabled registry; MUST precede hvd in test signatures so
    the coordinator binds its counters to it."""
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()


def _run2(fn, num_proc=2, env=None, **kw):
    try:
        return run(fn, num_proc=num_proc, env=env or _ENV, **kw)
    except RuntimeError as e:
        if _CPU_MULTIPROC in str(e):
            pytest.skip("XLA backend has no multiprocess collectives "
                        "(CPU test platform); runs on TPU/GPU pods")
        raise


def _quiet_background(coord):
    """Park the background flush loop on a long wait so the test's own
    flush_ready calls are the only dispatcher (hold_cycle can't be
    used: flush_ready honors the pause flag by design)."""
    coord._config.cycle_time_ms = 5000.0
    time.sleep(0.05)  # let the loop re-read the new period


class TestReadinessDispatch:
    def test_flush_ready_noop_when_disabled(self, reg, hvd):
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        _quiet_background(coord)
        h = hvd.allreduce_async(np.ones((8, 64), np.float32),
                                average=False, name="off.t0")
        coord.flush_ready()
        assert reg.counter("hvd_overlap_ready_flushes_total").value == 0
        hvd.synchronize(h)

    def test_flush_ready_drains_sealed_group_keeps_partial(self, reg,
                                                           hvd):
        """A fusion group whose queued bytes crossed the threshold is
        dispatched by flush_ready while a below-threshold group stays
        queued for the final drain — the seal detection that makes
        dispatch ride inside the backward window."""
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        cfg = coord._config
        cfg.overlap_eager = True
        cfg.fusion_threshold = 2048
        _quiet_background(coord)

        # different average flag -> different fusion group (same key
        # scheme as _make_plan), so "partial" really means a separate
        # group, not a member of the sealed one
        h_small = hvd.allreduce_async(np.ones((8, 4), np.float32),
                                      average=True, name="seal.small")
        coord.flush_ready()
        assert reg.counter("hvd_overlap_ready_flushes_total").value == 0

        big = np.arange(8.0 * 64, dtype=np.float32).reshape(8, 64)
        h_big = hvd.allreduce_async(big, average=False, name="seal.big")
        coord.flush_ready()
        assert reg.counter("hvd_overlap_ready_flushes_total").value == 1
        assert reg.counter("hvd_overlap_ready_tensors_total").value == 1

        out_big = np.asarray(hvd.synchronize(h_big))
        out_small = np.asarray(hvd.synchronize(h_small))
        np.testing.assert_allclose(
            out_big, np.tile(big.sum(0, keepdims=True), (8, 1)),
            rtol=1e-6)
        np.testing.assert_allclose(out_small, np.ones((8, 4)),
                                   rtol=1e-6)

    def test_reverse_order_enqueue_dispatches_before_final_drain(
            self, reg, hvd):
        """allreduce_gradients under HOROVOD_OVERLAP_EAGER enqueues in
        reverse tree order with flush_ready between enqueues: with two
        groups' worth of bytes, at least one ready drain must land
        BEFORE the whole-tree synchronize, and results come back in
        original leaf order."""
        import horovod_tpu
        from horovod_tpu import optim
        coord = horovod_tpu.common.state.global_state().coordinator
        cfg = coord._config
        cfg.overlap_eager = True
        cfg.fusion_threshold = 2048
        _quiet_background(coord)

        rng = np.random.RandomState(7)
        grads = {f"layer{i}": rng.randn(8, 64).astype(np.float32)
                 for i in range(4)}  # 2048 B each: every leaf seals
        out = optim.allreduce_gradients(grads, average=False)
        assert reg.counter("hvd_overlap_ready_flushes_total").value >= 1
        assert reg.counter("hvd_overlap_ready_tensors_total").value >= 1
        for k, g in grads.items():
            np.testing.assert_allclose(
                np.asarray(out[k]),
                np.tile(g.sum(0, keepdims=True), (8, 1)), rtol=1e-5)


class TestBitForBitParity:
    def _grads(self, seed):
        rng = np.random.RandomState(seed)
        return {f"l{i}": rng.randn(8, 48 + 16 * i).astype(np.float32)
                for i in range(5)}

    def test_fp32_overlap_matches_barrier_bitwise(self, reg, hvd):
        """Per-element psum is insensitive to bucket composition and
        dispatch order, so fp32 results must be IDENTICAL — not close —
        between the barrier path and readiness-ordered dispatch."""
        import horovod_tpu
        from horovod_tpu import optim
        coord = horovod_tpu.common.state.global_state().coordinator
        cfg = coord._config
        cfg.fusion_threshold = 4096
        grads = self._grads(11)

        cfg.overlap_eager = False
        barrier = jax.tree_util.tree_map(
            np.asarray, optim.allreduce_gradients(grads, average=True))
        cfg.overlap_eager = True
        _quiet_background(coord)
        overlap = jax.tree_util.tree_map(
            np.asarray, optim.allreduce_gradients(grads, average=True))

        for k in grads:
            assert barrier[k].dtype == overlap[k].dtype == np.float32
            assert np.array_equal(barrier[k], overlap[k]), k

    @pytest.mark.slow
    def test_fp32_parity_two_process(self):
        """Same bit-for-bit claim across real processes: each rank
        reduces the same pytree with overlap off then on; both must
        agree exactly on every rank."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu import optim
            from horovod_tpu.common import state

            hvd.init()
            cfg = state.global_state().config
            cfg.fusion_threshold = 4096
            rng = np.random.RandomState(3)
            grads = {f"l{i}": rng.randn(32 + 16 * i).astype(np.float32)
                     for i in range(4)}
            cfg.overlap_eager = False
            a = {k: np.asarray(v) for k, v in optim.allreduce_gradients(
                grads, average=True).items()}
            cfg.overlap_eager = True
            b = {k: np.asarray(v) for k, v in optim.allreduce_gradients(
                grads, average=True).items()}
            hvd.shutdown()
            return {k: bool(np.array_equal(a[k], b[k])) for k in grads}

        for res in _run2(fn):
            assert all(res.values()), res


class TestHierarchicalEngine:
    def test_invalid_local_size_raises(self, hvd):
        from horovod_tpu.ops.process_collectives import (
            HierarchicalProcessEngine)
        with pytest.raises(ValueError, match="divide"):
            HierarchicalProcessEngine(3)  # 1 % 3 != 0

    def test_trivial_world_quantized_matches_flat_math(self, hvd):
        """With one process the two-level schedule degenerates to the
        flat path's encode → sum → requant → decode — byte-for-byte the
        same kernels, so the results must agree exactly."""
        from horovod_tpu.ops import quantization as q
        from horovod_tpu.ops.process_collectives import (
            HierarchicalProcessEngine)
        eng = HierarchicalProcessEngine(1)
        rng = np.random.RandomState(5)
        x = rng.randn(600).astype(np.float32)
        block = 256
        full, comp, dec = eng.allreduce_quantized(
            jnp.asarray(x), "int8", block)
        flat, dec_flat = q.stacked_wire_allreduce(
            jnp.asarray(x)[None, :], block, "int8", False, 600)
        np.testing.assert_array_equal(np.asarray(full)[:600],
                                      np.asarray(flat)[0])
        # the EF shards it returns are the compensated input and its
        # own-wire decode
        np.testing.assert_array_equal(np.asarray(comp)[:600], x)
        np.testing.assert_array_equal(np.asarray(dec)[:600],
                                      np.asarray(dec_flat)[0])

    def test_hier_engine_ineligible_single_process(self, reg, hvd):
        """nproc==1 can never split: the coordinator property reports
        None and the quantized path stays flat."""
        import horovod_tpu
        coord = horovod_tpu.common.state.global_state().coordinator
        coord._config.overlap_hierarchical = True
        coord._config.overlap_local_size = 1
        assert coord._hier_engine is None

    def test_fingerprint_suffix_only_when_hierarchical(self, hvd):
        from horovod_tpu.common import state
        from horovod_tpu.ops import quantization as q
        cfg = state.global_state().config
        base = q.config_fingerprint(cfg)
        assert "/h" not in base
        cfg.overlap_hierarchical = True
        cfg.overlap_local_size = 4
        try:
            assert q.config_fingerprint(cfg) == base + "/h4"
        finally:
            cfg.overlap_hierarchical = False
            cfg.overlap_local_size = 0

    def test_account_leg_counters(self, reg):
        from horovod_tpu.ops import quantization as q
        q.account_leg("intra", None, 4096)
        q.account_leg("inter", "int8", 1040)
        fam = reg.counter("hvd_wire_leg_bytes_total",
                          labels=("leg", "codec"))
        assert fam.labels(leg="intra", codec="none").value == 4096
        assert fam.labels(leg="inter", codec="int8").value == 1040

    def test_error_feedback_peek(self, hvd):
        from horovod_tpu.ops import quantization as q
        ef = q.ErrorFeedback()
        assert ef.peek("k") is None
        comp = jnp.asarray(np.random.RandomState(0)
                           .randn(256).astype(np.float32))
        pl, sc = q.encode(comp, 256, "int8")
        ef.update("k", comp, q.decode(pl, sc, 256, 256), 256)
        assert ef.peek("k").shape == (256,)
        assert ef.peek("k", shape=(256,)) is not None
        assert ef.peek("k", shape=(512,)) is None

    @pytest.mark.slow
    def test_two_process_hierarchical_int8_inter_leg_only(self):
        """2 processes, local_size=1 (every process its own host): the
        fused eager allreduce rides the two-level engine, the int8
        codec crosses only the inter-host leg (wire-leg counters), and
        the sums are exact for values int8 blocks represent exactly."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            from horovod_tpu.utils import metrics as hvd_metrics

            import os
            hvd_metrics.reset(enabled=True)
            hvd.init()
            coord = state.global_state().coordinator
            # process identity: hvd.rank() counts DEVICES (8 virtual
            # ones per worker under the suite's XLA_FLAGS)
            r = int(os.environ["HVD_PROCESS_ID"])
            x = np.full((512,), float(r + 1), np.float32)
            out = np.asarray(hvd.allreduce(x, average=False,
                                           name="hier.t0"))
            eng = coord._hier_engine
            snap = hvd_metrics.get_registry().snapshot()["metrics"]
            legs = {tuple(sorted(v["labels"].items())): v["value"]
                    for v in snap.get("hvd_wire_leg_bytes_total",
                                      {}).get("values", [])}
            hvd.shutdown()
            return dict(
                ok=bool(np.allclose(out, 3.0)),
                hier=eng is not None,
                legs={str(k): v for k, v in legs.items()})

        # knobs go in via env so every rank NEGOTIATES the same wire
        # fingerprint from init (mutating config after init trips the
        # MismatchError guard by design)
        env = dict(_ENV)
        env["HOROVOD_COMPRESSION"] = "int8"
        env["HOROVOD_QUANT_MIN_BYTES"] = "0"
        env["HOROVOD_OVERLAP_HIERARCHICAL"] = "1"
        env["HOROVOD_OVERLAP_LOCAL_SIZE"] = "1"
        for res in _run2(fn, env=env):
            assert res["ok"] and res["hier"], res
            inter_int8 = [v for k, v in res["legs"].items()
                          if "inter" in k and "int8" in k]
            intra_int8 = [v for k, v in res["legs"].items()
                          if "intra" in k and "int8" in k]
            assert inter_int8 and inter_int8[0] > 0, res
            assert not intra_int8, res


class TestChaosDelayedInterHostLeg:
    @pytest.mark.slow
    def test_delayed_negotiation_leg_still_completes(self):
        """Chaos-delay the negotiated control plane under overlap +
        hierarchy: the retry/stall machinery must absorb the late leg
        and every collective still completes with exact sums."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd

            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])  # hvd.rank() is per device
            outs = []
            for i in range(3):
                x = np.full((64,), float((r + 1) * (i + 1)), np.float32)
                outs.append(float(np.asarray(hvd.allreduce(
                    x, average=False, name=f"chaos.t{i}"))[0]))
            hvd.shutdown()
            return outs

        env = dict(_ENV)
        env["HOROVOD_OVERLAP_EAGER"] = "1"
        env["HOROVOD_OVERLAP_HIERARCHICAL"] = "1"
        env["HOROVOD_OVERLAP_LOCAL_SIZE"] = "1"
        env["HVD_CHAOS_SPEC"] = "negotiation:*:delay_response:0.5"
        env["HVD_CHAOS_DELAY_MS"] = "120"
        env["HVD_CHAOS_SEED"] = "17"
        for res in _run2(fn, env=env, start_timeout_s=300.0):
            assert res == [3.0 * (i + 1) for i in range(3)], res
