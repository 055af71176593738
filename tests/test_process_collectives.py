"""The device-side cross-process data plane (ops/process_collectives.py):
the eager multi-process path must execute ONE bandwidth-optimal XLA
collective on device — the TPU analogue of the reference's in-place
MPI_Allreduce/ncclAllReduce on the fused buffer (mpi_operations.cc:48,
nccl_operations.cc:85) — not a host-staged allgather + local sum."""

import numpy as np

from horovod_tpu.run.launch import run

_ENV = {"JAX_PLATFORMS": "cpu"}


class TestDevicePlane:
    def test_allreduce_lowry_is_all_reduce_not_allgather(self):
        """The compiled data-plane HLO must contain an all-reduce over
        the process axis and no all-gather: O(M) wire bytes per process,
        not the O(P*M) of gather-then-sum."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            # run one real allreduce so the engine exists and the math is
            # checked end to end
            out = hvd.allreduce(np.full((256,), 2.0, np.float32),
                                average=False)
            ok = bool(np.allclose(np.asarray(out), 4.0))
            eng = state.global_state().coordinator._proc_engine
            x = eng._stack(np.ones((256,), np.float32))
            hlo = eng._allreduce_fn.lower(x, False).compile().as_text()
            hvd.shutdown()
            return ok, ("all-reduce" in hlo), ("all-gather" in hlo)

        for ok, has_ar, has_ag in run(fn, num_proc=2, env=_ENV):
            assert ok
            assert has_ar, "data plane must lower to an XLA all-reduce"
            assert not has_ag, "no allgather leg in the allreduce plane"

    def test_results_are_device_backed(self):
        """Outputs stay on device (jax.Array), not host numpy — the
        fusion-buffer memcpys of the reference are device-side here."""
        def fn():
            import jax
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = jax.process_index()
            ar = hvd.allreduce(np.ones((8,), np.float32), average=True)
            bc = hvd.broadcast(np.full((4,), float(r), np.float32),
                               root_rank=1)
            kinds = (isinstance(ar, jax.Array), isinstance(bc, jax.Array))
            vals = (float(np.asarray(ar)[0]), float(np.asarray(bc)[0]))
            hvd.shutdown()
            return kinds, vals

        for kinds, vals in run(fn, num_proc=2, env=_ENV):
            assert kinds == (True, True)
            assert vals == (1.0, 1.0)

    def test_fused_bucket_single_collective(self):
        """A burst fused by the coordinator must execute as ONE device
        collective on the concatenated buffer and still un-fuse to the
        right per-tensor sums."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            handles = [hvd.allreduce_async(
                np.full((16,), float(i), np.float32), average=False,
                name=f"fuse{i}") for i in range(4)]
            outs = [float(np.asarray(hvd.synchronize(h))[0])
                    for h in handles]
            hvd.shutdown()
            return outs

        for outs in run(fn, num_proc=2, env=_ENV):
            assert outs == [0.0, 2.0, 4.0, 6.0]

    def test_dtype_coverage_across_processes(self):
        """The device plane must carry every wire dtype the reference's
        MPI/NCCL ops dispatch on (mpi_operations.cc): floats down to
        f16/bf16 and ints — with exact sums at the carried precision.
        Wide inputs (f64/i64) follow jax's dtype canonicalization: with
        x64 disabled (the framework default) they are carried as
        f32/i32, the same rule every other jax value in the program
        follows — asserted here so the contract is explicit, not
        accidental."""
        def fn():
            import jax.numpy as jnp
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            r = state.process_rank()
            eng = state.global_state().coordinator._proc_engine
            out = {}
            for name, dtype, val in [
                    ("f64", np.float64, 1.25), ("f16", np.float16, 0.5),
                    ("i32", np.int32, 3), ("i64", np.int64, 1 << 20)]:
                x = np.full((4,), val, dtype) * (r + 1)
                res = eng.allreduce(x)
                out[name] = (str(res.dtype),
                             np.asarray(res).tolist())
            bf = jnp.full((4,), 1.5, jnp.bfloat16) * (r + 1)
            res = eng.allreduce(bf)
            out["bf16"] = (str(res.dtype),
                           np.asarray(res, np.float32).tolist())
            hvd.shutdown()
            return out

        for res in run(fn, num_proc=2, env=_ENV):
            # canonicalized wide dtypes (jax x64 disabled)
            assert res["f64"] == ("float32", [3.75] * 4)   # 1.25*(1+2)
            assert res["i64"] == ("int32", [3 << 20] * 4)
            # narrow dtypes carried as-is
            assert res["f16"] == ("float16", [1.5] * 4)
            assert res["i32"] == ("int32", [9] * 4)        # 3*(1+2)
            assert res["bf16"] == ("bfloat16", [4.5] * 4)

    def test_large_payload_fused(self):
        """A multi-MB fused buffer survives the device plane intact
        (exercises real DMA/collective paths, not just tiny shapes)."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            n = 1 << 20  # 4 MB of float32
            x = np.arange(n, dtype=np.float32)
            out = np.asarray(hvd.allreduce(x, average=True))
            ok = bool(np.array_equal(out, x))
            hvd.shutdown()
            return ok

        assert run(fn, num_proc=2, env=_ENV) == [True, True]

    def test_engine_ops_three_processes(self):
        """Value checks for every engine op at P=3 (odd world size
        exercises non-power-of-two rings)."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            r = state.process_rank()
            eng = state.global_state().coordinator._proc_engine
            ar = np.asarray(eng.allreduce(
                np.full((2,), r + 1.0, np.float32)))          # 1+2+3 = 6
            bc = np.asarray(eng.broadcast(
                np.full((2,), r * 10.0, np.float32), 2))      # 20
            ag = np.asarray(eng.allgather_stacked(
                np.asarray([float(r)], np.float32)))          # [0,1,2]
            rs = np.asarray(eng.reducescatter(
                np.arange(6, dtype=np.float32) + r))          # my 2-row sum
            a2a = np.asarray(eng.alltoall(
                np.asarray([r * 3.0, r * 3 + 1, r * 3 + 2],
                           np.float32)))                      # column r
            hvd.shutdown()
            return (ar.tolist(), bc.tolist(), ag.ravel().tolist(),
                    rs.tolist(), a2a.tolist())

        results = run(fn, num_proc=3, env=_ENV)
        base = np.arange(6, dtype=np.float32)
        want_rs = (3 * base + 3).reshape(3, 2)  # sum_r (base + r)
        for r, (ar, bc, ag, rs, a2a) in enumerate(results):
            assert ar == [6.0, 6.0]
            assert bc == [20.0, 20.0]
            assert ag == [0.0, 1.0, 2.0]
            assert rs == want_rs[r].tolist()
            assert a2a == [float(r), 3.0 + r, 6.0 + r]
