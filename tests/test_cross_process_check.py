"""Cross-process consistency checking (the coordinator's ConstructResponse
error checks, operations.cc:209-371): ranks submitting mismatched
shapes/dtypes to the same named collective must get a MismatchError naming
the tensor, not a transport hang/crash. Workers are spawned via the
programmatic run(fn) launcher (test_spark.py-style, closures shipped by
cloudpickle)."""

from horovod_tpu.run.launch import run

# NOTE: worker closures must not reference this module's globals —
# cloudpickle would serialize them by reference and the spawned workers
# cannot import the test module. The CPU-platform env rides run(env=...)
# so it is set before the worker's first import of jax.
_ENV = {"JAX_PLATFORMS": "cpu"}


class TestCrossProcessConsistency:
    def test_matching_allreduce_succeeds(self):
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            out = hvd.allreduce(np.ones((2, 3), np.float32), average=False)
            hvd.shutdown()
            return float(np.asarray(out)[0, 0])

        assert run(fn, num_proc=2, env=_ENV) == [2.0, 2.0]

    def test_shape_mismatch_raises_named_error(self):
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            rank = int(os.environ["HVD_PROCESS_ID"])
            # rank-dependent shape — the reference's error-path test
            # pattern (test_torch.py rank-dependent dims)
            shape = (2, 3) if rank == 0 else (2, 4)
            try:
                hvd.allreduce(np.ones(shape, np.float32), name="bad.shape")
                return "no error"
            except hvd.MismatchError as e:
                return f"mismatch:{('bad.shape' in str(e))}"
            finally:
                hvd.shutdown()

        assert run(fn, num_proc=2, env=_ENV) == ["mismatch:True", "mismatch:True"]

    def test_dtype_mismatch_raises(self):
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            rank = int(os.environ["HVD_PROCESS_ID"])
            dtype = np.float32 if rank == 0 else np.int32
            try:
                hvd.allreduce(np.ones((2, 2), dtype), name="bad.dtype")
                return "no error"
            except hvd.MismatchError:
                return "mismatch"
            finally:
                hvd.shutdown()

        assert run(fn, num_proc=2, env=_ENV) == ["mismatch", "mismatch"]

    def test_reducescatter_and_alltoall_cross_process(self):
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            # reducescatter: both submit [4] vectors; each keeps its half
            rs = np.asarray(hvd.reducescatter(
                np.full((4,), r + 1.0, np.float32)))
            # alltoall: rank r sends [10r, 10r+1]; rank i receives
            # [10*0+i, 10*1+i]
            a2a = np.asarray(hvd.alltoall(
                np.asarray([10.0 * r, 10.0 * r + 1], np.float32)))
            hvd.shutdown()
            return (rs.tolist(), a2a.tolist())

        out = run(fn, num_proc=2, env=_ENV)
        assert out[0] == ([3.0, 3.0], [0.0, 10.0])
        assert out[1] == ([3.0, 3.0], [1.0, 11.0])

    def test_allgather_first_dim_may_differ(self):
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            rank = int(os.environ["HVD_PROCESS_ID"])
            x = np.full((rank + 1, 2), float(rank), np.float32)
            out = np.asarray(hvd.allgather(x))
            hvd.shutdown()
            return out.shape[0]

        # variable-first-dim allgatherv (MPIAllgather parity)
        assert run(fn, num_proc=2, env=_ENV) == [3, 3]
