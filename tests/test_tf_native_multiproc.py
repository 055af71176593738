"""Multi-process TF native-ops tests, split from test_tf_native_ops.py so CI/review windows can chunk the process-spawning half separately."""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

from horovod_tpu.run.launch import run  # noqa: E402

_ENV = {"JAX_PLATFORMS": "cpu"}


def _native():
    from horovod_tpu.tensorflow import native
    if not native.available():
        pytest.skip("libhvd_tf.so unavailable (no TF headers / toolchain)")
    return native


class TestMultiProcess:
    def test_collectives_three_processes(self):
        def _collectives_worker():
            import os
            import numpy as np
            import tensorflow as tf
            from horovod_tpu.tensorflow import native

            rank = int(os.environ["HVD_PROCESS_ID"])
            size = int(os.environ["HVD_NUM_PROC"])
            if not native.available():
                return "unavailable"
            assert native.ensure_plane(rank, size)
            try:
                report = {}

                total = sum(r + 1 for r in range(size))
                ra = native.allreduce(
                    tf.constant(np.full(1000, rank + 1, np.float32)),
                    average=False, name="t.a")
                report["sum_f32"] = float(ra.numpy()[0])
                rb = native.allreduce(
                    tf.constant(np.arange(5, dtype=np.float64) * (rank + 1)),
                    average=True, name="t.b")
                report["avg_f64"] = rb.numpy().tolist()

                # 16-bit software sum (role of reference common/half.cc float16_sum)
                rc = native.allreduce(
                    tf.cast(tf.fill([64], float(rank + 1)), tf.bfloat16),
                    average=False, name="t.c")
                report["sum_bf16"] = float(tf.cast(rc, tf.float32).numpy()[0])
                rh = native.allreduce(
                    tf.cast(tf.fill([64], float(rank + 1)), tf.float16),
                    average=False, name="t.h")
                report["sum_f16"] = float(tf.cast(rh, tf.float32).numpy()[0])
                # subnormal f16 (2^-15 < 2^-14): the software sum must
                # decode subnormals at full value, not half
                rs = native.allreduce(
                    tf.fill([16], tf.cast(2.0 ** -15, tf.float16)),
                    average=False, name="t.s")
                report["sum_f16_subnormal"] = float(
                    tf.cast(rs, tf.float32).numpy()[0])

                # allgatherv: per-rank first dims differ (rank+1 rows)
                rg = native.allgather(
                    tf.constant(np.full((rank + 1, 3), rank, np.int32)), name="t.g")
                report["gathered"] = rg.numpy().tolist()

                rd = native.broadcast(
                    tf.constant(np.full(17, rank * 10.0, np.float32)),
                    root_rank=1, name="t.d")
                report["bcast"] = float(rd.numpy()[0])

                # compiled graph with TWO independent collectives: the executor
                # may schedule them in either order per rank; negotiation must
                # still run them in one agreed order everywhere
                @tf.function
                def step(t, u):
                    x = native.allreduce(t, average=True, name="s.g0")
                    y = native.allreduce(u, average=False, name="s.g1")
                    return x + y[: t.shape[0]]

                outs = []
                for i in range(4):
                    o = step(tf.fill([8], float(rank + i)), tf.fill([16], 1.0))
                    outs.append(float(o.numpy()[0]))
                report["steps"] = outs
                return report
            finally:
                native.shutdown_plane()

        results = run(_collectives_worker, num_proc=3, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_tf.so unavailable in workers")
        total = 1 + 2 + 3
        exp_gather = np.concatenate(
            [np.full((r + 1, 3), r, np.int32) for r in range(3)]).tolist()
        for rep in results:
            assert rep["sum_f32"] == total
            np.testing.assert_allclose(rep["avg_f64"],
                                       np.arange(5) * (total / 3))
            assert rep["sum_bf16"] == total
            assert rep["sum_f16"] == total
            assert rep["sum_f16_subnormal"] == 3 * 2.0 ** -15
            assert rep["gathered"] == exp_gather
            assert rep["bcast"] == 10.0
            np.testing.assert_allclose(
                rep["steps"], [np.mean([r + i for r in range(3)]) + 3
                               for i in range(4)])

    def test_distributed_optimizer_uses_native_route(self):
        def _optimizer_worker():
            import os
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd
            from horovod_tpu.tensorflow import native

            hvd.init()
            if not native.available():
                hvd.shutdown()
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])
            v = tf.Variable([2.0, 4.0])
            opt = hvd.DistributedOptimizer(
                __import__("keras").optimizers.SGD(1.0))
            core_calls = []
            orig = hvd._core.allreduce_async

            def spy(t, **kw):
                core_calls.append(kw.get("name"))
                return orig(t, **kw)

            hvd._core.allreduce_async = spy

            @tf.function
            def step():
                g = tf.constant([1.0, 1.0]) * float(r + 1)
                opt.apply_gradients([(g, v)])
                return v

            out = np.asarray(step())
            hvd._core.allreduce_async = orig
            native_used = native._state["plane_up"]
            hvd.shutdown()
            return out.tolist(), len(core_calls), bool(native_used)

        results = run(_optimizer_worker, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_tf.so unavailable in workers")
        for vals, n_core_calls, native_used in results:
            # v - lr * mean_grad = [2,4] - 1.0*[1.5,1.5]
            np.testing.assert_allclose(vals, [0.5, 2.5])
            assert native_used, "native plane did not come up"
            # the whole step stayed in-graph: the eager core saw nothing
            assert n_core_calls == 0

    def test_mismatched_submission_errors_cleanly(self):
        """Same tensor name submitted with different sizes across ranks:
        the coordinator must surface an error on every rank (reference
        ConstructResponse error checking, operations.cc:198-400) — and
        the plane must survive for subsequent correct collectives."""
        def worker():
            import os
            import numpy as np
            import tensorflow as tf
            from horovod_tpu.tensorflow import native

            rank = int(os.environ["HVD_PROCESS_ID"])
            size = int(os.environ["HVD_NUM_PROC"])
            if not native.available():
                return "unavailable"
            assert native.ensure_plane(rank, size)
            try:
                got_error = False
                try:
                    native.allreduce(tf.zeros([4 + rank]), name="clash")
                except tf.errors.OpError as e:
                    got_error = "mismatched" in str(e)
                avg_error = False
                try:
                    native.allreduce(tf.zeros([4]), average=rank == 0,
                                     name="clash.avg")
                except tf.errors.OpError as e:
                    avg_error = "mismatched" in str(e)
                root_error = False
                try:
                    native.broadcast(tf.zeros([4]), root_rank=5,
                                     name="clash.root")
                except tf.errors.OpError as e:
                    root_error = "out of range" in str(e)
                # the plane survives: a well-formed collective still works
                out = native.allreduce(tf.fill([8], float(rank + 1)),
                                       average=False, name="after")
                return (got_error, avg_error, root_error,
                        float(out.numpy()[0]))
            finally:
                native.shutdown_plane()

        results = run(worker, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_tf.so unavailable in workers")
        for got_error, avg_error, root_error, after in results:
            assert got_error, "size mismatch did not raise"
            assert avg_error, "average-mode mismatch did not raise"
            assert root_error, "out-of-range root did not raise"
            assert after == 3.0

    def test_broadcast_shape_mismatch_errors(self):
        """Same byte count, different shapes ([2,3] vs [3,2]): the shape
        digest in the READY payload must surface an error instead of
        silently delivering reinterpreted data (the reference errors on
        shape mismatch in ConstructResponse)."""
        def worker():
            import os
            import tensorflow as tf
            from horovod_tpu.tensorflow import native

            rank = int(os.environ["HVD_PROCESS_ID"])
            size = int(os.environ["HVD_NUM_PROC"])
            if not native.available():
                return "unavailable"
            assert native.ensure_plane(rank, size)
            try:
                bcast_err = False
                try:
                    t = tf.zeros([2, 3] if rank == 0 else [3, 2])
                    native.broadcast(t, root_rank=0, name="shape.clash")
                except tf.errors.OpError as e:
                    bcast_err = "mismatched" in str(e)
                ar_err = False
                try:
                    t = tf.zeros([6] if rank == 0 else [2, 3])
                    native.allreduce(t, name="shape.clash.ar")
                except tf.errors.OpError as e:
                    ar_err = "mismatched" in str(e)
                # allgather: dim0 may differ, inner dims may NOT — equal
                # row bytes with different inner shapes must be rejected
                ag_err = False
                try:
                    t = tf.zeros([2, 2, 3] if rank == 0 else [4, 3, 2])
                    native.allgather(t, name="shape.clash.ag")
                except tf.errors.OpError as e:
                    ag_err = "mismatched" in str(e)
                # matching shapes still work after the rejected ones
                out = native.broadcast(tf.fill([2, 2], float(rank + 1)),
                                       root_rank=1, name="shape.ok")
                return bcast_err, ar_err, ag_err, float(out.numpy()[0][0])
            finally:
                native.shutdown_plane()

        results = run(worker, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_tf.so unavailable in workers")
        for bcast_err, ar_err, ag_err, ok_val in results:
            assert bcast_err, "broadcast shape mismatch did not raise"
            assert ar_err, "allreduce shape mismatch did not raise"
            assert ag_err, "allgather inner-shape mismatch did not raise"
            assert ok_val == 2.0

    def test_custom_compressor_rides_pyfunc_route(self):
        """A custom Compressor (compress/decompress overridden, no
        wire_dtype) cannot be re-expressed in-graph: the fused route must
        fall back to the py_function path where the eager core applies it
        — not silently skip compression on the native plane."""
        def worker():
            import os
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd
            from horovod_tpu.tensorflow import native
            from horovod_tpu.ops.compression import Compressor

            hvd.init()
            if not native.available():
                hvd.shutdown()
                return "unavailable"
            r = int(os.environ["HVD_PROCESS_ID"])

            class Spy(Compressor):
                calls = []

                @classmethod
                def compress(cls, tensor):
                    cls.calls.append("c")
                    return tensor, None

                @classmethod
                def decompress(cls, tensor, ctx):
                    return tensor

            v = tf.Variable([2.0, 4.0])
            opt = hvd.DistributedOptimizer(
                __import__("keras").optimizers.SGD(1.0), compression=Spy)

            @tf.function
            def step():
                g = tf.constant([1.0, 1.0]) * float(r + 1)
                opt.apply_gradients([(g, v)])
                return v

            out = np.asarray(step())
            # the custom compressor must not pay the native bootstrap it
            # cannot use: the plane stays down on this route entirely
            plane_up = native._state["plane_up"]
            hvd.shutdown()
            return out.tolist(), len(Spy.calls), bool(plane_up)

        results = run(worker, num_proc=2, env=_ENV)
        if results[0] == "unavailable":
            pytest.skip("libhvd_tf.so unavailable in workers")
        for vals, n_compress_calls, plane_up in results:
            np.testing.assert_allclose(vals, [0.5, 2.5])
            assert n_compress_calls > 0, \
                "custom compressor was skipped on the native route"
            assert not plane_up, \
                "native plane bootstrapped for a route that cannot use it"

    def test_absent_rank_falls_back_to_pyfunc_everywhere(self):
        """A rank that cannot run the native plane (HVD_TF_NATIVE=0) must
        not hang the others: their plane init times out and BOTH ranks
        train through the py_function route with correct averaging."""
        def worker():
            import os
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd
            from horovod_tpu.tensorflow import native

            r = int(os.environ["HVD_PROCESS_ID"])
            if r == 1:
                os.environ["HVD_TF_NATIVE"] = "0"
            os.environ["HVD_TF_NATIVE_TIMEOUT"] = "3"
            hvd.init()
            v = tf.Variable([2.0, 4.0])
            opt = hvd.DistributedOptimizer(
                __import__("keras").optimizers.SGD(1.0))

            @tf.function
            def step():
                g = tf.constant([1.0, 1.0]) * float(r + 1)
                opt.apply_gradients([(g, v)])
                return v

            out = np.asarray(step())
            native_used = native._state["plane_up"]
            hvd.shutdown()
            return out.tolist(), bool(native_used)

        results = run(worker, num_proc=2, env=_ENV)
        for vals, native_used in results:
            np.testing.assert_allclose(vals, [0.5, 2.5])
            assert not native_used

    def test_gradient_tape_in_tf_function(self):
        """DistributedGradientTape inside tf.function rides the fused
        in-graph route (native or py_function) — both ranks see the
        averaged gradient."""
        def _tape_graph_worker():
            import os
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd

            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            v = tf.Variable([3.0, 5.0])

            @tf.function
            def grads():
                with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
                    loss = tf.reduce_sum(v * float(r + 1))
                return tape.gradient(loss, [v])[0]

            g = np.asarray(grads())
            hvd.shutdown()
            return g.tolist()

        results = run(_tape_graph_worker, num_proc=2, env=_ENV)
        for g in results:
            np.testing.assert_allclose(g, [1.5, 1.5])
