"""Native TF AsyncOpKernel collectives (libhvd_tf.so): the compiled-graph
route of the TF frontend — real custom ops over the rank-0-negotiated TCP
ring (_native/src/tf_ops.cc; role of the reference tensorflow/mpi_ops.cc
:276-463 + the MPI CPU ops underneath, common/ops/mpi_operations.cc).

Multi-process cases spawn real workers via run.launch.run, like
test_negotiation.py — the plane's bootstrap (HELLO/ENDPOINTS), negotiation
(READY/ORDER), ring reduce-scatter/allgather, the fp16/bf16 software sum,
and the in-graph fused DistributedOptimizer route all execute for real.
"""

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


class TestSingleProcess:
    def test_library_builds_and_loads(self):
        assert _native().available()

    def test_ops_are_identity_at_size_one(self):
        native = _native()
        x = tf.constant([1.0, 2.5, 3.0])
        np.testing.assert_allclose(native.allreduce(x).numpy(), x.numpy())
        np.testing.assert_allclose(native.allgather(x).numpy(), x.numpy())
        np.testing.assert_allclose(native.broadcast(x).numpy(), x.numpy())

    def test_inside_tf_function(self):
        native = _native()

        @tf.function
        def step(t):
            return native.allreduce(t, name="g") * 2.0

        np.testing.assert_allclose(
            step(tf.constant([1.0, 2.0])).numpy(), [2.0, 4.0])

    def test_allgather_scalar_size_one_is_vector(self):
        """At size 1 a scalar input must still come back rank-1: the shape
        fn promises a vector, and the multi-process path delivers one."""
        native = _native()
        out = native.allgather(tf.constant(7.0))
        assert out.shape.rank == 1
        np.testing.assert_allclose(out.numpy(), [7.0])

    def test_allgather_shape_fn_unknown_first_dim(self):
        native = _native()

        @tf.function(input_signature=[
            tf.TensorSpec([4, 3], tf.float32)])
        def g(t):
            out = native.allgather(t, name="ag")
            # graph-time shape: first dim unknown, rest preserved
            assert out.shape.as_list() == [None, 3]
            return out

        assert g(tf.zeros([4, 3])).shape == (4, 3)
