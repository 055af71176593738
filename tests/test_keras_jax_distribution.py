"""Keras-on-JAX distributed training via hvd.keras.use_jax_distribution():
the framework's answer for the backend where DistributedOptimizer cannot
intercept apply_gradients (it runs inside Keras's jit step). Runs in a
subprocess so KERAS_BACKEND=jax and the 8-device CPU mesh are set before
keras/jax import."""

import numpy as np
import pytest

pytest.importorskip("keras")

from horovod_tpu.run.launch import run  # noqa: E402

_ENV = {
    "JAX_PLATFORMS": "cpu",
    "KERAS_BACKEND": "jax",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
}


def test_fit_data_parallel_over_8_device_mesh():
    def worker():
        import jax
        import numpy as np
        import keras
        import horovod_tpu.keras as hvd

        hvd.init()
        dist = hvd.use_jax_distribution()
        n_devices = len(jax.devices())

        rng = np.random.RandomState(0)
        true_w = rng.randn(6, 1).astype(np.float32)
        x = rng.randn(512, 6).astype(np.float32)
        y = x @ true_w

        model = keras.Sequential(
            [keras.layers.Input((6,)), keras.layers.Dense(1)])
        model.compile(optimizer=keras.optimizers.SGD(0.1), loss="mse")
        hist = model.fit(x, y, batch_size=64, epochs=30, verbose=0)
        losses = hist.history["loss"]
        learned = np.asarray(model.layers[-1].kernel).ravel()
        hvd.shutdown()
        return {
            "n_devices": n_devices,
            "dist_set": keras.distribution.distribution() is dist,
            "first": float(losses[0]),
            "last": float(losses[-1]),
            "w_err": float(np.abs(learned - true_w.ravel()).max()),
        }

    rep = run(worker, num_proc=1, env=_ENV)[0]
    assert rep["n_devices"] == 8
    assert rep["dist_set"]
    assert rep["last"] < 1e-3 < rep["first"]
    assert rep["w_err"] < 0.05


def test_tf_backend_raises():
    """On the TF backend jax_distribution must refuse (the TF story is
    DistributedOptimizer)."""
    keras = pytest.importorskip("keras")
    if keras.backend.backend() != "tensorflow":
        pytest.skip("suite not running the TF backend")
    import horovod_tpu.keras as hvd
    with pytest.raises(ValueError, match="JAX backend"):
        hvd.jax_distribution()


def test_mesh_device_order_is_used():
    def worker():
        import jax
        import keras
        import horovod_tpu.keras as hvd
        from horovod_tpu.parallel import mesh as mesh_mod

        hvd.init()
        m = mesh_mod.build_mesh(dp=len(jax.devices()))
        dist = hvd.jax_distribution(mesh=m)
        hvd.shutdown()
        # DataParallel over exactly the mesh's devices, in mesh order
        got = [d.id for d in dist.device_mesh.devices.flat]
        want = [d.id for d in m.devices.flat]
        return got == want and len(got) == 8

    assert run(worker, num_proc=1, env=_ENV)[0]
