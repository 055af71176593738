"""TF + Keras frontends: collectives on tf tensors, DistributedOptimizer /
DistributedGradientTape, broadcast_variables, Keras callbacks (reference
test_tensorflow.py / test_keras.py patterns — single-process, so the
mechanics rather than cross-worker numerics are under test)."""

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")
keras = pytest.importorskip("keras")


@pytest.fixture
def tfhvd(hvd):
    import horovod_tpu.tensorflow as tfhvd_mod
    return tfhvd_mod


@pytest.fixture
def khvd(hvd):
    import horovod_tpu.keras as khvd_mod
    return khvd_mod


class TestTfOps:
    def test_allreduce(self, tfhvd):
        x = tf.constant([1.0, 2.0, 3.0])
        out = tfhvd.allreduce(x, average=True)
        assert out.dtype == tf.float32
        np.testing.assert_allclose(out.numpy(), x.numpy())

    def test_allreduce_fp16_compression(self, tfhvd):
        x = tf.random.normal([8])
        out = tfhvd.allreduce(x, average=True,
                              compression=tfhvd.Compression.fp16)
        assert out.dtype == tf.float32
        np.testing.assert_allclose(out.numpy(), x.numpy(), atol=1e-2)

    def test_allreduce_bfloat16(self, tfhvd):
        x = tf.cast(tf.constant([1.5, 2.5]), tf.bfloat16)
        out = tfhvd.allreduce(x, average=False)
        assert out.dtype == tf.bfloat16
        np.testing.assert_allclose(tf.cast(out, tf.float32).numpy(),
                                   [1.5, 2.5])

    def test_indexed_slices_allreduce(self, tfhvd):
        s = tf.IndexedSlices(tf.constant([[1.0, 2.0], [3.0, 4.0]]),
                             tf.constant([0, 3]),
                             dense_shape=tf.constant([5, 2]))
        out = tfhvd.allreduce(s, average=True)
        assert isinstance(out, tf.IndexedSlices)
        np.testing.assert_allclose(out.values.numpy(),
                                   [[1.0, 2.0], [3.0, 4.0]])

    def test_async_poll_synchronize(self, tfhvd):
        h = tfhvd.allreduce_async(tf.ones([3]) * 4, average=False)
        out = tfhvd.synchronize(h)
        np.testing.assert_allclose(out.numpy(), 4 * np.ones(3))
        with pytest.raises(ValueError, match="already been synchronized"):
            tfhvd.synchronize(h)

    def test_broadcast_variables(self, tfhvd):
        v = tf.Variable([5.0, 6.0])
        want = v.numpy()
        tfhvd.broadcast_variables([v], root_rank=0)
        np.testing.assert_allclose(v.numpy(), want)

    def test_size_rank_process_level(self, tfhvd):
        assert tfhvd.size() == tfhvd.process_count()
        assert tfhvd.rank() == tfhvd.process_rank()


class TestTfTraining:
    def test_distributed_gradient_tape(self, tfhvd):
        w = tf.Variable([[2.0], [1.0]])
        x = tf.constant([[1.0, 2.0], [3.0, 4.0]])
        with tfhvd.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_mean((x @ w) ** 2)
        grads = tape.gradient(loss, [w])
        expect = tf.GradientTape()
        with expect as t2:
            loss2 = tf.reduce_mean((x @ w) ** 2)
        np.testing.assert_allclose(np.asarray(grads[0]),
                                   np.asarray(t2.gradient(loss2, [w])[0]))

    def test_distributed_optimizer_trains(self, tfhvd):
        opt = tfhvd.DistributedOptimizer(keras.optimizers.SGD(0.1))
        assert isinstance(opt, keras.optimizers.SGD)
        w = tf.Variable([[2.0], [-1.0]])
        x = tf.constant([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
        y = tf.constant([[1.0], [2.0], [3.0]])
        for _ in range(150):
            with tf.GradientTape() as tape:
                loss = tf.reduce_mean((x @ w - y) ** 2)
            opt.apply_gradients(zip(tape.gradient(loss, [w]), [w]))
        assert float(loss) < 1e-3
        np.testing.assert_allclose(w.numpy(), [[1.0], [2.0]], atol=1e-2)


class TestKerasFrontend:
    def _model(self):
        model = keras.Sequential([
            keras.layers.Input((4,)),
            keras.layers.Dense(8, activation="relu"),
            keras.layers.Dense(1)])
        return model

    def test_fit_with_callbacks(self, khvd):
        model = self._model()
        model.compile(optimizer=khvd.DistributedOptimizer(
            keras.optimizers.SGD(0.05, momentum=0.9)), loss="mse")
        rng = np.random.RandomState(0)
        X = rng.randn(64, 4).astype(np.float32)
        Y = (X @ np.array([[1.0], [-2.0], [0.5], [0.0]],
                          np.float32))
        hist = model.fit(
            X, Y, epochs=6, batch_size=16, verbose=0,
            callbacks=[
                khvd.callbacks.BroadcastGlobalVariablesCallback(0),
                khvd.callbacks.MetricAverageCallback(),
                khvd.callbacks.LearningRateWarmupCallback(
                    warmup_epochs=3, steps_per_epoch=4, verbose=0)])
        losses = hist.history["loss"]
        assert losses[-1] < losses[0]
        assert "lr" in hist.history

    def test_warmup_reaches_full_lr(self, khvd):
        model = self._model()
        base_lr = 0.08
        model.compile(optimizer=keras.optimizers.SGD(base_lr), loss="mse")
        cb = khvd.callbacks.LearningRateWarmupCallback(
            warmup_epochs=2, steps_per_epoch=4)
        X = np.random.RandomState(1).randn(32, 4).astype(np.float32)
        Y = np.zeros((32, 1), np.float32)
        model.fit(X, Y, epochs=3, batch_size=8, verbose=0, callbacks=[cb])
        # single worker: multiplier → 1.0 after warmup
        assert abs(float(np.asarray(model.optimizer.learning_rate))
                   - base_lr) < 1e-6

    def test_broadcast_global_variables(self, khvd):
        model = self._model()
        before = [w.copy() for w in model.get_weights()]
        khvd.broadcast_global_variables(model, root_rank=0)
        for a, b in zip(model.get_weights(), before):
            np.testing.assert_allclose(a, b)

    def test_load_model_rewraps_optimizer(self, khvd, tmp_path):
        model = self._model()
        model.compile(optimizer=keras.optimizers.SGD(0.01), loss="mse")
        path = str(tmp_path / "m.keras")
        model.save(path)
        loaded = khvd.load_model(path)
        assert type(loaded.optimizer).__name__ == "SGD"
        assert hasattr(loaded.optimizer, "_hvd_compression")


class TestTfKerasNamespace:
    def test_tf_keras_wrapper_mirrors_keras(self, hvd):
        """The reference exposes the Keras adapters under both
        horovod.keras and horovod.tensorflow.keras; same here."""
        import horovod_tpu.keras as k
        import horovod_tpu.tensorflow.keras as tfk
        assert tfk.DistributedOptimizer is k.DistributedOptimizer
        assert tfk.load_model is k.load_model
        assert (tfk.broadcast_global_variables
                is k.broadcast_global_variables)
        assert tfk.callbacks is k.callbacks
        assert tfk.size is k.size and tfk.rank is k.rank


class TestGraphFusedAllreduce:
    """The in-graph fused gradient route (_graph_fused_allreduce): one
    tf.concat fusion buffer per dtype, ONE py_function host crossing per
    step, dlpack zero-copy ingestion — the AsyncOpKernel role
    (reference tensorflow/mpi_ops.cc:276-304)."""

    def test_values_and_one_core_op_per_dtype_group(self, tfhvd,
                                                    monkeypatch):
        # pin the py_function fallback: the native AsyncOpKernel route has
        # its own suite (test_tf_native_ops.py)
        monkeypatch.setattr(tfhvd, "_native_graph_ready", lambda: False)
        core_names = []
        orig_async = tfhvd._core.allreduce_async

        def spy(tensor, **kw):
            core_names.append(kw.get("name"))
            return orig_async(tensor, **kw)

        tfhvd._core.allreduce_async = spy
        try:
            a = tf.constant([[1.0, 2.0], [3.0, 4.0]])
            b = tf.constant([5.0, 6.0, 7.0])
            c = tf.constant([1.5, 2.5], tf.float64)

            @tf.function
            def f(a, b, c):
                return tfhvd._graph_fused_allreduce(
                    [a, b, c], tfhvd.Compression.none,
                    tfhvd._fusion_tag([a, b, c]))

            oa, ob, oc = f(a, b, c)
        finally:
            tfhvd._core.allreduce_async = orig_async
        # single process: averaging is the identity, but shapes/dtypes
        # must round-trip through the fusion buffer exactly
        np.testing.assert_allclose(oa.numpy(), a.numpy())
        np.testing.assert_allclose(ob.numpy(), b.numpy())
        np.testing.assert_allclose(oc.numpy(), c.numpy())
        assert oa.dtype == tf.float32 and oc.dtype == tf.float64
        # THE contract: one core collective per dtype group (f32 fused
        # a+b, f64 alone) — not one per gradient. Names carry a per-call
        # tag so two fused call sites in one graph cannot collide.
        assert len(core_names) == 2
        assert [n.rsplit(".", 1)[-1] for n in core_names] == ["0", "1"]
        assert all(n.startswith("fused_grad.") for n in core_names)
        assert len({n.rsplit(".", 1)[0] for n in core_names}) == 1

    def test_two_process_graph_mode_training_averages(self):
        """End-to-end tf.function training across 2 real processes: the
        in-graph route must average gradients exactly and make identical
        updates on both workers."""
        from horovod_tpu.run.launch import run

        def fn():
            import os
            import numpy as np
            import tensorflow as tf
            import horovod_tpu.tensorflow as hvd
            hvd.init()
            # pin the py_function fallback (native route tested separately)
            hvd._native_graph_ready = lambda: False
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
                # rank-dependent gradient: mean must be (1+2)/2 = 1.5
                g = tf.constant([1.0, 1.0]) * float(r + 1)
                opt.apply_gradients([(g, v)])
                return v

            out = np.asarray(step())
            n_calls = len(core_calls)
            hvd._core.allreduce_async = orig
            hvd.shutdown()
            return out.tolist(), n_calls

        results = run(fn, num_proc=2,
                      env={"JAX_PLATFORMS": "cpu"})
        for vals, n_calls in results:
            # v - lr * mean_grad = [2,4] - 1.0*[1.5,1.5]
            np.testing.assert_allclose(vals, [0.5, 2.5])
            assert n_calls == 1, "one fused host collective per step"


class TestTf1Compat:
    def test_broadcast_global_variables_empty_collection_raises(
            self, tfhvd):
        """TF2-eager variables never enter the compat.v1 collection:
        silently broadcasting nothing would leave workers with divergent
        initial weights, so the empty case must raise with a pointer."""
        tf.Variable([3.0, 4.0], name="bgv_var")  # NOT in the collection
        with pytest.raises(ValueError, match="broadcast_variables"):
            tfhvd.broadcast_global_variables(0)

    def test_broadcast_global_variables_graph_mode(self, tfhvd):
        g = tf.Graph()
        with g.as_default():
            v = tf.compat.v1.get_variable("bgv_graph_var",
                                          initializer=[7.0, 8.0])
            with tf.compat.v1.Session(graph=g) as sess:
                sess.run(tf.compat.v1.global_variables_initializer())
                tfhvd.broadcast_global_variables(0)  # default session
                np.testing.assert_allclose(sess.run(v), [7.0, 8.0])

    def test_broadcast_hook_in_session(self, tfhvd):
        """The TF1 session hook (reference tensorflow/__init__.py:107-139):
        values round-trip session -> eager core broadcast -> session."""
        g = tf.Graph()
        with g.as_default():
            v = tf.compat.v1.get_variable(
                "hook_var", initializer=[1.5, 2.5])
            hook = tfhvd.BroadcastGlobalVariablesHook(0)
            hook.begin()
            with tf.compat.v1.Session(graph=g) as sess:
                sess.run(tf.compat.v1.global_variables_initializer())
                hook.after_create_session(sess, None)
                np.testing.assert_allclose(sess.run(v), [1.5, 2.5])
