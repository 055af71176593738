"""Quantized wire codecs (ops/quantization.py + ops/compression.py):
block encode/decode round-trip bounds vs numpy, error-feedback
convergence on a toy quadratic, digest determinism for the divergence
sentinel, the codec registry contract, and the multi-process
codec-mismatch fail-loud drill."""

import hashlib

import numpy as np
import pytest

from horovod_tpu.run.launch import run

_ENV = {"JAX_PLATFORMS": "cpu"}


def _run_watched(fn, tmp_path, env, num_proc=1):
    """``run(fn)`` for a ``fn`` of a few seconds, cut at 120 s instead of
    the launcher's (a deployment's) 600: a worker still inside ``fn``
    after 90 s dumps every thread's stack to a file of its own, and the
    failure carries what it wrote (ROADMAP D13 (i): a worker that is
    alive and never reports)."""
    stacks = str(tmp_path / "stacks-rank")

    def armed():
        import faulthandler
        import os
        with open(stacks + os.environ.get("HVD_PROCESS_ID", "0"), "w") as f:
            faulthandler.dump_traceback_later(90, exit=False, file=f)
            try:
                return fn()
            finally:
                faulthandler.cancel_dump_traceback_later()

    try:
        return run(armed, num_proc=num_proc, env=env, start_timeout_s=120)
    except Exception as e:
        dumped = "".join(f"--- {p.name}\n{p.read_text()}"
                         for p in sorted(tmp_path.glob("stacks-rank*")))
        raise AssertionError(
            f"{e}\nwhere each worker stood 90 s into fn (nothing: it "
            f"never got there, or had left):\n{dumped}") from e


def _q():
    from horovod_tpu.ops import quantization
    return quantization


def _np_block_amax(x, block):
    return np.abs(x.reshape(-1, block)).max(axis=1)


class TestBlockRoundTrip:
    """encode/decode against independent numpy math."""

    def test_int8_error_bounded_by_half_scale(self):
        q = _q()
        rng = np.random.RandomState(0)
        x = (rng.randn(4096).astype(np.float32) *
             np.repeat(10.0 ** rng.randint(-3, 3, 16), 256))
        payload, scales = q.encode(x, 256, "int8")
        assert str(payload.dtype) == "int8"
        dec = np.asarray(q.decode(payload, scales, 256, x.shape[0]))
        # symmetric int8: worst case is half a quantization step per
        # element, scale = amax/127 per block
        step = _np_block_amax(x, 256) / 127.0
        bound = np.repeat(step / 2, 256) + 1e-7
        assert (np.abs(dec - x) <= bound).all()
        # scales match the numpy amax definition
        assert np.allclose(np.asarray(scales),
                           _np_block_amax(x, 256) / 127.0, rtol=1e-6)

    def test_fp8_error_bounded_relative(self):
        q = _q()
        if not q.HAS_FP8:
            pytest.skip("no float8_e4m3fn in this jax build")
        rng = np.random.RandomState(1)
        x = rng.randn(2048).astype(np.float32) * 4.0
        payload, scales = q.encode(x, 256, "fp8")
        assert "float8_e4m3" in str(payload.dtype)
        dec = np.asarray(q.decode(payload, scales, 256, x.shape[0]))
        # e4m3 has a 3-bit mantissa: relative error <= 2^-4 for normal
        # values, plus one subnormal quantum (scale covers it) near 0
        scale = np.repeat(_np_block_amax(x, 256) / 448.0, 256)
        bound = np.abs(x) * 2.0 ** -4 + scale + 1e-7
        assert (np.abs(dec - x) <= bound).all()

    def test_zero_blocks_and_pad_tail_decode_exactly(self):
        q = _q()
        x = np.zeros(300, np.float32)
        x[:10] = np.linspace(-1, 1, 10)
        payload, scales = q.encode(x, 256, "int8")
        # 300 pads to 512: the all-pad second block gets scale 0, no inf
        assert payload.shape[0] == 512
        assert np.asarray(scales)[1] >= 0.0
        dec = np.asarray(q.decode(payload, scales, 256, 300))
        assert dec.shape == (300,)
        assert (dec[10:] == 0.0).all()
        # explicit multiple (the two-phase collective's block * nproc)
        p2, _ = q.encode(x, 256, "int8", multiple=256 * 4)
        assert p2.shape[0] == 1024

    def test_bf16_input_roundtrips_through_f32_math(self):
        import jax.numpy as jnp
        q = _q()
        x = (np.random.RandomState(2).randn(512).astype(np.float32))
        xb = jnp.asarray(x, jnp.bfloat16)
        payload, scales = q.encode(xb, 256, "int8")
        dec = np.asarray(q.decode(payload, scales, 256, 512))
        step = _np_block_amax(np.asarray(xb, np.float32), 256) / 127.0
        assert (np.abs(dec - np.asarray(xb, np.float32))
                <= np.repeat(step / 2, 256) + 1e-6).all()


class TestDigestDeterminism:
    """The divergence sentinel compares per-bucket digests across
    ranks; the quantized path must produce bit-identical reduced
    buffers everywhere or every quantized step would false-positive."""

    def test_stacked_rows_bitwise_identical(self):
        q = _q()
        rng = np.random.RandomState(3)
        stacked = rng.randn(4, 2048).astype(np.float32)
        out, _ = q.stacked_wire_allreduce(stacked, 256, "int8", False,
                                          2048)
        rows = np.asarray(out)
        digests = {hashlib.sha256(rows[i].tobytes()).hexdigest()
                   for i in range(rows.shape[0])}
        assert len(digests) == 1

    def test_repeated_encode_is_deterministic(self):
        q = _q()
        x = np.random.RandomState(4).randn(1024).astype(np.float32)
        p1, s1 = q.encode(x, 128, "int8")
        p2, s2 = q.encode(x, 128, "int8")
        assert np.asarray(p1).tobytes() == np.asarray(p2).tobytes()
        assert np.asarray(s1).tobytes() == np.asarray(s2).tobytes()

    def test_stacked_sum_matches_numpy_within_bound(self):
        q = _q()
        rng = np.random.RandomState(5)
        stacked = rng.randn(4, 4096).astype(np.float32)
        out, dec = q.stacked_wire_allreduce(stacked, 256, "int8", True,
                                            4096)
        ref = stacked.mean(axis=0)
        amax = np.abs(ref).max()
        assert np.abs(np.asarray(out)[0] - ref).max() <= 0.02 * amax
        # the EF reference really is each row's own-wire decode
        assert np.abs(np.asarray(dec) - stacked).max() <= \
            np.abs(stacked).max() / 127.0


class TestErrorFeedback:
    def test_residual_is_what_the_encode_dropped(self):
        q = _q()
        x = np.random.RandomState(6).randn(512).astype(np.float32)
        ef = q.ErrorFeedback()
        comp = ef.compensate("t", x)  # no residual yet: identity
        assert comp is x
        p, s = q.encode(comp, 256, "int8")
        dec = q.decode(p, s, 256, 512)
        ef.update("t", comp, dec, 256)
        comp2 = np.asarray(ef.compensate("t", x))
        assert np.allclose(comp2, x + (x - np.asarray(dec)), atol=1e-6)
        # shape change resets (elastic resize)
        assert ef.compensate("t", np.zeros(8, np.float32)).shape == (8,)

    def test_toy_quadratic_converges_like_full_width(self):
        """GD on 0.5*||w - t||^2 with the gradient pushed through the
        quantized wire: with EF the loss trajectory must track the
        full-width one; without EF the bias accumulates."""
        q = _q()
        rng = np.random.RandomState(7)
        t = rng.randn(512).astype(np.float32)
        lr, steps, block = 0.2, 60, 64

        def train(mode):
            w = np.zeros(512, np.float32)
            ef = q.ErrorFeedback()
            for _ in range(steps):
                g = w - t
                if mode == "exact":
                    gq = g
                else:
                    comp = ef.compensate("w", g) if mode == "ef" else g
                    p, s = q.encode(np.asarray(comp, np.float32), block,
                                    "int8")
                    gq = np.asarray(q.decode(p, s, block, 512))
                    if mode == "ef":
                        ef.update("w", comp, gq, block)
                w = w - lr * gq
            return 0.5 * float(((w - t) ** 2).sum())

        exact, with_ef = train("exact"), train("ef")
        # quantized-with-EF matches full width within the numerics
        # tolerance (absolute: both losses are ~0 at this horizon)
        assert with_ef <= exact + 1e-3, (with_ef, exact)

    def test_residual_norm_gauge_exported(self):
        from horovod_tpu.utils import metrics as hvd_metrics
        q = _q()
        reg = hvd_metrics.get_registry()
        if not reg.enabled:
            pytest.skip("metrics registry disabled")
        x = np.random.RandomState(8).randn(256).astype(np.float32)
        ef = q.ErrorFeedback()
        p, s = q.encode(x, 64, "int8")
        ef.update("t", x, q.decode(p, s, 64, 256), 64, anchor="grad/t")
        snap = reg.snapshot()
        mets = snap[1]["metrics"] if isinstance(snap, tuple) else \
            snap["metrics"]
        vals = mets["hvd_ef_residual_norm"]["values"]
        assert any(v["labels"].get("tensor") == "grad/t" and
                   v["value"] > 0 for v in vals)


class TestCodecRegistry:
    def test_from_name_and_names(self):
        from horovod_tpu.ops.compression import Compression
        assert set(Compression.names()) >= {"none", "fp16", "bf16",
                                            "int8"}
        assert Compression.from_name(None) is Compression.none
        assert Compression.from_name("") is Compression.none
        assert Compression.from_name(" BF16 ") is Compression.bf16
        assert Compression.from_name("int8") is Compression.int8
        with pytest.raises(ValueError, match="unknown compression"):
            Compression.from_name("zstd")

    def test_every_codec_skips_non_float(self):
        import jax.numpy as jnp
        from horovod_tpu.ops.compression import Compression
        inputs = [np.arange(6, dtype=np.int32),
                  np.array([True, False, True]),
                  np.array([1 + 2j, 3 - 1j], np.complex64),
                  jnp.arange(4, dtype=jnp.int8),
                  7,
                  [1, 2, 3]]
        for name in Compression.names():
            codec = Compression.from_name(name)
            for x in inputs:
                out, ctx = codec.compress(x)
                restored = np.asarray(codec.decompress(out, ctx))
                assert np.array_equal(restored, np.asarray(x)), \
                    (name, x)

    def test_cast_codecs_narrow_then_restore(self):
        import jax.numpy as jnp
        from horovod_tpu.ops.compression import Compression
        x = np.linspace(-2, 2, 64, dtype=np.float32)
        for name, wire in (("fp16", jnp.float16), ("bf16", jnp.bfloat16)):
            codec = Compression.from_name(name)
            out, ctx = codec.compress(x)
            assert out.dtype == wire
            back = codec.decompress(out, ctx)
            assert back.dtype == np.float32
            assert np.abs(np.asarray(back) - x).max() < 0.02
        # already at wire width: no-op, ctx None
        xb = jnp.asarray(x, jnp.bfloat16)
        out, ctx = Compression.bf16.compress(xb)
        assert ctx is None and out is xb

    def test_quantized_codec_is_fake_quant_on_this_path(self):
        from horovod_tpu.ops.compression import Compression
        x = np.random.RandomState(9).randn(3, 100).astype(np.float32)
        out, ctx = Compression.int8.compress(x)
        assert ctx is None
        out = np.asarray(out)
        assert out.shape == x.shape and out.dtype == x.dtype
        assert 0 < np.abs(out - x).max() <= np.abs(x).max() / 127.0

    def test_select_codec_gates(self):
        from horovod_tpu.common.config import HorovodConfig
        q = _q()
        cfg = HorovodConfig(compression="int8", quant_min_bytes=1024)
        assert q.select_codec(cfg, "float32", 4096) == "int8"
        assert q.select_codec(cfg, "float32", 64) is None   # too small
        assert q.select_codec(cfg, "int32", 4096) is None   # not float
        assert q.select_codec(cfg, None, 4096) is None      # no dtype
        cfg2 = HorovodConfig(compression="bf16", quant_min_bytes=0)
        assert q.select_codec(cfg2, "float32", 4096) == "bf16"
        assert q.select_codec(cfg2, "bfloat16", 4096) is None  # no-op
        cfg3 = HorovodConfig()
        assert q.select_codec(cfg3, "float32", 4096) is None

    def test_config_fingerprint_covers_every_wire_knob(self):
        from horovod_tpu.common.config import HorovodConfig
        q = _q()
        base = HorovodConfig(compression="int8")
        fp = q.config_fingerprint(base)
        for other in (HorovodConfig(compression="fp8"),
                      HorovodConfig(compression="int8", quant_block=128),
                      HorovodConfig(compression="int8",
                                    quant_min_bytes=2048),
                      HorovodConfig(compression="int8", quant_ef=False)):
            assert q.config_fingerprint(other) != fp

    def test_encoded_nbytes_accounting(self):
        q = _q()
        # int8: pad(5000, 256)=5120 payload + 20 f32 scales
        assert q.encoded_nbytes(5000, "int8", 256) == 5120 + 20 * 4
        assert q.encoded_nbytes(5000, "bf16", 256) == 10000
        # the acceptance ratio: int8-vs-bf16 wire >= 1.8x
        n = 1 << 20
        assert (q.encoded_nbytes(n, "bf16", 256) /
                q.encoded_nbytes(n, "int8", 256)) >= 1.8


class TestEagerQuantizedPath:
    """End-to-end through hvd.allreduce with HVD_COMPRESSION set
    (single process: the stacked/replicated simulated wire)."""

    def test_allreduce_quantized_with_metrics(self, tmp_path):
        env = dict(_ENV, HVD_COMPRESSION="int8", HVD_QUANT_MIN_BYTES="0",
                   HVD_METRICS="1")

        def fn():
            import numpy as np
            import jax.numpy as jnp
            import horovod_tpu as hvd
            from horovod_tpu.utils import metrics as hvd_metrics
            hvd.init()
            x = np.random.RandomState(0).randn(
                hvd.size(), 5000).astype(np.float32)
            out1 = np.asarray(hvd.allreduce(jnp.asarray(x),
                                            average=False, name="g"))
            # second step exercises the EF residual on the same bucket
            out2 = np.asarray(hvd.allreduce(jnp.asarray(x),
                                            average=False, name="g"))
            ref = np.broadcast_to(x.sum(axis=0), x.shape)
            scale = np.abs(ref).max()
            err = max(np.abs(out1 - ref).max(), np.abs(out2 - ref).max())
            # int tensors stay exact through the codec gate
            z = np.arange(64, dtype=np.int32)
            zi = np.asarray(hvd.allreduce(jnp.asarray(z), average=False,
                                          name="zi"))
            snap = hvd_metrics.get_registry().snapshot()
            mets = snap[1]["metrics"] if isinstance(snap, tuple) else \
                snap["metrics"]
            wire = {v["labels"]["codec"]: v["value"] for v in
                    mets["hvd_wire_bytes_total"]["values"]}
            raw = {v["labels"]["codec"]: v["value"] for v in
                    mets["hvd_wire_raw_bytes_total"]["values"]}
            hvd.shutdown()
            return (float(err / scale), bool((zi == z).all()),
                    wire.get("int8", 0), raw.get("int8", 0))

        (rel_err, ints_exact, wire_b, raw_b), = _run_watched(
            fn, tmp_path, env)
        assert rel_err < 0.02
        assert ints_exact
        # encoded bytes crossed the accounting: ~4x smaller than raw
        assert 0 < wire_b < raw_b / 3

    def test_unknown_codec_name_fails_at_init(self, tmp_path):
        env = dict(_ENV, HVD_COMPRESSION="zstd")

        def fn():
            import contextlib
            import horovod_tpu as hvd
            try:
                hvd.init()
            except ValueError as e:
                return str(e)
            finally:
                with contextlib.suppress(Exception):
                    hvd.shutdown()
            return "no error"

        (out,) = _run_watched(fn, tmp_path, env)
        assert "unknown compression codec" in out and "zstd" in out

    def test_codec_mismatch_fails_loudly_at_negotiation(self, tmp_path):
        """Acceptance: rank-asymmetric codec config must fail at
        negotiation (versioned plan field), never corrupt a sum."""
        env = dict(_ENV, HVD_QUANT_MIN_BYTES="0", HVD_NEGOTIATION="1")

        def fn():
            import os
            import jax.numpy as jnp
            rank = int(os.environ.get("HVD_PROCESS_ID", "0"))
            os.environ["HVD_COMPRESSION"] = \
                "int8" if rank == 0 else "none"
            import horovod_tpu as hvd
            from horovod_tpu.common.exceptions import MismatchError
            hvd.init()
            try:
                hvd.allreduce(jnp.ones(3000, jnp.float32), name="g")
                outcome = "no error"
            except MismatchError as e:
                outcome = str(e)
            hvd.shutdown()
            return outcome

        for outcome in _run_watched(fn, tmp_path, env, num_proc=2):
            assert "Mismatched wire-codec config" in outcome
            assert "int8" in outcome and "none" in outcome


# ---------------------------------------------------------------------------
# the eager data-parallel step of the examples
# ---------------------------------------------------------------------------

def test_int8_wire_on_the_eager_step(tmp_path):
    """The eager data-parallel step of the examples
    (bench_common._eager_step: stacked per-shard gradients, one fused
    eager allreduce, one apply) under each codec, toggled on the live
    coordinator. By the data plane's own counters int8 moves at least
    1.8x fewer bytes than bf16 for the same steps, and 30 steps on the
    int8 wire (error feedback on) end within 5% of the full-width loss
    from the same weights."""
    import os
    examples = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "examples")

    def fn():
        import sys
        sys.path.insert(0, examples)
        import numpy as np
        import jax.numpy as jnp
        import optax
        import horovod_tpu as hvd
        from bench_common import _eager_step
        from horovod_tpu.common import state
        from horovod_tpu.utils import metrics as hvd_metrics

        hvd.init()
        coord = state.global_state().coordinator
        cfg = coord._config
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(hvd.size(), 16, 64), jnp.float32)
        teacher = rng.randn(64, 32).astype(np.float32) / 8
        batch = (x, jnp.tanh(x @ teacher))
        params0 = {"w1": jnp.asarray(rng.randn(64, 128) / 8, jnp.float32),
                   "w2": jnp.asarray(rng.randn(128, 32) / 11, jnp.float32)}
        tx = optax.adam(3e-3)

        def loss_fn(p, b):
            return jnp.mean(
                (jnp.tanh(b[0] @ p["w1"]) @ p["w2"] - b[1]) ** 2)

        step = _eager_step(loss_fn, tx)

        def wire_bytes(codec):
            fam = hvd_metrics.get_registry().snapshot(max_events=0)[
                "metrics"].get("hvd_wire_bytes_total") or {"values": []}
            return sum(v["value"] for v in fam["values"]
                       if v["labels"].get("codec") == codec)

        moved, losses = {}, {}
        for codec in ("none", "bf16", "int8"):
            cfg.compression = codec
            coord._ef.reset()
            params, opt = params0, tx.init(params0)
            before = wire_bytes(codec)
            losses[codec] = []
            for _ in range(30):
                params, opt, loss = step(params, opt, batch)
                losses[codec].append(float(loss))
            moved[codec] = wire_bytes(codec) - before
        hvd.shutdown()
        return moved, losses

    env = dict(_ENV, HVD_METRICS="1", HVD_QUANT_MIN_BYTES="1024")
    (moved, losses), = _run_watched(fn, tmp_path, env)
    assert moved["int8"] > 0
    assert moved["bf16"] >= 1.8 * moved["int8"], moved
    full, int8 = losses["none"][-1], losses["int8"][-1]
    assert full < 0.5 * losses["none"][0]      # it trained
    assert abs(int8 - full) <= 0.05 * full, (int8, full)
