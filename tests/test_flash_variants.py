"""Numerics regression suite for every flash-attention forward variant
(online / lazy / twopass) against an independent ``jax.nn.softmax``
reference — NOT against ``full_attention`` (which shares this repo's
lineage) and not against each other.

The grid (docs/benchmarks.md, "Three forwards, one backward"): dtype ∈ {fp32,
bf16} × causal ∈ {True, False} × seq ∈ {128, 1024, 2048}, plus the
ragged-tail case (seq not a block multiple → the causal end-padding
path). Tolerances are asserted per dtype: fp32 2e-5 (fp32 MXU +
exp2-domain softmax vs the reference's exp), bf16 5e-2 (bf16 matmul
inputs). The flagship-sized sequences are marked ``slow`` — interpret
mode executes them on CPU; tier 1 and the fast kernel-numerics CI job
run the rest (see ci/run_tests.sh).

Gradients are checked per variant even though the backward kernels are
shared: each variant's forward writes the (out, lse) residuals the
backward re-materializes probabilities from, so a variant that computed
a subtly wrong lse would pass the forward check and still corrupt
training.
"""

import os

import numpy as np
import pytest

from tests.test_flash_attention import _qkv

VARIANTS = ("online", "lazy", "twopass")

# (rtol, atol) per input dtype, asserted on fp32-cast outputs
_TOL = {"float32": (2e-5, 2e-5), "bfloat16": (5e-2, 5e-2)}


def _ref_attention(q, k, v, causal):
    """Independent reference: fp32 logits, ``jax.nn.softmax``, fp32
    weighted sum; [b, s, h, d] operands like flash_attention."""
    import jax
    import jax.numpy as jnp
    qf = jnp.asarray(q, jnp.float32)
    kf = jnp.asarray(k, jnp.float32)
    vf = jnp.asarray(v, jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * (q.shape[-1] ** -0.5)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(jnp.asarray(mask), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def _check(variant, dtype_name, causal, s, b=2, h=2, d=32, block=64,
           rng=0):
    import jax.numpy as jnp
    dtype = getattr(jnp, dtype_name)
    from horovod_tpu.ops.flash_attention import flash_attention
    q, k, v = _qkv(rng, b=b, s=s, h=h, d=d, dtype=dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, variant=variant)
    assert out.dtype == dtype
    ref = _ref_attention(q, k, v, causal)
    rtol, atol = _TOL[dtype_name]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol)


class TestVariantNumerics:
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq128(self, hvd, variant, dtype, causal):
        _check(variant, dtype, causal, s=128)

    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_ragged_tail(self, hvd, variant, dtype):
        """seq 100 with 64-blocks: the causal end-padding path — the tail
        block carries 36 padded keys the mask must discard exactly."""
        _check(variant, dtype, causal=True, s=100, rng=4)

    @pytest.mark.slow
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq1024(self, hvd, variant, dtype, causal):
        # 4 k-tiles per q row at block 256: the lazy gate and the twopass
        # re-stream both run multi-tile
        _check(variant, dtype, causal, s=1024, b=1, h=2, block=256, rng=1)

    @pytest.mark.slow
    @pytest.mark.parametrize("variant", VARIANTS)
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq2048(self, hvd, variant, dtype, causal):
        # seq 2048: four k tiles at the default block
        _check(variant, dtype, causal, s=2048, b=1, h=1, block=512, rng=2)

    @pytest.mark.parametrize("variant", ("lazy", "twopass"))
    def test_adversarial_rising_max(self, hvd, variant):
        """Keys scaled so each later k tile strictly raises the row max —
        the lazy gate's worst case (rescale fires every tile) and the
        regime where deferred-rescale schemes lose precision if the
        accumulator correction is wrong."""
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(9, b=1, s=128, h=1, d=32)
        ramp = jnp.linspace(0.5, 8.0, 128)[None, :, None, None]
        k = (k * ramp).astype(k.dtype)
        out = flash_attention(q, k, v, causal=False, block_q=32,
                              block_k=32, variant=variant)
        ref = _ref_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def _ref_lse(q, k, causal):
    """Natural-log row log-sum-exp of the scaled (masked) logits,
    [b, h, s]: what the forward hands the backward and ring.py."""
    import jax
    import jax.numpy as jnp
    s = jnp.einsum("bqhd,bkhd->bhqk", jnp.asarray(q, jnp.float32),
                   jnp.asarray(k, jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        s = jnp.where(jnp.asarray(np.tril(np.ones((sq, sk), bool),
                                          k=sk - sq)), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)


class TestAutoForwardAtHeadDim128:
    """What ``auto`` runs (PR 41: the online chain with lane-replicated
    statistics in VMEM scratch) at the head width the cells run, 128,
    with two or more k tiles a row: the statistics are then replicated
    over all 128 lanes and rescaled between tiles, as compiled."""

    @pytest.mark.parametrize("kv", ["resident", "streamed"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_out_and_lse(self, hvd, monkeypatch, dtype, causal, kv):
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        if kv == "streamed":  # what rows past the VMEM budget take
            monkeypatch.setattr(fa, "_KV_RESIDENT_BYTES", 0)
        assert fa.kv_resident(384, 128, dtype) is (kv == "resident")
        q, k, v = _qkv(11, b=1, s=384, h=2, d=128,
                       dtype=getattr(jnp, dtype))
        variant = fa.resolve_variant("auto", causal=causal, nk=3)
        out, lse = fa._flash_fwd(q, k, v, causal, 128, 128, True,
                                 variant=variant)
        assert out.dtype == q.dtype and lse.dtype == jnp.float32
        assert lse.shape == (2, 8, 384)
        rtol, atol = _TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_ref_attention(q, k, v, causal), np.float32),
            rtol=rtol, atol=atol)
        want = np.asarray(_ref_lse(q, k, causal)).reshape(2, 1, 384)
        np.testing.assert_allclose(
            np.asarray(lse), np.broadcast_to(want, (2, 8, 384)),
            rtol=rtol, atol=atol)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_grad_matches_reference(self, hvd, dtype, causal):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(12, b=1, s=256, h=2, d=128,
                       dtype=getattr(jnp, dtype))

        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128).astype(
                jnp.float32) ** 2), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            _ref_attention(q, k, v, causal=causal) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        tol = {"float32": 1e-4, "bfloat16": 6e-2}[dtype]
        for a, b in zip(g, g_ref):
            assert a.dtype == q.dtype
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=tol, atol=tol)

    def test_rising_max(self, hvd):
        """Every later k tile raises the row max: the rescale of l and of
        the accumulator runs with alpha < 1 on every tile."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, k, v = _qkv(13, b=1, s=512, h=1, d=128)
        ramp = jnp.linspace(0.5, 8.0, 512)[None, :, None, None]
        k = (k * ramp).astype(k.dtype)
        out, lse = fa._flash_fwd(q, k, v, False, 128, 128, True,
                                 variant=fa.resolve_variant("auto", nk=4))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_ref_attention(q, k, v, False)),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse[:, 0]),
            np.asarray(_ref_lse(q, k, False)).reshape(1, 512),
            rtol=2e-5, atol=2e-5)

    def test_ragged_tail(self, hvd):
        """300 positions on 128-blocks: end-padded to 384, three k tiles
        on the last q block, 84 padded keys the mask has to discard."""
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(14, b=1, s=300, h=2, d=128, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128)
        assert out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_ref_attention(q, k, v, True), np.float32),
            rtol=5e-2, atol=5e-2)


class TestVariantGradients:
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_grad_matches_reference(self, hvd, variant):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(5, s=128)

        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            variant=variant) ** 2), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            _ref_attention(q, k, v, causal=True).astype(q.dtype) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_lse_identical_across_variants(self, hvd):
        """The backward contract: every variant writes the same
        natural-log lse residual (this is what makes the backward kernels
        shareable and ring.py's merge variant-agnostic)."""
        from horovod_tpu.ops import flash_attention as fa
        q, k, v = _qkv(6, s=128)
        lses = []
        for variant in VARIANTS:
            _, lse = fa._flash_fwd(q, k, v, True, 32, 32, True,
                                   variant=variant)
            lses.append(np.asarray(lse))
        for other in lses[1:]:
            np.testing.assert_allclose(lses[0], other, rtol=1e-6,
                                       atol=1e-6)


class TestVariantSelection:
    def test_explicit_names(self, hvd):
        from horovod_tpu.ops.flash_attention import resolve_variant
        for v in VARIANTS:
            assert resolve_variant(v, nk=4) == v

    def test_auto_heuristic(self, hvd):
        from horovod_tpu.ops.flash_attention import resolve_variant
        assert resolve_variant("auto", nk=1) == "online"
        assert resolve_variant("auto", nk=2) == "online"
        assert resolve_variant("auto", causal=False, nk=4) == "online"

    @pytest.mark.parametrize("s, block, nk", [
        (4096, 512, 8),    # the training cell: [64, 4096, 128]
        (128, 128, 1),     # serving prefill, the padded lengths
        (256, 256, 1),
        (512, 512, 1),
        (640, 128, 5),     # 512 does not divide it: fit_block halves
        (896, 128, 7),
        (1024, 512, 2),
    ])
    def test_what_a_call_runs_with(self, hvd, s, block, nk):
        """``auto``, the blocks and where K/V are read from are pure
        functions of the call's shapes (PR 41): at the training shape
        and at every prefill length the online forward on a head's
        whole K/V in VMEM, at the default 512-blocks or what fits."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        assert fa.call_block(512, s) == block
        assert -(-s // block) == nk
        assert fa.resolve_variant("auto", causal=True, nk=nk) == "online"
        assert fa.kv_resident(s, 128, jnp.bfloat16)

    @pytest.mark.parametrize("s, dtype, resident", [
        (8192, "bfloat16", True), (16384, "bfloat16", False),
        (4096, "float32", True), (8192, "float32", False)])
    def test_long_rows_are_streamed(self, hvd, s, dtype, resident):
        """Past 8 MiB of K/V buffers a head (two operands, held twice)
        the forward streams tiles, and VMEM use stays independent of the
        sequence length."""
        from horovod_tpu.ops import flash_attention as fa
        assert fa.kv_resident(s, 128, dtype) is resident

    def test_compiled_blocks_are_multiples_of_128(self, hvd):
        from horovod_tpu.ops import flash_attention as fa
        assert fa.call_block(512, 200) == 128
        assert fa.call_block(512, 200, compiled=False) == 200
        assert fa.call_block(512, 384) == 384
        assert fa.call_block(256, 4096) == 256

    def test_unknown_raises(self, hvd):
        from horovod_tpu.ops.flash_attention import resolve_variant
        with pytest.raises(ValueError, match="unknown flash variant"):
            resolve_variant("eager", nk=2)

    def test_env_overrides_everything(self, hvd, monkeypatch):
        from horovod_tpu.ops.flash_attention import resolve_variant
        monkeypatch.setenv("HVD_FLASH_VARIANT", "twopass")
        assert resolve_variant("online", nk=4) == "twopass"
        assert resolve_variant("auto", nk=1) == "twopass"
        monkeypatch.setenv("HVD_FLASH_VARIANT", "nonsense")
        with pytest.raises(ValueError, match="unknown flash variant"):
            resolve_variant("online", nk=4)

    def test_env_empty_is_ignored(self, hvd, monkeypatch):
        from horovod_tpu.ops.flash_attention import resolve_variant
        monkeypatch.setenv("HVD_FLASH_VARIANT", "")
        assert resolve_variant("auto", nk=4) == "online"

    def test_transformer_config_plumbs_variant(self, hvd):
        """cfg.flash_variant reaches the kernel: a model pinned to each
        variant produces the same logits (numerics parity at the model
        level, fp32)."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 64)), jnp.int32)
        outs = []
        for variant in VARIANTS:
            cfg = tr.TransformerConfig.tiny(
                dtype=jnp.float32, attention_impl="flash",
                flash_variant=variant)
            model = tr.TransformerLM(cfg)
            params = model.init(jax.random.PRNGKey(0), tokens)["params"]
            outs.append(np.asarray(
                model.apply({"params": params}, tokens)))
        for other in outs[1:]:
            np.testing.assert_allclose(outs[0], other, rtol=2e-5,
                                       atol=2e-5)


class TestLatentDecodeKernel:
    """``latent_decode_attention`` (ops/flash_attention.py): the Mosaic
    kernel, interpreted here, against the einsum under a length mask. ONE
    key head whose first ``value_dim`` lanes are the value; 2 x 128-position
    blocks a row. fp32 operands: 2e-5, the tolerance of the forward kernels
    (exp2-domain online softmax against ``jax.nn.softmax``)."""

    @staticmethod
    def _inputs(b=5, heads=4, s_max=256, lanes=256, planes=3, seed=0):
        import jax.numpy as jnp
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, heads, lanes)), jnp.float32)
        cache = jnp.asarray(rng.normal(size=(planes, b, s_max, 1, lanes)),
                            jnp.float32)
        return q, cache

    @pytest.mark.parametrize("lengths", [
        (0, 1, 128, 129, 256),      # nothing, one, a block's edge, max_len
        (256, 256, 256, 256, 256),
        (0, 0, 0, 0, 0),
        (7, 0, 255, 127, 1)])
    def test_the_kernel_is_the_einsum_at_every_length(self, lengths):
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, cache = self._inputs()
        lens = jnp.asarray(lengths, jnp.int32)
        for plane in (0, 2):
            # the CPU backend takes the einsum; the kernel by its wrapper
            want = fa.latent_decode_attention(q, cache, lens, plane, 128,
                                              scale=0.1)
            got = fa._latent_decode_attention_kernel(q, cache, lens, plane,
                                                     128, 0.1)
            assert got.shape == want.shape == (5, 4, 128)
            live = np.asarray(lengths) > 0
            np.testing.assert_allclose(np.asarray(got)[live],
                                       np.asarray(want)[live],
                                       rtol=2e-5, atol=2e-5)
            assert not np.asarray(got)[~live].any()   # a row of length 0

    def test_what_lies_above_a_length_is_never_read(self):
        """NaN above every row's length, in the plane that is read and
        all over the others: the kernel's output is finite and equal."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        kernel = fa._latent_decode_attention_kernel
        q, cache = self._inputs()
        lengths = np.asarray([3, 128, 200, 0, 256])
        lens = jnp.asarray(lengths, jnp.int32)
        want = kernel(q, cache, lens, 1, 128, 256 ** -0.5)
        above = np.arange(256)[None, :] >= lengths[:, None]
        poisoned = np.array(cache)
        poisoned[1][above] = np.nan
        poisoned[[0, 2]] = np.nan
        got = kernel(q, jnp.asarray(poisoned), lens, 1, 128, 256 ** -0.5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_the_value_is_the_keys_first_lanes_and_the_plane_is_data(self):
        """Against plain numpy; the plane index traced, as the decode
        program hands it over."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, cache = self._inputs(b=2, heads=3, lanes=384)
        lens = jnp.asarray([100, 256], jnp.int32)
        got = jax.jit(lambda plane: fa._latent_decode_attention_kernel(
            q, cache, lens, plane, 256, 384 ** -0.5))(jnp.int32(2))
        for row, n in enumerate((100, 256)):
            keys = np.asarray(cache)[2, row, :n, 0]
            logits = np.asarray(q)[row] @ keys.T * 384 ** -0.5
            p = np.exp(logits - logits.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(np.asarray(got)[row],
                                       p @ keys[:, :256], rtol=2e-5,
                                       atol=2e-5)

    def test_the_kernel_is_chosen_from_what_the_call_sees(self, monkeypatch):
        from horovod_tpu.ops import flash_attention as fa
        cell = (7, 64, 1536, 1, 640)
        assert not fa._latent_kernel_selected(cell, 512)     # the CPU
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._latent_kernel_selected(cell, 512)
        # rows of no whole blocks, lanes of no whole tiles, two key heads
        assert not fa._latent_kernel_selected((7, 64, 1500, 1, 640), 512)
        assert not fa._latent_kernel_selected((7, 64, 1536, 1, 576), 512)
        assert not fa._latent_kernel_selected((7, 64, 1536, 2, 640), 512)
        assert not fa._latent_kernel_selected(cell, 500)
        with pytest.raises(ValueError, match="wants q"):
            fa.latent_decode_attention(
                np.zeros((2, 4, 576), np.float32),
                np.zeros((1, 2, 128, 1, 640), np.float32),
                np.zeros(2, np.int32), 0, 512)
