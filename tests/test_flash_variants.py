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
        assert resolve_variant("auto", nk=2) == "lazy"
        assert resolve_variant("auto", nk=4) == "lazy"

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
        assert resolve_variant("auto", nk=4) == "lazy"

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
