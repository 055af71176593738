"""Numerics of the flash forward (``ops/flash_attention._fwd_kernel``)
against an independent ``jax.nn.softmax`` reference: NOT against
``full_attention`` (which shares this repo's lineage).

The grid: dtype in {fp32, bf16} x causal in {True, False} x seq in {128,
1024, 2048}, plus the ragged-tail case (seq not a block multiple: the
causal end-padding path), each over both places the kernel reads K/V
from (``kv``: a head's whole K/V in VMEM, or tiles streamed from HBM,
which the shapes alone decide: ``kv_resident``). Tolerances are asserted
per dtype: fp32 2e-5 (fp32 MXU + exp2-domain softmax vs the reference's
exp), bf16 5e-2 (bf16 matmul inputs). The flagship-sized sequences are
marked ``slow``: interpret mode executes them on CPU; tier 1 and the fast
kernel-numerics CI job run the rest (see ci/run_tests.sh).

The log-sum-exp is held to the reference beside the output: the backward
re-materializes probabilities from it and ``parallel/ring.py`` merges
shards on it, so a forward with a subtly wrong lse would pass an output
check and still corrupt training.
"""

import numpy as np
import pytest

from tests.test_flash_attention import _qkv

# (rtol, atol) per input dtype, asserted on fp32-cast outputs
_TOL = {"float32": (2e-5, 2e-5), "bfloat16": (5e-2, 5e-2)}


@pytest.fixture(params=["resident", "streamed"])
def kv(request, monkeypatch):
    """Where the forward reads K/V from. Every shape of this file fits
    the VMEM budget; ``streamed`` takes the budget away, which is what
    rows past it (long context, ring shards) run."""
    from horovod_tpu.ops import flash_attention as fa
    if request.param == "streamed":
        monkeypatch.setattr(fa, "_KV_RESIDENT_BYTES", 0)
    return request.param


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


def _check(dtype_name, causal, s, b=2, h=2, d=32, block=64, rng=0):
    import jax.numpy as jnp
    dtype = getattr(jnp, dtype_name)
    from horovod_tpu.ops.flash_attention import flash_attention
    q, k, v = _qkv(rng, b=b, s=s, h=h, d=d, dtype=dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block)
    assert out.dtype == dtype
    ref = _ref_attention(q, k, v, causal)
    rtol, atol = _TOL[dtype_name]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol)


class TestForwardNumerics:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq128(self, hvd, kv, dtype, causal):
        _check(dtype, causal, s=128)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_ragged_tail(self, hvd, kv, dtype):
        """seq 100 with 64-blocks: the causal end-padding path — the tail
        block carries 36 padded keys the mask must discard exactly."""
        _check(dtype, causal=True, s=100, rng=4)

    @pytest.mark.slow
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq1024(self, hvd, dtype, causal):
        # 4 k-tiles per q row at block 256
        _check(dtype, causal, s=1024, b=1, h=2, block=256, rng=1)

    @pytest.mark.slow
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("causal", [True, False])
    def test_seq2048(self, hvd, dtype, causal):
        # seq 2048: four k tiles at the default block
        _check(dtype, causal, s=2048, b=1, h=1, block=512, rng=2)


class TestForwardAsCompiled:
    """The forward (the online chain with lane-replicated statistics in
    VMEM scratch) where its statistics span whole lane tiles, as
    compiled: head widths of 64 (half a tile: the statistics are then 64
    lanes wide), 128 (what the cells run) and 256 (two tiles an
    accumulator row), two or more k tiles a row so that the statistics
    are rescaled between tiles, and q and k blocks of different
    lengths."""

    # (head_dim, seq, block_q, block_k, dtype, causal)
    @pytest.mark.parametrize("d, s, bq, bk, dtype, causal", [
        (128, 384, 128, 128, "float32", True),
        (128, 384, 128, 128, "float32", False),
        (128, 384, 128, 128, "bfloat16", True),
        (128, 384, 128, 128, "bfloat16", False),
        (64, 384, 128, 128, "float32", True),
        (64, 384, 128, 128, "bfloat16", False),
        (256, 384, 128, 128, "float32", False),
        (256, 384, 128, 128, "bfloat16", True),
        (128, 512, 256, 128, "float32", True),   # two k tiles a q block
        (128, 512, 128, 256, "float32", True),   # a k tile past the diagonal
    ])
    def test_out_and_lse(self, hvd, kv, d, s, bq, bk, dtype, causal):
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        assert fa.kv_resident(s, d, dtype) is (kv == "resident")
        q, k, v = _qkv(11, b=1, s=s, h=2, d=d, dtype=getattr(jnp, dtype))
        out, lse = fa._flash_fwd(q, k, v, causal, bq, bk, True)
        assert out.dtype == q.dtype and lse.dtype == jnp.float32
        assert lse.shape == (2, 8, s)
        rtol, atol = _TOL[dtype]
        np.testing.assert_allclose(
            np.asarray(out, np.float32),
            np.asarray(_ref_attention(q, k, v, causal), np.float32),
            rtol=rtol, atol=atol)
        want = np.asarray(_ref_lse(q, k, causal)).reshape(2, 1, s)
        np.testing.assert_allclose(
            np.asarray(lse), np.broadcast_to(want, (2, 8, s)),
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

    def test_rising_max(self, hvd, kv):
        """Every later k tile raises the row max: the rescale of l and of
        the accumulator runs with alpha < 1 on every tile."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, k, v = _qkv(13, b=1, s=512, h=1, d=128)
        ramp = jnp.linspace(0.5, 8.0, 512)[None, :, None, None]
        k = (k * ramp).astype(k.dtype)
        out, lse = fa._flash_fwd(q, k, v, False, 128, 128, True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_ref_attention(q, k, v, False)),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse[:, 0]),
            np.asarray(_ref_lse(q, k, False)).reshape(1, 512),
            rtol=2e-5, atol=2e-5)

    def test_ragged_tail(self, hvd, kv):
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

    def test_shards_merge_on_the_lse(self, hvd, kv):
        """``parallel/ring.py``'s merge (``_ring_flash_fwd_impl``: on the
        CPU the ring runs a pure-jax twin of the kernel, so the kernel's
        own lse never meets the merge in tests/test_ring_attention.py):
        a causal diagonal pair and a fully visible past pair, each from
        the kernel, merged on their lse in natural-log units, are the
        attention over both."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        from horovod_tpu.parallel import ring
        q, k, v = _qkv(15, b=1, s=512, h=2, d=128)
        q = q[:, 256:]                      # the second shard's queries
        out, lse = jnp.zeros(q.shape, jnp.float32), None
        for keys, values, causal in ((k[:, 256:], v[:, 256:], True),
                                     (k[:, :256], v[:, :256], False)):
            o_i, lse_i = fa._flash_fwd(q, keys, values, causal, 128, 128,
                                       True)
            lse_i = ring._lse_to_bhs(lse_i, 1, 2, 256)
            if lse is None:
                out, lse = o_i, lse_i
                continue
            merged = jnp.logaddexp(lse, lse_i)
            w, w_i = (jnp.exp(t - merged).transpose(0, 2, 1)[..., None]
                      for t in (lse, lse_i))
            out, lse = out * w + o_i * w_i, merged
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(_ref_attention(q, k, v, True)),
            rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(_ref_lse(q, k, True)),
            rtol=2e-5, atol=2e-5)


class TestGradients:
    def test_grad_matches_reference(self, hvd, kv):
        """The backward reads the forward's (out, lse) residuals."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(5, s=128)

        g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(
            _ref_attention(q, k, v, causal=True).astype(q.dtype) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


class TestWhatACallRunsWith:
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
        """The blocks and where K/V are read from are pure functions of
        the call's shapes (PR 41): at the training shape and at every
        prefill length a head's whole K/V in VMEM, at the default
        512-blocks or what fits."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        assert fa.call_block(512, s) == block
        assert -(-s // block) == nk
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
