"""The banded flash forward (ops/flash_attention.window_attention, the
prefill attention of a window layer) in interpret mode against masked dense
attention, and that it visits the band's tiles and no other.

Tolerance 2e-5: float32 operands on both sides, the same softmax in
another order (online, in the exp2 domain); measured 8e-7. In bfloat16
(the served type) 2e-2 against the same dense attention computed in
float32 from the bfloat16 operands; measured 4e-3.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa


def dense(q, k, v, window):
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k, v = (jnp.repeat(t.astype(jnp.float32), rep, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k) \
        * d ** -0.5
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    scores = jnp.where((gap >= 0) & (gap < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def operands(s, h, hk, d=32, b=2, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (b, s, h, d), dtype),
            jax.random.normal(keys[1], (b, s, hk, d), dtype),
            jax.random.normal(keys[2], (b, s, hk, d), dtype))


@pytest.mark.parametrize("s,window,block,h,hk", [
    (64, 8, 16, 4, 1),      # a band narrower than a tile, group 4
    (64, 16, 16, 3, 1),     # window == block: two tiles a q tile, group 3
    (48, 20, 16, 6, 2),     # a band across three tiles, groups of 3
    (40, 8, 16, 4, 2),      # a length no block divides: end-padded
    (128, 100, 32, 2, 2),   # a wide band, no grouping
    (32, 64, 16, 2, 1),     # a window longer than the sequence: causal
])
def test_the_band_is_masked_dense_attention(s, window, block, h, hk):
    q, k, v = operands(s, h, hk, seed=s + window)
    out = fa.window_attention(q, k, v, window, block=block)
    assert out.shape == q.shape and out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense(q, k, v, window)), atol=2e-5)


def test_the_band_in_the_served_type():
    q, k, v = operands(64, 4, 1, dtype=jnp.bfloat16)
    out = fa.window_attention(q, k, v, 16, block=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense(q, k, v, 16)), atol=2e-2)


def test_tiles_outside_the_band_are_not_visited():
    """Every key and value in a tile that lies wholly outside a q tile's
    band is NaN for that q tile: a visit, even under a mask that zeroes
    its probabilities, would put 0 x NaN into the accumulator. Block 16,
    window 16: q tile 3 (queries 48..63) may read tiles 2 and 3 alone."""
    s, block, window = 64, 16, 16
    q, k, v = operands(s, 2, 1, b=1)
    want = np.asarray(dense(q, k, v, window))
    for qi in range(s // block):
        first = max((qi * block - window + 1) // block, 0)
        outside = np.ones(s, bool)
        outside[first * block:(qi + 1) * block] = False
        bad = jnp.where(jnp.asarray(outside)[None, :, None, None], jnp.nan, 1.0)
        out = np.asarray(fa.window_attention(q, k * bad, v * bad, window,
                                             block=block))
        rows = slice(qi * block, (qi + 1) * block)
        assert np.isfinite(out[:, rows]).all(), qi
        np.testing.assert_allclose(out[:, rows], want[:, rows], atol=2e-5)
    # the count of tiles, from the shapes: 1 + 2 + 2 + 2 of the 10 under
    # the diagonal
    first, last = fa.band_tiles(s, block, block, window)
    assert [hi - max(lo, 0) + 1 for lo, hi in zip(first, last)] == \
        [1, 2, 2, 2]
    # at the cell's shape, 4,096 tokens in tiles of 512 under a window of
    # 512: 15 tiles where causal attention visits 36
    first, last = fa.band_tiles(4096, 512, 512, 512)
    assert sum(hi - max(lo, 0) + 1 for lo, hi in zip(first, last)) == 15


def test_a_key_value_head_is_read_as_it_lies():
    """Grouped queries through the block index, not a repeated K/V: the
    kernel's K/V operands keep their own head count."""
    q, k, v = operands(32, 6, 2, b=1)
    text = jax.jit(lambda q, k, v: fa.window_attention(
        q, k, v, 8, block=16)).lower(q, k, v).as_text()
    assert "2x32x32" in text and "6x32x32" in text      # [b * heads, s, d]
    with pytest.raises(ValueError, match="window_attention"):
        fa.window_attention(q, k[:, :, :1].repeat(4, 2), v, 8)
