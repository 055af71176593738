"""Flash-attention kernel tests (interpret mode on CPU): numerical parity
with the reference full attention, gradients, causality, and the
transformer attention_impl='flash' wiring."""

import numpy as np
import pytest


def _qkv(rng, b=2, s=128, h=4, d=32, dtype=None):
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    r = np.random.RandomState(rng)
    mk = lambda: jnp.asarray(r.randn(b, s, h, d) * 0.3, dtype)
    return mk(), mk(), mk()


class TestFlashForward:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, hvd, causal):
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(0)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        ref = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bhsd_layout_matches_bshd(self, hvd):
        """layout="bhsd" (head-major operands, reshape-only flatten) is
        numerically identical to the default layout, forward and
        backward, including the indivisible-seq padding path."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(2, 45, 3, 16), jnp.float32)
                   for _ in range(3))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        def bshd(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=32,
                                   block_k=32)

        def bhsd(q, k, v):
            return flash_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                causal=True, block_q=32, block_k=32,
                layout="bhsd").swapaxes(1, 2)

        np.testing.assert_allclose(np.asarray(bshd(q, k, v)),
                                   np.asarray(bhsd(q, k, v)), atol=1e-5)
        g1 = jax.grad(loss(bshd), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(bhsd), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_single_block(self, hvd):
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(1, s=64)
        out = flash_attention(q, k, v, block_q=64, block_k=64)
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16_io(self, hvd):
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(2, dtype=jnp.bfloat16)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        assert out.dtype == jnp.bfloat16
        ref = full_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=5e-2, atol=5e-2)

    def test_causality(self, hvd):
        # output at position t must not depend on k/v after t
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(3, s=64)
        out1 = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        k2 = k.at[:, 40:].set(999.0)
        v2 = v.at[:, 40:].set(-999.0)
        out2 = flash_attention(q, k2, v2, causal=True, block_q=16,
                               block_k=16)
        np.testing.assert_allclose(np.asarray(out1[:, :40]),
                                   np.asarray(out2[:, :40]), rtol=1e-5)

    def test_pads_indivisible_causal(self, hvd):
        # causal self-attention end-pads to the block multiple and slices
        # back; must match the unpadded reference exactly
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(4, s=100)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        want = full_attention(q, k, v, causal=True)
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_indivisible_noncausal(self, hvd):
        from horovod_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv(4, s=100)
        with pytest.raises(ValueError, match="divisible"):
            flash_attention(q, k, v, causal=False, block_q=64, block_k=64)

    def test_block_shrinks_to_fit_seq(self, hvd):
        # the 256 default must not reject lengths a 128-block handles:
        # non-causal seq 384 and cross-length causal (sq != sk) shrink the
        # block instead of raising
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(5, s=384)
        out = flash_attention(q, k, v, causal=False)
        want = full_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        q2, _, _ = _qkv(6, s=128)
        _, k2, v2 = _qkv(7, s=384)
        out2 = flash_attention(q2, k2, v2, causal=False)
        want2 = full_attention(q2, k2, v2, causal=False)
        np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                                   rtol=2e-5, atol=2e-5)


class TestFlashBackward:
    def test_grad_matches_reference(self, hvd):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(5, s=64)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=32, block_k=32) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(full_attention(q, k, v, causal=True) ** 2)

        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("bq,bk", [(32, 64), (64, 32)])
    def test_grad_asymmetric_blocks(self, hvd, bq, bk):
        """Unequal block_q/block_k exercises the diagonal start/stop index
        math (qb_start, nk) off its degenerate equal-block form."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(11, s=128)

        g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(full_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_grad_non_causal(self, hvd):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(3, s=64)

        g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=False, block_q=32, block_k=32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(full_attention(
            q, k, v, causal=False) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_grad_padded_causal(self, hvd):
        """Backward through the end-padding path (seq 100, block 64):
        padded rows/keys must contribute exactly nothing."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import flash_attention
        from horovod_tpu.parallel.ring import full_attention
        q, k, v = _qkv(7, s=100)

        g_flash = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(lambda q, k, v: jnp.sum(full_attention(
            q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestTransformerFlash:
    def test_flash_model_matches_full(self, hvd):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        cfg_full = tr.TransformerConfig.tiny(dtype=jnp.float32)
        cfg_flash = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                              attention_impl="flash")
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg_full.vocab_size,
                                             (2, 64)), jnp.int32)
        m_full, m_flash = tr.TransformerLM(cfg_full), \
            tr.TransformerLM(cfg_flash)
        params = m_full.init(jax.random.PRNGKey(0), tokens)["params"]
        out_full = m_full.apply({"params": params}, tokens)
        out_flash = m_flash.apply({"params": params}, tokens)
        np.testing.assert_allclose(np.asarray(out_flash),
                                   np.asarray(out_full), rtol=2e-4,
                                   atol=2e-4)

    def test_flash_model_trains(self, hvd):
        import jax
        import jax.numpy as jnp
        import optax
        from horovod_tpu.models import transformer as tr
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="flash")
        model = tr.TransformerLM(cfg)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 65)),
            jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
        loss_fn = tr.lm_loss_fn(model)
        tx = optax.adamw(3e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens)
            losses.append(float(loss))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]


class TestDecodeAttention:
    """Single-query decode path (serving plane): numerics against the
    reference full attention and KV-cached generation parity."""

    @pytest.mark.parametrize("length", [1, 5, 24, 64])
    def test_matches_full_attention_last_row(self, hvd, length):
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import decode_attention
        from horovod_tpu.parallel.ring import full_attention
        s_max = 64
        q_all, k, v = _qkv(0, b=2, s=s_max, h=4, d=32)
        # causal full attention over the first `length` tokens: its last
        # row is exactly one query attending a `length`-long prefix
        ref = full_attention(q_all[:, :length], k[:, :length],
                             v[:, :length], causal=True)[:, -1:]
        lengths = jnp.full((2,), length, jnp.int32)
        out = decode_attention(q_all[:, length - 1:length], k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_masks_beyond_length_per_row(self, hvd):
        """Garbage K/V past each row's length must not leak into the
        output — rows with different lengths, same padded cache."""
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import decode_attention
        q, k, v = _qkv(1, b=2, s=32, h=2, d=16)
        lengths = jnp.asarray([3, 17], jnp.int32)
        out = decode_attention(q[:, :1], k, v, lengths)
        # poison the tail beyond each row's length: output unchanged
        k2 = k.at[0, 3:].set(1e4).at[1, 17:].set(-1e4)
        v2 = v.at[0, 3:].set(1e4).at[1, 17:].set(-1e4)
        out2 = decode_attention(q[:, :1], k2, v2, lengths)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))

    def test_preserves_query_dtype(self, hvd):
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import decode_attention
        q, k, v = _qkv(2, b=1, s=16, h=2, d=16, dtype=jnp.bfloat16)
        out = decode_attention(q[:, :1], k, v,
                               jnp.asarray([9], jnp.int32))
        assert out.dtype == jnp.bfloat16
        assert out.shape == (1, 1, 2, 16)

    def test_rejects_multi_query(self, hvd):
        import jax.numpy as jnp
        from horovod_tpu.ops.flash_attention import decode_attention
        q, k, v = _qkv(3, b=1, s=8, h=2, d=16)
        with pytest.raises(ValueError):
            decode_attention(q, k, v, jnp.asarray([8], jnp.int32))


# the kernel of the decode path, interpreted: row lengths around a block's
# edge, alone and mixed in one batch (0: a slot that does not decode)
_DECODE_LENGTHS = {"1": [1], "127": [127], "128": [128], "129": [129],
                   "max_len": [256], "mixed": [129, 0, 1, 256, 127, 128]}


class TestDecodeKernel:
    """``_decode_attention_kernel`` (Pallas, interpreted here) against the
    einsum of ``decode_attention``, its plain reference."""

    @pytest.mark.parametrize("lengths", list(_DECODE_LENGTHS))
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("heads,kv_heads,d",
                             [(32, 32, 128), (20, 4, 64)])
    def test_reads_live_blocks_of_its_layer_like_the_einsum(
            self, hvd, heads, kv_heads, d, dtype, lengths):
        """Equal and grouped heads, both dtypes: the einsum's values on
        layer ``layer`` of a whole cache; NaN keys and infinite values
        above each row's length, and NaN in every other layer, reach no
        output; a row of length 0 comes back as zeros."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        dtype = jnp.dtype(dtype)
        lens = _DECODE_LENGTHS[lengths]
        layers, layer, b, s_max = 3, len(lens) % 3, len(lens), 256
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(heads + b), 3)
        q = jax.random.normal(kq, (b, 1, heads, d), dtype)
        k = jax.random.normal(kk, (b, s_max, kv_heads, d), dtype)
        v = jax.random.normal(kv, (b, s_max, kv_heads, d), dtype)
        lengths = jnp.asarray(lens, jnp.int32)
        want = fa.decode_attention(q, k, v, lengths)
        above = (jnp.arange(s_max)[None, :] >= lengths[:, None]
                 )[:, :, None, None]
        cache_k = jnp.full((layers,) + k.shape, jnp.nan, dtype).at[layer].set(
            jnp.where(above, jnp.nan, k))
        cache_v = jnp.full((layers,) + v.shape, jnp.nan, dtype).at[layer].set(
            jnp.where(above, jnp.inf, v))
        got = jax.jit(fa._decode_attention_kernel, static_argnums=(5,))(
            q, cache_k, cache_v, lengths, jnp.int32(layer), d ** -0.5)
        assert got.shape == want.shape and got.dtype == q.dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        live = np.asarray(lens) > 0
        tol = 2e-5 if dtype == jnp.float32 else 1e-2
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
        assert not got[~live].any()

    def test_the_choice_is_made_from_the_call(self, hvd, monkeypatch):
        """No option: on the CPU backend ``decode_attention`` takes the
        einsum, with a whole cache and a layer too (the slice it took
        itself before); where the predicate says yes the same call runs
        the kernel; a cache the kernel cannot tile takes the einsum."""
        import jax.numpy as jnp
        from horovod_tpu.ops import flash_attention as fa
        q, k, v = _qkv(4, b=2, s=256, h=4, d=16)
        lengths = jnp.asarray([130, 7], jnp.int32)
        cache_k, cache_v = jnp.stack([k * 0, k]), jnp.stack([v * 0, v])
        want = fa.decode_attention(q[:, :1], k, v, lengths)
        assert not fa._decode_kernel_selected(cache_k.shape, None)
        calls = []
        kernel = fa._decode_attention_kernel
        monkeypatch.setattr(fa, "_decode_attention_kernel",
                            lambda *a: calls.append(1) or kernel(*a))
        got = fa.decode_attention(q[:, :1], cache_k, cache_v, lengths,
                                  layer=1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not calls
        monkeypatch.setattr(fa, "_decode_kernel_selected",
                            lambda shape, sharding: True)
        got = fa.decode_attention(q[:, :1], cache_k, cache_v, lengths,
                                  layer=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert calls == [1]
        monkeypatch.undo()
        # what the predicate looks at besides the backend
        monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
        assert fa._decode_kernel_selected((10, 16, 1536, 32, 128), None)
        assert fa._decode_kernel_selected((6, 32, 1536, 4, 128), None)
        assert not fa._decode_kernel_selected((10, 16, 1536, 32, 128),
                                              object())  # head-sharded
        assert not fa._decode_kernel_selected((2, 2, 48, 4, 128), None)
        assert not fa._decode_kernel_selected((2, 2, 1536, 12, 64), None)
        # six key/value heads are padded in the cache's tiles: a copy
        assert not fa._decode_kernel_selected((12, 8, 1024, 6, 128), None)
        assert fa.decode_block(1536) == 128 and fa.decode_block(48) == 48


class TestKVCachedGeneration:
    def test_cached_greedy_matches_no_cache_token_for_token(self, hvd):
        """Prefill + decode_attention steps reproduce the no-cache
        full-forward greedy continuation exactly (temp 0, fp32)."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.serving.decode import (decode_step,
                                                prefill_forward)
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        model, params = tr.init_params(cfg, jax.random.PRNGKey(0))
        prompt = [7, 3, 11, 19, 2]
        n_new = 12

        # reference: full forward over the growing sequence every step
        ref_toks = list(prompt)
        ref_out = []
        for _ in range(n_new):
            logits = model.apply({"params": params},
                                 jnp.asarray([ref_toks], jnp.int32))
            nxt = int(jnp.argmax(logits[0, -1]))
            ref_out.append(nxt)
            ref_toks.append(nxt)

        # cached: one prefill, then single-token decode steps
        max_len = 32
        logits, pk, pv = prefill_forward(
            cfg, params, jnp.asarray([prompt], jnp.int32))
        kv_k = jnp.zeros((cfg.num_layers, 1, max_len, cfg.num_heads,
                          cfg.d_model // cfg.num_heads), cfg.dtype)
        kv_v = jnp.zeros_like(kv_k)
        kv_k = kv_k.at[:, :, :len(prompt)].set(pk)
        kv_v = kv_v.at[:, :, :len(prompt)].set(pv)
        tok = int(jnp.argmax(logits[0, -1]))
        got = [tok]
        pos = len(prompt)
        for _ in range(n_new - 1):
            logits, kv_k, kv_v = decode_step(
                cfg, params, jnp.asarray([tok], jnp.int32),
                jnp.asarray([pos], jnp.int32), kv_k, kv_v)
            tok = int(jnp.argmax(logits[0]))
            got.append(tok)
            pos += 1
        assert got == ref_out
