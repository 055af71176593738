"""Prefill and single-token decode forwards over a training checkpoint.

models/transformer.py defines the LM as flax modules; serving needs two
extra entry points the training forward doesn't expose: a prefill that
RETURNS the per-layer K/V it computed (to seed the cache), and a
one-token decode that reads/extends that cache. Rather than threading
cache plumbing through the training model (risking its numerics and
sharding annotations), this module re-runs the SAME flax primitives —
nn.Dense / nn.RMSNorm / nn.Embed with identical dtype policy, the
model's own ``_rope`` — applied directly to the checkpoint's param
leaves. The param tree layout (embed / layer_i.{ln_attn,attn,ln_mlp,
mlp} / ln_f / lm_head) is the numerics contract;
tests/test_flash_attention.py and tests/test_serving.py pin it by
asserting logits equality and token-for-token greedy agreement against
``TransformerLM.apply``.

A model of another kind (models/hybrid.py: a recurrent mixer beside
grouped-query attention) brings its own two forwards; ``state_shapes``,
``prefill`` and ``decode`` at the end of this module are what the engine
and the cache call, and they find the model by its configuration's type.

Attention: prefill uses the model's own dispatch (flash kernel on TPU,
exact full attention on CPU); decode uses ops/flash_attention.py's
``decode_attention`` (q_len=1 against the cache, per-row lengths as data
— jit-stable as rows join/retire; on one TPU chip a kernel that reads
each row's live blocks and no more, elsewhere an einsum over the whole
row under a length mask).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..models import hybrid
from ..models.transformer import _dispatch_attention, _rope
from ..ops.flash_attention import decode_attention
from ..parallel import mesh as mesh_lib


def _dense(x, kernel, dtype):
    return nn.Dense(kernel.shape[-1], use_bias=False,
                    dtype=dtype).apply({"params": {"kernel": kernel}}, x)


def _rmsnorm(x, scale, dtype):
    return nn.RMSNorm(dtype=dtype).apply({"params": {"scale": scale}}, x)


def _embed(cfg, params, tokens):
    return nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype).apply(
        {"params": {"embedding": params["embed"]["embedding"]}}, tokens)


def _logits(cfg, params, x):
    # same head math as TransformerLM: logits straight from the MXU
    # accumulator in acc precision, tied or separate kernel
    acc = jnp.float32 if cfg.logits_fp32 else cfg.dtype
    if cfg.tie_embeddings:
        kernel = params["embed"]["embedding"].T
    else:
        kernel = params["lm_head"]["kernel"]
    return jnp.dot(x.astype(cfg.dtype), kernel.astype(cfg.dtype),
                   preferred_element_type=acc)


def _check_dense(cfg):
    if cfg.num_experts > 0:
        raise NotImplementedError(
            "serving supports dense configs only (num_experts=0); the "
            "MoE expert dispatch has no cached decode path yet")


def _qkv(cfg, layer, y, positions):
    head_dim = cfg.d_model // cfg.num_heads
    qkv = _dense(y, layer["attn"]["qkv"]["kernel"], cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(t.shape[:-1] + (cfg.num_heads, head_dim))
    q, k, v = map(heads, (q, k, v))
    return _rope(q, positions), _rope(k, positions), v


def _mlp(cfg, layer, y):
    gate = _dense(y, layer["mlp"]["gate"]["kernel"], cfg.dtype)
    up = _dense(y, layer["mlp"]["up"]["kernel"], cfg.dtype)
    return _dense(nn.silu(gate) * up, layer["mlp"]["down"]["kernel"],
                  cfg.dtype)


def prefill_forward(cfg, params, tokens):
    """Full causal forward over ``tokens`` [b, s], also returning the
    rotated per-layer K/V to seed the cache.

    Returns (logits [b, s, vocab], k [layers, b, s, h, d], v like k).
    Right-padded prompts are safe: causal masking makes every real
    position's output independent of later pad positions, and the
    engine only copies the real prefix into the cache.
    """
    _check_dense(cfg)
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    x = _embed(cfg, params, tokens)
    ks, vs = [], []
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        y = _rmsnorm(x, layer["ln_attn"]["scale"], cfg.dtype)
        q, k, v = _qkv(cfg, layer, y, positions)
        ks.append(k)
        vs.append(v)
        attn = _dispatch_attention(cfg, q, k, v, None)
        attn = attn.reshape(b, s, cfg.d_model)
        x = x + _dense(attn, layer["attn"]["out"]["kernel"], cfg.dtype)
        y = _rmsnorm(x, layer["ln_mlp"]["scale"], cfg.dtype)
        x = x + _mlp(cfg, layer, y)
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.dtype)
    return _logits(cfg, params, x), jnp.stack(ks), jnp.stack(vs)


def decode_step(cfg, params, tokens, positions, kv_k, kv_v, mask=None):
    """One decode token for every cache row at a static shape.

    tokens     [b] int32 — the token each row feeds in this step
    positions  [b] int32 — where that token sits (== tokens already in
               the row's cache; its K/V are written there)
    kv_k/kv_v  [layers, b, s_max, h, d] — the dense cache; rows beyond
               a row's length hold junk that the length mask hides, so
               K/V of inactive slots may receive garbage writes
               harmlessly (true of K/V alone: a recurrent state has no
               such hiding place, see ``decode`` below)
    mask       [b] bool or None — the rows this pass decodes. A row
               outside it still parks its K/V write where ``positions``
               says, but attends to nothing (length 0: nothing of its
               row is read) and its logits mean nothing

    Returns (logits [b, vocab], kv_k, kv_v) with the new token's K/V
    appended at ``positions``; attention spans 0..positions inclusive.
    """
    _check_dense(cfg)
    b = tokens.shape[0]
    rows = jnp.arange(b)
    pos2 = positions[:, None]  # [b, 1] per-row positions for rope
    x = _embed(cfg, params, tokens[:, None])
    lengths = positions + 1
    if mask is not None:
        lengths = jnp.where(mask, lengths, 0)
    # trace-time hint: head-sharded attention over the committed global
    # mesh's tp axis (None on dp-only engines — byte-identical program)
    heads = mesh_lib.decode_head_sharding(cfg.num_heads)
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        y = _rmsnorm(x, layer["ln_attn"]["scale"], cfg.dtype)
        q, k, v = _qkv(cfg, layer, y, pos2)
        kv_k = kv_k.at[i, rows, positions].set(k[:, 0])
        kv_v = kv_v.at[i, rows, positions].set(v[:, 0])
        attn = decode_attention(q, kv_k, kv_v, lengths,
                                head_sharding=heads, layer=i)
        attn = attn.reshape(b, 1, cfg.d_model)
        x = x + _dense(attn, layer["attn"]["out"]["kernel"], cfg.dtype)
        y = _rmsnorm(x, layer["ln_mlp"]["scale"], cfg.dtype)
        x = x + _mlp(cfg, layer, y)
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.dtype)
    return _logits(cfg, params, x)[:, 0], kv_k, kv_v


# -- what the engine and the cache call, for any model ------------------------

def _is_hybrid(cfg):
    return isinstance(cfg, hybrid.HybridConfig)


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct} of the per-slot state the model keeps,
    every kind ``[layers, slots, ...]``: what ``KVCache`` allocates."""
    if _is_hybrid(cfg):
        return hybrid.state_shapes(cfg, num_slots, max_len)
    kv = jax.ShapeDtypeStruct(
        (cfg.num_layers, num_slots, max_len, cfg.num_heads,
         cfg.d_model // cfg.num_heads), cfg.dtype)
    return {"k": kv, "v": kv}


def prefill(cfg, params, tokens, last_index):
    """(logits [1, vocab] at ``last_index``, {kind: [layers, 1, ...]})
    of ONE right-padded prompt ``tokens`` [1, s_pad]: the first token's
    logits and the state the prefill leaves for its row, every kind, AS IT
    STANDS AFTER THE LAST REAL TOKEN (``last_index``; the prompt is
    right-padded). For K/V that is the whole padded prefix — the length
    mask hides the pad; a recurrent kind must not have seen the pad."""
    if _is_hybrid(cfg):
        return hybrid.prefill(cfg, params, tokens, last_index)
    logits, k, v = prefill_forward(cfg, params, tokens)
    return logits[0, last_index][None], {"k": k, "v": v}


def decode(cfg, params, tokens, positions, state, mask=None):
    """(logits [b, vocab], state): every kind of ``state`` advanced by
    one token for the rows in ``mask`` ([b] bool, the pass's cohort;
    None: every row). A row outside the mask keeps its recurrent kinds
    bit for bit, parks its K/V write where ``positions`` says, and
    attends to nothing."""
    if _is_hybrid(cfg):
        return hybrid.decode(cfg, params, tokens, positions, state, mask)
    logits, k, v = decode_step(cfg, params, tokens, positions, state["k"],
                               state["v"], mask)
    return logits, {"k": k, "v": v}
