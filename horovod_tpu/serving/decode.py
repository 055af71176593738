"""Prefill and single-token decode forwards over a training checkpoint.

models/transformer.py defines the LM as flax modules; serving needs two
extra entry points the training forward doesn't expose: a prefill that
RETURNS the per-layer K/V it computed (to seed the cache), and a
one-token decode that reads/extends that cache. Rather than threading
cache plumbing through the training model (risking its numerics and
sharding annotations), this module re-runs the SAME flax primitives —
nn.Dense / nn.RMSNorm / nn.Embed with identical dtype policy
(models/transformer.py's ``_dense``, ``_rmsnorm``, ``_embed``,
``_logits``, ``_mlp``), the model's own ``_rope`` — applied directly to
the checkpoint's param leaves. The param tree layout (embed /
layer_i.{ln_attn,attn,ln_mlp,mlp} / ln_f / lm_head) is the numerics
contract;
tests/test_flash_attention.py and tests/test_serving.py pin it by
asserting logits equality and token-for-token greedy agreement against
``TransformerLM.apply``.

A model of another kind (models/hybrid.py: a recurrent mixer; latent_moe.py:
latent attention, dropless experts; window_moe.py: window and full layers;
sambay.py: a self-decoder and a cross-decoder that reads its one plane)
brings its own two forwards; ``state_shapes``, ``prefill`` and ``decode`` at
the end are what the engine and the cache call, by type.
A model whose layer stack runs several times over one set of weights
(models/looped.py) is served by THIS module: ``_stack`` is the dense block
of ``prefill_forward`` / ``decode_step`` with a norm closing each branch,
run ``passes`` times with the same ``_qkv``, ``_mlp``, ``_rmsnorm``,
``_logits``, ``_rope`` and kernels, K/V kept per (pass, layer) PLANE of
the cache, ``passes x layers`` of them. The dense model's two forwards
keep a loop of their own: PR 37 measured the dense serving cell's
set-up 8 s longer when they ran through ``_stack``, with the same
lowered text and the cause not found (PERF.md §7).

Attention: prefill uses the model's own dispatch (flash kernel on TPU,
exact full attention on CPU); decode uses ops/flash_attention.py's
``decode_attention`` (q_len=1 against the cache, per-row lengths as data
— jit-stable as rows join/retire; on one TPU chip a kernel that reads
each row's live blocks, elsewhere an einsum under a length mask).
"""

import jax
import jax.numpy as jnp

from ..models import hybrid, latent_moe, looped, sambay, window_moe
from ..models.transformer import (_dense, _dispatch_attention, _embed,
                                  _logits, _mlp, _rmsnorm, _rope)
from ..ops.flash_attention import decode_attention
from ..parallel import mesh as mesh_lib


def _check_dense(cfg):
    if cfg.num_experts > 0:
        raise NotImplementedError(
            "a TransformerConfig is served dense (num_experts=0): experts are "
            "served dropless, by a LatentMoEConfig or a WindowMoEConfig")


def _check_served(cfg):
    if _is_looped(cfg):
        looped.check_served(cfg)
    else:
        _check_dense(cfg)


def _is_looped(cfg):
    return isinstance(cfg, looped.LoopedConfig)


def passes(cfg):
    """Times a decoding row runs the layer stack for one token: the
    stack's passes over its one set of weights (models/looped.py), 1 for
    every other model. K/V planes are ``passes x layers``."""
    return cfg.passes if _is_looped(cfg) else 1


def _qkv(cfg, layer, y, positions):
    head_dim = cfg.d_model // cfg.num_heads
    qkv = _dense(y, layer["attn"]["qkv"]["kernel"], cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(t.shape[:-1] + (cfg.num_heads, head_dim))
    q, k, v = map(heads, (q, k, v))
    # the dense model's rotary base is ``_rope``'s own default
    rope = {"base": cfg.rope_theta} if _is_looped(cfg) else {}
    return _rope(q, positions, **rope), _rope(k, positions, **rope), v


def _stack(cfg, params, x, positions, attend, past):
    """The layer stack of a looped model (models/looped.py) over ``x``
    [b, s, d]: the dense block of ``prefill_forward`` / ``decode_step``
    below with a norm closing each branch, ``cfg.passes`` times over its
    one set of weights, the final norm closing EVERY pass (it feeds the
    next one). The passes are ONE ``lax.scan`` whose body is the layers,
    so a program holds one copy of them whatever ``passes`` is (PERF.md
    §6, PR 37: unrolled, the cell's nine programs took 170 s to compile
    and 91 s to load back).
    ``attend(past, plane, q, k, v)`` -> (past, [b, s, heads, head_dim],
    kept) is how this forward sees the past: pass t of layer i reads and
    extends K/V plane ``t * layers + i`` (a traced index) and no other.
    ``past`` is carried from plane to plane. Returns (each pass's
    normalised hidden state [passes, b, s, d], the last of which the head
    reads; past; every plane's ``kept``, stacked ``[passes x layers,
    ...]`` in plane order)."""
    sandwich = cfg.sandwich_norm

    def one_pass(carry, t):
        x, past = carry
        kept = []
        for i in range(cfg.num_layers):
            layer = params[f"layer_{i}"]
            y = _rmsnorm(x, layer["ln_attn"]["scale"], cfg.dtype)
            q, k, v = _qkv(cfg, layer, y, positions)
            past, attn, keep = attend(past, t * cfg.num_layers + i, q, k, v)
            kept.append(keep)
            attn = _dense(attn.reshape(x.shape),
                          layer["attn"]["out"]["kernel"], cfg.dtype)
            if sandwich:
                attn = _rmsnorm(attn, layer["ln_attn_out"]["scale"],
                                cfg.dtype)
            x = x + attn
            y = _rmsnorm(x, layer["ln_mlp"]["scale"], cfg.dtype)
            y = _mlp(cfg, layer, y)
            if sandwich:
                y = _rmsnorm(y, layer["ln_mlp_out"]["scale"], cfg.dtype)
            x = x + y
        x = _rmsnorm(x, params["ln_f"]["scale"], cfg.dtype)
        return (x, past), (x, kept)
    # names the passes' operations in the serving programs (HLO metadata)
    with jax.named_scope("hvd.loop.passes"):
        if cfg.passes == 1:  # nothing to roll: the dense model's program
            (_, past), out = one_pass((x, past), 0)
            hidden, kept = jax.tree_util.tree_map(lambda a: a[None], out)
        else:
            (_, past), (hidden, kept) = jax.lax.scan(
                one_pass, (x, past), jnp.arange(cfg.passes))
    # a layer's [passes, ...] each -> [passes x layers, ...], plane t*L+i
    kept = jax.tree_util.tree_map(
        lambda *by_layer: jnp.stack(by_layer, axis=1).reshape(
            (cfg.planes,) + by_layer[0].shape[1:]), *kept)
    return hidden, past, kept


def hidden_states(cfg, params, tokens):
    """A looped model's full causal forward over ``tokens`` [b, s] up to
    the head: (each pass's normalised hidden state [passes, b, s, d];
    k [planes, b, s, h, d]; v like k), the rotated K/V of every (pass,
    layer) plane."""
    _check_served(cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def attend(past, plane, q, k, v):
        return past, _dispatch_attention(cfg, q, k, v, None), (k, v)
    hidden, _, (ks, vs) = _stack(cfg, params, _embed(cfg, params, tokens),
                                 positions, attend, None)
    return hidden, ks, vs


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


def looped_decode_step(cfg, params, tokens, positions, kv_k, kv_v, mask=None):
    """``decode_step`` for a stack that runs ``cfg.passes`` times
    (models/looped.py): the same contract over a cache of ``passes x
    layers`` planes ``[planes, b, s_max, h, d]``. Pass t of layer i writes
    the token's K/V into plane ``t * layers + i`` at ``positions`` and
    attends over that plane and no other; a row outside ``mask`` parks
    its write in every plane and reads nothing."""
    _check_served(cfg)
    rows = jnp.arange(tokens.shape[0])
    lengths = positions + 1
    if mask is not None:
        lengths = jnp.where(mask, lengths, 0)
    heads = mesh_lib.decode_head_sharding(cfg.num_heads)

    def attend(past, plane, q, k, v):
        # write, then read: the token attends to itself, in its own plane
        kv_k, kv_v = past
        kv_k = kv_k.at[plane, rows, positions].set(k[:, 0])
        kv_v = kv_v.at[plane, rows, positions].set(v[:, 0])
        return (kv_k, kv_v), decode_attention(
            q, kv_k, kv_v, lengths, head_sharding=heads, layer=plane), None
    hidden, (kv_k, kv_v), _ = _stack(
        cfg, params, _embed(cfg, params, tokens[:, None]),
        positions[:, None], attend, (kv_k, kv_v))
    return _logits(cfg, params, hidden[-1])[:, 0], kv_k, kv_v


# -- what the engine and the cache call, for any model ------------------------

def _own(cfg):  # the module of a model that brings its own forwards
    return _OWN_FORWARDS.get(type(cfg))


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct} of the per-slot state the model keeps,
    every kind ``[planes, slots, ...]``: what ``KVCache`` allocates. A
    plane is a layer, and for a stack that runs several times a (pass,
    layer): ``passes(cfg) x layers`` planes of K/V over ``layers``
    weights."""
    if _own(cfg):
        return _own(cfg).state_shapes(cfg, num_slots, max_len)
    _check_served(cfg)
    kv = jax.ShapeDtypeStruct(
        (passes(cfg) * cfg.num_layers, num_slots, max_len, cfg.num_heads,
         cfg.d_model // cfg.num_heads), cfg.dtype)
    return {"k": kv, "v": kv}


def prefill(cfg, params, tokens, last_index):
    """(logits [1, vocab] at ``last_index``, {kind: [planes, 1, ...]})
    of ONE right-padded prompt ``tokens`` [1, s_pad]: the first token's
    logits and the state the prefill leaves for its row, every kind, AS IT
    STANDS AFTER THE LAST REAL TOKEN (``last_index``; the prompt is
    right-padded). For K/V that is the whole padded prefix — the length
    mask hides the pad; a recurrent kind must not have seen the pad."""
    if _own(cfg):
        return _own(cfg).prefill(cfg, params, tokens, last_index)
    if _is_looped(cfg):
        # every pass over the padded prompt; the head on the one row
        hidden, ks, vs = hidden_states(cfg, params, tokens)
        row = jax.lax.dynamic_index_in_dim(hidden[-1], last_index, axis=1,
                                           keepdims=False)
        return _logits(cfg, params, row), {"k": ks, "v": vs}
    logits, k, v = prefill_forward(cfg, params, tokens)
    return logits[0, last_index][None], {"k": k, "v": v}


def decode(cfg, params, tokens, positions, state, mask=None):
    """(logits [b, vocab], state): every kind of ``state`` advanced by
    one token for the rows in ``mask`` ([b] bool, the pass's cohort;
    None: every row). A row outside the mask keeps its recurrent kinds
    bit for bit, parks its positional write where ``positions`` says, and
    attends to nothing. A model with experts: a third, ``ROUTED_COUNTS``."""
    if _own(cfg):
        return _own(cfg).decode(cfg, params, tokens, positions, state, mask)
    if _is_looped(cfg):
        logits, k, v = looped_decode_step(cfg, params, tokens, positions,
                                          state["k"], state["v"], mask)
        return logits, {"k": k, "v": v}
    logits, k, v = decode_step(cfg, params, tokens, positions, state["k"],
                               state["v"], mask)
    return logits, {"k": k, "v": v}


_OWN_FORWARDS = {hybrid.HybridConfig: hybrid,
                 latent_moe.LatentMoEConfig: latent_moe,
                 window_moe.WindowMoEConfig: window_moe,
                 sambay.SambaYConfig: sambay}


def positional_kinds(cfg):
    """The kinds of ``state_shapes`` that hold one entry a POSITION of a
    row: masked by the row's length, so a row that a pass does not decode
    may park its write at the row's end, and counted by the step record's
    ``kv_bytes``. Every other kind is recurrent: one state a row, which a
    pass must leave bit for bit for the rows it does not decode. ``k`` and
    ``v``, or what a model of its own declares (``POSITIONAL``)."""
    return getattr(_own(cfg), "POSITIONAL", ("k", "v"))


def ring_kinds(cfg):
    """The positional kinds that are RINGS (models/window_moe.py: K and V of
    a layer whose attention looks back ``cfg.window`` tokens and no
    further): a row holds ``cfg.window`` entries and a place to park,
    position ``p`` at ``p mod cfg.window``, whatever ``max_len`` is. None
    for every other model."""
    return getattr(_own(cfg), "RING", ())


def plane_readers(cfg):
    """Layers that read each plane of the ``max_len`` class in one decode
    step: 1 where every layer that attends owns its plane (every model
    but one); models/sambay.py's one plane is read by its full layer and by
    every cross layer, which have no K or V of their own (``readers``)."""
    own = _own(cfg)
    return own.readers(cfg) if hasattr(own, "readers") else 1


def prefill_extents(cfg, padded_len):
    """{count: tokens} of the step record for a prefill of ``padded_len``
    positions by a model whose prefill has TWO token extents
    (models/sambay.py ``prefill_extents``: the self-decoder over the padded
    prompt, the cross-decoder over the last token alone); {} for every
    other model."""
    own = _own(cfg)
    return own.prefill_extents(cfg, padded_len) \
        if hasattr(own, "prefill_extents") else {}


#: what a model with experts returns from ``decode`` as a third result, an
#: int32 vector read back with the pass's ids: the (layer, expert) pairs
#: that a decoding row was routed to, summed over the expert layers, and
#: the most assignments any one expert got
ROUTED_COUNTS = ("experts_touched", "expert_tokens_max")
