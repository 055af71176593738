"""A decoder with LATENT attention and a DROPLESS mixture of experts
(the GLM-4.7-Flash / DeepSeek-V3 block; benchmarks/reference/
glm_moe_lite.py is the plain reference, equation by equation):

    x = E[token]
    for l in 0..L-1:
        h = x + MLA_l(N1_l(x))
        x = h + FFN_l(N2_l(h))       FFN_l = a dense SwiGLU for l < first_dense
                                     else Shared_l(y) + Routed_l(y)
    logits = W_head N_f(x)

    Routed(y)  models/moe.py ``route`` and ``experts``: sigmoid scores in
               float32, the top ``experts_per_tok`` of score + bias, their
               weights the scores alone, normalised, times ``route_scale``;
               EVERY token gets all of its experts (no capacity, no drop)
    Shared(y)  one more SwiGLU of ``shared_experts x d_expert`` that every
               token takes with weight 1
    MLA(y)     c_q = Nq(y W_qa);  q = c_q W_qb -> heads x [nope ; rope]
               [c ; k_r] = y W_kva;  c = Nkv(c);  k_r = RoPE(k_r), ONE
               rotary key that all heads share
               [k_nope_h ; v_h] = c W_kvb
               q_h = [q_nope_h ; RoPE(q_rope_h)],  k_h = [k_nope_h ; k_r]
               o_h = causal softmax(q_h k_h^T / sqrt(nope + rope)) v_h
               out = [o_1 .. o_H] W_o

What a token leaves behind is the pair (c, k_r): ``kv_rank + rope_dim``
numbers a layer and nothing per head. That is the ONE kind of serving
state, ``latent`` ``[layers, slots, max_len, 1, latent_lanes]``,
positional (``POSITIONAL``: masked by length, parked at a row's end), c
after its norm and k_r after its rotation, then zeros up to a whole
number of 128-lane tiles (``latent_lanes``: 576 numbers lie in 640 lanes).
The chip's tiled layout pads a 576-wide row to 640 lanes in HBM whatever
shape is declared, and its kernel compiler refuses to cut a block out of
an array whose rows are no whole tiles; declared, the padding costs no
byte that was not spent already and the decode kernel reads the blocks as
they lie.

Attention therefore has two formulations of one mathematics. ``prefill``
(and the plain ``forward``) EXPAND: c goes through W_kvb to heads of keys
and values, k's last ``rope_dim`` lanes the shared rotary key, and the
model's own attention dispatch runs over them (the flash kernel on the
chip). ``decode`` ABSORBS W_kvb into the query and the output and never
expands a cached token:

    q'_h = q_nope_h (W_kvb^K_h)^T                      in R^kv_rank
    score_h,t = (q'_h . c_t + RoPE(q_rope_h) . k_r,t) / sqrt(nope + rope)
    o_h = (sum_t p_h,t c_t) W_kvb^V_h

i.e. every query head of width ``kv_rank + rope_dim`` over ONE key head
whose first ``kv_rank`` lanes are also the value
(ops/flash_attention.latent_decode_attention: on one TPU chip a kernel
that reads each row's live blocks once, in place; elsewhere an einsum
under a length mask).

The projections, norms, embedding, head, rotary and the dense SwiGLU are
models/transformer.py's own (``_dense``, ``_rmsnorm`` with this model's
eps, ``_embed``, ``_logits``, ``_mlp``, ``_rope``), used and not copied. ``jax.named_scope`` names the parts in
both serving programs: ``hvd.mla.attend``, ``hvd.moe.route``,
``hvd.moe.experts``, ``hvd.moe.shared``.

Not here: a training path (the grouped product has no backward here), a
mesh (refused by name), and a multi-token-prediction block (a step that
yields other than one token a row is ROADMAP R9's).
"""

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.flash_attention import latent_decode_attention
from . import moe
from .transformer import (_dense, _dispatch_attention, _embed, _logits,
                          _mlp, _rmsnorm, _rope)

#: the kinds of ``state_shapes`` that hold one entry a position
POSITIONAL = ("latent",)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 1024
    num_heads: int = 8
    # latent attention: the ranks of the two low-rank projections and the
    # split of a head into a part without and a part with rotary position
    q_rank: int = 384
    kv_rank: int = 256
    nope_dim: int = 96
    rope_dim: int = 32
    v_dim: int = 128
    rope_theta: float = 10000.0
    # the feed-forward: ``first_dense`` leading layers a dense SwiGLU of
    # ``d_ff``, every later one ``num_experts`` routed experts of
    # ``d_expert`` of which a token takes ``experts_per_tok``, plus
    # ``shared_experts`` that every token takes
    d_ff: int = 4096
    first_dense: int = 1
    num_experts: int = 8
    experts_per_tok: int = 2
    shared_experts: int = 1
    d_expert: int = 512
    route_scale: float = 1.0
    route_normalise: bool = True
    rms_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False
    logits_fp32: bool = True
    attention_impl: str = "full"

    @property
    def qk_dim(self):
        return self.nope_dim + self.rope_dim

    @property
    def latent_dim(self):
        """What a token leaves in the cache, a layer: c and k_r."""
        return self.kv_rank + self.rope_dim

    @property
    def latent_lanes(self):
        """``latent_dim`` in whole 128-lane tiles: a cache entry's width."""
        return -(-self.latent_dim // 128) * 128

    @property
    def expert_layers(self):
        return self.num_layers - self.first_dense

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, num_layers=3, d_model=64, num_heads=4,
                    q_rank=16, kv_rank=32, nope_dim=24, rope_dim=8,
                    v_dim=16, d_ff=128, first_dense=1, num_experts=8,
                    experts_per_tok=2, shared_experts=1, d_expert=32,
                    route_scale=1.8, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def check_served(cfg):
    if not 0 <= cfg.first_dense <= cfg.num_layers:
        raise ValueError(f"first_dense={cfg.first_dense} of "
                         f"{cfg.num_layers} layers")
    if cfg.expert_layers and not \
            0 < cfg.experts_per_tok <= cfg.num_experts:
        raise ValueError(f"{cfg.experts_per_tok} experts a token of "
                         f"{cfg.num_experts}")


def init_params(cfg, key):
    """A seeded parameter tree: matrices N(0,1)/sqrt(fan_in) (an expert's
    fan-in is its own rows, not the stack's), norm gains 1 + 0.1 N(0,1),
    the router's selection bias 0.1 N(0,1)."""
    d, h = cfg.d_model, cfg.num_heads
    keys = iter(jax.random.split(key, 24 * cfg.num_layers + 8))

    def mat(*shape):
        return {"kernel": jax.random.normal(next(keys), shape, jnp.float32)
                / shape[-2] ** 0.5}

    def gain(n):
        return {"scale": 1.0 + 0.1 * jax.random.normal(
            next(keys), (n,), jnp.float32)}

    def swiglu(width):
        return {"gate": mat(d, width), "up": mat(d, width),
                "down": mat(width, d)}

    params = {"embed": {"embedding": jax.random.normal(
        next(keys), (cfg.vocab_size, d), jnp.float32)}, "ln_f": gain(d),
        "lm_head": mat(d, cfg.vocab_size)}
    for i in range(cfg.num_layers):
        layer = {"ln_attn": gain(d), "ln_mlp": gain(d), "attn": {
            "q_a": mat(d, cfg.q_rank), "q_norm": gain(cfg.q_rank),
            "q_b": mat(cfg.q_rank, h * cfg.qk_dim),
            "kv_a": mat(d, cfg.latent_dim), "kv_norm": gain(cfg.kv_rank),
            "kv_b": mat(cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim)),
            "out": mat(h * cfg.v_dim, d)}}
        if i < cfg.first_dense:
            layer["mlp"] = swiglu(cfg.d_ff)
        else:
            e, f = cfg.num_experts, cfg.d_expert
            layer["router"] = dict(mat(d, e), bias=0.1 * jax.random.normal(
                next(keys), (e,), jnp.float32))
            layer["experts"] = {"gate": mat(e, d, f)["kernel"],
                                "up": mat(e, d, f)["kernel"],
                                "down": mat(e, f, d)["kernel"]}
            layer["shared"] = {"mlp": swiglu(cfg.shared_experts * f)}
        params[f"layer_{i}"] = layer
    return params


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct}: ONE positional kind, the latent."""
    check_served(cfg)
    return {"latent": jax.ShapeDtypeStruct(
        (cfg.num_layers, num_slots, max_len, 1, cfg.latent_lanes),
        cfg.dtype)}


# -- the block's parts, shared by every forward -------------------------------

def _norm(cfg, x, p):
    return _rmsnorm(x, p["scale"], cfg.dtype, cfg.rms_eps)


def _queries(cfg, p, y, positions):
    """y [b, s, d] -> (q_nope [b, s, h, nope], q_rope [b, s, h, rope]
    rotated)."""
    c_q = _norm(cfg, _dense(y, p["q_a"]["kernel"], cfg.dtype), p["q_norm"])
    q = _dense(c_q, p["q_b"]["kernel"], cfg.dtype)
    q = q.reshape(q.shape[:-1] + (cfg.num_heads, cfg.qk_dim))
    return q[..., :cfg.nope_dim], \
        _rope(q[..., cfg.nope_dim:], positions, cfg.rope_theta)


def _latent(cfg, p, y, positions):
    """y [b, s, d] -> [b, s, 1, latent_lanes]: what the cache keeps of
    each token, c after its norm, the one rotary key after its rotation,
    zeros to the end of the last lane tile."""
    ckr = _dense(y, p["kv_a"]["kernel"], cfg.dtype)
    c = _norm(cfg, ckr[..., :cfg.kv_rank], p["kv_norm"])
    k_r = _rope(ckr[..., None, cfg.kv_rank:], positions, cfg.rope_theta)
    return _to_lanes(cfg, jnp.concatenate([c[..., None, :], k_r], axis=-1))


def _to_lanes(cfg, x):
    """[..., latent_dim] -> [..., latent_lanes], zeros behind."""
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                   + ((0, cfg.latent_lanes - cfg.latent_dim),))


def _kv_b(cfg, p):
    """W_kvb as (W^K [kv_rank, h, nope], W^V [kv_rank, h, v])."""
    w = p["kv_b"]["kernel"].astype(cfg.dtype).reshape(
        cfg.kv_rank, cfg.num_heads, cfg.nope_dim + cfg.v_dim)
    return w[..., :cfg.nope_dim], w[..., cfg.nope_dim:]


def _attend_expanded(cfg, p, y, positions):
    """Causal latent attention over whole sequences, EXPANDED: (out
    [b, s, d], latent [b, s, 1, latent_lanes])."""
    b, s, _ = y.shape
    h = cfg.num_heads
    q_nope, q_rope = _queries(cfg, p, y, positions)
    latent = _latent(cfg, p, y, positions)
    kv = _dense(latent[..., 0, :cfg.kv_rank], p["kv_b"]["kernel"],
                cfg.dtype).reshape(b, s, h, -1)
    k_r = jnp.broadcast_to(latent[..., cfg.kv_rank:cfg.latent_dim],
                           (b, s, h, cfg.rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([kv[..., :cfg.nope_dim], k_r], axis=-1)
    v = kv[..., cfg.nope_dim:]
    if cfg.v_dim < cfg.qk_dim:  # one head width for the kernel
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, cfg.qk_dim - cfg.v_dim),))
    elif cfg.v_dim > cfg.qk_dim:
        raise NotImplementedError(
            f"values wider than keys ({cfg.v_dim} > {cfg.qk_dim})")
    out = _dispatch_attention(cfg, q, k, v, None)[..., :cfg.v_dim]
    return _dense(out.reshape(b, s, h * cfg.v_dim), p["out"]["kernel"],
                  cfg.dtype), latent


def _attend_absorbed(cfg, p, y, positions, cache, plane, rows, lengths):
    """One token a row against the cache, ABSORBED: writes the token's
    latent at ``positions`` of plane ``plane``, then attends over the
    plane. y [b, 1, d]; returns (out [b, 1, d], cache)."""
    b = y.shape[0]
    pos2 = positions[:, None]
    q_nope, q_rope = _queries(cfg, p, y, pos2)
    latent = _latent(cfg, p, y, pos2)
    cache = cache.at[plane, rows, positions].set(latent[:, 0])
    w_k, w_v = _kv_b(cfg, p)
    # q'_h = q_nope_h (W^K_h)^T: the key's expansion folded into the query
    q_c = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_k,
                     preferred_element_type=jnp.float32).astype(cfg.dtype)
    q = _to_lanes(cfg, jnp.concatenate([q_c, q_rope[:, 0]], axis=-1))
    o_c = latent_decode_attention(q, cache, lengths, plane, cfg.kv_rank,
                                  scale=cfg.qk_dim ** -0.5)
    # o_h = (sum_t p_t c_t) W^V_h: the value's expansion on the output
    out = jnp.einsum("bhc,chv->bhv", o_c, w_v,
                     preferred_element_type=jnp.float32).astype(cfg.dtype)
    return _dense(out.reshape(b, 1, -1), p["out"]["kernel"],
                  cfg.dtype), cache


def _feed_forward(cfg, layer, y, mask):
    """FFN of one layer over y [b, s, d]; ``mask`` [b, s] bool or None:
    the tokens that are there (the others are routed to no expert).
    Returns (out, (idx, weights, load) of the routed experts or None)."""
    if "experts" not in layer:
        return _mlp(cfg, layer, y), None
    b, s, d = y.shape
    with jax.named_scope("hvd.moe.route"):
        idx, weights = moe.route(
            y.reshape(b * s, d), layer["router"]["kernel"],
            layer["router"]["bias"], cfg.experts_per_tok,
            cfg.route_scale, cfg.route_normalise)
    with jax.named_scope("hvd.moe.experts"):
        e = layer["experts"]
        routed, load = moe.experts(
            y.reshape(b * s, d), idx, weights, e["gate"].astype(cfg.dtype),
            e["up"].astype(cfg.dtype), e["down"].astype(cfg.dtype),
            None if mask is None else mask.reshape(b * s))
    with jax.named_scope("hvd.moe.shared"):
        shared = _mlp(cfg, layer["shared"], y)
    return shared + routed.reshape(b, s, d), (idx, weights, load)


def _block(cfg, layer, x, attend, mask):
    y = _norm(cfg, x, layer["ln_attn"])
    with jax.named_scope("hvd.mla.attend"):
        attended, kept = attend(layer["attn"], y)
    x = x + attended
    fed, routed = _feed_forward(cfg, layer, _norm(cfg, x, layer["ln_mlp"]),
                                mask)
    return x + fed, kept, routed


# -- the forwards -------------------------------------------------------------

def hidden_states(cfg, params, tokens, mask=None):
    """Whole causal sequences ``tokens`` [b, s] up to the final norm:
    (hidden [b, s, d], latent [layers, b, s, 1, latent_lanes], routing:
    one (idx [b*s, k], weights [b*s, k], load [E]) an expert layer)."""
    check_served(cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]
    x = _embed(cfg, params, tokens)
    latents, routing = [], []
    for i in range(cfg.num_layers):
        x, latent, routed = _block(
            cfg, params[f"layer_{i}"], x,
            lambda p, y: _attend_expanded(cfg, p, y, positions), mask)
        latents.append(latent)
        if routed is not None:
            routing.append(routed)
    return _norm(cfg, x, params["ln_f"]), jnp.stack(latents), routing


def forward(cfg, params, tokens):
    """The plain forward, no cache: (logits [b, s, vocab], routing as
    ``hidden_states`` gives it)."""
    hidden, _, routing = hidden_states(cfg, params, tokens)
    return _logits(cfg, params, hidden), routing


def prefill(cfg, params, tokens, last_index):
    """(logits [1, vocab] at ``last_index``, {"latent": [layers, 1, s_pad,
    1, latent_lanes]}) of ONE right-padded prompt: causal masking hides
    the pad from attention, the length mask hides its latent in the cache,
    and the pad's tokens are routed to no expert."""
    real = jnp.arange(tokens.shape[1])[None, :] <= last_index
    hidden, latent, _ = hidden_states(cfg, params, tokens, real)
    row = jax.lax.dynamic_index_in_dim(hidden, last_index, axis=1,
                                       keepdims=False)
    return _logits(cfg, params, row), {"latent": latent}


def decode(cfg, params, tokens, positions, state, mask=None):
    """One token for every cache row at a static shape: ``tokens``,
    ``positions`` [b] as serving/decode.decode_step, ``state`` the cache's
    arrays. A row outside ``mask`` parks its latent where ``positions``
    says, attends to nothing and is routed to no expert.

    Returns (logits [b, vocab], state, routed): ``routed`` int32 [2], the
    (layer, expert) pairs that a decoding row was routed to in this pass,
    summed over the expert layers, and the most assignments any one
    expert got."""
    check_served(cfg)
    rows = jnp.arange(tokens.shape[0])
    lengths = positions + 1
    if mask is not None:
        lengths = jnp.where(mask, lengths, 0)
    cache = state["latent"]
    there = None if mask is None else mask[:, None]
    x = _embed(cfg, params, tokens[:, None])
    touched = fullest = jnp.zeros((), jnp.int32)
    for i in range(cfg.num_layers):
        # what the block keeps of attention here is the cache, written
        x, cache, routed = _block(
            cfg, params[f"layer_{i}"], x,
            lambda p, y, plane=i, cache=cache: _attend_absorbed(
                cfg, p, y, positions, cache, plane, rows, lengths), there)
        if routed is not None:
            touched = touched + jnp.sum(routed[2] > 0, dtype=jnp.int32)
            fullest = jnp.maximum(fullest, jnp.max(routed[2]))
    x = _norm(cfg, x, params["ln_f"])
    return _logits(cfg, params, x)[:, 0], {"latent": cache}, \
        jnp.stack([touched, fullest])
