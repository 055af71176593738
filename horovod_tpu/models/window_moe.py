"""A decoder whose layers mix WINDOW and FULL attention, gate each head's
output, and feed a DROPLESS mixture of many narrow experts (the Laguna
block; benchmarks/reference/laguna.py is the plain reference, equation by
equation):

    x = E[token]
    for l in 0..L-1:
        y = N1_l(x);  h = x + (Gate_l(y) * Attn_l(y)) W_o
        x = h + FFN_l(N2_l(h))       FFN_l = a dense SwiGLU for l < first_dense
                                     else Shared_l(y) + Routed_l(y)
    logits = W_head N_f(x)

    Attn_l(y)  grouped-query attention: ``heads_per_layer[l]`` query heads
               over ``num_kv_heads`` key/value heads of ``head_dim``; the
               head COUNT differs by layer on the query side only. A
               ``full`` layer is causal; on a ``window`` layer key j is
               visible to query i iff ``0 <= i - j < window``.
    rotary     one law a layer KIND (``Rotary``): a full layer rotates the
               first ``fraction`` of a head's lanes under YaRN-blended
               inverse frequencies, cos and sin times the law's
               ``attention_factor``; a window layer rotates every lane at
               its own base. Rotate-half pairing inside the rotated lanes.
    Gate_l(y)  ``gate_activation(y W_g)``, ONE number a head a token
               (``gate_granularity``: per head), on the head's output
               before W_o.
    Routed(y)  models/moe.py ``route`` and ``experts``: sigmoid scores in
               float32 (``router_score``), the top ``experts_per_tok``,
               their weights normalised, times ``route_scale``, no
               selection bias; EVERY token gets all of its experts.
    Shared(y)  one more SwiGLU of ``d_shared`` that every token takes with
               weight 1.

What a token leaves behind is K and V of ``num_kv_heads x head_dim`` a
layer, after the rotation, in TWO classes of serving state:

  * ``k``, ``v`` ``[full layers, slots, max_len, kv_heads, head_dim]``:
    positional, as every other model's;
  * ``k_ring``, ``v_ring`` ``[window layers, slots, window + park,
    kv_heads, head_dim]`` (``RING``): position ``p`` lies at ``p mod
    window``, so a row never holds more than its last ``window`` tokens. A
    row that a pass does not decode parks its write at index ``window``,
    OUTSIDE the ring (the rest of the park is there so that a row is whole
    blocks of the decode kernel); a prefill of more than ``window`` tokens
    leaves its last ``window``. Keys are cached after their rotation, so
    where in the ring a token lies is nothing the softmax sees:
    ``ops/flash_attention.decode_attention`` reads a ring as it lies, at
    lengths ``min(length, window)``, the one kernel at groups of
    ``heads_per_layer[l] / num_kv_heads`` that differ by layer.

Planes of one class stack because the head counts differ on the query side
only. The projections, norms, embedding, head and the dense SwiGLU are
models/transformer.py's own (``_dense``, ``_rmsnorm`` with this model's
eps, ``_embed``, ``_logits``, ``_mlp``), used and not copied.
``jax.named_scope`` names the parts in both serving programs:
``hvd.full.attend``, ``hvd.swa.attend``, ``hvd.moe.route``,
``hvd.moe.experts``, ``hvd.moe.shared``.

Not here: a training path (neither the banded forward nor the grouped
product has a backward), a mesh (refused by name), a ring under a paged
cache.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import (DECODE_BLOCK, decode_attention,
                                   window_attention)
from . import moe
from .transformer import (_dense, _dispatch_attention, _embed, _logits,
                          _mlp, _rmsnorm)

#: the kinds of ``state_shapes`` that hold one entry a position, and those
#: of them that are rings of ``cfg.window`` entries
POSITIONAL = ("k", "v", "k_ring", "v_ring")
RING = ("k_ring", "v_ring")


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One kind of layer's rotary law. ``fraction`` of a head's lanes are
    rotated (the first ones); ``factor`` > 1 is YaRN: inverse frequencies
    blended between the base's own (extrapolation) and those divided by
    ``factor`` (interpolation) by a linear ramp over the rotated lanes'
    pairs between the two correction dimensions, and cos and sin times
    ``attention_factor``."""
    theta: float = 10000.0
    fraction: float = 1.0
    factor: float = 1.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 32000
    d_model: int = 1024
    head_dim: int = 128
    num_kv_heads: int = 8
    # one entry a layer: "full" or "window", and its query heads
    layer_types: tuple = ("full", "window", "window", "window")
    heads_per_layer: tuple = (16, 24, 24, 24)
    window: int = 512
    rope_full: Rotary = Rotary()
    rope_window: Rotary = Rotary()
    # the feed-forward: ``first_dense`` leading layers a dense SwiGLU of
    # ``d_ff``, every later one ``num_experts`` routed experts of
    # ``d_expert`` of which a token takes ``experts_per_tok``, plus one
    # shared SwiGLU of ``d_shared``
    d_ff: int = 4096
    first_dense: int = 1
    num_experts: int = 16
    experts_per_tok: int = 4
    d_expert: int = 256
    d_shared: int = 256
    route_scale: float = 1.0
    route_normalise: bool = True
    rms_eps: float = 1e-6
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = False
    logits_fp32: bool = True
    attention_impl: str = "full"

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def expert_layers(self):
        return self.num_layers - self.first_dense

    def planes(self, kind):
        """Layers of ``kind`` ("full" or "window"): that class's planes."""
        return sum(t == kind for t in self.layer_types)

    def plane(self, layer):
        """Layer ``layer``'s plane within its own class."""
        kind = self.layer_types[layer]
        return sum(t == kind for t in self.layer_types[:layer])

    @property
    def ring_len(self):
        """Entries a ring row holds: the window, and a place to park
        outside it: whole blocks of the decode kernel where the window is."""
        return self.window + (DECODE_BLOCK if self.window % DECODE_BLOCK == 0
                              else 1)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, d_model=64, head_dim=16, num_kv_heads=1,
                    layer_types=("full", "window", "window", "window",
                                 "full"),
                    heads_per_layer=(3, 4, 4, 4, 3), window=8,
                    rope_full=Rotary(theta=500000.0, fraction=0.5,
                                     factor=8.0, original_len=16,
                                     beta_fast=4.0, beta_slow=1.0,
                                     attention_factor=0.1 * math.log(8.0)
                                     + 1.0),
                    rope_window=Rotary(theta=10000.0), d_ff=128,
                    first_dense=1, num_experts=16, experts_per_tok=4,
                    d_expert=32, d_shared=32, route_scale=2.5,
                    max_seq_len=128)
        base.update(kw)
        return cls(**base)


def check_served(cfg):
    if len(cfg.heads_per_layer) != cfg.num_layers or \
            set(cfg.layer_types) - {"full", "window"}:
        raise ValueError(f"layer_types {cfg.layer_types} and heads_per_layer "
                         f"{cfg.heads_per_layer}: one entry a layer, 'full' "
                         "or 'window'")
    if any(h % cfg.num_kv_heads for h in cfg.heads_per_layer):
        raise ValueError(f"{cfg.heads_per_layer} query heads over "
                         f"{cfg.num_kv_heads} key/value heads")
    if not 0 <= cfg.first_dense <= cfg.num_layers:
        raise ValueError(f"first_dense={cfg.first_dense} of "
                         f"{cfg.num_layers} layers")
    if cfg.expert_layers and not \
            0 < cfg.experts_per_tok <= cfg.num_experts:
        raise ValueError(f"{cfg.experts_per_tok} experts a token of "
                         f"{cfg.num_experts}")
    if cfg.window < 1:
        raise ValueError(f"window={cfg.window}")


def init_params(cfg, key):
    """A seeded parameter tree: matrices N(0,1)/sqrt(fan_in) (an expert's
    fan-in is its own rows, not the stack's), norm gains 1 + 0.1 N(0,1)."""
    d, hk, dh = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
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
    for i, h in enumerate(cfg.heads_per_layer):
        layer = {"ln_attn": gain(d), "ln_mlp": gain(d), "attn": {
            "q": mat(d, h * dh), "k": mat(d, hk * dh), "v": mat(d, hk * dh),
            "gate": mat(d, h), "out": mat(h * dh, d)}}
        if i < cfg.first_dense:
            layer["mlp"] = swiglu(cfg.d_ff)
        else:
            e, f = cfg.num_experts, cfg.d_expert
            layer["router"] = mat(d, e)
            layer["experts"] = {"gate": mat(e, d, f)["kernel"],
                                "up": mat(e, d, f)["kernel"],
                                "down": mat(e, f, d)["kernel"]}
            layer["shared"] = {"mlp": swiglu(cfg.d_shared)}
        params[f"layer_{i}"] = layer
    return params


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct}: K and V of the full layers, ``max_len`` a
    row, and of the window layers, a ring of ``ring_len`` a row."""
    check_served(cfg)
    tail = (cfg.num_kv_heads, cfg.head_dim)
    out = {}
    if cfg.planes("full"):
        out["k"] = out["v"] = jax.ShapeDtypeStruct(
            (cfg.planes("full"), num_slots, max_len) + tail, cfg.dtype)
    if cfg.planes("window"):
        out["k_ring"] = out["v_ring"] = jax.ShapeDtypeStruct(
            (cfg.planes("window"), num_slots, cfg.ring_len) + tail,
            cfg.dtype)
    return out


# -- the three conventions no published key settles, one function each --------

def gate_activation(x):
    """The output gate's nonlinearity (assumed: a sigmoid)."""
    return jax.nn.sigmoid(x)


def router_score(cfg, y, w_router):
    """(idx, weights) of a token's experts (assumed: sigmoid scores,
    normalised over the chosen, no selection bias)."""
    return moe.route(y, w_router, None, cfg.experts_per_tok,
                     cfg.route_scale, cfg.route_normalise)


def shared_expert(cfg, layer, y):
    """The shared expert's branch (assumed: added unweighted)."""
    return _mlp(cfg, layer["shared"], y)


# -- rotary -------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def inverse_frequencies(law, head_dim):
    """float32 [rotated lanes / 2]: the law's inverse frequencies, one a
    rotated pair, computed where the program is traced (the law is a
    constant of the configuration)."""
    dim = int(head_dim * law.fraction)
    pos = law.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if law.factor == 1.0:
        return (1.0 / pos).astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(law.original_len / (rotations * 2 * math.pi)) \
            / (2 * math.log(law.theta))
    low = max(math.floor(correction_dim(law.beta_fast)), 0)
    high = min(math.ceil(correction_dim(law.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the base's own frequency (extrapolation); 1: divided by factor
    return ((1.0 / pos) * (1.0 - ramp)
            + (1.0 / (law.factor * pos)) * ramp).astype(np.float32)


def rotate(x, positions, law):
    """Rotary embedding of ``x`` [..., seq, heads, head_dim] at
    ``positions`` [..., seq] under ``law``: the first ``fraction`` of the
    lanes rotated (rotate-half pairing inside them), the rest as they are.
    Angles in float32, the rotation in x's dtype (models/transformer.py
    ``_rope``'s policy)."""
    dim = int(x.shape[-1] * law.fraction)
    half = dim // 2
    angles = positions[..., None, None].astype(jnp.float32) \
        * inverse_frequencies(law, x.shape[-1])
    sin = (jnp.sin(angles) * law.attention_factor).astype(x.dtype)
    cos = (jnp.cos(angles) * law.attention_factor).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], axis=-1)


# -- the block's parts, shared by every forward -------------------------------

def _norm(cfg, x, p):
    return _rmsnorm(x, p["scale"], cfg.dtype, cfg.rms_eps)


def _law(cfg, i):
    return cfg.rope_full if cfg.layer_types[i] == "full" else cfg.rope_window


def _qkv(cfg, i, p, y, positions):
    """y [b, s, d] -> q [b, s, heads of layer i, dh], k and v [b, s,
    kv_heads, dh], q and k rotated under the layer's law."""

    def heads(t):
        return t.reshape(t.shape[:-1] + (-1, cfg.head_dim))
    q = heads(_dense(y, p["q"]["kernel"], cfg.dtype))
    k = heads(_dense(y, p["k"]["kernel"], cfg.dtype))
    v = heads(_dense(y, p["v"]["kernel"], cfg.dtype))
    law = _law(cfg, i)
    return rotate(q, positions, law), rotate(k, positions, law), v


def _gated_out(cfg, p, y, attended):
    """attended [b, s, h, dh] under the per-head gate of ``y``, through
    W_o: [b, s, d]."""
    gate = gate_activation(_dense(y, p["gate"]["kernel"], cfg.dtype)
                           .astype(jnp.float32)).astype(cfg.dtype)
    gated = attended * gate[..., None]
    return _dense(gated.reshape(gated.shape[:2] + (-1,)), p["out"]["kernel"],
                  cfg.dtype)


def _attend_whole(cfg, i, q, k, v):
    """Causal attention of layer ``i`` over whole sequences: the model's
    own dispatch on a full layer (the flash kernel takes equal head counts:
    K/V repeated a group for the call), the banded kernel on a window
    layer, which reads the key/value heads as they lie."""
    rep = q.shape[2] // k.shape[2]
    if cfg.layer_types[i] == "full":
        return _dispatch_attention(cfg, q, jnp.repeat(k, rep, axis=2),
                                   jnp.repeat(v, rep, axis=2), None)
    if cfg.attention_impl == "flash":
        return window_attention(q, k, v, cfg.window)
    s = q.shape[1]
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    qg = q.reshape(q.shape[:2] + (k.shape[2], rep, cfg.head_dim))
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                        preferred_element_type=jnp.float32) \
        * cfg.head_dim ** -0.5
    logits = jnp.where((gap >= 0) & (gap < cfg.window), logits, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd",
                     jax.nn.softmax(logits, axis=-1).astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def ring_of(cfg, kv, last_index):
    """What a window layer keeps of a prefill's ``kv`` [b, s, kv_heads, dh]
    whose last real token sits at ``last_index``: [b, min(s, window), ...],
    entry r the LAST real position p with ``p mod window == r`` (junk
    where the row has none yet: the length hides it until its own token
    overwrites it)."""
    s, w = kv.shape[1], cfg.window
    if s <= w:
        return kv
    r = jnp.arange(w)
    return jnp.take(kv, jnp.clip(last_index - (last_index - r) % w, 0),
                    axis=1)


def _feed_forward(cfg, layer, y, mask):
    """FFN of one layer over y [b, s, d]; ``mask`` [b, s] bool or None:
    the tokens that are there (the others are routed to no expert).
    Returns (out, the experts' load [E] or None)."""
    if "experts" not in layer:
        return _mlp(cfg, layer, y), None
    b, s, d = y.shape
    with jax.named_scope("hvd.moe.route"):
        idx, weights = router_score(cfg, y.reshape(b * s, d),
                                    layer["router"]["kernel"])
    with jax.named_scope("hvd.moe.experts"):
        e = layer["experts"]
        routed, load = moe.experts(
            y.reshape(b * s, d), idx, weights, e["gate"].astype(cfg.dtype),
            e["up"].astype(cfg.dtype), e["down"].astype(cfg.dtype),
            None if mask is None else mask.reshape(b * s))
    with jax.named_scope("hvd.moe.shared"):
        shared = shared_expert(cfg, layer, y)
    return shared + routed.reshape(b, s, d), load


def _block(cfg, i, layer, x, attend, mask):
    y = _norm(cfg, x, layer["ln_attn"])
    scope = "hvd.full.attend" if cfg.layer_types[i] == "full" \
        else "hvd.swa.attend"
    with jax.named_scope(scope):
        attended, kept = attend(y)
        x = x + _gated_out(cfg, layer["attn"], y, attended)
    fed, load = _feed_forward(cfg, layer, _norm(cfg, x, layer["ln_mlp"]),
                              mask)
    return x + fed, kept, load


# -- the forwards -------------------------------------------------------------

def hidden_states(cfg, params, tokens, mask=None):
    """Whole causal sequences ``tokens`` [b, s] up to the final norm:
    (hidden [b, s, d], [(k, v) [b, s, kv_heads, dh] a layer, rotated],
    [load [E] an expert layer])."""
    check_served(cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]
    x = _embed(cfg, params, tokens)
    kept, loads = [], []
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]

        def attend(y, i=i, p=layer["attn"]):
            q, k, v = _qkv(cfg, i, p, y, positions)
            return _attend_whole(cfg, i, q, k, v), (k, v)
        x, kv, load = _block(cfg, i, layer, x, attend, mask)
        kept.append(kv)
        if load is not None:
            loads.append(load)
    return _norm(cfg, x, params["ln_f"]), kept, loads


def forward(cfg, params, tokens):
    """The plain forward, no cache: (logits [b, s, vocab], the experts'
    loads as ``hidden_states`` gives them)."""
    hidden, _, loads = hidden_states(cfg, params, tokens)
    return _logits(cfg, params, hidden), loads


def prefill(cfg, params, tokens, last_index):
    """(logits [1, vocab] at ``last_index``, state) of ONE right-padded
    prompt: ``k``/``v`` [full layers, 1, s_pad, kv_heads, dh], the padded
    prefix (the length mask hides the pad), and ``k_ring``/``v_ring``
    [window layers, 1, min(s_pad, window), ...], the last ``window`` real
    tokens where they belong in the ring (``ring_of``). The pad's tokens
    are routed to no expert."""
    real = jnp.arange(tokens.shape[1])[None, :] <= last_index
    hidden, kept, _ = hidden_states(cfg, params, tokens, real)
    row = jax.lax.dynamic_index_in_dim(hidden, last_index, axis=1,
                                       keepdims=False)
    state = {}
    for name, j in (("k", 0), ("v", 1)):
        full = [kv[j] for kv, t in zip(kept, cfg.layer_types) if t == "full"]
        ring = [ring_of(cfg, kv[j], last_index)
                for kv, t in zip(kept, cfg.layer_types) if t == "window"]
        if full:
            state[name] = jnp.stack(full)
        if ring:
            state[name + "_ring"] = jnp.stack(ring)
    return _logits(cfg, params, row), state


def decode(cfg, params, tokens, positions, state, mask=None):
    """One token for every cache row at a static shape: ``tokens``,
    ``positions`` [b] as serving/decode.decode_step, ``state`` the cache's
    arrays. A full layer writes the token's K/V at ``positions`` of its
    plane and attends over ``positions + 1`` entries; a window layer writes
    at ``positions mod window`` of its ring and attends over ``min(positions
    + 1, window)``. A row outside ``mask`` parks its write (where
    ``positions`` says in a full plane, at index ``window`` of a ring),
    attends to nothing and is routed to no expert.

    Returns (logits [b, vocab], state, routed): ``routed`` int32 [2], the
    (layer, expert) pairs that a decoding row was routed to in this pass,
    summed over the expert layers, and the most assignments any one
    expert got."""
    check_served(cfg)
    rows = jnp.arange(tokens.shape[0])
    pos2 = positions[:, None]
    lengths = positions + 1
    ring_at = positions % cfg.window
    if mask is not None:
        lengths = jnp.where(mask, lengths, 0)
        ring_at = jnp.where(mask, ring_at, cfg.window)
    ring_lengths = jnp.minimum(lengths, cfg.window)
    state = dict(state)
    there = None if mask is None else mask[:, None]
    x = _embed(cfg, params, tokens[:, None])
    touched = fullest = jnp.zeros((), jnp.int32)
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        plane = cfg.plane(i)
        if cfg.layer_types[i] == "full":
            names, at, live = ("k", "v"), positions, lengths
        else:
            names, at, live = RING, ring_at, ring_lengths

        def attend(y, i=i, p=layer["attn"], names=names, at=at, live=live,
                   plane=plane):
            # write, then read: the token attends to itself, in its plane
            q, k, v = _qkv(cfg, i, p, y, pos2)
            kn, vn = names
            state[kn] = state[kn].at[plane, rows, at].set(k[:, 0])
            state[vn] = state[vn].at[plane, rows, at].set(v[:, 0])
            return decode_attention(q, state[kn], state[vn], live,
                                    layer=plane), None
        x, _, load = _block(cfg, i, layer, x, attend, there)
        if load is not None:
            touched = touched + jnp.sum(load > 0, dtype=jnp.int32)
            fullest = jnp.maximum(fullest, jnp.max(load))
    x = _norm(cfg, x, params["ln_f"])
    return _logits(cfg, params, x)[:, 0], state, \
        jnp.stack([touched, fullest])
