"""A decoder-hybrid-decoder (SambaY with differential attention,
arXiv:2507.06607; benchmarks/reference/phi4flash.py is the plain reference,
equation by equation): a SELF-decoder of Mamba-1 mixers alternating with
differential attention under a window, closed by one more Mamba layer and
ONE full-attention layer, then a CROSS-decoder whose layers have no cache
of their own: gated memory units that read the last Mamba layer's output
of the same token, alternating with differential cross-attention over the
full layer's K/V.

    x = E[token]
    for i in 0..L-1:     x = x + Mix_i(LN(x));  x = x + SwiGLU(LN(x))
    logits = E^T LN_f(x)                      (LayerNorm, scale and bias)

    layer_kinds(cfg)   with H = L / 2:
      i <= H even   "mamba"   Mamba-1, arXiv:2312.00752 (ops/mamba1.py)
      i <  H odd    "window"  differential attention, causal, key j visible
                              to query i iff 0 <= i - j < window
      i == H + 1    "full"    differential attention, causal: THE plane
      i >  H + 1    "gmu" (even) and "cross" (odd)

    mamba   [x, z] = W_in y;  x = silu(conv(x) + b);  [r, B, C] = W_x x;
            dt = softplus(W_dt r + b_dt);  A = -exp(A_log);
            S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t;  m_t = S_t C_t + D x_t;
            out = W_out (m_t * silu(z_t)). Layer H's ``m`` (before the gate)
            is the MEMORY of the token.
    gmu     W_out (m_t * silu(W_in y)), ``m`` the memory OF THE SAME TOKEN:
            no state, no cache.
    diff    ``num_heads`` query heads of ``head_dim`` are num_heads / 2
            differential heads (q1, q2), ``num_kv_heads`` key/value heads
            num_kv_heads / 2 pairs (k1, k2, and v the pair's two values side
            by side); differential head h reads pair h // (heads per pair):
              a = softmax(q1 k1^T s + mask) v - lambda softmax(q2 k2^T s +
                  mask) v,          s = head_dim ** -0.5
              lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
              o = (1 - lambda_init(i)) RMSNorm(a);  out = W_o concat(o) + b_o
            No positional encoding anywhere.
    cross   the same with its own q, lambdas and norm, over the FULL
            layer's k and v.

How the differential form runs on the attention this repo has: keys are
kept as pairs ``[k1|k2]`` and values as ``[v1|v2]`` (PACKED heads of 2 x
head_dim lanes: 128 at the published 64), and a query ``[q1|0]`` (or
``[0|q2]``) against a packed head at scale ``s`` IS ``q1 k1^T s`` over the
pair's whole value. So the flash forward, ``window_attention`` and the
decode kernel compute it as two plain heads a differential head, reading
each K/V block once for its whole pair; the subtraction, the norm and the
scale come after.

What a token leaves behind, in THREE classes of serving state:

  * ``k``, ``v`` ``[1, slots, max_len, 1, pairs x lanes]``: ONE plane, the
    full layer's, read by ``readers(cfg)`` layers a step (the full layer
    and every cross layer);
  * ``k_ring``, ``v_ring`` ``[window layers, slots, window + park, 1, pairs
    x lanes]`` (``RING``; models/window_moe.py's rings);
  * ``ssm`` ``[mamba layers, slots, d_state, d_inner]`` float32 (held
    state-major: ops/mamba1.py) and ``conv`` ``[mamba layers, slots, d_conv
    - 1, d_inner]``: recurrent.

The gmu and cross layers hold nothing. A row of K/V is one run of whole
lane tiles (``[.., 1, 1280]``, not ``[.., 20, 64]``): what
``ops/flash_attention.packed_decode_attention`` reads as it lies.

A prefill runs the self-decoder over the padded prompt and the
cross-decoder for the LAST real token only (its hidden state, its memory,
every key and value of the full layer): the cross-decoder keeps no cache, so
nothing of it is owed for the other positions. ``forward`` runs every
layer at every position; the two agree at the last position.

``jax.named_scope`` names the parts in both serving programs:
``hvd.mamba1.mix``, ``hvd.gmu``, ``hvd.diff.window``, ``hvd.diff.full``,
``hvd.diff.cross``. The projections, embedding, head and SwiGLU are
models/transformer.py's own (``_dense``, ``_embed``, ``_logits``, ``_mlp``).

Not here: a training path, a mesh (refused by name), a ring under a paged
cache.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import mamba1
from ..ops import ssm as ssm_ops
from ..ops.flash_attention import (DECODE_BLOCK, flash_attention,
                                   packed_decode_attention, window_attention)
from .transformer import _dense, _embed, _logits, _mlp
from .window_moe import ring_of

#: the kinds of ``state_shapes`` that hold one entry a position, and those
#: of them that are rings of ``cfg.window`` entries
POSITIONAL = ("k", "v", "k_ring", "v_ring")
RING = ("k_ring", "v_ring")


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 32000
    num_layers: int = 8
    d_model: int = 1024
    d_ff: int = 4096
    # head_dim = d_model / num_heads; two heads are one differential head
    # on the query side, one pair on the key/value side
    num_heads: int = 16
    num_kv_heads: int = 8
    window: int = 512
    # the Mamba-1 mixer: d_inner = expand x d_model channels, each with a
    # state of d_state, dt through a bottleneck of dt_rank
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 64
    ln_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    tie_embeddings: bool = True
    logits_fp32: bool = True
    attention_impl: str = "full"

    @property
    def head_dim(self):
        return self.d_model // self.num_heads

    @property
    def lanes(self):
        """Lanes of a packed head: a pair of keys, a pair of values."""
        return 2 * self.head_dim

    @property
    def pairs(self):
        return self.num_kv_heads // 2

    @property
    def d_inner(self):
        return self.expand * self.d_model

    @property
    def memory_layer(self):
        """The Mamba layer whose output the gated memory units read."""
        return self.num_layers // 2

    @property
    def ring_len(self):
        """Entries a ring row holds (models/window_moe.py): the window and
        a place to park outside it."""
        return self.window + (DECODE_BLOCK if self.window % DECODE_BLOCK == 0
                              else 1)

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, num_layers=8, d_model=64, d_ff=128,
                    num_heads=8, num_kv_heads=4, window=8, d_state=4,
                    dt_rank=4, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def layer_kinds(cfg):
    """One of "mamba", "window", "full", "gmu", "cross" a layer."""
    half = cfg.memory_layer
    return tuple(
        ("mamba" if i % 2 == 0 else "window") if i <= half
        else "full" if i == half + 1
        else ("gmu" if i % 2 == 0 else "cross")
        for i in range(cfg.num_layers))


def planes(cfg, kind):
    return sum(k == kind for k in layer_kinds(cfg))


def plane(cfg, layer):
    """Layer ``layer``'s plane within its own kind."""
    kinds = layer_kinds(cfg)
    return sum(k == kinds[layer] for k in kinds[:layer])


def readers(cfg):
    """Layers that read the full plane in one decode step: the full layer
    itself and every cross layer."""
    return 1 + planes(cfg, "cross")


def prefill_extents(cfg, padded_len):
    """Positions a prefill of ``padded_len`` runs each decoder over, as the
    step record counts them: the self-decoder all, the cross-decoder the
    last real one."""
    return {"self_tokens": padded_len, "cross_tokens": 1}


def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def check_served(cfg):
    if cfg.num_layers < 4 or cfg.num_layers % 4:
        raise ValueError(f"num_layers={cfg.num_layers}: a self-decoder of "
                         "L/2 + 2 layers and a cross-decoder of gmu and "
                         "cross pairs want a multiple of 4")
    if cfg.d_model % cfg.num_heads or cfg.num_heads % 2 or \
            cfg.num_kv_heads % 2 or cfg.num_heads % cfg.num_kv_heads:
        raise ValueError(f"{cfg.num_heads} query heads over "
                         f"{cfg.num_kv_heads} key/value heads of a model "
                         f"{cfg.d_model} wide: pairs on both sides")
    if cfg.window < 1:
        raise ValueError(f"window={cfg.window}")


def init_params(cfg, key):
    """A seeded parameter tree: matrices N(0,1)/sqrt(fan_in), biases 0.1
    N(0,1), norm gains 1 + 0.1 N(0,1), the embedding's rows N(0,1) times 2^-k
    (about 1 / sqrt(d): the tied head then gives logits of order 1 and no
    token answers for itself), the lambda vectors 0.1 N(0,1),
    and Mamba's own start: ``A_log[n, c] = log(n + 1)``, ``dt`` log-uniform in
    [0.001, 0.1] (its inverse softplus is the bias), ``D`` 1."""
    check_served(cfg)
    d, di, f32 = cfg.d_model, cfg.d_inner, jnp.float32
    keys = iter(jax.random.split(key, 24 * cfg.num_layers + 8))

    def noise(*shape):
        return jax.random.normal(next(keys), shape, f32)

    def mat(*shape):
        return {"kernel": noise(*shape) / shape[-2] ** 0.5}

    def biased(*shape):
        return dict(mat(*shape), bias=0.1 * noise(shape[-1]))

    def norm(n):
        return {"scale": 1.0 + 0.1 * noise(n), "bias": 0.1 * noise(n)}

    def diff():
        return {"lambda": {n: 0.1 * noise(cfg.head_dim)
                           for n in ("q1", "k1", "q2", "k2")},
                "subln": {"scale": 1.0 + 0.1 * noise(cfg.lanes)},
                "out": biased(d, d)}
    # the head is the embedding: N(0,1) rows would give logits of standard
    # deviation sqrt(d), and a token's own row in the residual would answer
    # the head with |e|^2 (every step serves its input again), so the rows
    # carry the nearest power of two to 1 / sqrt(d)
    gain = 2.0 ** -round(0.5 * math.log2(d))
    params = {"embed": {"embedding": gain * noise(cfg.vocab_size, d)},
              "ln_f": norm(d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        layer = {"ln_mix": norm(d), "ln_mlp": norm(d),
                 "mlp": {"gate": mat(d, cfg.d_ff), "up": mat(d, cfg.d_ff),
                         "down": mat(cfg.d_ff, d)}}
        if kind == "mamba":
            step = jnp.exp(jax.random.uniform(
                next(keys), (di,), f32, math.log(1e-3), math.log(1e-1)))
            layer["mixer"] = {
                "in_proj": mat(d, 2 * di),
                "conv": {"kernel": noise(cfg.d_conv, di) / cfg.d_conv ** 0.5,
                         "bias": 0.1 * noise(di)},
                "x_proj": mat(di, cfg.dt_rank + 2 * cfg.d_state),
                "dt_proj": {"kernel": mat(cfg.dt_rank, di)["kernel"],
                            "bias": step + jnp.log(-jnp.expm1(-step))},
                "A_log": jnp.log(jnp.broadcast_to(
                    jnp.arange(1, cfg.d_state + 1, dtype=f32)[:, None],
                    (cfg.d_state, di))),
                "D": jnp.ones((di,), f32),
                "out_proj": mat(di, d)}
        elif kind == "gmu":
            layer["gmu"] = {"in_proj": mat(d, di), "out_proj": mat(di, d)}
        elif kind == "cross":
            layer["attn"] = dict(diff(), q=biased(d, d))
        else:
            layer["attn"] = dict(diff(), qkv=biased(
                d, d + 2 * cfg.num_kv_heads * cfg.head_dim))
        params[f"layer_{i}"] = layer
    return params


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct}: ONE plane of K and V, ``max_len`` a row; a
    ring of ``ring_len`` a window layer; the recurrent state (state-major,
    float32) and the convolution's window a Mamba layer."""
    check_served(cfg)
    row = (1, cfg.pairs * cfg.lanes)
    mamba, di = planes(cfg, "mamba"), cfg.d_inner
    out = {}
    out["k"] = out["v"] = jax.ShapeDtypeStruct(
        (1, num_slots, max_len) + row, cfg.dtype)
    out["k_ring"] = out["v_ring"] = jax.ShapeDtypeStruct(
        (planes(cfg, "window"), num_slots, cfg.ring_len) + row, cfg.dtype)
    out["ssm"] = jax.ShapeDtypeStruct(
        (mamba, num_slots, cfg.d_state, di), jnp.float32)
    out["conv"] = jax.ShapeDtypeStruct(
        (mamba, num_slots, cfg.d_conv - 1, di), cfg.dtype)
    return out


# -- the block's parts, shared by every forward -------------------------------

def _layernorm(cfg, x, p):
    """LayerNorm with scale and bias, statistics in float32."""
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + cfg.ln_eps)
            * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(cfg.dtype)


def _biased(cfg, x, p):
    return _dense(x, p["kernel"], cfg.dtype) + p["bias"].astype(cfg.dtype)


def _pack_queries(cfg, q):
    """q [.., num_heads * head_dim] -> [.., num_heads, lanes]: an even head
    (a differential head's q1) in the low lanes, an odd one (q2) in the
    high lanes, zeros in the other half."""
    q = q.reshape(q.shape[:-1] + (cfg.num_heads // 2, 2, cfg.head_dim))
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], zero], axis=-1),
                      jnp.concatenate([zero, q[..., 1, :]], axis=-1)],
                     axis=-2).reshape(q.shape[:-3] + (cfg.num_heads,
                                                      cfg.lanes))


def _qkv(cfg, p, y):
    """y [b, s, d] -> packed q [b, s, heads, lanes], k and v [b, s, 1,
    pairs x lanes]: a row of the cache as it lies."""
    qkv = _biased(cfg, y, p["qkv"])
    d, w = cfg.d_model, cfg.pairs * cfg.lanes
    row = y.shape[:2] + (1, w)
    return _pack_queries(cfg, qkv[..., :d]), \
        qkv[..., d:d + w].reshape(row), qkv[..., d + w:].reshape(row)


def _differential(cfg, i, p, attended):
    """attended [b, s, heads, lanes] (each differential head's two plain
    heads side by side) -> [b, s, d]: the subtraction under the layer's
    lambda, the norm over a head's lanes, the scale, W_o and its bias."""
    f32 = jnp.float32
    lam = {n: v.astype(f32) for n, v in p["lambda"].items()}
    init = lambda_init(i)
    lam = jnp.exp(jnp.sum(lam["q1"] * lam["k1"])) \
        - jnp.exp(jnp.sum(lam["q2"] * lam["k2"])) + init
    a = attended.astype(f32).reshape(
        attended.shape[:2] + (cfg.num_heads // 2, 2, cfg.lanes))
    a = a[..., 0, :] - lam * a[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                          + cfg.ln_eps) * p["subln"]["scale"].astype(f32)
    a = ((1.0 - init) * a).astype(cfg.dtype)
    return _biased(cfg, a.reshape(a.shape[:2] + (cfg.d_model,)), p["out"])


def _attend_whole(cfg, q, k, v, window):
    """Causal attention of packed queries q [b, s, heads, lanes] over whole
    sequences of packed k, v [b, s, 1, pairs x lanes]; under ``window`` key
    j is visible to query i iff ``0 <= i - j < window``. The kernels with
    ``attention_impl="flash"`` (the banded one reads the pairs as they lie;
    the flash forward takes equal head counts: K/V repeated for the call),
    the plain softmax otherwise."""
    b, s, h, lanes = q.shape
    k = k.reshape(b, s, cfg.pairs, lanes)
    v = v.reshape(b, s, cfg.pairs, lanes)
    rep = h // cfg.pairs
    scale = cfg.head_dim ** -0.5
    if cfg.attention_impl == "flash":
        if window is not None:
            return window_attention(q, k, v, window, scale=scale)
        return flash_attention(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2), causal=True,
                               scale=scale)
    gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = gap >= 0 if window is None else (gap >= 0) & (gap < window)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk",
                        q.reshape(b, s, cfg.pairs, rep, lanes), k,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def _mamba_in(cfg, p, y):
    """y [b, s, d] -> the convolution's input x [b, s, d_inner] (what the
    ``conv`` state keeps) and the gate z, in the model's dtype."""
    xz = _dense(y, p["in_proj"]["kernel"], cfg.dtype)
    return xz[..., :cfg.d_inner], xz[..., cfg.d_inner:]


def _mamba_rates(cfg, p, act):
    """The activated convolution's output [.., d_inner] -> dt [.., d_inner]
    float32, B and C [.., d_state]."""
    r, n = cfg.dt_rank, cfg.d_state
    rbc = _dense(act, p["x_proj"]["kernel"], cfg.dtype)
    dt = jnp.dot(rbc[..., :r], p["dt_proj"]["kernel"].astype(cfg.dtype),
                 preferred_element_type=jnp.float32)
    return jax.nn.softplus(dt + p["dt_proj"]["bias"].astype(jnp.float32)), \
        rbc[..., r:r + n], rbc[..., r + n:]


def _mamba_out(cfg, p, scanned, act, z):
    """(the mixer's output [.., d], the memory m [.., d_inner] float32):
    the skip, then the gate and out_proj."""
    f32 = jnp.float32
    m = scanned + p["D"].astype(f32) * act.astype(f32)
    gated = (m * jax.nn.silu(z.astype(f32))).astype(cfg.dtype)
    return _dense(gated, p["out_proj"]["kernel"], cfg.dtype), m


def _gmu(cfg, p, y, m):
    """The gated memory unit over y [.., d] and the memory m [.., d_inner]
    of the same tokens."""
    gate = jax.nn.silu(_dense(y, p["in_proj"]["kernel"], cfg.dtype)
                       .astype(jnp.float32))
    return _dense((m * gate).astype(cfg.dtype), p["out_proj"]["kernel"],
                  cfg.dtype)


def _close(cfg, layer, x, mixed):
    x = x + mixed
    return x + _mlp(cfg, layer, _layernorm(cfg, x, layer["ln_mlp"]))


# -- the forwards -------------------------------------------------------------

def _self_decoder(cfg, params, tokens, last_index):
    """Layers 0 .. H + 1 over right-padded ``tokens`` [b, s] whose last real
    token sits at ``last_index``: (x [b, s, d], the memory m [b, s,
    d_inner] float32, kept), ``kept`` the state each layer leaves:
    ``("mamba", ssm [b, n, d_inner], conv [b, d_conv - 1, d_inner])`` AS
    THEY STAND AFTER ``last_index``, or ``(kind, k, v)`` packed [b, s, 1,
    pairs x lanes]."""
    s = tokens.shape[1]
    real = (jnp.arange(s)[None, :] <= last_index)[..., None]
    x = _embed(cfg, params, tokens)
    kept, memory = [], None
    kinds = layer_kinds(cfg)
    for i in range(cfg.memory_layer + 2):
        layer = params[f"layer_{i}"]
        y = _layernorm(cfg, x, layer["ln_mix"])
        if kinds[i] == "mamba":
            with jax.named_scope("hvd.mamba1.mix"):
                p = layer["mixer"]
                xs, z = _mamba_in(cfg, p, y)
                # the window after the last REAL token (zeros before the
                # sequence began)
                w = cfg.d_conv - 1
                conv = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(xs, ((0, 0), (w, 0), (0, 0))), last_index + 1,
                    w, axis=1)
                act = jax.nn.silu(ssm_ops.causal_conv(
                    xs, p["conv"]["kernel"], p["conv"]["bias"])) \
                    .astype(cfg.dtype)
                dt, bs, cs = _mamba_rates(cfg, p, act)
                # dt = 0 on the pad: the scan holds its state there
                scanned, last = mamba1.selective_scan(
                    act, jnp.where(real, dt, 0.0), -jnp.exp(p["A_log"]),
                    bs, cs)
                mixed, m = _mamba_out(cfg, p, scanned, act, z)
            kept.append(("mamba", last, conv))
            if i == cfg.memory_layer:
                memory = m
        else:
            scope = "hvd.diff." + kinds[i]
            with jax.named_scope(scope):
                p = layer["attn"]
                q, k, v = _qkv(cfg, p, y)
                attended = _attend_whole(
                    cfg, q, k, v, cfg.window if kinds[i] == "window"
                    else None)
                mixed = _differential(cfg, i, p, attended)
            kept.append((kinds[i], k, v))
        x = _close(cfg, layer, x, mixed)
    return x, memory, kept


def _cross_decoder(cfg, params, x, memory, attend):
    """Layers H + 2 .. L - 1 over x [b, t, d] and the memory [b, t,
    d_inner] of the same tokens. ``attend(q)`` -> [b, t, heads, lanes] is
    how a cross layer's packed queries see the full layer's K/V."""
    kinds = layer_kinds(cfg)
    for i in range(cfg.memory_layer + 2, cfg.num_layers):
        layer = params[f"layer_{i}"]
        y = _layernorm(cfg, x, layer["ln_mix"])
        if kinds[i] == "gmu":
            with jax.named_scope("hvd.gmu"):
                mixed = _gmu(cfg, layer["gmu"], y, memory)
        else:
            with jax.named_scope("hvd.diff.cross"):
                p = layer["attn"]
                q = _pack_queries(cfg, _biased(cfg, y, p["q"]))
                mixed = _differential(cfg, i, p, attend(q))
        x = _close(cfg, layer, x, mixed)
    return _layernorm(cfg, x, params["ln_f"])


def forward(cfg, params, tokens):
    """The plain forward, no cache and no short-cut: every layer at every
    position of ``tokens`` [b, s]. Logits [b, s, vocab]."""
    check_served(cfg)
    x, memory, kept = _self_decoder(cfg, params, tokens,
                                    tokens.shape[1] - 1)
    _, k, v = kept[-1]
    hidden = _cross_decoder(
        cfg, params, x, memory,
        lambda q: _attend_whole(cfg, q, k, v, None))
    return _logits(cfg, params, hidden)


def prefill(cfg, params, tokens, last_index):
    """(logits [1, vocab] at ``last_index``, state) of ONE right-padded
    prompt: the self-decoder over the padded prompt, the cross-decoder over
    the last real token alone. ``k``/``v`` [1, 1, s_pad, 1, w] (the length
    mask hides the pad), ``k_ring``/``v_ring`` [window layers, 1, min(s_pad,
    window), 1, w] (``ring_of``), ``ssm`` and ``conv`` as they stand after
    the last real token."""
    check_served(cfg)
    x, memory, kept = _self_decoder(cfg, params, tokens, last_index)
    state = {"k": kept[-1][1][None], "v": kept[-1][2][None]}
    for name, j in (("k_ring", 1), ("v_ring", 2)):
        state[name] = jnp.stack([ring_of(cfg, kv[j], last_index)
                                 for kv in kept if kv[0] == "window"])
    for name, j in (("ssm", 1), ("conv", 2)):
        state[name] = jnp.stack([kv[j] for kv in kept if kv[0] == "mamba"])

    def last(t):
        return jax.lax.dynamic_slice_in_dim(t, last_index, 1, axis=1)
    lengths = jnp.reshape(last_index + 1, (1,))
    hidden = _cross_decoder(
        cfg, params, last(x), last(memory),
        lambda q: packed_decode_attention(
            q[:, 0], state["k"], state["v"], lengths, 0,
            cfg.head_dim ** -0.5)[:, None])
    return _logits(cfg, params, hidden[:, 0]), state


def decode(cfg, params, tokens, positions, state, mask=None):
    """One token for every cache row at a static shape: ``tokens``,
    ``positions`` [b] as serving/decode.decode_step, ``state`` the cache's
    arrays. The full layer writes the token's K/V at ``positions`` of the
    one plane, and it and every cross layer attend over ``positions + 1``
    entries of it; a window layer writes at ``positions mod window`` of its
    ring and attends over ``min(positions + 1, window)``; a Mamba layer
    advances its state and its window. A row outside ``mask`` parks its
    positional writes (where ``positions`` says in the plane, at index
    ``window`` of a ring), attends to nothing, and keeps ``ssm`` and
    ``conv`` BIT-IDENTICAL. Returns (logits [b, vocab], state)."""
    check_served(cfg)
    rows = jnp.arange(tokens.shape[0])
    lengths = positions + 1
    ring_at = positions % cfg.window
    if mask is not None:
        lengths = jnp.where(mask, lengths, 0)
        ring_at = jnp.where(mask, ring_at, cfg.window)
    ring_lengths = jnp.minimum(lengths, cfg.window)
    state = dict(state)
    scale = cfg.head_dim ** -0.5
    kinds = layer_kinds(cfg)
    x = _embed(cfg, params, tokens[:, None])
    memory = None
    for i in range(cfg.memory_layer + 2):
        layer = params[f"layer_{i}"]
        at = plane(cfg, i)
        y = _layernorm(cfg, x, layer["ln_mix"])
        if kinds[i] == "mamba":
            with jax.named_scope("hvd.mamba1.mix"):
                p = layer["mixer"]
                xs, z = _mamba_in(cfg, p, y)
                window = state["conv"][at]
                act = jax.nn.silu(ssm_ops.causal_conv(
                    xs, p["conv"]["kernel"], p["conv"]["bias"], window)) \
                    .astype(cfg.dtype)
                dt, bs, cs = _mamba_rates(cfg, p, act[:, 0])
                state["ssm"], scanned = mamba1.decode_update(
                    state["ssm"], at, act[:, 0], dt, -jnp.exp(p["A_log"]),
                    bs, cs, mask)
                slid = jnp.concatenate([window[:, 1:], xs], axis=1)
                if mask is not None:
                    slid = jnp.where(mask[:, None, None], slid, window)
                state["conv"] = state["conv"].at[at].set(slid)
                mixed, m = _mamba_out(cfg, p, scanned[:, None], act, z)
            if i == cfg.memory_layer:
                memory = m
        else:
            if kinds[i] == "window":
                names, where, live = RING, ring_at, ring_lengths
            else:
                names, where, live = ("k", "v"), positions, lengths
            with jax.named_scope("hvd.diff." + kinds[i]):
                # write, then read: the token attends to itself
                p = layer["attn"]
                q, k, v = _qkv(cfg, p, y)
                kn, vn = names
                state[kn] = state[kn].at[at, rows, where].set(k[:, 0])
                state[vn] = state[vn].at[at, rows, where].set(v[:, 0])
                attended = packed_decode_attention(
                    q[:, 0], state[kn], state[vn], live, at, scale)
                mixed = _differential(cfg, i, p, attended[:, None])
        x = _close(cfg, layer, x, mixed)
    hidden = _cross_decoder(
        cfg, params, x, memory,
        lambda q: packed_decode_attention(
            q[:, 0], state["k"], state["v"], lengths, 0, scale)[:, None])
    return _logits(cfg, params, hidden)[:, 0], state
