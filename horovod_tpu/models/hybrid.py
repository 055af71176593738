"""A decoder whose every layer runs a Mamba-2 mixer and grouped-query
attention IN PARALLEL on one normalised input, then a SwiGLU — the
Falcon-H1 block (docs/serving.md; benchmarks/reference/falcon_h1.py is
the plain reference, equation by equation):

    h = RMSNorm(x);  x += ssm_out * Mixer(h) + attention_out * Attn(h)
    x += MLP(RMSNorm(x))

with the family's forward multipliers (embedding, per-projection, per
mixer segment, head) as configuration. This module is the ONE definition
of the block that serving reads: ``prefill`` and ``decode`` below share
every projection, norm and multiplier and differ only in how attention
and the recurrence see the past (whole sequence, or the cache).

Serving state, declared by ``state_shapes`` and owned by
serving/kv_cache.KVCache: ``k``/``v`` with the KEY/VALUE head count (the
cache never holds the query heads), ``ssm`` (the recurrent state, float32:
an accumulator over every token of the row) and ``conv`` (the last
``conv_width - 1`` inputs of the mixer's causal convolution).

A right-padded prompt: causal masking hides the pad from attention, but
nothing hides it from a recurrence, so ``prefill`` takes the index of the
last real token and leaves the state AS IT STANDS AFTER THAT TOKEN —
``dt = 0`` past it (the scan holds its state, ops/ssm.py) and the
convolution window gathered at it — and applies the head to that one
position only ([s_pad, vocab] float32 logits are a gigabyte at this
family's vocabulary).

No training path: the scan has no custom backward and the model no flax
module (ROADMAP R2).
"""

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops import ssm as ssm_ops
from ..ops.flash_attention import decode_attention
from ..parallel import mesh as mesh_lib
from .transformer import _dispatch_attention, _rope


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 32000
    num_layers: int = 4
    d_model: int = 1024
    d_ff: int = 4096
    # grouped-query attention: query head i reads key/value head
    # i // (num_heads / num_kv_heads); head_dim is NOT d_model / num_heads
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0
    # the mixer: ssm_heads x ssm_head_dim channels, ssm_groups groups of
    # heads sharing B and C, a state of ssm_state per channel
    ssm_heads: int = 8
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_width: int = 4
    chunk: int = 128            # the prefill scan's chunk
    rms_eps: float = 1e-5
    # forward multipliers (1.0 everywhere is the plain block)
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5   # z, x, B, C, dt
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)  # gate, down
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "full"

    @property
    def d_ssm(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self):
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, num_layers=2, d_model=64, d_ff=128,
                    num_heads=4, num_kv_heads=2, head_dim=16, ssm_heads=4,
                    ssm_head_dim=8, ssm_state=16, ssm_groups=2, chunk=16,
                    max_seq_len=128)
        base.update(kw)
        return cls(**base)


def init_params(cfg, key):
    """A seeded parameter tree: matrices N(0,1)/sqrt(fan_in), norm gains
    1, ``A`` uniform in [1, 16], ``dt`` log-uniform in [0.001, 0.1] (its
    inverse softplus is the bias), ``D`` 1 — Mamba-2's own start."""
    d, dt = cfg.d_model, cfg.dtype
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 4))

    def mat(*shape):
        fan = 1
        for n in shape[:-1]:
            fan *= n
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / fan ** 0.5).astype(dt)

    def ones(n):
        return jnp.ones((n,), dt)
    params = {"embed": jax.random.normal(
        next(keys), (cfg.vocab_size, d), jnp.float32).astype(dt),
        "ln_f": ones(d), "lm_head": mat(d, cfg.vocab_size)}
    hq, hk = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    for i in range(cfg.num_layers):
        a = jax.random.uniform(next(keys), (cfg.ssm_heads,), jnp.float32,
                               1.0, 16.0)
        step = jnp.exp(jax.random.uniform(
            next(keys), (cfg.ssm_heads,), jnp.float32,
            jnp.log(1e-3), jnp.log(1e-1)))
        params[f"layer_{i}"] = {
            "ln_in": ones(d), "ln_ff": ones(d),
            "attn": {"q": mat(d, hq), "k": mat(d, hk), "v": mat(d, hk),
                     "o": mat(hq, d)},
            "mixer": {
                "in_proj": mat(d, cfg.d_ssm + cfg.conv_dim + cfg.ssm_heads),
                "conv": mat(cfg.conv_width, cfg.conv_dim),
                "conv_bias": jnp.zeros((cfg.conv_dim,), dt),
                "A_log": jnp.log(a),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "D": jnp.ones((cfg.ssm_heads,), jnp.float32),
                "norm": ones(cfg.d_ssm),
                "out_proj": mat(cfg.d_ssm, d)},
            "mlp": {"gate": mat(d, cfg.d_ff), "up": mat(d, cfg.d_ff),
                    "down": mat(cfg.d_ff, d)}}
    return params


def state_shapes(cfg, num_slots, max_len):
    """{kind: ShapeDtypeStruct} of the per-slot serving state, every kind
    ``[layers, slots, ...]``."""
    def arr(shape, dtype):
        return jax.ShapeDtypeStruct((cfg.num_layers, num_slots) + shape,
                                    dtype)
    kv = arr((max_len, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
    return {"k": kv, "v": kv,
            "ssm": arr((cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                       jnp.float32),
            "conv": arr((cfg.conv_width - 1, cfg.conv_dim), cfg.dtype)}


# -- the block's parts, shared by prefill and decode --------------------------

def _dense(x, kernel, out=None):
    y = jnp.dot(x, kernel, preferred_element_type=jnp.float32)
    return y.astype(x.dtype if out is None else out)


def _rmsnorm(x, scale, eps, dtype):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(dtype)


def _times(x, m):
    return x if m == 1.0 else x * jnp.asarray(m, x.dtype)


def _qkv(cfg, p, h, positions):
    """h [b, s, d] -> q [b, s, heads, dh], k and v [b, s, kv_heads, dh],
    q and k rotated."""
    def heads(t, n):
        return t.reshape(t.shape[:-1] + (n, cfg.head_dim))
    q = heads(_dense(_times(h, cfg.attention_in_multiplier), p["q"]),
              cfg.num_heads)
    k = heads(_times(_dense(h, p["k"]), cfg.key_multiplier),
              cfg.num_kv_heads)
    v = heads(_dense(h, p["v"]), cfg.num_kv_heads)
    return (_rope(q, positions, cfg.rope_theta),
            _rope(k, positions, cfg.rope_theta), v)


def _mixer_in(cfg, p, h):
    """h [b, s, d] -> gate z [b, s, d_ssm] float32, the convolution's
    input xBC [b, s, conv_dim] in the model's dtype (what the ``conv``
    state keeps), raw dt [b, s, heads] float32; each scaled by its
    segment's multiplier."""
    u = _dense(_times(h, cfg.ssm_in_multiplier), p["in_proj"], jnp.float32)
    mz, mx, mb, mc, mdt = cfg.ssm_multipliers
    gn = cfg.ssm_groups * cfg.ssm_state
    d_ssm = cfg.d_ssm
    z = u[..., :d_ssm] * mz
    xbc = jnp.concatenate(
        [u[..., d_ssm:2 * d_ssm] * mx,
         u[..., 2 * d_ssm:2 * d_ssm + gn] * mb,
         u[..., 2 * d_ssm + gn:d_ssm + cfg.conv_dim] * mc], axis=-1)
    return z, xbc.astype(cfg.dtype), u[..., d_ssm + cfg.conv_dim:] * mdt


def _mixer_split(cfg, xbc):
    """The activated convolution's output -> x [.., heads, head], B and C
    [.., groups, state]."""
    gn = cfg.ssm_groups * cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :cfg.d_ssm].reshape(lead + (cfg.ssm_heads,
                                             cfg.ssm_head_dim))
    b = xbc[..., cfg.d_ssm:cfg.d_ssm + gn].reshape(
        lead + (cfg.ssm_groups, cfg.ssm_state))
    c = xbc[..., cfg.d_ssm + gn:].reshape(
        lead + (cfg.ssm_groups, cfg.ssm_state))
    return x, b, c


def _mixer_out(cfg, p, y, x, z):
    """The skip, the gate, the grouped norm (gate first) and out_proj.
    y and x [.., heads, head], z [.., d_ssm]."""
    lead = z.shape[:-1]
    y = y + p["D"][:, None] * x.astype(jnp.float32)
    y = y.reshape(lead + (cfg.d_ssm,)) * jax.nn.silu(z)
    y = y.reshape(lead + (cfg.ssm_groups, cfg.d_ssm // cfg.ssm_groups))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg.rms_eps)
    y = y.reshape(lead + (cfg.d_ssm,)) * p["norm"].astype(jnp.float32)
    return _dense(y.astype(cfg.dtype), p["out_proj"])


def _mlp(cfg, p, g):
    gate = _times(_dense(g, p["gate"]), cfg.mlp_multipliers[0])
    y = _dense(jax.nn.silu(gate) * _dense(g, p["up"]), p["down"])
    return _times(y, cfg.mlp_multipliers[1])


def _close(cfg, layer, x, mixed, attended):
    """Both branches onto the residual, then the feed-forward."""
    x = x + _times(mixed, cfg.ssm_out_multiplier) \
        + _times(attended, cfg.attention_out_multiplier)
    with jax.named_scope("hvd.mlp"):
        return x + _mlp(cfg, layer["mlp"],
                        _rmsnorm(x, layer["ln_ff"], cfg.rms_eps, cfg.dtype))


def _embed(cfg, params, tokens):
    return _times(params["embed"][tokens].astype(cfg.dtype),
                  cfg.embedding_multiplier)


def _logits(cfg, params, x):
    x = _rmsnorm(x, params["ln_f"], cfg.rms_eps, cfg.dtype)
    return _dense(x, params["lm_head"], jnp.float32) \
        * cfg.lm_head_multiplier


# -- the two serving forwards -----------------------------------------------

def prefill(cfg, params, tokens, last_index):
    """Causal forward over right-padded ``tokens`` [b, s] whose last real
    token sits at ``last_index`` (a traced scalar).

    Returns (logits [b, vocab] float32 AT ``last_index``, state): ``k``,
    ``v`` [layers, b, s, kv_heads, dh] (the pad's rows are junk the
    length mask hides), ``ssm`` [layers, b, heads, head, state] and
    ``conv`` [layers, b, width-1, conv_dim] as they stand after the token
    at ``last_index``.
    """
    b, s = tokens.shape
    positions = jnp.arange(s)[None, :]
    real = (positions <= last_index)[..., None]          # [1, s, 1]
    rep = cfg.num_heads // cfg.num_kv_heads
    pad = -s % cfg.chunk
    x = _embed(cfg, params, tokens)
    state = {"k": [], "v": [], "ssm": [], "conv": []}
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        h = _rmsnorm(x, layer["ln_in"], cfg.rms_eps, cfg.dtype)
        with jax.named_scope("hvd.attn"):
            q, k, v = _qkv(cfg, layer["attn"], h, positions)
            state["k"].append(k)
            state["v"].append(v)
            # the flash kernel takes equal head counts: K/V are repeated
            # per group for this one call (5 MB a layer at 1024 tokens);
            # the cache keeps the kv_heads it was given
            attended = _dispatch_attention(
                cfg, q, jnp.repeat(k, rep, axis=2),
                jnp.repeat(v, rep, axis=2), None)
            attended = _dense(attended.reshape(b, s, -1),
                              layer["attn"]["o"])
        with jax.named_scope("hvd.mixer"):
            p = layer["mixer"]
            z, xbc, dt = _mixer_in(cfg, p, h)
            # the window after the last REAL token: inputs last_index-2..
            # last_index (zeros before the sequence began)
            w = cfg.conv_width - 1
            state["conv"].append(jax.lax.dynamic_slice_in_dim(
                jnp.pad(xbc, ((0, 0), (w, 0), (0, 0))), last_index + 1, w,
                axis=1))
            act = jax.nn.silu(ssm_ops.causal_conv(xbc, p["conv"],
                                                  p["conv_bias"]))
            xs, bs, cs = _mixer_split(cfg, act.astype(cfg.dtype))
            # dt = 0 on the pad: the scan holds its state there
            dt = jnp.where(real, jax.nn.softplus(dt + p["dt_bias"]), 0.0)
            if pad:
                xs, bs, cs, dt = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                          * (t.ndim - 2))
                                  for t in (xs, bs, cs, dt))
            y, last = ssm_ops.chunked_scan(xs, dt, -jnp.exp(p["A_log"]),
                                           bs, cs, cfg.chunk)
            state["ssm"].append(last)
            mixed = _mixer_out(cfg, p, y[:, :s], xs[:, :s], z)
        x = _close(cfg, layer, x, mixed, attended)
    row = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                       keepdims=False)
    return _logits(cfg, params, row), \
        {kind: jnp.stack(v) for kind, v in state.items()}


def decode(cfg, params, tokens, positions, state, mask=None):
    """One token for every cache row at a static shape.

    tokens, positions [b] as serving/decode.decode_step; ``state`` the
    cache's arrays (``state_shapes``); ``mask`` [b] bool, the rows this
    pass decodes. K/V of a row outside the mask are written where
    ``positions`` says (the engine parks them at max_len - 1, which the
    length mask hides); its ``ssm`` and ``conv`` are left BIT-IDENTICAL —
    a recurrent state has nowhere to park. ``mask=None`` advances every
    row (an empty slot may be written freely: a prefill overwrites every
    kind whole).

    Returns (logits [b, vocab] float32, state).
    """
    b = tokens.shape[0]
    rows = jnp.arange(b)
    pos2 = positions[:, None]
    lengths = positions + 1
    if mask is not None:  # a row outside the pass attends to nothing
        lengths = jnp.where(mask, lengths, 0)
    heads = mesh_lib.decode_head_sharding(cfg.num_kv_heads)
    kv_k, kv_v, ssm, conv = (state[n] for n in ("k", "v", "ssm", "conv"))
    x = _embed(cfg, params, tokens[:, None])
    for i in range(cfg.num_layers):
        layer = params[f"layer_{i}"]
        h = _rmsnorm(x, layer["ln_in"], cfg.rms_eps, cfg.dtype)
        with jax.named_scope("hvd.attn"):
            q, k, v = _qkv(cfg, layer["attn"], h, pos2)
            kv_k = kv_k.at[i, rows, positions].set(k[:, 0])
            kv_v = kv_v.at[i, rows, positions].set(v[:, 0])
            attended = decode_attention(q, kv_k, kv_v, lengths,
                                        head_sharding=heads, layer=i)
            attended = _dense(attended.reshape(b, 1, -1),
                              layer["attn"]["o"])
        with jax.named_scope("hvd.mixer"):
            p = layer["mixer"]
            z, xbc, dt = _mixer_in(cfg, p, h)
            window = conv[i]
            act = jax.nn.silu(ssm_ops.causal_conv(xbc, p["conv"],
                                                  p["conv_bias"], window))
            xs, bs, cs = _mixer_split(cfg, act[:, 0])
            dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"])
            ssm, y = ssm_ops.decode_update(
                ssm, i, xs, dt, -jnp.exp(p["A_log"]), bs, cs, mask)
            slid = jnp.concatenate([window[:, 1:], xbc], axis=1)
            if mask is not None:
                slid = jnp.where(mask[:, None, None], slid, window)
            conv = conv.at[i].set(slid)
            mixed = _mixer_out(cfg, p, y[:, None], xs[:, None], z)
        x = _close(cfg, layer, x, mixed, attended)
    return _logits(cfg, params, x[:, 0]), \
        {"k": kv_k, "v": kv_v, "ssm": ssm, "conv": conv}
