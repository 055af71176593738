"""Plain reference for the Falcon-H1 block (tiiuae/Falcon-H1-34B-Instruct).

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no chunks,
nothing imported from the program.  Every layer runs a Mamba-2 mixer and
grouped-query attention IN PARALLEL on one normalised input, then a SwiGLU:

  h = RMSNorm(x; ln_in)                                     (eps 1e-5)
  x <- x + ssm_out_multiplier * Mixer(h) + attention_out_multiplier * Attn(h)
  x <- x + MLP(RMSNorm(x; ln_ff))

  Attn   q = Wq (attention_in_multiplier * h), k = key_multiplier * Wk h,
         v = Wv h; query head i reads key/value head i // (heads / kv
         heads); rotary over the whole head, rotate-half pairing, base
         ``rope_theta``; causal softmax at scale head_dim^-0.5; Wo.
  Mixer  u = in_proj(ssm_in_multiplier * h), split [z | xBC | dt] and
         scaled by segment with ``ssm_multipliers`` in the order z, x, B,
         C, dt; xBC <- silu(causal depthwise conv(xBC) + bias); split x
         [heads, head], B and C [groups, state]; dt = softplus(dt +
         dt_bias), A = -exp(A_log); per head, with its group's B and C,
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
         as a literal ``lax.scan`` over positions; then the gate and the
         grouped norm (``mamba_norm_before_gate`` false): y <-
         RMSNorm_groups(y * silu(z)), variance per group of d_ssm / groups
         channels; out_proj.
  MLP    down(silu(mlp_multipliers[0] * gate(g)) * up(g)) * mlp_multipliers[1]

Embedding rows times ``embedding_multiplier``; after the last layer
RMSNorm, the untied head, logits times ``lm_head_multiplier``.  No
projection has a bias; the convolution has one.

Departures and conventions (also under ``assumed`` in the configuration
file; there is no network here to read ``modeling_falcon_h1.py`` again):
the split order of ``in_proj``, the per-segment placement of
``ssm_multipliers``, the grouped norm and the rotary pairing are the
family's convention (Mamba-2, ``transformers``), not keys of
``config.json``.  ``attention_in_multiplier`` is 1 in the published
configuration, so whether it also scales the inputs of Wk and Wv cannot be
told from a result; it scales Wq's here, as written above.
``mamba_expand`` is not used once ``mamba_d_ssm`` is given.  Depth is what
the caller passes.

Weights are seeded noise (``weight_shapes`` + ``benchmarks/lib/weights.py``)
mapped to the model's leaves by the rule the configuration file states
under ``assumed.init`` (``gain`` and ``mixer_vectors`` below; the
program's adapter applies the same rule by its own code): a power-of-two
gain on each matrix, applied here to the product (exact), and Mamba-2's
own initialisation of ``A_log``, ``dt_bias`` and ``D`` from the drawn
normal values through their distribution function.

Leaves arrive in the served type (bfloat16) and are widened where they are
used; no float32 copy of the model is held, and the head runs over the
vocabulary in blocks (a float32 head of 261120 x 5120 is 5.3 GB, which
does not fit beside 10.5 GB of leaves) and only on the rows asked for.

``quant="int8"`` is the control of the served check, the reference itself
one precision step below the served model: every matmul's weights rounded
per output channel and activations per token to int8.  Never a result.
"""

import math

import jax
import jax.numpy as jnp

HEAD_BLOCKS = 8  # the head's vocabulary blocks (261120 = 8 x 32640)


def sizes(cfg):
    heads, g, n = cfg["mamba_n_heads"], cfg["mamba_n_groups"], \
        cfg["mamba_d_state"]
    d_ssm = cfg["mamba_d_ssm"]
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                v=cfg["vocab_size"], hq=cfg["num_attention_heads"],
                hk=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                heads=heads, p=cfg["mamba_d_head"], g=g, n=n, d_ssm=d_ssm,
                conv=d_ssm + 2 * g * n, k=cfg["mamba_d_conv"])


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of one model of ``layers`` layers.  Vectors
    named ``*.scale``/``*.bias`` follow those laws of ``lib/weights.py``;
    ``mixer.A``, ``mixer.dt`` and ``mixer.D`` are drawn N(0,1) (fan-in 1)
    and mapped by ``mixer_vectors``."""
    z = sizes(cfg)
    d = z["d"]
    shapes = {"embed": (z["v"], d)}
    for i in range(layers):
        p = f"layers.{i}."
        shapes[p + "ln_in.scale"] = (d,)
        shapes[p + "attn.q"] = (d, z["hq"] * z["dh"])
        shapes[p + "attn.k"] = (d, z["hk"] * z["dh"])
        shapes[p + "attn.v"] = (d, z["hk"] * z["dh"])
        shapes[p + "attn.o"] = (z["hq"] * z["dh"], d)
        shapes[p + "mixer.in_proj"] = (d, z["d_ssm"] + z["conv"] + z["heads"])
        shapes[p + "mixer.conv"] = (z["k"], z["conv"])
        shapes[p + "mixer.conv.bias"] = (z["conv"],)
        shapes[p + "mixer.dt"] = (z["heads"],)
        shapes[p + "mixer.A"] = (z["heads"],)
        shapes[p + "mixer.D"] = (z["heads"],)
        shapes[p + "mixer.norm.scale"] = (z["d_ssm"],)
        shapes[p + "mixer.out_proj"] = (z["d_ssm"], d)
        shapes[p + "ln_ff.scale"] = (d,)
        shapes[p + "mlp.gate"] = (d, z["f"])
        shapes[p + "mlp.up"] = (d, z["f"])
        shapes[p + "mlp.down"] = (z["f"], d)
    shapes["ln_f.scale"] = (d,)
    shapes["head"] = (d, z["v"])
    return shapes


# -- the seeded-weight rule (configuration file, ``assumed.init``) -----------

def gain(cfg, name):
    """The power-of-two gain of matrix ``name`` (its last dotted part)."""
    return 2.0 ** cfg["assumed"]["init"]["gains_log2"].get(
        name.rpartition(".")[2], 0)


def _uniform(noise):
    """N(0,1) draws -> uniform in (0, 1), through the normal's
    distribution function."""
    return 0.5 * (1.0 + jax.lax.erf(noise.astype(jnp.float32)
                                    / math.sqrt(2.0)))


def mixer_vectors(cfg, noise_a, noise_dt):
    """(A_log, dt_bias, D) as Mamba-2 initialises them: A uniform in
    [A.min, A.max], dt log-uniform in [dt.min, dt.max] with ``dt_bias`` its
    inverse softplus, D = 1."""
    init = cfg["assumed"]["init"]
    a = init["A"]["min"] + (init["A"]["max"] - init["A"]["min"]) \
        * _uniform(noise_a)
    lo, hi = math.log(init["dt"]["min"]), math.log(init["dt"]["max"])
    dt = jnp.exp(lo + (hi - lo) * _uniform(noise_dt))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return jnp.log(a), dt_bias, jnp.full_like(a, init["D"])


# -- arithmetic ---------------------------------------------------------------

def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(x / scale) * scale


def matmul(x, w, quant):
    """``x @ w`` in float32, both operands rounded as ``quant`` says."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)


def project(w, name, x, cfg, quant):
    return matmul(x, w[name], quant) * gain(cfg, name)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rotary(x, positions, base):
    """x [s, h, dh]; rotate-half pairing (i with i + dh/2)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, p, h, cfg, quant):
    z = sizes(cfg)
    s = h.shape[0]
    positions = jnp.arange(s)
    q = project(w, p + "attn.q", cfg["attention_in_multiplier"] * h, cfg,
                quant).reshape(s, z["hq"], z["dh"])
    k = cfg["key_multiplier"] * project(w, p + "attn.k", h, cfg, quant)
    k = k.reshape(s, z["hk"], z["dh"])
    v = project(w, p + "attn.v", h, cfg, quant).reshape(s, z["hk"], z["dh"])
    q = rotary(q, positions, float(cfg["rope_theta"]))
    k = rotary(k, positions, float(cfg["rope_theta"]))
    rep = z["hq"] // z["hk"]  # query head i reads key/value head i // rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * z["dh"] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return project(w, p + "attn.o", a.reshape(s, -1), cfg, quant)


def mixer(w, p, h, cfg, quant):
    z = sizes(cfg)
    s = h.shape[0]
    heads, hd, g, n, d_ssm = z["heads"], z["p"], z["g"], z["n"], z["d_ssm"]
    m = cfg["ssm_multipliers"]
    u = project(w, p + "mixer.in_proj", cfg["ssm_in_multiplier"] * h, cfg,
                quant)
    gate = u[:, :d_ssm] * m[0]
    xbc = u[:, d_ssm:d_ssm + z["conv"]]
    xbc = jnp.concatenate([xbc[:, :d_ssm] * m[1],
                           xbc[:, d_ssm:d_ssm + g * n] * m[2],
                           xbc[:, d_ssm + g * n:] * m[3]], axis=-1)
    dt = u[:, d_ssm + z["conv"]:] * m[4]
    # causal depthwise convolution: tap j weighs the input k-1-j back
    taps = w[p + "mixer.conv"].astype(jnp.float32)
    padded = jnp.pad(xbc, ((z["k"] - 1, 0), (0, 0)))
    xbc = sum(taps[j] * padded[j:j + s] for j in range(z["k"])) \
        + w[p + "mixer.conv.bias"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :d_ssm].reshape(s, heads, hd)
    b = xbc[:, d_ssm:d_ssm + g * n].reshape(s, g, n)
    c = xbc[:, d_ssm + g * n:].reshape(s, g, n)
    a_log, dt_bias, skip = mixer_vectors(cfg, w[p + "mixer.A"],
                                         w[p + "mixer.dt"])
    dt = jax.nn.softplus(dt + dt_bias)
    a = -jnp.exp(a_log)
    per = heads // g  # head j reads group j // per
    b, c = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)

    def step(state, inputs):
        x_t, b_t, c_t, dt_t = inputs
        state = jnp.exp(dt_t * a)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)
    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, n), jnp.float32),
                        (x, b, c, dt))
    y = (y + skip[:, None] * x).reshape(s, d_ssm)
    y = y * jax.nn.silu(gate)
    y = y.reshape(s, g, d_ssm // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    y = y.reshape(s, d_ssm) * w[p + "mixer.norm.scale"].astype(jnp.float32)
    return project(w, p + "mixer.out_proj", y, cfg, quant)


def mlp(w, p, x, cfg, quant):
    m = cfg["mlp_multipliers"]
    gate = project(w, p + "mlp.gate", x, cfg, quant) * m[0]
    up = project(w, p + "mlp.up", x, cfg, quant)
    return project(w, p + "mlp.down", jax.nn.silu(gate) * up, cfg,
                   quant) * m[1]


def layer(w, p, x, cfg, quant):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, w[p + "ln_in.scale"], eps)
    x = x + cfg["ssm_out_multiplier"] * mixer(w, p, h, cfg, quant) \
        + cfg["attention_out_multiplier"] * attention(w, p, h, cfg, quant)
    return x + mlp(w, p, rms_norm(x, w[p + "ln_ff.scale"], eps), cfg, quant)


def hidden_states(w, tokens, cfg, layers, quant=None):
    """Final-norm hidden states [s, d] of ONE sequence ``tokens`` [s]."""
    x = w["embed"][tokens].astype(jnp.float32) * cfg["embedding_multiplier"]
    for i in range(layers):
        x = layer(w, f"layers.{i}.", x, cfg, quant)
    return rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])


def head(w, x, cfg, quant):
    """Logits [rows, vocab] of ``x`` [rows, d], the vocabulary taken in
    blocks so that no float32 head is held."""
    v = w["head"].shape[1]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    size = v // blocks

    def block(i):
        cols = jax.lax.dynamic_slice_in_dim(w["head"], i * size, size, 1)
        return matmul(x, cols, quant)
    out = jax.lax.map(block, jnp.arange(blocks))   # [blocks, rows, size]
    out = jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)
    return out * gain(cfg, "head") * cfg["lm_head_multiplier"]


def logits_at(w, tokens, rows, cfg, layers, quant=None):
    """Logits [len(rows), vocab] of sequence ``tokens`` [s] at positions
    ``rows`` only."""
    x = hidden_states(w, tokens, cfg, layers, quant)
    return head(w, x[rows], cfg, quant)
