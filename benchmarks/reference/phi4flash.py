"""Plain reference for the decoder-hybrid-decoder of
microsoft/Phi-4-mini-flash-reasoning (``model_type`` ``phi4flash``; SambaY
with differential attention, arXiv:2507.06607; Mamba, arXiv:2312.00752;
Differential Transformer, arXiv:2410.05258).

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no ring, no
packing of heads, no short-cut, nothing imported from the program.  Every
layer at EVERY position.  LN is a LayerNorm with scale and bias, eps
``layer_norm_eps``; no positional encoding anywhere.

  x = E[token]
  for i in 0..L-1:   x = x + Mix_i(LN(x));   x = x + W2 (silu(g) * u),
                     [g, u] = W1 LN(x)          (no bias in the SwiGLU)
  logits = E^T LN_f(x)                          (the head is the embedding)

  ``layer_kind(cfg, i)`` with H = L / 2 (``assumed.layer_plan``):
    i <= H even  mamba    i < H odd  window    i = H + 1  full
    i > H + 1    gmu (even), cross (odd)

  mamba   [x, z] = W_in y;  x = silu(conv4(x) + b_conv), depthwise, causal;
          [r, B, C] = W_x x;  dt = softplus(W_dt r + b_dt);
          A = -exp(A_log) [d_inner, d_state];  for channel c, state n:
            S_t[c,n] = exp(dt_t[c] A[c,n]) S_{t-1}[c,n] + dt_t[c] x_t[c] B_t[n]
            m_t[c]   = sum_n S_t[c,n] C_t[n] + D[c] x_t[c]
          a literal ``lax.scan`` over positions; out = W_out (m_t * silu(z_t)).
          Layer H's m (before the gate) is the MEMORY.
  gmu     W_out (m_t * silu(W_in y)), m the memory of the same token.
  window, full
          q = W_q y + b [40 heads of 64], k, v = W_kv y + b [20 of 64];
          differential head h (of 20) has q1 = q[2h], q2 = q[2h + 1]; its
          pair p = h // 2 has k1 = k[2p], k2 = k[2p + 1], v = [v[2p] |
          v[2p + 1]] (128 wide):
            a = softmax(q1 k1^T / 8 + mask) v
                - lambda softmax(q2 k2^T / 8 + mask) v     (two softmaxes)
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
            lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)
            o_h = (1 - lambda_init(i)) RMSNorm_128(a), learned scale, eps
            as LN's;  out = W_o concat(o_h) + b_o
          mask: key j is visible to query i iff j <= i, and on a window
          layer iff moreover i - j < sliding_window: an explicit mask.
  cross   the same with q = W_q y + b its own, its own lambdas and norm, k
          and v THE FULL LAYER'S, causal over the whole row.

What is no key of ``config.json`` is under ``assumed`` in the configuration
file, each with its source, and ONE function or constant here.

Weights are seeded noise (``weight_shapes`` + ``benchmarks/lib/weights.py``)
mapped to the model's leaves by ``assumed.init`` (the program's adapter
applies the same rules by its own code): ``A_log[c, n] = log(n + 1)`` and
``D = 1`` whatever was drawn (Mamba's own start: a normal draw of A_log
would make half the states grow), ``b_dt`` the inverse softplus of a
log-uniform dt in [dt.min, dt.max] from the drawn normal through its
distribution function, and one exact power-of-two gain on the embedding,
which is the head too (N(0,1) rows give logits of standard deviation
sqrt(hidden) = 51, and a token's own row in the residual answers the head
with |e|^2: every step would serve its input token again).  The four lambda vectors are the
columns of ONE leaf ``[64, 4]`` so that the law gives them 1/8 each.

Leaves arrive in the served type (bfloat16) and are widened where they are
used; no float32 copy of the model is held.  The head runs over the
vocabulary in blocks and only on the rows asked for, attention over blocks
of query positions.

``quant="int8"`` is the control of the served check, the reference itself
one precision step below the served model: every matmul's weights rounded
per output channel and activations per token to int8.  Never a result.
"""

import math

import jax
import jax.numpy as jnp

HEAD_BLOCKS = 8      # the head's vocabulary blocks (200064 = 8 x 25008)
QUERY_BLOCK = 512    # attention's blocks of query positions


def sizes(cfg):
    a = cfg["assumed"]["mamba"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, f=cfg["intermediate_size"], v=cfg["vocab_size"],
                h=h, hk=cfg["num_key_value_heads"], dh=d // h,
                di=a["expand"] * d, n=a["d_state"], k=a["d_conv"],
                r=a["dt_rank"])


def layer_kind(cfg, i):
    """``assumed.layer_plan``: Mamba every ``mb_per_layer``-th layer of
    the self-decoder (the first half, one more Mamba layer and the one full
    layer), window attention between; then gated memory units and
    cross-attention alternating."""
    half, every = cfg["num_hidden_layers"] // 2, cfg["mb_per_layer"]
    if i <= half:
        return "mamba" if i % every == 0 else "window"
    if i == half + 1:
        return "full"
    return "gmu" if i % every == 0 else "cross"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of the first ``layers`` layers (the plan is the
    whole model's).  ``*.scale`` and ``*.bias`` follow those laws of
    ``lib/weights.py``, every matrix N(0,1)/sqrt(fan_in); ``mixer.dt``,
    ``mixer.A_log`` and ``mixer.D`` are mapped by ``mixer_vectors``."""
    z = sizes(cfg)
    d, di = z["d"], z["di"]
    kv = z["hk"] * z["dh"]
    shapes = {"embed": (z["v"], d)}

    def diff(p):
        shapes[p + "attn.lambda"] = (z["dh"], 4)   # lq1, lk1, lq2, lk2
        shapes[p + "attn.subln.scale"] = (2 * z["dh"],)
        shapes[p + "attn.o"] = (d, d)
        shapes[p + "attn.o.bias"] = (d,)
    for i in range(layers):
        p = f"layers.{i}."
        kind = layer_kind(cfg, i)
        shapes[p + "ln_mix.scale"] = (d,)
        shapes[p + "ln_mix.bias"] = (d,)
        if kind == "mamba":
            shapes[p + "mixer.in_proj"] = (d, 2 * di)
            shapes[p + "mixer.conv"] = (z["k"], di)
            shapes[p + "mixer.conv.bias"] = (di,)
            shapes[p + "mixer.x_proj"] = (di, z["r"] + 2 * z["n"])
            shapes[p + "mixer.dt_proj"] = (z["r"], di)
            shapes[p + "mixer.dt"] = (di,)
            shapes[p + "mixer.A_log"] = (di, z["n"])
            shapes[p + "mixer.D"] = (di,)
            shapes[p + "mixer.out_proj"] = (di, d)
        elif kind == "gmu":
            shapes[p + "gmu.in_proj"] = (d, di)
            shapes[p + "gmu.out_proj"] = (di, d)
        elif kind == "cross":
            shapes[p + "attn.q"] = (d, d)
            shapes[p + "attn.q.bias"] = (d,)
            diff(p)
        else:
            shapes[p + "attn.qkv"] = (d, d + 2 * kv)
            shapes[p + "attn.qkv.bias"] = (d + 2 * kv,)
            diff(p)
        shapes[p + "ln_mlp.scale"] = (d,)
        shapes[p + "ln_mlp.bias"] = (d,)
        shapes[p + "mlp.gate"] = (d, z["f"])
        shapes[p + "mlp.up"] = (d, z["f"])
        shapes[p + "mlp.down"] = (z["f"], d)
    shapes["ln_f.scale"] = (d,)
    shapes["ln_f.bias"] = (d,)
    return shapes


# -- the seeded-weight rule (configuration file, ``assumed.init``) -----------

def embed_gain(cfg):
    return 2.0 ** cfg["assumed"]["init"]["embed_gain_log2"]


def gain(cfg, name):
    """The power-of-two gain of matrix ``name`` (its last dotted part)."""
    return 2.0 ** cfg["assumed"]["init"].get("gains_log2", {}).get(
        name.rpartition(".")[2], 0)


def mixer_vectors(cfg, noise_dt):
    """(A_log [d_inner, d_state], dt_bias, D) as Mamba initialises them:
    ``A_log[c, n] = log(n + 1)``, dt log-uniform in [dt.min, dt.max] with
    ``dt_bias`` its inverse softplus, D = 1."""
    init = cfg["assumed"]["init"]
    z = sizes(cfg)
    uniform = 0.5 * (1.0 + jax.lax.erf(noise_dt.astype(jnp.float32)
                                       / math.sqrt(2.0)))
    lo, hi = math.log(init["dt"]["min"]), math.log(init["dt"]["max"])
    dt = jnp.exp(lo + (hi - lo) * uniform)
    a_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, z["n"] + 1, dtype=jnp.float32)),
        (z["di"], z["n"]))
    return a_log, dt + jnp.log(-jnp.expm1(-dt)), \
        jnp.full((z["di"],), float(init["D"]), jnp.float32)


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
    y = matmul(x, w[name], quant) * gain(cfg, name)
    if name + ".bias" in w:
        y = y + w[name + ".bias"].astype(jnp.float32)
    return y


def layer_norm(w, name, x, cfg):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + cfg["layer_norm_eps"]) \
        * w[name + ".scale"].astype(jnp.float32) \
        + w[name + ".bias"].astype(jnp.float32)


# -- the mixers ---------------------------------------------------------------

def mamba(w, p, y, cfg, quant):
    """(the mixer's output [s, d], m [s, d_inner] before the gate) over
    y [s, d]."""
    z = sizes(cfg)
    s, di, n, r = y.shape[0], z["di"], z["n"], z["r"]
    xz = project(w, p + "mixer.in_proj", y, cfg, quant)
    x, gate = xz[:, :di], xz[:, di:]
    # causal depthwise convolution: tap j weighs the input k-1-j back
    taps = w[p + "mixer.conv"].astype(jnp.float32)
    padded = jnp.pad(x, ((z["k"] - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(taps[j] * padded[j:j + s] for j in range(z["k"]))
                    + w[p + "mixer.conv.bias"].astype(jnp.float32))
    rbc = project(w, p + "mixer.x_proj", x, cfg, quant)
    a_log, dt_bias, skip = mixer_vectors(cfg, w[p + "mixer.dt"])
    dt = jax.nn.softplus(
        project(w, p + "mixer.dt_proj", rbc[:, :r], cfg, quant) + dt_bias)
    b, c = rbc[:, r:r + n], rbc[:, r + n:]
    a = -jnp.exp(a_log)

    def step(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t
    _, scanned = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                              (x, dt, b, c))
    m = scanned + skip * x
    return project(w, p + "mixer.out_proj", m * jax.nn.silu(gate), cfg,
                   quant), m


def gmu(w, p, y, memory, cfg, quant):
    gate = jax.nn.silu(project(w, p + "gmu.in_proj", y, cfg, quant))
    return project(w, p + "gmu.out_proj", memory * gate, cfg, quant)


def keys_values(w, p, y, cfg, quant):
    """(q [s, 40, 64], k and v [s, 20, 64]) of a window or full layer."""
    z = sizes(cfg)
    s, d, kv = y.shape[0], z["d"], z["hk"] * z["dh"]
    qkv = project(w, p + "attn.qkv", y, cfg, quant)
    return qkv[:, :d].reshape(s, z["h"], z["dh"]), \
        qkv[:, d:d + kv].reshape(s, z["hk"], z["dh"]), \
        qkv[:, d + kv:].reshape(s, z["hk"], z["dh"])


def differential(w, i, q, k, v, cfg, quant, window=None):
    """Differential attention of layer ``i``'s queries q [s, 40, 64] over
    k, v [s, 20, 64], in its published two-softmax form, through W_o."""
    p = f"layers.{i}."
    z = sizes(cfg)
    s, dh = q.shape[0], z["dh"]
    heads, pairs = z["h"] // 2, z["hk"] // 2
    per = heads // pairs                     # differential heads a pair
    q = q.reshape(s, heads, 2, dh)
    k = jnp.repeat(k.reshape(s, pairs, 2, dh), per, axis=1)
    v = jnp.repeat(v.reshape(s, pairs, 2 * dh), per, axis=1)
    lq1, lk1, lq2, lk2 = w[p + "attn.lambda"].astype(jnp.float32).T
    init = lambda_init(i)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + init
    positions = jnp.arange(s)
    size = min(QUERY_BLOCK, s)
    if s % size:
        raise ValueError(f"{s} positions in blocks of {size}")

    def block(start):
        """Query positions start..start + size over every key, under the
        explicit mask: [size, heads, 2 dh]."""
        rows = start + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, start, size, 0)
        gap = rows[:, None] - positions[None, :]            # i - j
        visible = gap >= 0
        if window is not None:
            visible = visible & (gap < window)

        def one(j):
            scores = jnp.einsum("qhd,khd->hqk", qb[:, :, j], k[:, :, j]) \
                * dh ** -0.5
            scores = jnp.where(visible[None], scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        return one(0) - lam * one(1)
    a = jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, heads, 2 * dh)
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), -1, keepdims=True)
                          + cfg["layer_norm_eps"]) \
        * w[p + "attn.subln.scale"].astype(jnp.float32)
    return project(w, p + "attn.o", ((1.0 - init) * a).reshape(s, -1), cfg,
                   quant)


def swiglu(w, p, y, cfg, quant):
    gate = project(w, p + "mlp.gate", y, cfg, quant)
    up = project(w, p + "mlp.up", y, cfg, quant)
    return project(w, p + "mlp.down", jax.nn.silu(gate) * up, cfg, quant)


def mix(w, i, x, carried, cfg, quant):
    """(layer ``i``'s mixer branch over x [s, d] before the residual add,
    carried): ``carried`` holds what later layers read of earlier ones:
    ``memory`` [s, d_inner] and the full layer's ``k``, ``v``."""
    p = f"layers.{i}."
    kind = layer_kind(cfg, i)
    y = layer_norm(w, p + "ln_mix", x, cfg)
    if kind == "mamba":
        out, m = mamba(w, p, y, cfg, quant)
        if i == cfg["num_hidden_layers"] // 2:
            carried = dict(carried, memory=m)
        return out, carried
    if kind == "gmu":
        return gmu(w, p, y, carried["memory"], cfg, quant), carried
    if kind == "cross":
        z = sizes(cfg)
        q = project(w, p + "attn.q", y, cfg, quant) \
            .reshape(-1, z["h"], z["dh"])
        return differential(w, i, q, carried["k"], carried["v"], cfg,
                            quant), carried
    q, k, v = keys_values(w, p, y, cfg, quant)
    if kind == "full":
        carried = dict(carried, k=k, v=v)
        return differential(w, i, q, k, v, cfg, quant), carried
    return differential(w, i, q, k, v, cfg, quant,
                        window=cfg["sliding_window"]), carried


def layer(w, i, x, carried, cfg, quant):
    mixed, carried = mix(w, i, x, carried, cfg, quant)
    x = x + mixed
    p = f"layers.{i}."
    return x + swiglu(w, p, layer_norm(w, p + "ln_mlp", x, cfg), cfg,
                      quant), carried


def hidden_states(w, tokens, cfg, layers, quant=None):
    """Final-norm hidden states [s, d] of ONE sequence ``tokens`` [s]."""
    x = w["embed"][tokens].astype(jnp.float32) * embed_gain(cfg)
    carried = {}
    for i in range(layers):
        x, carried = layer(w, i, x, carried, cfg, quant)
    return layer_norm(w, "ln_f", x, cfg)


def head(w, x, cfg, quant):
    """Logits [rows, vocab] of ``x`` [rows, d] through the TIED head (the
    embedding under its gain), the vocabulary taken in blocks so that no
    float32 head is held."""
    v = w["embed"].shape[0]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    size = v // blocks

    def block(i):
        rows = jax.lax.dynamic_slice_in_dim(w["embed"], i * size, size, 0)
        return matmul(x, rows.T, quant)
    out = jax.lax.map(block, jnp.arange(blocks))   # [blocks, rows, size]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v) * embed_gain(cfg)


def logits_at(w, tokens, rows, cfg, layers, quant=None):
    """Logits [len(rows), vocab] of sequence ``tokens`` [s] at positions
    ``rows`` only."""
    x = hidden_states(w, tokens, cfg, layers, quant)
    return head(w, x[rows], cfg, quant)
