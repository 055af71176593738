"""Plain reference for the Laguna decoder (poolside/Laguna-XS.2,
``model_type`` ``laguna``): window and full attention layers with
different head counts, a per-head gate on the attention output, and a
dropless mixture of 256 narrow experts.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no ring, no
sorting, nothing imported from the program.  y = N(x) is an RMSNorm of eps
``rms_norm_eps`` with its own scale; no bias anywhere.

  x = E[token]
  for l in 0..L-1:                  h_l = num_attention_heads_per_layer[l]
      y = N1_l(x)
      q = y W_q  [h_l, 128];  k = y W_k,  v = y W_v  [8, 128]
      q, k = Rot_l(q), Rot_l(k)
      a_i = softmax over visible j of (q_i . k_j / sqrt(128)) v_j, query
            head i reading key/value head i // (h_l / 8); key j is VISIBLE
            to query i iff j <= i, and on a ``sliding_attention`` layer
            iff moreover i - j < sliding_window: an explicit [s, s] mask
      g = gate_activation(y W_g)  [h_l]: one number a head a token
      h = x + [g_1 a_1 .. g_h a_h] W_o
      x = h + FFN_l(N2_l(h))        mlp_layer_types[l] = dense: a SwiGLU of
                                    ``intermediate_size``; sparse:
                                    Shared_l(y) + Routed_l(y)
  logits = W_head N_f(x)

  Rot_l      full_attention: YaRN on the first partial_rotary_factor x 128
             = 64 lanes (``yarn_inverse_frequencies``), cos and sin times
             ``attention_factor``; the other 64 lanes as they are.
             sliding_attention: all 128 lanes, base 10000, no scaling.
             Rotate-half pairing inside the rotated lanes.
  Routed(y)  s   = router_score(W_r y)     float32, s in R^256
             idx = top-8 of s              no selection bias
             w   = s[idx] / (sum s[idx] + 1e-20) * moe_routed_scaling_factor
             out = sum_j w_j Expert_idx_j(y), the weight on the expert's
             OUTPUT; Expert_e = a SwiGLU of ``moe_intermediate_size``.
             Every token gets all eight of its experts: no capacity, no
             dropped token.  Here: a loop over the 256 experts, each
             applied to every token under that token's weight for it (0
             for a token that did not choose it).
  Shared(y)  one more SwiGLU of ``shared_expert_intermediate_size`` that
             every token takes with weight 1

Conventions that are no key of ``config.json`` (also under ``assumed`` in
the configuration file), each ONE function here so that a correction is
one line: ``gate_activation`` (sigmoid), the gate's granularity (W_g's
shape in ``weight_shapes``: per head, as the sibling ``Laguna-S-2.1``
states), ``router_score`` (sigmoid; normalised over the chosen), the
shared expert added unweighted (``shared_weight``), no q/k norm, YaRN's
factor on cos and sin as the public ``transformers`` code applies it.
Depth is what the caller passes; the per-layer lists are read at the
layer's own index.

Weights are seeded noise (``weight_shapes`` + ``benchmarks/lib/weights.py``).
The experts are STACKED ``[256, 2048, 512]``; the law scales a stack by
1 / sqrt(256 x rows), the stack's rows and not the expert's, sixteen times
too small: the configuration's ``assumed.init`` states the exact
power-of-two gain (``expert_gain`` = sqrt(256) = 16) that this file and the
adapter each apply where they use a stack.

Leaves arrive in the served type (bfloat16) and are widened where they are
used; no float32 copy of the model is held.  The head runs over the
vocabulary in blocks and only on the rows asked for; the experts one at a
time; attention over blocks of query positions.

``quant="int8"`` is the control of the served check, the reference itself
one precision step below the served model: every matmul's weights rounded
per output channel and activations per token to int8.  Never a result.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HEAD_BLOCKS = 8      # the head's vocabulary blocks (100352 = 8 x 12544)
QUERY_BLOCK = 512    # attention's blocks of query positions
ROUTER_EPSILON = 1e-20


def expert_gain(cfg):
    return 2.0 ** cfg["assumed"]["init"]["expert_gain_log2"]


def sparse(cfg, i):
    return cfg["mlp_layer_types"][i] == "sparse"


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of the first ``layers`` layers of the model.
    ``*.scale`` follows that law of ``lib/weights.py``, every matrix
    N(0,1)/sqrt(fan_in); the experts' three stacks are 16 times too small
    by that law (see above) and are used times ``expert_gain``.  The gate
    is PER HEAD: ``[hidden, heads of the layer]``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dh, hk = cfg["head_dim"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(layers):
        p = f"layers.{i}."
        h = cfg["num_attention_heads_per_layer"][i]
        shapes[p + "ln_attn.scale"] = (d,)
        shapes[p + "attn.q"] = (d, h * dh)
        shapes[p + "attn.k"] = (d, hk * dh)
        shapes[p + "attn.v"] = (d, hk * dh)
        shapes[p + "attn.gate"] = (d, h)
        shapes[p + "attn.o"] = (h * dh, d)
        shapes[p + "ln_mlp.scale"] = (d,)
        if sparse(cfg, i):
            shapes[p + "router.w"] = (d, e)
            shapes[p + "experts.gate"] = (e, d, f)
            shapes[p + "experts.up"] = (e, d, f)
            shapes[p + "experts.down"] = (e, f, d)
            width = cfg["shared_expert_intermediate_size"]
            shapes[p + "shared.gate"] = (d, width)
            shapes[p + "shared.up"] = (d, width)
            shapes[p + "shared.down"] = (width, d)
        else:
            width = cfg["intermediate_size"]
            shapes[p + "mlp.gate"] = (d, width)
            shapes[p + "mlp.up"] = (d, width)
            shapes[p + "mlp.down"] = (width, d)
    shapes["ln_f.scale"] = (d,)
    shapes["head"] = (d, v)
    return shapes


# -- the assumed conventions, one function each -------------------------------

def gate_activation(x):
    return jax.nn.sigmoid(x)


def router_score(logits):
    return jax.nn.sigmoid(logits)


def shared_weight():
    return 1.0


# -- arithmetic ---------------------------------------------------------------

def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.round(x / scale) * scale


def matmul(x, w, quant, gain=1.0):
    """``x @ (gain w)`` in float32, both operands rounded as ``quant``
    says (``gain`` a power of two: exact)."""
    w = w.astype(jnp.float32) * gain
    if quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def swiglu(y, gate, up, down, quant, gain=1.0):
    hidden = jax.nn.silu(matmul(y, gate, quant, gain)) \
        * matmul(y, up, quant, gain)
    return matmul(hidden, down, quant, gain)


def yarn_inverse_frequencies(law, dim):
    """[dim / 2] float32 inverse frequencies of the ``dim`` rotated lanes
    under ``rope_parameters.full_attention``: ``1 / theta^(2i/dim)``
    (extrapolation) where the ramp is 0, the same over ``factor``
    (interpolation) where it is 1, the ramp linear over the pairs between
    the two correction dimensions."""
    theta, factor = float(law["rope_theta"]), float(law["factor"])
    original = law["original_max_position_embeddings"]
    pairs = np.arange(dim // 2, dtype=np.float64)
    extrapolation = 1.0 / theta ** (2.0 * pairs / dim)
    interpolation = extrapolation / factor

    def correction(rotations):
        return dim * math.log(original / (rotations * 2.0 * math.pi)) \
            / (2.0 * math.log(theta))
    low = max(math.floor(correction(law["beta_fast"])), 0)
    high = min(math.ceil(correction(law["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((pairs - low) / (high - low), 0.0, 1.0)
    return (interpolation * ramp + extrapolation * (1.0 - ramp)).astype(
        np.float32)


def rotary(x, positions, law, head_dim):
    """x [s, h, head_dim] under one kind of layer's law; rotate-half
    pairing (i with i + dim/2) inside the first ``dim`` lanes."""
    dim = int(head_dim * law["partial_rotary_factor"])
    if law["rope_type"] == "yarn":
        inv_freq = yarn_inverse_frequencies(law, dim)
        gain = law["attention_factor"]
    elif law["rope_type"] == "default":
        inv_freq = (1.0 / float(law["rope_theta"]) ** (
            2.0 * np.arange(dim // 2, dtype=np.float64) / dim)).astype(
                np.float32)
        gain = 1.0
    else:
        raise NotImplementedError(f"rope_type {law['rope_type']!r}")
    ang = positions[:, None, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(ang) * gain, jnp.cos(ang) * gain
    half = dim // 2
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


# -- the block ----------------------------------------------------------------

def attention(w, i, y, cfg, quant):
    """The gated attention branch of layer ``i`` over ``y`` [s, d], before
    the residual add."""
    p = f"layers.{i}."
    s = y.shape[0]
    dh, hk = cfg["head_dim"], cfg["num_key_value_heads"]
    h = cfg["num_attention_heads_per_layer"][i]
    kind = cfg["layer_types"][i]
    law = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    positions = jnp.arange(s)
    q = rotary(matmul(y, w[p + "attn.q"], quant).reshape(s, h, dh),
               positions, law, dh)
    k = rotary(matmul(y, w[p + "attn.k"], quant).reshape(s, hk, dh),
               positions, law, dh)
    v = matmul(y, w[p + "attn.v"], quant).reshape(s, hk, dh)
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    gate = gate_activation(matmul(y, w[p + "attn.gate"], quant))   # [s, h]
    scale = dh ** -0.5
    size = min(QUERY_BLOCK, s)
    if s % size:
        raise ValueError(f"{s} positions in blocks of {size}")

    def block(start):
        """Query positions start..start + size over every key, under the
        explicit mask."""
        rows = start + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, start, size, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        gap = rows[:, None] - positions[None, :]            # i - j
        visible = gap >= 0
        if window is not None:
            visible = visible & (gap < window)
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    a = jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, h, dh)
    return matmul((a * gate[..., None]).reshape(s, h * dh),
                  w[p + "attn.o"], quant)


def routing(w, p, y, cfg, quant=None):
    """(idx [s, k] int32, weights [s, k], scores [s, E]) of the routed
    experts of layer ``p`` for tokens ``y`` [s, d]."""
    scores = router_score(matmul(y, w[p + "router.w"], quant))
    _, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True)
                         + ROUTER_EPSILON)
    return idx, weights * cfg["moe_routed_scaling_factor"], scores


def routed(w, p, y, idx, weights, cfg, quant):
    """``sum_j w_j Expert_idx_j(y)``: one expert after the other over every
    token, under the weight each token gave it (0 where it did not choose
    it), the weight on the expert's OUTPUT
    (``moe_apply_router_weight_on_input`` false).  No capacity: no token is
    dropped."""
    if cfg["moe_apply_router_weight_on_input"]:
        raise NotImplementedError("router weights on the experts' inputs")
    gain = expert_gain(cfg)

    def one(out, e):
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        piece = swiglu(y, w[p + "experts.gate"][e], w[p + "experts.up"][e],
                       w[p + "experts.down"][e], quant, gain)
        return out + share[:, None] * piece, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(cfg["num_experts"]))
    return out


def branches(w, i, x, cfg, quant):
    """Of layer ``i`` on the residual ``x`` [s, d]: (attention's branch,
    the shared expert's or the dense SwiGLU's, the routed experts' or
    None, (idx, weights, scores) or None)."""
    p, eps = f"layers.{i}.", cfg["rms_norm_eps"]
    attended = attention(w, i, rms_norm(x, w[p + "ln_attn.scale"], eps),
                         cfg, quant)
    y = rms_norm(x + attended, w[p + "ln_mlp.scale"], eps)
    if not sparse(cfg, i):
        return attended, swiglu(y, w[p + "mlp.gate"], w[p + "mlp.up"],
                                w[p + "mlp.down"], quant), None, None
    shared = shared_weight() * swiglu(
        y, w[p + "shared.gate"], w[p + "shared.up"], w[p + "shared.down"],
        quant)
    idx, weights, scores = routing(w, p, y, cfg, quant)
    return attended, shared, routed(w, p, y, idx, weights, cfg, quant), \
        (idx, weights, scores)


def hidden_state(w, tokens, cfg, layers, quant=None):
    """(the final norm's output [s, d] of ONE sequence ``tokens`` [s],
    [(idx, weights, scores) of every expert layer])."""
    x = w["embed"][tokens].astype(jnp.float32)
    routes = []
    for i in range(layers):
        attended, dense, experts, route = branches(w, i, x, cfg, quant)
        x = x + attended + dense
        if experts is not None:
            x = x + experts
            routes.append(route)
    return rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"]), routes


def head(w, x, quant):
    """Logits [rows, vocab] of ``x`` [rows, d], the vocabulary taken in
    blocks so that no float32 head is held."""
    v = w["head"].shape[1]
    blocks = HEAD_BLOCKS if v % HEAD_BLOCKS == 0 else 1
    size = v // blocks

    def block(i):
        cols = jax.lax.dynamic_slice_in_dim(w["head"], i * size, size, 1)
        return matmul(x, cols, quant)
    out = jax.lax.map(block, jnp.arange(blocks))   # [blocks, rows, size]
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)


def logits_at(w, tokens, rows, cfg, layers, quant=None):
    """Logits [len(rows), vocab] of sequence ``tokens`` [s] at positions
    ``rows`` only."""
    hidden, _ = hidden_state(w, tokens, cfg, layers, quant)
    return head(w, hidden[rows], quant)


def routes_at(w, tokens, cfg, layers):
    """For the CPU tests and the tools: (idx [expert layers, s, k],
    weights like idx, scores [expert layers, s, E]) of ``tokens``."""
    _, routes = hidden_state(w, tokens, cfg, layers)
    return tuple(jnp.stack(part) for part in zip(*routes))
