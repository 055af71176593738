"""Plain reference for the GLM-4.7-Flash decoder (zai-org/GLM-4.7-Flash,
``model_type`` ``glm4_moe_lite``): latent attention and a dropless
mixture of experts.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no sorting,
nothing imported from the program.  y = N(x) is an RMSNorm of eps
``rms_norm_eps`` with its own scale; no bias anywhere but the router's
selection bias.

  x = E[token]
  for l in 0..L-1:
      h = x + MLA_l(N1_l(x))
      x = h + FFN_l(N2_l(h))        FFN_0 = W_down(silu(W_gate y) * (W_up y)),
                                    width ``intermediate_size``
                                    (``first_k_dense_replace`` such layers)
                                    FFN_l>=1 = Shared_l(y) + Routed_l(y)
  logits = W_head N_f(x)

  Routed(y)  s   = sigmoid(W_r y)          float32, s in R^64
             idx = top-4 of (s + b)        b SELECTS and never weighs;
                                           n_group = topk_group = 1: the
                                           group restriction is vacuous
             w   = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
             out = sum_j w_j Expert_idx_j(y)   Expert_e = a SwiGLU of width
                                           ``moe_intermediate_size``
             every token gets all four of its experts: no capacity, no
             dropped token.  Here: a loop over the 64 experts, each applied
             to every token under that token's weight for it (0 for a
             token that did not choose it), each expert's matrices widened
             where they are used.
  Shared(y)  one more SwiGLU (``n_shared_experts`` x the expert's width)
             that every token takes with weight 1

  MLA(y)     c_q = Nq(y W_qa) in R^768;  q = c_q W_qb -> 20 heads x
             [q_nope (192) ; q_rope (64)]
             [c ; k_r] = y W_kva in R^(512+64);  c = Nkv(c);  k_r =
             RoPE(k_r), ONE rotary key shared by all heads
             [k_nope_h ; v_h] = c W_kvb -> 20 heads x (192 + 256)
             q_h = [q_nope_h ; RoPE(q_rope_h)],  k_h = [k_nope_h ; k_r]
             o_h = causal softmax(q_h k_h^T / sqrt(256)) v_h
             out = [o_1 .. o_20] W_o
             the EXPANDED form, over the whole sequence: a reference needs
             no cache, so it never absorbs W_kvb.

Departures and conventions (also under ``assumed`` in the configuration
file; there is no network here to read the published modelling code
again): the two inner norms Nq and Nkv, the bias entering the selection
only, the 1e-20, sigmoid scores in float32, the softmax scale 256^-0.5 with
no long-context factor (``rope_scaling`` null) and the rotate-half pairing
of rotary lanes are DeepSeek-V3's published modelling code, from which
``glm4_moe_lite`` takes its attention and its router, as ISSUE 42 states
them; none is a key of ``config.json``.  The multi-token-prediction block
(``num_nextn_predict_layers``) is no part of the next-token logits and is
not computed.  Depth is what the caller passes.

Weights are seeded noise (``weight_shapes`` + ``benchmarks/lib/weights.py``).
The experts are published STACKED ``[64, 2048, 1536]``: 1,152 leaves of
3.1 M elements would all join the one flat float32 draw that
``lib/weights.py`` makes of every leaf under 4.2 M elements (14.5 GB); a
stack gets a draw of its own, but the law scales it by 1 / sqrt(64 x 2048),
the stack's rows and not the expert's: eight times too small.  The
configuration's ``assumed.init`` states the exact power-of-two gain
(``expert_gain`` = sqrt(64) = 8) that this file and the adapter each apply
to the three stacks where they use them, and a second one for the
selection bias (``bias_gain`` = 1/16): a trained bias keeps the experts'
load even, a drawn one of 0.1 beside scores that spread by 0.2 makes it
uneven, and the cell's byte count rests on near-uniform routing.

Leaves arrive in the served type (bfloat16) and are widened where they are
used; no float32 copy of the model is held.  The head runs over the
vocabulary in blocks and only on the rows asked for; the experts one at a
time; attention over blocks of query positions.

``quant="int8"`` is the control of the served check, the reference itself
one precision step below the served model: every matmul's weights rounded
per output channel and activations per token to int8.  Never a result.
"""

import jax
import jax.numpy as jnp

HEAD_BLOCKS = 8      # the head's vocabulary blocks (154880 = 8 x 19360)
QUERY_BLOCK = 512    # attention's blocks of query positions


def expert_gain(cfg):
    return 2.0 ** cfg["assumed"]["init"]["expert_gain_log2"]


def bias_gain(cfg):
    return 2.0 ** cfg["assumed"]["init"]["bias_gain_log2"]


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of one model of ``layers`` layers, the first
    ``first_k_dense_replace`` of them dense.  ``*.scale`` and ``*.bias``
    follow those laws of ``lib/weights.py``, every matrix
    N(0,1)/sqrt(fan_in); the experts' three stacks are 8 times too small
    by that law (see above) and are used times ``expert_gain``."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed": (v, d)}
    for i in range(layers):
        p = f"layers.{i}."
        shapes[p + "ln_attn.scale"] = (d,)
        shapes[p + "attn.q_a"] = (d, rq)
        shapes[p + "attn.q_norm.scale"] = (rq,)
        shapes[p + "attn.q_b"] = (rq, h * (nope + rope))
        shapes[p + "attn.kv_a"] = (d, rkv + rope)
        shapes[p + "attn.kv_norm.scale"] = (rkv,)
        shapes[p + "attn.kv_b"] = (rkv, h * (nope + cfg["v_head_dim"]))
        shapes[p + "attn.o"] = (h * cfg["v_head_dim"], d)
        shapes[p + "ln_mlp.scale"] = (d,)
        if i < cfg["first_k_dense_replace"]:
            width = cfg["intermediate_size"]
            shapes[p + "mlp.gate"] = (d, width)
            shapes[p + "mlp.up"] = (d, width)
            shapes[p + "mlp.down"] = (width, d)
        else:
            shapes[p + "router.w"] = (d, e)
            shapes[p + "router.bias"] = (e,)
            shapes[p + "experts.gate"] = (e, d, f)
            shapes[p + "experts.up"] = (e, d, f)
            shapes[p + "experts.down"] = (e, f, d)
            width = cfg["n_shared_experts"] * f
            shapes[p + "shared.gate"] = (d, width)
            shapes[p + "shared.up"] = (d, width)
            shapes[p + "shared.down"] = (width, d)
    shapes["ln_f.scale"] = (d,)
    shapes["head"] = (d, v)
    return shapes


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


def rotary(x, positions, base):
    """x [s, h, dh]; rotate-half pairing (i with i + dh/2)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(y, gate, up, down, quant, gain=1.0):
    hidden = jax.nn.silu(matmul(y, gate, quant, gain)) \
        * matmul(y, up, quant, gain)
    return matmul(hidden, down, quant, gain)


# -- the block ----------------------------------------------------------------

def attention(w, p, y, cfg, quant):
    """Causal latent attention of one layer over ``y`` [s, d], expanded."""
    s = y.shape[0]
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    rkv = cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    positions = jnp.arange(s)
    c_q = rms_norm(matmul(y, w[p + "attn.q_a"], quant),
                   w[p + "attn.q_norm.scale"], eps)
    q = matmul(c_q, w[p + "attn.q_b"], quant).reshape(s, h, nope + rope)
    ckr = matmul(y, w[p + "attn.kv_a"], quant)
    c = rms_norm(ckr[:, :rkv], w[p + "attn.kv_norm.scale"], eps)
    k_r = rotary(ckr[:, None, rkv:], positions, base)          # [s, 1, rope]
    kv = matmul(c, w[p + "attn.kv_b"], quant).reshape(s, h, -1)
    q = jnp.concatenate([q[..., :nope],
                         rotary(q[..., nope:], positions, base)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (s, h, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5

    def block(start):
        """Query positions start..start + block over every key."""
        rows = start + jnp.arange(min(QUERY_BLOCK, s))
        qb = jax.lax.dynamic_slice_in_dim(q, start, len(rows), 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        scores = jnp.where(positions[None, None, :] <= rows[None, :, None],
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    size = min(QUERY_BLOCK, s)
    if s % size:
        raise ValueError(f"{s} positions in blocks of {size}")
    a = jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, -1)
    return matmul(a, w[p + "attn.o"], quant)


def routing(w, p, y, cfg, quant=None):
    """(idx [s, k] int32, weights [s, k], scores + bias [s, E]) of the
    routed experts of layer ``p`` for tokens ``y`` [s, d]."""
    k = cfg["num_experts_per_tok"]
    if cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1 or \
            cfg["topk_group"] != 1:
        raise NotImplementedError(
            "the reference states noaux_tc routing with one group")
    scores = jax.nn.sigmoid(matmul(y, w[p + "router.w"], quant))
    chosen = scores + w[p + "router.bias"].astype(jnp.float32) \
        * bias_gain(cfg)
    _, idx = jax.lax.top_k(chosen, k)
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return idx, weights * cfg["routed_scaling_factor"], chosen


def routed(w, p, y, idx, weights, cfg, quant):
    """``sum_j w_j Expert_idx_j(y)``: one expert after the other over every
    token, under the weight each token gave it (0 where it did not choose
    it).  No capacity: no token is dropped."""
    gain = expert_gain(cfg)

    def one(out, e):
        share = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)
        piece = swiglu(y, w[p + "experts.gate"][e], w[p + "experts.up"][e],
                       w[p + "experts.down"][e], quant, gain)
        return out + share[:, None] * piece, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(cfg["n_routed_experts"]))
    return out


def branches(w, i, x, cfg, quant):
    """Of layer ``i`` on the residual ``x`` [s, d]: (attention's branch,
    the shared expert's or the dense SwiGLU's, the routed experts' or
    None, (idx, weights, scores + bias) or None)."""
    p, eps = f"layers.{i}.", cfg["rms_norm_eps"]
    attended = attention(w, p, rms_norm(x, w[p + "ln_attn.scale"], eps),
                         cfg, quant)
    y = rms_norm(x + attended, w[p + "ln_mlp.scale"], eps)
    if i < cfg["first_k_dense_replace"]:
        return attended, swiglu(y, w[p + "mlp.gate"], w[p + "mlp.up"],
                                w[p + "mlp.down"], quant), None, None
    shared = swiglu(y, w[p + "shared.gate"], w[p + "shared.up"],
                    w[p + "shared.down"], quant)
    idx, weights, chosen = routing(w, p, y, cfg, quant)
    return attended, shared, routed(w, p, y, idx, weights, cfg, quant), \
        (idx, weights, chosen)


def hidden_state(w, tokens, cfg, layers, quant=None):
    """(the final norm's output [s, d] of ONE sequence ``tokens`` [s],
    [(idx, weights, scores + bias) of every expert layer])."""
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
    weights like idx, scores + bias [expert layers, s, E]) of ``tokens``."""
    _, routes = hidden_state(w, tokens, cfg, layers)
    return tuple(jnp.stack(part) for part in zip(*routes))
