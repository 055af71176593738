"""Plain reference for the Ouro looped decoder (ByteDance/Ouro-2.6B).

Straightforward ``jax.numpy`` in float32: no kernels, no cache, nothing
imported from the program.  One set of L layers runs T =
``total_ut_steps`` times:

  x = E[token]
  for t in 0..T-1:                                  (the SAME L layers)
      for i in 0..L-1:
          a = x + N2_i(Attn_i(N1_i(x)))
          x = a + N4_i(W_down_i(silu(W_gate_i N3_i(a)) * (W_up_i N3_i(a))))
      x   = N_f(x)                 (closes EVERY pass and feeds the next)
      g_t = sigmoid(w_g . x + b_g)                   (exit gate, hidden -> 1)
  logits = W_head x                                  (of the LAST pass)
  exit pdf: p_t = g_t prod_{s<t}(1 - g_s) for t < T-1, p_{T-1} = the rest

  Attn_i(y)  q, k, v = y Wq_i, y Wk_i, y Wv_i (no bias), 16 heads of 128,
             rotate-half rotary of base ``rope_theta`` on q and k, causal
             softmax(q k^T / sqrt(128)) v, then Wo_i.  A forward over the
             whole sequence needs no cache: pass t of layer i attends to
             the keys and values that pass t of layer i computed, which is
             what "K/V per (pass, layer) plane" means in a server.

Every N is an RMSNorm (eps ``rms_norm_eps``) with its own scale.  At
``early_exit_threshold`` 1 the running sum of the exit pdf reaches the
threshold only at the last pass, so the logits are the last pass's.

Departures and conventions (also under ``assumed`` in the configuration
file; there is no network here to read ``modeling_ouro.py`` again): the
two norms round each branch, the final norm between passes, the gate and
the per-pass K/V are the family's published description (arXiv:2510.25741)
as ISSUE 37 states them, not keys of ``config.json``.  Depth is what the
caller passes.  Weights are seeded noise (``weight_shapes`` +
``benchmarks/lib/weights.py``), no gain applied.

Leaves arrive in the served type (bfloat16) and are widened where they are
used; no float32 copy of the model is held.  The head runs over the
vocabulary in blocks and only on the rows asked for; one sequence of 1536
positions needs no blocking over positions (float32 scores of 16 heads are
151 MB).

``quant="int8"`` is the control of the served check, the reference itself
one precision step below the served model: every matmul's weights rounded
per output channel and activations per token to int8.  Never a result.
"""

import jax
import jax.numpy as jnp

HEAD_BLOCKS = 8  # the head's vocabulary blocks (49152 = 8 x 6144)


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of one model of ``layers`` weight layers
    (which every pass shares).  ``*.scale`` and ``*.bias`` follow those
    laws of ``lib/weights.py``, every matrix N(0,1)/sqrt(fan_in)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    shapes = {"embed": (v, d)}
    for i in range(layers):
        p = f"layers.{i}."
        shapes[p + "ln_attn.scale"] = (d,)
        shapes[p + "attn.q"] = (d, hq)
        shapes[p + "attn.k"] = (d, hk)
        shapes[p + "attn.v"] = (d, hk)
        shapes[p + "attn.o"] = (hq, d)
        shapes[p + "ln_attn_out.scale"] = (d,)
        shapes[p + "ln_mlp.scale"] = (d,)
        shapes[p + "mlp.gate"] = (d, f)
        shapes[p + "mlp.up"] = (d, f)
        shapes[p + "mlp.down"] = (f, d)
        shapes[p + "ln_mlp_out.scale"] = (d,)
    shapes["ln_f.scale"] = (d,)
    shapes["exit_gate.w"] = (d, 1)
    shapes["exit_gate.bias"] = (1,)
    shapes["head"] = (d, v)
    return shapes


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


def attention(w, p, y, cfg, quant):
    """Causal attention of one layer in one pass over ``y`` [s, d]."""
    s = y.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, base = cfg["head_dim"], float(cfg["rope_theta"])
    positions = jnp.arange(s)
    q = matmul(y, w[p + "attn.q"], quant).reshape(s, hq, dh)
    k = matmul(y, w[p + "attn.k"], quant).reshape(s, hk, dh)
    v = matmul(y, w[p + "attn.v"], quant).reshape(s, hk, dh)
    q, k = rotary(q, positions, base), rotary(k, positions, base)
    k, v = jnp.repeat(k, hq // hk, axis=1), jnp.repeat(v, hq // hk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return matmul(a.reshape(s, -1), w[p + "attn.o"], quant)


def mlp(w, p, y, quant):
    gate = matmul(y, w[p + "mlp.gate"], quant)
    up = matmul(y, w[p + "mlp.up"], quant)
    return matmul(jax.nn.silu(gate) * up, w[p + "mlp.down"], quant)


def branches(w, p, x, cfg, quant):
    """(N2(Attn(N1 x)), N4(MLP(N3 a))) of layer ``p`` with a = x + the
    first: the two normalised branches, as they join the residual."""
    eps = cfg["rms_norm_eps"]
    attended = rms_norm(
        attention(w, p, rms_norm(x, w[p + "ln_attn.scale"], eps), cfg, quant),
        w[p + "ln_attn_out.scale"], eps)
    a = x + attended
    fed = rms_norm(mlp(w, p, rms_norm(a, w[p + "ln_mlp.scale"], eps), quant),
                   w[p + "ln_mlp_out.scale"], eps)
    return attended, fed


def layer(w, p, x, cfg, quant):
    attended, fed = branches(w, p, x, cfg, quant)
    return x + attended + fed


def pass_states(w, tokens, cfg, layers, quant=None):
    """Each pass's normalised hidden state [T, s, d] of ONE sequence
    ``tokens`` [s]: the same ``layers`` layers, T times."""
    x = w["embed"][tokens].astype(jnp.float32)
    out = []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(layers):
            x = layer(w, f"layers.{i}.", x, cfg, quant)
        x = rms_norm(x, w["ln_f.scale"], cfg["rms_norm_eps"])
        out.append(x)
    return jnp.stack(out)


def exit_pdf(w, hidden):
    """p_t [T, s] from each pass's hidden state [T, s, d]: the gate's
    probability of leaving after pass t, given it was reached; the last
    pass takes what the others left."""
    g = jax.nn.sigmoid(
        jnp.matmul(hidden, w["exit_gate.w"].astype(jnp.float32))[..., 0]
        + w["exit_gate.bias"].astype(jnp.float32))
    pdf, reached = [], jnp.ones_like(g[0])
    for t in range(g.shape[0] - 1):
        pdf.append(g[t] * reached)
        reached = reached * (1.0 - g[t])
    return jnp.stack(pdf + [reached])


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
    ``rows`` only: the LAST pass's (``early_exit_threshold`` 1)."""
    if cfg["early_exit_threshold"] < 1:
        raise NotImplementedError(
            "the reference states the logits of the last pass; an exit "
            "threshold below 1 mixes passes by the exit pdf")
    return head(w, pass_states(w, tokens, cfg, layers, quant)[-1][rows],
                quant)
