"""Plain reference for the Baichuan-7B block (baichuan-inc/Baichuan-7B).

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no
batching tricks, nothing imported from the program: token embedding,
then per layer RMSNorm -> fused W_pack (q, k, v) -> rotary positions
(rotate-half, base 10000) -> causal softmax attention -> o_proj ->
residual -> RMSNorm -> SwiGLU (gate, up, down) -> residual; a final
RMSNorm and an untied head.  No biases.  The loss is the mean next-token
cross-entropy over every position but the last of each sequence.

Departures from the published model: none in the block.  Weights are
seeded noise (``weight_shapes`` + ``benchmarks/lib/weights.py``), depth
is whatever the caller passes.

Leaves arrive in the type they are served or trained in (bfloat16 for the
serving check, float32 for training) and are widened to float32 where they
are used: the operand of ``matmul``, the gathered rows of ``embed``, the
norm gains.  bfloat16 -> float32 is exact, so no value differs from a
float32 copy of all of them, which is never held.

``quant`` selects the control of the correctness check, the reference
itself computed one precision step down (never used for a result):
``None`` is float32; ``"fp8"`` rounds every matmul operand to float8
e4m3 with a per-tensor scale (below bf16 compute, the training cells);
``"int8"`` rounds weights per output channel and activations per token to
int8 (below bf16 weights and compute, the serving cell).
"""

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6  # config.json rms_norm_eps
ROPE_BASE = 10000.0


def weight_shapes(cfg, layers):
    """Ordered {name: shape} of one model of ``layers`` layers."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {"embed": (v, d)}
    for i in range(layers):
        p = f"layers.{i}."
        shapes[p + "ln_attn.scale"] = (d,)
        shapes[p + "qkv"] = (d, 3 * d)
        shapes[p + "out"] = (d, d)
        shapes[p + "ln_mlp.scale"] = (d,)
        shapes[p + "gate"] = (d, f)
        shapes[p + "up"] = (d, f)
        shapes[p + "down"] = (f, d)
    shapes["ln_f.scale"] = (d,)
    shapes["head"] = (d, v)
    return shapes


def _ste(x, rounded):
    """Value of ``rounded``, gradient of ``x`` (straight-through)."""
    return x + jax.lax.stop_gradient(rounded - x)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return _ste(x, q)


def _int8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return _ste(x, jnp.round(x / scale) * scale)


def matmul(x, w, quant):
    """``x @ w`` in float32, both operands rounded as ``quant`` says."""
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x), _fp8(w)
    elif quant == "int8":
        x, w = _int8(x, -1), _int8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)


def rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * scale.astype(jnp.float32)


def rotary(x, positions):
    """x [b, s, h, dh]; rotate-half convention of the published model."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (ROPE_BASE ** (jnp.arange(half, dtype=jnp.float32)
                                    / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v):
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def attention(q, k, v, head_block=None):
    """Causal softmax attention, q/k/v [b, s, h, dh] -> [b, s, h, dh].
    ``head_block`` only bounds memory: heads are independent, so they are
    taken ``head_block`` at a time, each group rematerialised in the
    backward pass (float32 scores of 32 heads x 4096^2 are 2 GB)."""
    h = q.shape[2]
    if not head_block or head_block >= h:
        return _attend(q, k, v)
    groups = [t.reshape(t.shape[:2] + (h // head_block, head_block, -1))
              for t in (q, k, v)]
    groups = [jnp.moveaxis(t, 2, 0) for t in groups]
    out = jax.lax.map(lambda qkv: jax.checkpoint(_attend)(*qkv),
                      tuple(groups))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape)


def layer(w, p, x, positions, heads, quant, head_block):
    b, s, _ = x.shape
    y = rms_norm(x, w[p + "ln_attn.scale"])
    qkv = matmul(y, w[p + "qkv"], quant)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (t.reshape(b, s, heads, -1) for t in (q, k, v))
    a = attention(rotary(q, positions), rotary(k, positions), v, head_block)
    x = x + matmul(a.reshape(b, s, -1), w[p + "out"], quant)
    y = rms_norm(x, w[p + "ln_mlp.scale"])
    gate = matmul(y, w[p + "gate"], quant)
    up = matmul(y, w[p + "up"], quant)
    return x + matmul(jax.nn.silu(gate) * up, w[p + "down"], quant)


def hidden_states(w, tokens, cfg, layers, quant=None, head_block=None,
                  remat_layers=False):
    """Final-norm hidden states [b, s, d] of ``tokens`` [b, s].
    ``remat_layers`` only bounds memory: each layer is rematerialised in
    the backward pass."""
    heads = cfg["num_attention_heads"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = w["embed"][tokens].astype(jnp.float32)
    for i in range(layers):
        p = f"layers.{i}."
        lw = {k: v for k, v in w.items() if k.startswith(p)}
        fn = lambda lw, x, p=p: layer(lw, p, x, positions, heads, quant,
                                      head_block)
        x = (jax.checkpoint(fn) if remat_layers else fn)(lw, x)
    return rms_norm(x, w["ln_f.scale"])


def logits_at(w, tokens, rows, cfg, layers, quant=None):
    """Logits [len(rows), vocab] of sequence ``tokens`` [s] at positions
    ``rows`` only (the serving check reads a few hundred rows of 64000)."""
    x = hidden_states(w, tokens[None], cfg, layers, quant)[0]
    return matmul(x[rows], w["head"], quant)


def _nll_sum(w, x, targets, quant):
    logp = jax.nn.log_softmax(matmul(x, w["head"], quant), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], 1))


def batch_loss(w, batch, cfg, layers, quant=None, head_block=None,
               position_block=None, remat_layers=False):
    """Mean next-token cross-entropy of ``batch`` [b, s]; the last
    position of each row has no target.  ``position_block`` only bounds
    memory: the head and the loss are taken that many positions at a
    time, rematerialised in the backward pass."""
    x = hidden_states(w, batch, cfg, layers, quant, head_block,
                      remat_layers)[:, :-1]
    x = x.reshape(-1, x.shape[-1])
    targets = batch[:, 1:].reshape(-1)
    n = targets.shape[0]
    if not position_block or position_block >= n:
        return _nll_sum(w, x, targets, quant) / n
    pad = -n % position_block
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, position_block,
                                                x.shape[-1])
    tb = jnp.pad(targets, (0, pad)).reshape(-1, position_block)
    keep = (jnp.arange(n + pad) < n).reshape(-1, position_block)

    def block(args):
        xs, ts, ks = args
        logp = jax.nn.log_softmax(matmul(xs, w["head"], quant), axis=-1)
        nll = -jnp.take_along_axis(logp, ts[:, None], 1)[:, 0]
        return jnp.sum(jnp.where(ks, nll, 0.0))
    return jnp.sum(jax.lax.map(jax.checkpoint(block), (xb, tb, keep))) / n


def make_batch(key, traffic, cfg):
    """The step's resident batch: ``global_batch`` rows of ``seq_len``
    token ids, every row different."""
    return jax.random.randint(
        key, (traffic["global_batch"], traffic["seq_len"]), 0,
        cfg["vocab_size"], jnp.int32)
