"""Decoder-only transformer LM — the flagship long-context/distributed model.

The reference has no transformer (it predates them; SURVEY.md §5 notes
sequence parallelism is absent upstream), but BASELINE.json's configs include
a Llama-style LM, and long-context + multi-axis parallelism are first-class
requirements for the TPU build. Design is TPU-first:

  * bf16 compute, fp32 params (MXU-native mixed precision)
  * large fused matmuls (qkv in one projection; gated MLP in two)
  * static shapes, no data-dependent control flow — jit-clean
  * Megatron-style tensor parallelism expressed as GSPMD shardings:
    column-parallel qkv/ffn-in kernels on 'tp', row-parallel out/ffn-out on
    'tp' (param_specs below); XLA inserts the all-reduces on ICI
  * sequence axis shardable on 'sp' (ring attention in parallel/ring.py
    gives the O(seq) comm path for long context)
  * optional remat (jax.checkpoint) per block to trade FLOPs for HBM
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 2048
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    # jax.checkpoint policy under remat: None saves nothing (max memory
    # savings, full recompute); "dots" saves every matmul result;
    # "dots_no_batch" saves only batch-dim-free dots (projection/MLP
    # outputs — attention recomputed; the usual transformer sweet spot)
    remat_policy: Optional[str] = None
    # share the input embedding matrix with the lm_head (GPT-2 ties
    # them); saves d_model*vocab params and the separate head-matrix
    # optimizer update, and removes one [vocab, d] gradient scatter-add
    tie_embeddings: bool = False
    # fp32 logits (straight from the MXU accumulator). False keeps the
    # logits in `dtype` — halves the [B, S, vocab] HBM traffic through
    # the loss; trainer.softmax_cross_entropy still accumulates its
    # logsumexp in fp32, so only the stored logit values themselves
    # round (the usual pure-bf16-LM trade).
    logits_fp32: bool = True
    # 'full' (default), 'ring', or 'ulysses': how attention handles a
    # sequence-sharded input. ring/ulysses take effect when the model runs
    # inside shard_map with the 'sp' axis bound (parallel/ring.py); under
    # plain GSPMD jit the full path is used and XLA inserts gathers.
    attention_impl: str = "full"
    # Mixture-of-Experts: num_experts > 0 replaces the dense MLP with
    # models/moe.py's expert layer (experts shard over the 'ep' mesh axis).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    expert_capacity_factor: float = 1.25

    @classmethod
    def tiny(cls, **kw):
        return cls(vocab_size=256, num_layers=2, num_heads=4, d_model=64,
                   d_ff=256, max_seq_len=128, **kw)

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(vocab_size=50304, num_layers=12, num_heads=12,
                   d_model=768, d_ff=3072, max_seq_len=1024, **kw)

    @classmethod
    def gpt2_small_tpu(cls, **kw):
        """GPT-2-small with a TPU-native head shape: 6 heads x 128
        head_dim instead of 12 x 64. Identical parameter count, layer
        count, d_model and attention matmul FLOPs — but head_dim
        matches the TPU's 128-lane register width, so the flash kernels
        run unpadded (64-lane heads are zero-padded to 128, doubling
        every attention matmul's physical MXU work and q/k/v VMEM/HBM
        residency) and the softmax VPU traffic (prop. to heads x seq^2)
        halves. Measured on v5e at b8 s1024: 116.5k tok/s/chip vs 98.6k
        for the 12x64 shape (+18%, 0.61 vs 0.51 MFU)."""
        return cls(vocab_size=50304, num_layers=12, num_heads=6,
                   d_model=768, d_ff=3072, max_seq_len=1024, **kw)

    @classmethod
    def llama_1b(cls, **kw):
        return cls(vocab_size=32000, num_layers=16, num_heads=16,
                   d_model=2048, d_ff=8192, max_seq_len=4096, **kw)


def _active_sp_axis(tokens):
    """'sp' iff the model runs inside shard_map with the 'sp' axis bound AND
    the token array actually varies over it (i.e. the sequence is sharded,
    not merely replicated across an sp axis that happens to be in the mesh).
    Keying on real sharding rather than axis binding avoids both
    wrong-global-positions on replicated data and silent local-only
    attention on sharded data."""
    from ..ops.collective_ops import _bound_axis_names
    if "sp" not in _bound_axis_names():
        return None
    varying = getattr(getattr(tokens, "aval", None), "vma", frozenset())
    return "sp" if "sp" in varying else None


def _dispatch_attention(cfg, q, k, v, sp):
    """Pick the attention algorithm for this context. ``sp`` is the active
    sequence-sharding axis (None when the sequence is whole on this
    worker)."""
    from ..parallel import ring
    known = ("full", "ring", "ring_flash", "ulysses", "flash")
    if cfg.attention_impl not in known:
        raise ValueError(
            f"Unknown attention_impl={cfg.attention_impl!r}; "
            f"expected one of {known}.")
    if sp is not None:
        if cfg.attention_impl == "ring":
            return ring.ring_attention(q, k, v, axis_name=sp, causal=True)
        if cfg.attention_impl == "ring_flash":
            return ring.ring_flash_attention(q, k, v, axis_name=sp,
                                             causal=True)
        if cfg.attention_impl == "ulysses":
            return ring.ulysses_attention(q, k, v, axis_name=sp, causal=True)
        raise ValueError(
            "The sequence is sharded over the 'sp' mesh axis but "
            f"attention_impl={cfg.attention_impl!r} cannot attend across "
            "shards — construct the model with attention_impl='ring', "
            "'ring_flash', or 'ulysses' for sequence parallelism.")
    if cfg.attention_impl in ("flash", "ring_flash"):
        # ring_flash with the whole sequence on this worker: the flash
        # kernel IS the single-block ring
        from ..ops.flash_attention import flash_attention
        attend = functools.partial(flash_attention, causal=True)
        spec = _gspmd_attention_spec(q.shape)
        if spec is not None:
            # A pallas_call has no GSPMD partitioning rule: left bare
            # under a sharded jit, lowering for a TPU fails ("Mosaic
            # kernels cannot be automatically partitioned"). shard_map
            # over the ambient mesh hands each chip its own (batch/dp,
            # heads/tp) slice.
            # check_vma=False: the CPU tests' interpreted kernel does not
            # type-check under varying-manual-axes tracking, and the
            # specs claim nothing the check would have to prove (every
            # unnamed mesh axis sees replicated operands).
            attend = jax.shard_map(attend, in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=False)
        return attend(q, k, v)
    return ring.full_attention(q, k, v, causal=True)


def _gspmd_attention_spec(shape):
    """PartitionSpec of [b, s, h, d] attention operands under the mesh the
    enclosing jit is traced with (trainer.make_gspmd_step sets it): batch
    over 'dp', heads over 'tp' — the layout batch_spec() and _TP_RULES
    give the activations. None when there is nothing to split: no ambient
    mesh, already inside a shard_map (every axis manual), or neither axis
    larger than 1 and dividing its dimension."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return None
    b, _, h, _ = shape

    def axis(name, dim):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and dim % size == 0 else None

    dp, tp = axis("dp", b), axis("tp", h)
    if dp is None and tp is None:
        return None
    return P(dp, None, tp, None)


def _rope(x, positions, base=10000.0):
    """Rotary position embedding on ``[..., seq, heads, head_dim]`` —
    the model's native layout, no head-major transpose required.
    ``base`` is the rotary base (``rope_theta``).

    Angles are computed in fp32 (positional precision matters at long
    seq), but the rotation itself runs in x's own dtype: multiplying
    bf16 activations by fp32 sin/cos upcasts the whole tensor, and XLA
    materializes a full-size fp32 copy of q and k per layer plus the
    converts back — measured ~1.5 ms/step at b16 s1024 (round 4). In
    bf16 the rotation fuses into the surrounding elementwise ops; the
    precision is that of the bf16 activations either way.
    """
    half = x.shape[-1] // 2
    freq = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    # [..., s] -> [..., s, 1, half]: broadcast over the heads axis
    angles = positions[..., None, None].astype(jnp.float32) * freq
    sin = jnp.sin(angles).astype(x.dtype)
    cos = jnp.cos(angles).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


# -- a checkpoint's leaves through the same flax primitives: what the
# -- cached forwards (serving/decode.py and the other models' own) are built of

def _dense(x, kernel, dtype):
    return nn.Dense(kernel.shape[-1], use_bias=False,
                    dtype=dtype).apply({"params": {"kernel": kernel}}, x)


def _rmsnorm(x, scale, dtype, eps=1e-6):
    norm = nn.RMSNorm(epsilon=eps, dtype=dtype)
    return norm.apply({"params": {"scale": scale}}, x)


def _embed(cfg, params, tokens):
    return nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype).apply(
        {"params": {"embedding": params["embed"]["embedding"]}}, tokens)


def _logits(cfg, params, x):
    # same head math as TransformerLM: logits straight from the MXU
    # accumulator in acc precision, tied or separate kernel
    acc = jnp.float32 if cfg.logits_fp32 else cfg.dtype
    if cfg.tie_embeddings:
        kernel = params["embed"]["embedding"].T
    else:
        kernel = params["lm_head"]["kernel"]
    return jnp.dot(x.astype(cfg.dtype), kernel.astype(cfg.dtype),
                   preferred_element_type=acc)


def _mlp(cfg, layer, y):
    gate = _dense(y, layer["mlp"]["gate"]["kernel"], cfg.dtype)
    up = _dense(y, layer["mlp"]["up"]["kernel"], cfg.dtype)
    return _dense(nn.silu(gate) * up, layer["mlp"]["down"]["kernel"],
                  cfg.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    sp: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.num_heads
        # One fused qkv projection: a single large matmul keeps the MXU busy.
        qkv = nn.Dense(3 * cfg.d_model, use_bias=False, dtype=cfg.dtype,
                       name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(t.shape[:-1] + (cfg.num_heads, head_dim))
        q, k, v = map(heads, (q, k, v))  # [b, s, h, d]
        q = _rope(q, positions)
        k = _rope(k, positions)
        # (measured: routing the flash path through layout="bhsd" to skip
        # the kernel-side transposes is step-time neutral on v5e — XLA
        # already cancels the swapaxes/transpose pairs; see
        # docs/benchmarks.md flash-kernel lessons)
        out = _dispatch_attention(cfg, q, k, v, self.sp)
        out = out.reshape(out.shape[:2] + (cfg.d_model,))
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="out")(out)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        # Gated (SwiGLU-style) MLP: two column-parallel matmuls + one
        # row-parallel.
        gate = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                        name="gate")(x)
        up = nn.Dense(cfg.d_ff, use_bias=False, dtype=cfg.dtype,
                      name="up")(x)
        return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                        name="down")(nn.silu(gate) * up)


class Block(nn.Module):
    cfg: TransformerConfig
    sp: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        y = nn.RMSNorm(dtype=cfg.dtype, name="ln_attn")(x)
        x = x + Attention(cfg, sp=self.sp, name="attn")(y, positions)
        y = nn.RMSNorm(dtype=cfg.dtype, name="ln_mlp")(x)
        if cfg.num_experts > 0:
            from .moe import MoEMLP
            x = x + MoEMLP(cfg, name="mlp")(y)
        else:
            x = x + MLP(cfg, name="mlp")(y)
        return x


class _FP32Head(nn.Module):
    """lm_head emitting logits straight from the MXU accumulator in
    ``acc`` precision (fp32 avoids an extra [B, S, vocab] cast buffer a
    bf16-matmul + astype would materialize). Same param path/shape/init
    as the nn.Dense it replaces (``lm_head/kernel``) — checkpoints are
    interchangeable."""
    vocab_size: int
    dtype: jnp.dtype
    acc: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.vocab_size))
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype),
                       preferred_element_type=self.acc)


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        """Logits [B, S, vocab]; with ``return_hidden=True``, the final-norm
        hidden states [B, S, d_model] instead — the pre-head activations the
        chunked-vocab loss consumes without materializing the logits."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                         dtype=cfg.dtype, name="embed")
        x = embed(tokens)
        s_loc = tokens.shape[1]
        sp = _active_sp_axis(tokens)
        if sp is not None:
            # sequence-sharded input: positions are global
            offset = jax.lax.axis_index(sp) * s_loc
        else:
            offset = 0
        positions = (offset + jnp.arange(s_loc))[None, :]
        block = Block
        if cfg.remat:
            policies = {
                None: None,
                "dots": jax.checkpoint_policies.dots_saveable,
                "dots_no_batch":
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            }
            if cfg.remat_policy not in policies:
                raise ValueError(
                    f"remat_policy={cfg.remat_policy!r}: expected one of "
                    f"{sorted(k or 'None' for k in policies)}")
            block = nn.remat(Block, static_argnums=(),
                             policy=policies[cfg.remat_policy])
        for i in range(cfg.num_layers):
            x = block(cfg, sp=sp, name=f"layer_{i}")(x, positions)
        x = nn.RMSNorm(dtype=cfg.dtype, name="ln_f")(x)
        if return_hidden:
            # head params (lm_head, or the tied embedding) still exist:
            # init() runs the default path
            return x
        # fp32 logits come straight out of the MXU accumulator
        # (preferred_element_type) — an .astype(float32) after a bf16
        # matmul would materialize BOTH the bf16 and the fp32
        # [B, S, vocab] buffers (~2.5 GB extra HBM traffic at GPT-2
        # scale; measured ~3.8 ms/step on v5e).
        acc = jnp.float32 if cfg.logits_fp32 else cfg.dtype
        if cfg.tie_embeddings:
            return jnp.dot(x.astype(cfg.dtype),
                           embed.embedding.T.astype(cfg.dtype),
                           preferred_element_type=acc)
        return _FP32Head(cfg.vocab_size, cfg.dtype, acc,
                         name="lm_head")(x)


# ---------------------------------------------------------------------------
# Sharding rules: Megatron-style TP expressed as GSPMD PartitionSpecs.
# ---------------------------------------------------------------------------

_TP_RULES = (
    # (path suffix, spec) — first match wins.
    (("attn", "qkv", "kernel"), P(None, "tp")),      # column parallel
    (("attn", "out", "kernel"), P("tp", None)),      # row parallel
    (("mlp", "gate", "kernel"), P(None, "tp")),
    (("mlp", "up", "kernel"), P(None, "tp")),
    (("mlp", "down", "kernel"), P("tp", None)),
    # MoE expert stacks: experts over 'ep', ffn dim over 'tp'
    (("mlp", "w_gate"), P("ep", None, "tp")),
    (("mlp", "w_up"), P("ep", None, "tp")),
    (("mlp", "w_down"), P("ep", "tp", None)),
    (("mlp", "router", "kernel"), P()),
    (("lm_head", "kernel"), P(None, "tp")),          # vocab-sharded head
    (("embed", "embedding"), P(None, None)),
)


def param_specs(params):
    """PartitionSpec pytree for tensor-parallel parameter placement.

    Unmatched leaves are replicated. Feed to
    jax.jit(in_shardings=...)/NamedSharding over a mesh with a 'tp' axis.

    Tied-embedding models (no ``lm_head`` in the tree) shard the
    embedding over 'tp' on the VOCAB axis, so it keeps playing the
    vocab-sharded-head role the separate lm_head rule encodes — without
    it, a tp mesh would materialize the full [B, S, vocab] fp32 logits
    on every shard. GSPMD handles the token-id gather against the
    vocab-sharded table on the input side.
    """
    tied = "lm_head" not in params

    def spec_for(path, leaf):
        names = tuple(str(getattr(p, "key", getattr(p, "name", p)))
                      for p in path)
        if tied and names[-2:] == ("embed", "embedding"):
            return P("tp", None)
        for suffix, spec in _TP_RULES:
            if names[-len(suffix):] == suffix:
                return spec
        return P()
    return jax.tree_util.tree_map_with_path(spec_for, params)


def batch_spec(sp=False):
    """Activation sharding for [batch, seq] token arrays: batch over 'dp',
    sequence over 'sp' when sequence parallelism is on."""
    return P("dp", "sp" if sp else None)


def chunked_softmax_cross_entropy(hidden, head_kernel, targets,
                                  chunk=8192, weights=None):
    """Mean next-token cross entropy WITHOUT materializing the
    [B, S, vocab] logits: a ``lax.scan`` over vocab chunks of the lm_head
    matmul with an online (running max + sum-exp) logsumexp, rematerialized
    in the backward pass.

    Why: for GPT-2-small at batch 8 × seq 1024 the fp32 logits alone are
    ~1.6 GB of HBM — often THE activation-memory ceiling of an LM step.
    Chunking caps the live logits at [B, S, chunk] for ~2× extra head
    FLOPs (a few % of the step), the standard memory/FLOPs trade on TPU
    (HBM is the bottleneck, SURVEY.md §7 hard parts).

    ``hidden`` [B, S, D] (any dtype), ``head_kernel`` [D, V],
    ``targets`` [B, S] int ids.
    """
    d, v = head_kernel.shape
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    chunk = min(chunk, v)
    n = -(-v // chunk)
    pad = n * chunk - v
    if pad:
        head_kernel = jnp.pad(head_kernel, ((0, 0), (0, pad)))
    kc = jnp.moveaxis(
        head_kernel.reshape(d, n, chunk), 1, 0)  # [n, D, chunk]

    def body(carry, xs):
        m, s, tgt_logit = carry
        k_i, idx0 = xs
        logits = jnp.einsum("bsd,dc->bsc", hidden,
                            k_i.astype(hidden.dtype)).astype(jnp.float32)
        col = idx0 + jnp.arange(chunk)
        logits = jnp.where(col[None, None, :] < v, logits, -jnp.inf)
        new_m = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = (s * jnp.exp(m - new_m)
             + jnp.sum(jnp.exp(logits - new_m[..., None]), axis=-1))
        in_chunk = (targets >= idx0) & (targets < idx0 + chunk)
        loc = jnp.clip(targets - idx0, 0, chunk - 1)
        t = jnp.take_along_axis(logits, loc[..., None], axis=-1)[..., 0]
        tgt_logit = jnp.where(in_chunk, t, tgt_logit)
        return (new_m, s, tgt_logit), None

    init = (jnp.full(targets.shape, -jnp.inf, jnp.float32),
            jnp.zeros(targets.shape, jnp.float32),
            jnp.zeros(targets.shape, jnp.float32))
    # remat: the scan's VJP would otherwise save every chunk's logits —
    # the exact buffer this function exists to avoid. prevent_cse=False is
    # the documented form for checkpoint-under-scan (no optimization
    # barriers needed there).
    (m, s, tgt_logit), _ = lax.scan(
        jax.checkpoint(body, prevent_cse=False), init,
        (kc, jnp.arange(n, dtype=jnp.int32) * chunk))
    nll = m + jnp.log(s) - tgt_logit
    if weights is None:
        return jnp.mean(nll)
    weights = weights.astype(nll.dtype)
    return jnp.sum(nll * weights) / jnp.sum(weights)


def lm_loss_fn(model, aux_weight=0.01, vocab_chunk=0):
    """Next-token loss for TransformerLM that automatically includes the
    MoE load-balance auxiliary loss when cfg.num_experts > 0.

    Use this (or replicate its mutable=['losses'] plumbing) for MoE
    configs: a plain ``model.apply`` without the mutable collection
    silently discards the sown aux loss and the router trains with no
    load-balancing pressure.

    ``vocab_chunk > 0`` computes the cross entropy blockwise over the
    vocab (chunked_softmax_cross_entropy) instead of materializing the
    full logits — the memory-bound large-batch/long-seq configuration.
    Best with pure data parallelism; under tp the head kernel is
    vocab-sharded and the chunking reshape forces a gather.
    """
    from .. import trainer as trainer_mod

    def head_kernel(params):
        """[d_model, vocab] head matrix for the chunked-CE path —
        the tied embedding transposed, or the separate lm_head."""
        if model.cfg.tie_embeddings:
            return params["embed"]["embedding"].T
        return params["lm_head"]["kernel"]

    def loss_fn(params, tokens):
        # Full-length inputs keep the sequence dim tile-aligned: a 1024
        # sequence runs every matmul at 1024, where the classic
        # inputs[:-1]/targets[1:] split runs at 1023 and XLA pads each
        # (8, 128) tile (~8% step time on v5e, see docs/benchmarks.md).
        # The final position gets a rolled dummy target with zero
        # weight; causal masking makes the other positions' outputs
        # independent of the extra input token, so for dense configs the
        # loss is identical to the shifted split. For MoE the router's
        # load-balance statistics intentionally include the final token
        # (it is a real token — only its CE target is unknowable here).
        inputs = tokens
        targets = jnp.roll(tokens, -1, axis=1)
        weights = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        if model.cfg.num_experts > 0:
            from .moe import aux_loss_from
            if vocab_chunk:
                hidden, mut = model.apply({"params": params}, inputs,
                                          return_hidden=True,
                                          mutable=["losses"])
                ce = chunked_softmax_cross_entropy(
                    hidden, head_kernel(params), targets,
                    chunk=vocab_chunk, weights=weights)
            else:
                logits, mut = model.apply({"params": params}, inputs,
                                          mutable=["losses"])
                ce = trainer_mod.softmax_cross_entropy(logits, targets,
                                                       weights)
            return ce + aux_loss_from(mut, weight=aux_weight)
        if vocab_chunk:
            hidden = model.apply({"params": params}, inputs,
                                 return_hidden=True)
            return chunked_softmax_cross_entropy(
                hidden, head_kernel(params), targets,
                chunk=vocab_chunk, weights=weights)
        logits = model.apply({"params": params}, inputs)
        return trainer_mod.softmax_cross_entropy(logits, targets, weights)
    return loss_fn


def matmul_flops_per_token(cfg, seq):
    """Matmul FLOPs per token, PaLM appendix-B convention:
    ``6·P_matmul + 12·L·seq·d_model``. P_matmul counts qkv+out
    projections (4·d²), the gated SwiGLU MLP (THREE d×d_ff kernels:
    gate/up/down — MLP above), and the lm_head. Head-count independent,
    so MFU numbers are comparable across head shapes (gpt2_small vs
    gpt2_small_tpu)."""
    p_matmul = (cfg.num_layers * (4 * cfg.d_model ** 2 +
                                  3 * cfg.d_model * cfg.d_ff) +
                cfg.d_model * cfg.vocab_size)
    return 6 * p_matmul + 12 * cfg.num_layers * seq * cfg.d_model


def init_params(cfg, rng, batch_size=2, seq_len=None):
    model = TransformerLM(cfg)
    seq_len = seq_len or min(cfg.max_seq_len, 128)
    tokens = jnp.zeros((batch_size, seq_len), jnp.int32)
    return model, model.init(rng, tokens)["params"]
