"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has none of this (SURVEY.md §5: 'Long-context / sequence
parallelism: Absent') — the only ring there is the ring-allreduce inside
MPI/NCCL. For the TPU build, long context is first-class: these primitives
shard the *sequence* dimension across the 'sp' mesh axis so attention over
sequences far larger than one chip's HBM runs with O(seq/sp) memory and
overlapped ICI communication.

* ``ring_attention`` — blockwise causal attention with online softmax
  (flash-attention accumulation), passing K/V blocks around the ring with
  ``lax.ppermute``. Comm volume per step is one K/V block over ICI, fully
  overlappable with the block matmul: the TPU-native analogue of the
  ring-allreduce pipelining idea the reference gets from NCCL.
* ``ulysses_attention`` — all-to-all sequence→head reshard, local full
  attention, head→sequence reshard back (DeepSpeed-Ulysses style). Cheaper
  at moderate sequence lengths; needs num_heads % sp == 0.

Both are pure jax and run inside shard_map over the 'sp' axis.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _block_attn(q, k, v, mask, scale):
    """One q-block x k-block attention with fp32 logits.

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; mask: [sq, sk] bool or None.
    Returns (scores_max [b,h,sq], exp_sums [b,h,sq], out [b,sq,h,d*fp32]).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                        # [b,h,q]
    p = jnp.exp(logits - m[..., None])                  # [b,h,q,k]
    l = jnp.sum(p, axis=-1)                             # [b,h,q]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def ring_attention(q, k, v, axis_name="sp", causal=True):
    """Blockwise ring attention over the sequence-parallel axis.

    Args:
      q, k, v: per-shard [batch, seq_local, heads, head_dim]; the global
        sequence is the concatenation of shards along the axis in rank
        order.
      axis_name: mesh axis carrying the sequence shards.
      causal: apply a causal mask in *global* positions.

    Returns per-shard attention output [batch, seq_local, heads, head_dim]
    with exact (non-approximate) softmax, accumulated in fp32.
    """
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = d ** -0.5
    q_pos = my_idx * s_loc + jnp.arange(s_loc)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        # the block currently held arrived from rank (my_idx - i) mod W
        src = (my_idx - i) % axis_size
        k_pos = src * s_loc + jnp.arange(s_loc)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        bm, bl, bo = _block_attn(q, k_cur, v_cur, mask, scale)
        # online softmax merge (flash accumulation)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(bm - m_new)
        l_new = l * alpha + bl * beta
        o_new = (o * alpha.transpose(0, 2, 1)[..., None] +
                 bo * beta.transpose(0, 2, 1)[..., None])
        # rotate K/V to the next rank; XLA overlaps this with the matmuls
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    o0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # The loop carry must have consistent varying-manual-axes types:
    # accumulators start unvarying, and k/v may be varying over fewer
    # axes than the loop body produces (ppermute adds the ring axis; q's
    # mask/merge add any other bound axes). Cast everything in the carry
    # to varying over all bound axes.
    from ..ops.collective_ops import _bound_axis_names
    axes = tuple(_bound_axis_names())

    def vary(t):
        have = getattr(getattr(t, "aval", None), "vma", frozenset())
        missing = tuple(a for a in axes if a not in have)
        return lax.pcast(t, missing, to="varying") if missing else t
    o0, m0, l0, k, v = map(vary, (o0, m0, l0, k, v))
    o, m, l, _, _ = lax.fori_loop(0, axis_size, body, (o0, m0, l0, k, v))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sp", causal=True,
                      attn_fn=None):
    """All-to-all sequence parallelism (Ulysses).

    Reshards [b, s/W, H, d] → [b, s, H/W, d] with one all-to-all, runs full
    (local) attention over the complete sequence on each rank's head slice,
    and reshards back. The alltoall primitive is the one the public API
    exposes (mpi_ops.alltoall).
    """
    axis_size = lax.axis_size(axis_name)
    h = q.shape[2]
    assert h % axis_size == 0, (
        f"num_heads {h} must divide the sp axis size {axis_size}")

    def seq_to_heads(t):
        # [b, s_loc, h, d] -> [b, s_loc*W, h/W, d]
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(t):
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = map(seq_to_heads, (q, k, v))
    if attn_fn is None:
        out = full_attention(qg, kg, vg, causal=causal)
    else:
        out = attn_fn(qg, kg, vg)
    return heads_to_seq(out.astype(q.dtype))


def _fit_block(block, s):
    from ..ops.flash_attention import fit_block
    b = fit_block(block, s)
    if s % b:
        raise ValueError(
            f"ring_flash_attention: local sequence {s} not divisible by "
            f"any block size <= {block}")
    return b


def _lse_to_bhs(lse, b, h, s):
    """Kernel lse layout [b*h, 8, s] (sublane-replicated) → [b, h, s]."""
    return lse[:, 0, :].reshape(b, h, s)


def _lse_to_kernel(lse, b, h, s):
    return jnp.broadcast_to(lse.reshape(b * h, 1, s), (b * h, 8, s))


def _pair_fwd_ref(q, k, v, causal, scale):
    """Pure-jax twin of the flash forward for one ring pair: normalized
    out + per-row lse, identical math to ops/flash_attention._flash_fwd.
    Used on non-TPU backends, where the interpret-mode kernel cannot run
    under shard_map's varying-manual-axes checking (the kernel itself is
    covered by tests/test_flash_attention.py)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    lse = m + jnp.log(l)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   (p / l[..., None]).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), lse


def _pair_bwd_ref(q, k, v, out, lse, g, causal, scale):
    """Pure-jax twin of the flash backward for one ring pair, using the
    MERGED lse (p_ij = exp(s_ij - lse_total_i) is the global softmax
    restricted to this pair — the flash recomputation identity)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    p = jnp.exp(logits - lse[..., None])                    # [b,h,q,k]
    gf = g.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", gf, out.astype(jnp.float32))
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, v.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q, block_k,
                         scale):
    from ..ops import flash_attention as fa
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    interpret = fa._auto_interpret()
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    out_run = jnp.zeros((b, s_loc, h, d), jnp.float32)
    lse_run = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    k_cur, v_cur = k, v
    # python-unrolled ring: step index i is static, so the diagonal
    # block (i == 0, the only pair needing a causal mask) picks the
    # causal kernel statically — no traced branching around pallas
    for i in range(axis_size):
        if interpret:
            o_i, lse_i = _pair_fwd_ref(q, k_cur, v_cur, causal and i == 0,
                                       scale)
        else:
            o_i, lse_i = fa._flash_fwd(q, k_cur, v_cur, causal and i == 0,
                                       block_q, block_k, False,
                                       scale=scale)
            lse_i = _lse_to_bhs(lse_i, b, h, s_loc)
        if causal and i > 0:
            # block from rank (my_idx - i) % W is fully visible iff it
            # is in the past (my_idx >= i); future blocks merge with
            # weight exp(-inf) = 0. Every row IS visible to its own
            # diagonal block (i == 0), so lse_run is finite from the
            # first merge on and the exp() weights below never see
            # (-inf) - (-inf).
            lse_i = jnp.where(my_idx >= i, lse_i, _NEG_INF)
        lse_new = jnp.logaddexp(lse_run, lse_i)
        w_run = jnp.exp(lse_run - lse_new).transpose(0, 2, 1)[..., None]
        w_i = jnp.exp(lse_i - lse_new).transpose(0, 2, 1)[..., None]
        out_run = out_run * w_run + o_i.astype(jnp.float32) * w_i
        lse_run = lse_new
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
    return out_run.astype(q.dtype), lse_run


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash_core(q, k, v, axis_name, causal, block_q, block_k,
                     scale):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q,
                                  block_k, scale)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, block_q, block_k,
                        scale):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, block_q,
                                    block_k, scale)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, block_q, block_k, scale,
                        residuals, g):
    """Second ring pass: per pair, the standard flash backward with the
    MERGED lse re-materializes that pair's probabilities exactly
    (p_ij = exp(s_ij - lse_total_i) is the global softmax restricted to
    the pair). dK/dV partials ride the ring alongside their K/V block
    and arrive home after the full rotation."""
    from ..ops import flash_attention as fa
    q, k, v, out, lse = residuals
    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    interpret = fa._auto_interpret()
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    dq = jnp.zeros((b, s_loc, h, d), jnp.float32)
    k_cur, v_cur = k, v
    dk_cur = jnp.zeros((b, s_loc, h, d), jnp.float32)
    dv_cur = jnp.zeros((b, s_loc, h, d), jnp.float32)
    for i in range(axis_size):
        # Future pairs (my_idx < i under causal) must contribute EXACT
        # zeros. Zeroing the outputs after an unmasked backward would be
        # wrong: p = exp(s - lse) uses the merged lse, which excludes
        # future blocks, so a drifting future logit can overflow exp and
        # 0 * inf = NaN would poison the step. Setting those rows' lse
        # to +big makes p underflow to exactly 0 INSIDE the kernel.
        if causal and i > 0:
            lse_i = jnp.where(my_idx >= i, lse, 1e30)
        else:
            lse_i = lse
        if interpret:
            dq_i, dk_i, dv_i = _pair_bwd_ref(q, k_cur, v_cur, out, lse_i,
                                             g, causal and i == 0, scale)
        else:
            dq_i, dk_i, dv_i = fa._flash_bwd(
                q, k_cur, v_cur, out, _lse_to_kernel(lse_i, b, h, s_loc),
                g, causal and i == 0, block_q, block_k, False,
                scale=scale)
        dq = dq + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        k_cur, v_cur, dk_cur, dv_cur = (
            lax.ppermute(t, axis_name, perm)
            for t in (k_cur, v_cur, dk_cur, dv_cur))
    return (dq.astype(q.dtype), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


_ring_flash_core.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention(q, k, v, axis_name="sp", causal=True,
                         block_q=512, block_k=512):
    """Ring attention with the Pallas flash kernel as the per-pair
    engine, forward AND backward.

    Same contract as ``ring_attention`` (per-shard [b, s_loc, h, d],
    exact softmax in global positions), but each ring step runs the
    fused kernel instead of materializing the [s_loc, s_loc] logits —
    per-step memory is O(s_loc·d) regardless of shard length, which is
    what lets a multi-chip ring extend the measured 24k single-chip
    envelope (docs/benchmarks.md) instead of re-hitting the probs
    ceiling shard by shard. Comm volume is identical to ring_attention
    forward (one K/V block per step); backward additionally rotates the
    dK/dV partials with their blocks (2× ring volume, the standard ring
    -attention backward).
    """
    from ..ops import flash_attention as fa
    b, s_loc, h, d = q.shape
    scale = d ** -0.5  # true head_dim: padding must not change softmax
    bq = _fit_block(block_q, s_loc)
    bk = _fit_block(block_k, s_loc)
    pad_d = 0 if fa._auto_interpret() else -d % 128
    if pad_d:
        pads = ((0, 0), (0, 0), (0, 0), (0, pad_d))
        q, k, v = jnp.pad(q, pads), jnp.pad(k, pads), jnp.pad(v, pads)
    out = _ring_flash_core(q, k, v, axis_name, causal, bq, bk, scale)
    return out[..., :d] if pad_d else out


def full_attention(q, k, v, causal=True):
    """Single-device reference attention (for tests and the sp=1 path)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)
