"""Fused (flash) attention as a Pallas TPU kernel — forward and backward.

The hot op of the flagship transformer. XLA's default attention
materializes the [s, s] logits in HBM; this kernel walks block_k-sized
tiles of K/V, maintaining an online-softmax accumulator — HBM traffic is
O(s·d):

  * logits tiles computed with ``jnp.dot(..., preferred_element_type=
    fp32)`` → MXU at full precision for the softmax math
  * block sizes default to 512 (measured fastest on v5e; see
    ``call_block``); the lane dim is head_dim
  * causal masking per tile from broadcasted iotas, and the K-block loop
    stops at the diagonal (dynamic fori bound), skipping the ~half of
    tiles that are fully in the future
  * the forward holds one head's whole K and V in VMEM where they fit
    (``kv_resident``: the pipeline fetches the next head's behind this
    one's tiles); past that, and in the backward, K/V stay in HBM and
    tiles stream into double-buffered VMEM scratch with async DMA, tile
    t+1 issued before compute on tile t, so VMEM residency is O(block·d)
    regardless of sequence length

Backward is the standard flash-attention recomputation scheme, also in
Pallas: the forward additionally writes the per-row log-sum-exp (lse),
so the backward re-materializes each probability tile as
``exp(s − lse)`` without ever storing the [s, s] matrix. Where one
head's operands and a float32 dQ accumulator fit VMEM (``bwd_one_pass``,
from the shapes alone) ONE kernel takes dQ, dK and dV from one pass over
the head's tile pairs, each pair's scores computed once
(``_bwd_kernel``). Past that budget two kernels stand: one accumulates
dQ (gridded over Q blocks, streaming K/V), a second accumulates dK/dV
(gridded over K blocks, streaming Q/dO/lse/delta, and starting at the
diagonal for causal), seven products a pair for the mathematics' five.
Memory is O(s·d) in backward too, which is what makes long-context
training with this kernel viable.

On the CPU backend the kernel runs in Pallas interpret mode (the tests'
virtual mesh), selected automatically; a TPU never gets it.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import metrics as hvd_metrics


_NEG_INF = -1e30
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

def _auto_interpret():
    """Mosaic on a TPU, the Pallas interpreter on the CPU (the tests),
    and an error anywhere else: a backend that is neither must not get
    an interpreted kernel under the compiled kernel's name."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"flash attention kernels run compiled on 'tpu' and interpreted "
        f"on 'cpu'; the default backend is {backend!r}")


def _out_struct(shape, dtype, *like):
    """ShapeDtypeStruct matching the operands' varying-manual-axes type,
    so the kernels compose with shard_map (check_vma=True requires
    outputs to declare how they vary — e.g. ring attention calls these
    kernels on sequence-sharded blocks)."""
    vma = None
    for t in like:
        tv = getattr(getattr(t, "aval", None), "vma", None)
        if tv:
            vma = tv if vma is None else (vma | tv)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# both grid dims are independent (programs share no state): 'parallel'
# lets Mosaic software-pipeline across grid steps instead of flushing
# between them
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


def _stream(hbm, bh, block, scr, sem, seq_axis=1):
    """Double-buffered HBM→VMEM tile stream: returns ``dma(slot, i)`` for
    tile i of ``hbm[bh]`` (``block`` rows along ``seq_axis``) into scratch
    slot ``slot``. seq_axis=1 for [bh, s, d] matrices, seq_axis=2 for the
    sublane-replicated [bh, 8, s] row-statistic layout."""
    def dma(slot, i):
        if seq_axis == 2:
            src = hbm.at[bh, :, pl.ds(i * block, block)]
        else:
            src = hbm.at[bh, pl.ds(i * block, block), :]
        return pltpu.make_async_copy(src, scr.at[slot], sem.at[slot])
    return dma


def _start_all(streams, slot, i):
    for s in streams:
        s(slot, i).start()


def _wait_all(streams, slot, i):
    for s in streams:
        s(slot, i).wait()


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                seq_k, causal, scale, kv_resident):
    """The forward: online softmax, the rescale chain on every k tile.

    One k tile is two whole-tile matmuls with the softmax chain between
    them, in the exp2 domain with log2(e) folded into the scalar logit
    scale; lse converts back to natural log once at the end (the
    external contract: parallel/ring.py merges in natural-log units).
    ``k_ref`` / ``v_ref`` are one head's whole K and V in VMEM
    (``kv_resident``) or the HBM arrays, streamed tile by tile.

    Measured on a v5e at the training cell's shape, causal bf16
    [bh, s, dh] = [64, 4096, 128], 512 x 512 blocks (PR 41;
    docs/benchmarks.md, "Flash-kernel lessons", has the table): 2.22 ms a
    step, 62.8% of the compute roofline, where the MXU's own time is
    1.57 ms (1,024 cycles a tile: each of the four takes 512 rows of q
    and 512 of p, one row a cycle; 36 tiles a head). What a tile costs
    beyond that is vector loads and stores: the [512, 512] float32 logits
    are 256 vector registers, four register files, so every pass over
    them goes through VMEM, and that is not to be had cheaper. What WAS
    to be had, in the order it paid:

      * m, l and the accumulator live in VMEM scratch, read where they
        are used and written once a tile. Carried as loop values (this
        kernel until PR 41) their 192 registers were copied through
        spill slots at both ends of every iteration: 390 of 1,468
        bundles a tile in which the MXU stood still (3.31 -> 2.84 ms).
      * m and l are kept REPLICATED over the lanes, ``[block_q, 128]``:
        a lane reduction leaves its result on every lane, so they are
        stored as they fall and broadcast against the tile by naming the
        same registers again (``jnp.tile``): no relayout. A 1-D
        ``[block_q]`` statistic written to scratch is re-laid from
        sublanes to lanes and back on every tile: 1,172 ``vperm.slane``
        and 1,630 stores in 2,980 bundles.
      * K/V of the whole head in VMEM (2.84 -> 2.22 ms): streamed, each
        q block's first tile is waited for with nothing to hide it
        behind, about 1 us, 512 times a step. A third buffer does not
        help (2.85 ms) and the waits inside the loop are only 0.07 ms.

    Two other accumulation schemes were timed beside this one there and
    lost, so it is the only one: the rescale deferred to the tiles that
    raise a row's maximum (7.99 ms: its gate is a vector-to-scalar
    reduction feeding a branch, and its statistics were 1-D), and two
    passes, the maximum first and then a chain-free accumulation
    (4.65 ms: once the chain is out of the way the MXU bounds the
    forward, and half as many matmuls again cost what they look like).
    At one k tile a row (most serving prefills) there is nothing to defer
    or to pass over twice.

    Dead ends at this shape, counted in bundles of the compiled loop body
    (1,190 a tile streamed, 1,131 resident) and the first timed on the
    chip: masking only the diagonal tile in a second loop (1,189 and
    1,177 bundles; 2.847 ms beside 2.843: compare and select ride in
    free slots of an MXU-bound body, and the code doubles); the chain
    run per block of 64-256 rows (each block pushes the MXU's weights
    again: 1,508-2,299); the logits written to a scratch of their own
    and read back in row blocks (1,986-2,159); ``[block_q, 1]``
    statistics (1,968: a lane broadcast at every use); the first tile
    peeled to skip the zeroing (-4.5% of the bundles for a second copy
    of the body: not taken). A past builder's, from ``b8 s1024 h12 d64``
    and not measured again: folding the softmax scale into q; the row
    sum carried in a planted ones-lane of v's head-dim padding (at
    ``dh`` 128 there is no padding lane); a manual 1-deep software
    pipeline of the next tile's logits; the stock jax.experimental
    pallas flash kernel's grid-over-kv design.
    """
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    d = q_ref.shape[-1]
    # matmul operands stay in the input dtype (bf16 runs the MXU at full
    # rate; fp32 would quarter it on v5e): accumulation is fp32 via
    # preferred_element_type, softmax statistics are fp32 throughout.
    q = q_ref[0]                                # [block_q, d]
    scale2 = scale * _LOG2E                     # logits in log2 units
    # lanes the row statistics are replicated over: 128 compiled (blocks
    # and head_dim are multiples of it), what divides both interpreted
    lanes = math.gcd(128, block_k, d)

    nk_total = seq_k // block_k
    if causal:
        # stop at the diagonal: K tiles starting past this q tile's last
        # row contribute nothing
        nk = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k,
                         nk_total)
    else:
        nk = nk_total

    def attend(tile, m_scr, l_scr, acc_scr):
        """The k loop; ``tile(kb)`` hands over k and v of tile kb."""
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def body(kb, _):
            k, v = tile(kb)
            s = jnp.dot(q, k.T,
                        preferred_element_type=jnp.float32) * scale2
            if causal:
                # both iotas made here: hoisted, the [block_q, block_k]
                # positions are 256 registers read back every tile
                q_pos = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            m = m_scr[...]                      # [block_q, lanes]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp2(m - m_new)
            p = jnp.exp2(s - jnp.tile(m_new, (1, block_k // lanes)))
            m_scr[...] = m_new
            l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_scr[...] = acc_scr[...] * jnp.tile(alpha, (1, d // lanes)) \
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, nk, body, 0)
        l = jnp.clip(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / jnp.tile(l, (1, d // lanes))).astype(
            o_ref.dtype)
        # per-row log-sum-exp in NATURAL log (the backward's softmax
        # residual and ring.py's merge contract), replicated over an
        # 8-row sublane dim to satisfy the TPU (8, 128) tile rule: every
        # row of the transpose is the statistic, rows along the lanes
        lse = (m_scr[...] + jnp.log2(l)) * _LN2
        lse_ref[0] = jnp.broadcast_to(lse.T[:1], (8, block_q))

    stats = dict(m_scr=pltpu.VMEM((block_q, lanes), jnp.float32),
                 l_scr=pltpu.VMEM((block_q, lanes), jnp.float32),
                 acc_scr=pltpu.VMEM((block_q, d), jnp.float32))

    if kv_resident:
        # k_ref / v_ref are this head's whole K and V, in VMEM
        def whole(m_scr, l_scr, acc_scr):
            def tile(kb):
                rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
                return k_ref[0, rows, :], v_ref[0, rows, :]
            attend(tile, m_scr, l_scr, acc_scr)

        pl.run_scoped(whole, **stats)
        return

    def streamed(k_scr, v_scr, sem_k, sem_v, m_scr, l_scr, acc_scr):
        streams = [_stream(k_ref, bh, block_k, k_scr, sem_k),
                   _stream(v_ref, bh, block_k, v_scr, sem_v)]
        _start_all(streams, 0, 0)

        def tile(kb):
            slot = kb % 2

            @pl.when(kb + 1 < nk)
            def _prefetch():
                _start_all(streams, (kb + 1) % 2, kb + 1)

            _wait_all(streams, slot, kb)
            return k_scr[slot], v_scr[slot]

        attend(tile, m_scr, l_scr, acc_scr)

    pl.run_scoped(
        streamed,
        k_scr=pltpu.VMEM((2, block_k, d), k_ref.dtype),
        v_scr=pltpu.VMEM((2, block_k, d), v_ref.dtype),
        sem_k=pltpu.SemaphoreType.DMA((2,)),
        sem_v=pltpu.SemaphoreType.DMA((2,)), **stats)


#: Bytes of VMEM the forward may fill with one head's K and V, each
#: held twice (the pipeline fetches the next head's while this one's are
#: read): 8 MiB is what fits beside the kernel's own buffers under the
#: default 16 MiB limit (compiled for a described v5e: bf16 heads of 128
#: at 8,192 keys fit, at 16,384 they do not).
_KV_RESIDENT_BYTES = 8 << 20


def kv_resident(sk, d, dtype):
    """Whether the forward holds a head's whole K and V in VMEM
    (handed over by the pipeline, fetched once a head) or streams them
    tile by tile: from the shapes alone. Streamed, every q block's first
    tile is waited for with nothing to hide it behind, about 1 us on a
    v5e: 512 times a step at the training cell's shape, 2.84 ms where
    the resident kernel takes 2.22 (docs/benchmarks.md). Past the budget
    (long-context and ring shards) the stream keeps VMEM use independent
    of the sequence length."""
    return 4 * sk * d * jnp.dtype(dtype).itemsize <= _KV_RESIDENT_BYTES


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, scale=None,
               layout="bshd"):
    if layout == "bhsd":
        # head-major: the flatten to [b*h, s, d] is a free reshape — the
        # caller (e.g. the transformer block, which is in this layout for
        # RoPE anyway) skips the transpose pair around the kernel
        b, h, sq, d = q.shape
        sk = k.shape[2]
    else:
        b, sq, h, d = q.shape
        sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"flash_attention needs seq divisible by block sizes: "
            f"q {sq}%{block_q}, k {sk}%{block_k}")
    if scale is None:
        scale = d ** -0.5
    if layout == "bhsd":
        qf = q.reshape(b * h, sq, d)
        kf = k.reshape(b * h, sk, d)
        vf = v.reshape(b * h, sk, d)
    else:
        # [b, s, h, d] → [b*h, s, d]: each program handles one (batch, head)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    # a head's whole K/V in VMEM where they fit; past that they stay in
    # HBM and the kernel DMAs block_k tiles into double-buffered VMEM
    # scratch, so VMEM use is independent of sequence length
    resident = kv_resident(sk, d, k.dtype)
    if resident:
        kv_spec = pl.BlockSpec((1, sk, d), lambda i, j: (i, 0, 0))
    else:
        kv_spec = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_fwd_kernel, block_q=block_q,
                               block_k=block_k, seq_k=sk, causal=causal,
                               scale=scale, kv_resident=resident)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        compiler_params=_COMPILER_PARAMS,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _out_struct((b * h, sq, d), q.dtype, qf, kf, vf),
            _out_struct((b * h, 8, sq), jnp.float32, qf, kf, vf),
        ],
        interpret=interpret if interpret is not None else _auto_interpret(),
    )(qf, kf, vf)
    if layout == "bhsd":
        return out.reshape(b, h, sq, d), lse
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse


def _dq_kernel(q_ref, do_ref, lse_ref, delta_ref, k_hbm, v_hbm, dq_ref, *,
               block_q, block_k, seq_k, causal, scale):
    """dQ, gridded like the forward: one (batch·head, q-block) per program,
    K/V streamed from HBM. ds = p ∘ (dP − delta); dq = scale · ds @ K.

    VPU-lean like the forward: p re-materializes via exp2 against the
    log2-domain lse, and the constant logit scale moves out of the
    per-tile ds (a [bq, bk] multiply) onto the accumulated dq after the
    loop (a [bq, d] multiply, once)."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    d = q_ref.shape[-1]
    q = q_ref[0]               # input dtype into the MXU (see _fwd_kernel)
    do = do_ref[0]
    lse2 = lse_ref[0, 0] * _LOG2E   # row 0 of the replicated sublane dim
    delta = delta_ref[0, 0]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    scale2 = scale * _LOG2E

    nk_total = seq_k // block_k
    if causal:
        nk = jnp.minimum(((qi + 1) * block_q + block_k - 1) // block_k,
                         nk_total)
    else:
        nk = nk_total

    def scoped(k_scr, v_scr, sem_k, sem_v):
        streams = [_stream(k_hbm, bh, block_k, k_scr, sem_k),
                   _stream(v_hbm, bh, block_k, v_scr, sem_v)]
        _start_all(streams, 0, 0)

        def body(kb, dq):
            slot = kb % 2

            @pl.when(kb + 1 < nk)
            def _prefetch():
                _start_all(streams, (kb + 1) % 2, kb + 1)

            _wait_all(streams, slot, kb)
            k = k_scr[slot]
            v = v_scr[slot]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale2
            if causal:
                k_pos = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            p = jnp.exp2(s - lse2[:, None])
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(k.dtype)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(0, nk, body,
                               jnp.zeros((block_q, d), jnp.float32))
        dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    pl.run_scoped(
        scoped,
        k_scr=pltpu.VMEM((2, block_k, d), k_hbm.dtype),
        v_scr=pltpu.VMEM((2, block_k, d), v_hbm.dtype),
        sem_k=pltpu.SemaphoreType.DMA((2,)),
        sem_v=pltpu.SemaphoreType.DMA((2,)))


def _dkv_kernel(k_ref, v_ref, q_hbm, do_hbm, lse_hbm, delta_hbm, dk_ref,
                dv_ref, *, block_q, block_k, seq_q, causal, scale):
    """dK/dV, gridded over (batch·head, k-block), Q/dO/lse/delta streamed
    from HBM; for causal the Q loop starts at the diagonal block.
    Same VPU-lean scheme as _dq_kernel: exp2 against log2-lse, logit
    scale applied to dk once after the loop."""
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    d = k_ref.shape[-1]
    k = k_ref[0]               # input dtype into the MXU (see _fwd_kernel)
    v = v_ref[0]
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    scale2 = scale * _LOG2E

    nq_total = seq_q // block_q
    if causal:
        # first q block whose last row can see this k block's first row
        qb_start = (ki * block_k) // block_q
    else:
        qb_start = 0

    def scoped(q_scr, do_scr, lse_scr, delta_scr, sem_q, sem_do, sem_l,
               sem_dl):
        streams = [_stream(q_hbm, bh, block_q, q_scr, sem_q),
                   _stream(do_hbm, bh, block_q, do_scr, sem_do),
                   _stream(lse_hbm, bh, block_q, lse_scr, sem_l,
                           seq_axis=2),
                   _stream(delta_hbm, bh, block_q, delta_scr, sem_dl,
                           seq_axis=2)]
        _start_all(streams, qb_start % 2, qb_start)

        def body(qb, carry):
            dk, dv = carry
            slot = qb % 2

            @pl.when(qb + 1 < nq_total)
            def _prefetch():
                _start_all(streams, (qb + 1) % 2, qb + 1)

            _wait_all(streams, slot, qb)
            q = q_scr[slot]
            do = do_scr[slot]
            lse2 = lse_scr[slot, 0] * _LOG2E   # row 0 of replicated rows
            delta = delta_scr[slot, 0]

            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale2
            if causal:
                q_pos = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
            p = jnp.exp2(s - lse2[:, None])                # [bq, bk]
            dv = dv + jnp.dot(p.astype(do.dtype).T, do,
                              preferred_element_type=jnp.float32)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta[:, None])).astype(q.dtype)
            dk = dk + jnp.dot(ds.T, q,
                              preferred_element_type=jnp.float32)
            return dk, dv

        init = (jnp.zeros((block_k, d), jnp.float32),
                jnp.zeros((block_k, d), jnp.float32))
        dk, dv = jax.lax.fori_loop(qb_start, nq_total, body, init)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    pl.run_scoped(
        scoped,
        q_scr=pltpu.VMEM((2, block_q, d), q_hbm.dtype),
        do_scr=pltpu.VMEM((2, block_q, d), do_hbm.dtype),
        lse_scr=pltpu.VMEM((2, 8, block_q), jnp.float32),
        delta_scr=pltpu.VMEM((2, 8, block_q), jnp.float32),
        sem_q=pltpu.SemaphoreType.DMA((2,)),
        sem_do=pltpu.SemaphoreType.DMA((2,)),
        sem_l=pltpu.SemaphoreType.DMA((2,)),
        sem_dl=pltpu.SemaphoreType.DMA((2,)))


def _bwd_kernel(q_ref, do_ref, stat_ref, k_ref, v_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, *, block_q, block_k, causal,
                scale):
    """The whole backward of one head in one program: dQ, dK and dV from
    ONE pass over the head's tile pairs, each pair's scores, p, dP and dS
    computed once (five products a pair, the mathematics' count; the two
    kernels below make seven, ``s`` and ``dP`` twice).

    Walks K blocks j and, inside, the q blocks i the mask lets see them
    (from the diagonal on, causal): dV_j and dK_j accumulate over i as in
    ``_dkv_kernel``, and ``dS k_j`` adds into rows i of a float32
    ``[sq, d]`` accumulator that stays in VMEM for the head, j ascending:
    the order of ``_dq_kernel``'s sum. The logit scale goes onto dQ and
    dK once, after their sums. Everything the head reads and writes is in
    VMEM whole, handed over by the pipeline, which fetches the next
    head's behind this one's tiles: no tile is waited for.

    A tile pair is computed TRANSPOSED, ``s^T = k q^T`` ``[block_k,
    block_q]``, so that the row statistics (``stat_ref``: lse in row 0,
    delta in row 1 of the ``[8, sq]`` sublane-replicated layout, rows
    along the lanes) broadcast over the sublanes as they lie, and dV and
    dK are plain products of p^T and dS^T; only dQ's left operand is
    transposed. With ``s = q k^T`` the statistics are re-laid from lanes
    to sublanes every tile and two products want a transpose.

    Measured on a v5e at the training cell's shape, causal bf16
    ``[64, 4096, 128]``, 512 x 512 blocks (PR 50; docs/benchmarks.md,
    "Flash-kernel lessons", has the table and the other blocks): 4.41 ms
    a step where the two kernels take 8.63, 79% of the compute roofline;
    the MXU's own time is 3.93 ms (five products of 512 cycles, 36 tile
    pairs a head) and the compiled body is 2,307 bundles for those 2,560
    cycles. Beside it there: ``s = q k^T`` 4.95 ms (2,612 bundles); a
    grid over (head, k block) with the accumulator revisited 4.50 ms, and
    5.21 with q, dO and the statistics streamed tile by tile.
    """
    sq = q_ref.shape[1]
    sk = k_ref.shape[1]
    scale2 = scale * _LOG2E
    contract_last = (((1,), (1,)), ((), ()))
    contract_first = (((0,), (0,)), ((), ()))
    dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def k_block(j, _):
        k_rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, k_rows, :]    # input dtype into the MXU (_fwd_kernel)
        v = v_ref[0, k_rows, :]
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

        def q_block(i, _):
            q_rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            q = q_ref[0, q_rows, :]
            do = do_ref[0, q_rows, :]
            lse2 = stat_ref[0, pl.ds(0, 1), q_rows] * _LOG2E  # [1, block_q]
            delta = stat_ref[0, pl.ds(1, 1), q_rows]
            st = jax.lax.dot_general(
                k, q, contract_last,
                preferred_element_type=jnp.float32) * scale2
            if causal:
                k_pos = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                q_pos = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                st = jnp.where(k_pos <= q_pos, st, _NEG_INF)
            pt = jnp.exp2(st - lse2)                  # [block_k, block_q]
            dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, contract_last,
                                      preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dk_acc[...] += jnp.dot(dst, q,
                                   preferred_element_type=jnp.float32)
            dq_acc[q_rows, :] += jax.lax.dot_general(
                dst, k, contract_first, preferred_element_type=jnp.float32)
            return 0

        # first q block whose last row can see this k block's first row
        first = (j * block_k) // block_q if causal else 0
        jax.lax.fori_loop(first, sq // block_q, q_block, 0)
        dk_ref[0, k_rows, :] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, k_rows, :] = dv_acc[...].astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, sk // block_k, k_block, 0)
    dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


#: Bytes of VMEM the one-pass backward may fill with one head: q, dO, K,
#: V and the three gradients, each held twice (the pipeline fetches the
#: next head's and writes the last one's back while this one computes),
#: the float32 dQ accumulator and the row statistics. The call asks for
#: ``_BWD_VMEM_LIMIT`` and leaves the rest to a tile pair's
#: ``[block_k, block_q]`` float32 temporaries.
_BWD_ONE_PASS_BYTES = 40 << 20
_BWD_VMEM_LIMIT = 64 << 20


def bwd_one_pass(sq, sk, d, dtype):
    """Whether the backward is the one-pass kernel (``_bwd_kernel``) or
    the two that stand past its VMEM budget: from the shapes alone, as
    ``kv_resident`` decides for the forward."""
    item = jnp.dtype(dtype).itemsize
    held = 2 * item * d * (3 * sq + 4 * sk)       # operands and gradients
    held += 4 * sq * d + 2 * 4 * 8 * sq           # accumulator, statistics
    return held <= _BWD_ONE_PASS_BYTES


def _count_backward(kernel):
    """Trace-time count of which backward a call took (a compiled step
    cannot count at run time): once a layer a (re)trace."""
    reg = hvd_metrics.get_registry()
    if reg.enabled:
        reg.counter(
            "hvd_flash_backward_traced_total",
            "Flash-attention backward calls, counted at trace time, by "
            "the kernel the call's shapes chose.",
            labels=("kernel",)).labels(kernel=kernel).inc()


def _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k, interpret,
               scale=None, block_q_dkv=None, block_k_dkv=None,
               layout="bshd"):
    if layout == "bhsd":
        b, h, sq, d = q.shape
        sk = k.shape[2]
    else:
        b, sq, h, d = q.shape
        sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # the dK/dV kernel streams Q-side tiles and grids over K blocks —
    # its optimal tile shape need not match the dQ kernel's, so the two
    # are independently tunable
    block_q_dkv = min(block_q_dkv or block_q, sq)
    block_k_dkv = min(block_k_dkv or block_k, sk)
    if sq % block_q_dkv:
        block_q_dkv = block_q     # caller-validated fallback
    if sk % block_k_dkv:
        block_k_dkv = block_k
    if scale is None:
        scale = d ** -0.5
    interpret = interpret if interpret is not None else _auto_interpret()

    def flat(t, s):
        if layout == "bhsd":
            return t.reshape(b * h, s, d)
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def unflat(t, s):
        if layout == "bhsd":
            return t.reshape(b, h, s, d)
        return t.reshape(b, h, s, d).transpose(0, 2, 1, 3)

    qf, kf, vf = flat(q, sq), flat(k, sk), flat(v, sk)
    dof, of = flat(g, sq), flat(out, sq)
    # delta_i = Σ_d dO_i ⊙ O_i — the dP correction term; elementwise, XLA
    # fuses it, no kernel needed. Same sublane-replicated layout as lse.
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]

    if bwd_one_pass(sq, sk, d, q.dtype):
        _count_backward("one_pass")
        # lse and delta as ONE operand, rows 0 and 1 of the layout they
        # share: five operands, which benchmarks/readers/flash_roofline.py
        # takes for neither a forward (three) nor HALF a backward (six)
        row = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)
        stats = jnp.where(row == 1, delta, lse)

        operands = (qf, dof, stats, kf, vf)

        def head(s):
            return pl.BlockSpec((1, s, d), lambda i: (i, 0, 0))

        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kernel, block_q=block_q, block_k=block_k,
                              causal=causal, scale=scale),
            grid=(b * h,),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_BWD_VMEM_LIMIT),
            in_specs=[head(sq), head(sq),
                      pl.BlockSpec((1, 8, sq), lambda i: (i, 0, 0)),
                      head(sk), head(sk)],
            out_specs=[head(sq), head(sk), head(sk)],
            out_shape=[_out_struct((b * h, s, d), t.dtype, *operands)
                       for s, t in ((sq, q), (sk, k), (sk, v))],
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)],
            name="flash_backward",
            interpret=interpret,
        )(*operands)
        return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)

    _count_backward("two_kernel")
    delta = jnp.broadcast_to(delta, (b * h, 8, sq))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=block_q, block_k=block_k,
                          seq_k=sk, causal=causal, scale=scale),
        grid=(b * h, sq // block_q),
        compiler_params=_COMPILER_PARAMS,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 8, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=_out_struct((b * h, sq, d), q.dtype, qf, dof, lse,
                              delta, kf, vf),
        interpret=interpret,
    )(qf, dof, lse, delta, kf, vf)

    bq2, bk2 = block_q_dkv, block_k_dkv
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=bq2, block_k=bk2,
                          seq_q=sq, causal=causal, scale=scale),
        grid=(b * h, sk // bk2),
        compiler_params=_COMPILER_PARAMS,
        in_specs=[
            pl.BlockSpec((1, bk2, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk2, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, bk2, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk2, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _out_struct((b * h, sk, d), k.dtype, kf, vf, qf, dof, lse,
                        delta),
            _out_struct((b * h, sk, d), v.dtype, kf, vf, qf, dof, lse,
                        delta),
        ],
        interpret=interpret,
    )(kf, vf, qf, dof, lse, delta)
    return unflat(dq, sq), unflat(dk, sk), unflat(dv, sk)


def fit_block(block, s):
    """Largest block ≤ requested that divides the sequence, halving no
    further than 128 (the MXU-friendly floor) — a larger default must
    not reject lengths like 384 that 128-blocks handled. The single
    block-size policy for this kernel and its compositions
    (parallel/ring.py ring_flash_attention)."""
    b = min(block, s)
    while b > 128 and s % b:
        b //= 2
    return b


def call_block(block, s, compiled=True):
    """The block one ``flash_attention`` call runs a sequence of ``s``
    with: ``fit_block``, and compiled never a block that is no multiple
    of 128. Mosaic lays the [bh, 8, s] row statistics (lse, delta) out in
    128-lane tiles and refuses to slice a block out of them that is not a
    multiple ("Slice shape along dimension 2 must be aligned to tiling
    (128)" from the dK/dV kernel at s = 16, 24, 112, 200): compiled, such
    a sequence takes 128-blocks and end-padding. Serving prefill lengths
    land here. Forward and backward share the blocks: 512 x 512 is the
    fastest forward measured at ``[64, 4096, 128]`` (docs/benchmarks.md:
    1024 x 512 2.52 ms against 2.22, 512 x 1024 and 256 x 512 behind it),
    as it is the backward's."""
    b = fit_block(block, s)
    return b if not compiled or b % 128 == 0 else 128


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, causal, block_q, block_k, interpret, scale,
                block_q_dkv, block_k_dkv, layout):
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                        scale=scale, layout=layout)
    return out


def flash_attention(q, k, v, causal=True, block_q=512, block_k=512,
                    interpret=None, block_q_dkv=None, block_k_dkv=None,
                    layout="bshd", scale=None):
    """Fused attention; q/k/v [batch, seq, heads, head_dim] (or
    [batch, heads, seq, head_dim] with ``layout="bhsd"`` — the flatten to
    the kernel's physical [batch·heads, seq, head_dim] is then a free
    reshape, so a caller already in head-major layout, like the
    transformer block around RoPE, skips the transpose pair the default
    layout inserts on every operand and gradient). Causal mask in global
    positions. Numerically equivalent to parallel.ring.full_attention
    (exact softmax, fp32 accumulation), in forward and backward, with
    O(s·d) memory in both. Default 512-blocks measured fastest on v5e
    for the forward at the training cell's shape (PR 41, ``call_block``;
    a past builder read the same for forward and backward together at
    ``b8 s1024 h12 d64``).

    Sequence lengths need not divide the block sizes for causal
    self-attention (sq == sk): inputs are end-padded to the next block
    multiple (end-padded keys sit at positions after every real query, so
    the causal mask discards them exactly) and the output is sliced back.
    Other non-divisible cases would need an explicit key mask the kernel
    doesn't carry, so they raise. On real TPU, head_dim is zero-padded to
    the 128-lane tile (softmax scale keeps the true head_dim; zero columns
    drop out of every dot product). ``scale`` is the softmax scale where
    it is not ``head_dim ** -0.5`` (models/sambay.py: heads of 64 packed
    two to a 128-lane row)."""
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"unknown layout {layout!r}")
    seq_axis = 2 if layout == "bhsd" else 1
    sq, sk = q.shape[seq_axis], k.shape[seq_axis]
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    interpret_eff = interpret if interpret is not None else _auto_interpret()

    fit = functools.partial(call_block, compiled=not interpret_eff)
    bq, bk = fit(block_q, sq), fit(block_k, sk)
    bq2 = fit(block_q_dkv, sq) if block_q_dkv else None
    bk2 = fit(block_k_dkv, sk) if block_k_dkv else None
    pad_q, pad_k = -sq % bq, -sk % bk
    if (pad_q or pad_k) and not (causal and sq == sk):
        raise ValueError(
            f"flash_attention needs seq divisible by block sizes unless "
            f"causal self-attention: q {sq}%{bq}, k {sk}%{bk}")
    if pad_q or pad_k:
        def seq_pad(t, p):
            pads = [(0, 0)] * 4
            pads[seq_axis] = (0, p)
            return jnp.pad(t, pads)
        q, k, v = seq_pad(q, pad_q), seq_pad(k, pad_k), seq_pad(v, pad_k)
    pad_d = 0 if interpret_eff else -d % 128
    if pad_d:
        pads = ((0, 0), (0, 0), (0, 0), (0, pad_d))
        q, k, v = jnp.pad(q, pads), jnp.pad(k, pads), jnp.pad(v, pads)
    out = _flash_core(q, k, v, causal, bq, bk, interpret_eff, scale,
                      bq2, bk2, layout)
    if pad_d:
        out = out[..., :d]
    if pad_q:
        out = out[:, :, :sq] if layout == "bhsd" else out[:, :sq]
    return out


def _vjp_fwd(q, k, v, causal, block_q, block_k, interpret, scale,
             block_q_dkv, block_k_dkv, layout):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                          scale=scale, layout=layout)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, block_q, block_k, interpret, scale, block_q_dkv,
             block_k_dkv, layout, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd(q, k, v, out, lse, g, causal, block_q, block_k,
                      interpret, scale=scale, block_q_dkv=block_q_dkv,
                      block_k_dkv=block_k_dkv, layout=layout)


_flash_core.defvjp(_vjp_fwd, _vjp_bwd)


#: Positions of one block of the decode kernel: a row of the cache is
#: read in whole blocks up to its length. The engine's ``kv_bytes`` count
#: (serving/engine.py) is taken under the same blocking.
DECODE_BLOCK = 128
_DECODE_BUFFERS = 3


def decode_block(s_max):
    """Positions a block of the decode kernel holds for rows of ``s_max``:
    ``DECODE_BLOCK`` where it divides the row, else the whole row."""
    return DECODE_BLOCK if s_max % DECODE_BLOCK == 0 else s_max


def _on_one_tpu_chip():
    """A TPU backend and no committed mesh of several devices: where a
    bare ``pallas_call`` inside a serving program compiles (under a
    sharded jit Mosaic refuses it) and does not run interpreted."""
    if jax.default_backend() != "tpu":
        return False
    from ..parallel import mesh as mesh_lib  # at trace time, not at import
    mesh = mesh_lib.global_mesh_if_set()
    return mesh is None or mesh.size == 1


def _decode_kernel_selected(cache_shape, head_sharding):
    """Whether single-query attention over a cache of ``cache_shape``
    ``[layers, batch, s_max, kv_heads, head_dim]`` runs as the Mosaic
    kernel. Decided from what the call can see, no option: a TPU backend;
    a program on ONE chip — no head-sharded cache, no committed mesh of
    several devices (a bare ``pallas_call`` under a sharded jit is
    refused); and a cache whose ``[s_max * kv_heads, head_dim]`` view costs
    nothing: rows of whole 128-position blocks, 128-lane heads, and a
    number of key/value heads that the tiled layout holds without padding
    (1, 2, 4 or a multiple of 8 — with 6, 12 or 20 the compiled program
    re-lays the whole cache for every call, which costs more than the
    kernel saves). Everything else takes the einsum: the ``tp`` engines,
    and the CPU backend, where the engine's tests would otherwise
    interpret a kernel every decode step."""
    if head_sharding is not None or not _on_one_tpu_chip():
        return False
    _, _, s_max, hk, d = cache_shape
    return s_max % DECODE_BLOCK == 0 and d % 128 == 0 and \
        (hk % 8 == 0 or hk in (1, 2, 4))


def _decode_kernel(layer_ref, total_ref, row_ref, blk_ref, len_ref, q_ref,
                   key_ref, k_hbm, v_hbm, o_ref, k_scr, v_scr, sem, m_scr,
                   l_scr, acc_scr, *, block, hk, scale):
    """All rows' single-query attention in one program: a loop over the
    LIVE blocks of the cache, row after row (item i is block ``blk_ref[i]``
    of row ``row_ref[i]``; ``total_ref[0]`` items), so a block above a
    row's length is never asked for and the DMA queue stays full across
    rows of a few blocks each.

    The cache is seen as ``[layers, batch, s_max * hk, d]`` (a free
    reshape): one block is ``block * hk`` rows, position-major, and ALL
    heads' logits come from one matmul ``q[row] [heads, d] x block^T`` —
    entry (r, c) means something where column c holds query head r's
    key/value head (``c % hk == r // group``). ``key_ref`` carries c at
    those entries and a huge number elsewhere, so one compare against the
    row's live extent is both the head mask and the length mask. The MXU
    does ``hk`` times the needed work; the step is bound by the stream
    from HBM, not by it. Softmax statistics are fp32, in the exp2 domain
    as the forward kernels'.
    """
    layer = layer_ref[0]
    total = total_ref[0]
    cols = block * hk
    nbuf = k_scr.shape[0]
    # a row of length 0 (a slot that does not decode) has no item
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(i, buf):
        start = pl.multiple_of(blk_ref[i] * cols, cols)
        return [pltpu.make_async_copy(
            hbm.at[layer, row_ref[i], pl.ds(start, cols), :], scr.at[buf],
            sem.at[j, buf])
            for j, (hbm, scr) in enumerate(((k_hbm, k_scr), (v_hbm, v_scr)))]

    for j in range(nbuf - 1):
        @pl.when(j < total)
        def _prime():
            for c in copies(j, j):
                c.start()

    def body(i, _):
        buf = i % nbuf

        @pl.when(i + nbuf - 1 < total)
        def _prefetch():  # into the buffer item i - 1 has finished with
            for c in copies(i + nbuf - 1, (i + nbuf - 1) % nbuf):
                c.start()

        row = row_ref[i]
        # rows of this block below the row's length, in flattened rows
        live = (len_ref[row] - blk_ref[i] * block) * hk

        @pl.when(blk_ref[i] == 0)
        def _begin_row():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        for c in copies(i, buf):
            c.wait()

        @pl.when(live < cols)
        def _hide_the_tail():
            # whatever lies above the length in the row's last block must
            # not meet a zero probability in the matmul (0 x NaN)
            pos = jax.lax.broadcasted_iota(jnp.int32, v_scr.shape[1:], 0)
            v_scr[buf] = jnp.where(
                pos < live, v_scr[buf].astype(jnp.float32),
                0.0).astype(v_scr.dtype)

        s = jax.lax.dot_general(q_ref[row], k_scr[buf],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(key_ref[...] < live, s * (scale * _LOG2E), _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v_scr.dtype), v_scr[buf],
            preferred_element_type=jnp.float32)

        @pl.when(live <= cols)
        def _end_row():
            o_ref[row] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                          ).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, total, body, 0)


def _live_blocks(lengths, b, s_max, block):
    """The decode kernels' work list over rows of ``lengths`` (clipped to
    ``s_max``): item i is block ``blk_of[i]`` of row ``row_of[i]``, the
    rows' live blocks one row after another; ``ends[-1]`` items in all.
    Returns (lengths, ends, row_of, blk_of), int32."""
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, s_max)
    # item i is block i - ends[row - 1] of the row whose blocks end after i
    ends = jnp.cumsum((lengths + block - 1) // block)
    item = jnp.arange(b * (s_max // block), dtype=jnp.int32)
    before = ends[None, :] <= item[:, None]
    row_of = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    blk_of = item - jnp.max(jnp.where(before, ends[None, :], 0), axis=1)
    return lengths, ends, row_of, blk_of


def _decode_attention_kernel(q, k, v, lengths, layer, scale):
    """``decode_attention`` over layer ``layer`` of the whole cache
    ``k``/``v`` ``[layers, batch, s_max, kv_heads, d]`` as the Mosaic
    kernel. The cache goes in WHOLE (``memory_space=ANY``) with the layer
    as a scalar: a custom call cannot fuse a slice, and a sliced operand
    would be copied (two layers of cache a call)."""
    layers, b, s_max, hk, d = k.shape
    h = q.shape[2]
    block = decode_block(s_max)
    rows = -(-h // 16) * 16  # query heads, padded to a bf16 tile's sublanes
    cols = block * hk
    lengths, ends, row_of, blk_of = _live_blocks(lengths, b, s_max, block)
    r = jnp.arange(rows, dtype=jnp.int32)[:, None]
    c = jnp.arange(cols, dtype=jnp.int32)[None, :]
    key = jnp.where(c % hk == r // (h // hk), c, 2 ** 30).astype(jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    flat = (layers, b, s_max * hk, d)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block=block, hk=hk, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        in_specs=[smem] * 5 + [vmem, vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((_DECODE_BUFFERS, cols, d), k.dtype),
            pltpu.VMEM((_DECODE_BUFFERS, cols, d), v.dtype),
            pltpu.SemaphoreType.DMA((2, _DECODE_BUFFERS)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="decode_attention",
        interpret=_auto_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), ends[-1:], row_of, blk_of,
      lengths, jnp.pad(q[:, 0], ((0, 0), (0, rows - h), (0, 0))), key,
      k.reshape(flat), v.reshape(flat))
    return out[:, None, :h]


def decode_attention(q, k, v, lengths, scale=None, head_sharding=None,
                     layer=None):
    """Single-query attention against a cached K/V prefix — the decode
    step of the serving plane (docs/serving.md).

    q         [batch, 1, heads, head_dim]  — the current token's query
    k, v      [batch, s_max, kv_heads, head_dim] — the KV cache; only the
              first ``lengths[b]`` positions of row b are real, the rest
              is whatever the allocator left there (masked out here).
              ``kv_heads`` may divide ``heads`` (grouped-query attention:
              query head i reads key/value head i // (heads / kv_heads));
              the cache is read as it is, never repeated per query head.
              With ``layer`` they are the WHOLE cache ``[layers, batch,
              s_max, kv_heads, head_dim]``
    lengths   [batch] int32 — valid prefix length per row; a row of
              length 0 (a slot that does not decode) attends to nothing
              and its output means nothing
    scale     optional softmax scale (default head_dim ** -0.5, matching
              flash_attention)
    head_sharding  optional NamedSharding over the head axis
              (parallel.mesh.decode_head_sharding): constrains q/k/v so
              the tensor-parallel serving path keeps attention
              embarrassingly parallel over heads — each chip attends
              its own heads/tp slice of the cache, no cross-chip
              traffic until the output projection's psum
    layer     optional index into a whole cache: what the decode programs
              pass, so that the kernel can read its layer in place

    Two implementations of one contract. The op is bound by reading K/V
    from HBM, and what decides its time is HOW MUCH it reads: the einsum
    below reads every row to ``s_max`` and masks, the Mosaic kernel
    (``_decode_kernel``) takes ``lengths`` as data and streams each row's
    blocks of ``DECODE_BLOCK`` positions up to its length and none above.
    With a third of a 16 x 1536 cache live that took the decode program
    from 59% to 88% of its HBM roofline on a v5e (PERF_LEDGER.jsonl, PR
    30). ``_decode_kernel_selected`` picks the kernel from the call itself
    — a whole cache handed over with ``layer``, a TPU backend, one chip,
    no head sharding, a cache whose tiles it can read as they lie — and
    there is no option.
    The einsum is the plain reference the kernel's tests compare with, the
    head-sharded (``tp``) path, and what the CPU backend runs. Either way
    the shape is fixed across decode steps: ``lengths`` is data, and rows
    join and retire without a recompile.

    Numerics contract (tests/test_flash_attention.py): matches the last
    row of flash_attention / parallel.ring.full_attention over the same
    prefix — fp32 softmax, matmuls in the input dtype with fp32
    accumulation, output cast back to q.dtype.
    """
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"decode_attention wants q [b, 1, h, d], got "
                         f"{q.shape}")
    b, _, h, d = q.shape
    s_max, hk = k.shape[-3], k.shape[-2]
    if h % hk:
        raise ValueError(f"decode_attention: {h} query heads over {hk} "
                         f"key/value heads")
    scale = d ** -0.5 if scale is None else scale
    if layer is not None:
        if _decode_kernel_selected(k.shape, head_sharding):
            return _decode_attention_kernel(q, k, v, lengths, layer, scale)
        k, v = k[layer], v[layer]
    if head_sharding is not None:
        q = jax.lax.with_sharding_constraint(q, head_sharding)
        k = jax.lax.with_sharding_constraint(k, head_sharding)
        v = jax.lax.with_sharding_constraint(v, head_sharding)
    pos = jnp.arange(s_max, dtype=jnp.int32)[None, None, :]
    valid = pos < lengths.astype(jnp.int32)[:, None, None]
    if hk != h:
        # grouped: the r = h / hk query heads of a group share one read
        # of their key/value head
        qg = q[:, 0].reshape(b, hk, h // hk, d)
        logits = jnp.einsum("bgrd,bsgd->bgrs", qg, k,
                            preferred_element_type=jnp.float32)
        logits = logits.astype(jnp.float32) * scale
        logits = jnp.where(valid[:, :, None], logits, _NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrs,bsgd->bgrd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, h, d).astype(q.dtype)[:, None]
    # [b, h, d] x [b, s, h, d] -> [b, h, s] logits, fp32 accumulation
    logits = jnp.einsum("bhd,bshd->bhs", q[:, 0], k,
                        preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32) * scale
    logits = jnp.where(valid, logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)[:, None]


# -- single-query attention over a LATENT cache (models/latent_moe.py) --------

def _latent_kernel_selected(cache_shape, value_dim):
    """Whether ``latent_decode_attention`` over a cache of ``cache_shape``
    ``[planes, batch, s_max, 1, lanes]`` runs as the Mosaic kernel: the
    conditions of ``_decode_kernel_selected`` for ONE key head whose first
    ``value_dim`` lanes are the value, both in whole lane tiles (Mosaic
    refuses to slice a block ``[128, 576]`` out of HBM: "must be aligned
    to tiling (128)")."""
    if not _on_one_tpu_chip():
        return False
    _, _, s_max, hk, lanes = cache_shape
    return hk == 1 and s_max % DECODE_BLOCK == 0 and \
        lanes % 128 == 0 and value_dim % 128 == 0


def _latent_decode_kernel(plane_ref, total_ref, row_ref, blk_ref, len_ref,
                          q_ref, c_hbm, o_ref, c_scr, sem, m_scr, l_scr,
                          acc_scr, *, block, value_dim, scale):
    """``_decode_kernel`` for a latent cache: the same loop over the LIVE
    blocks, row after row, but ONE stream. A block ``[block, lanes]`` is
    read from HBM once and used twice: all of it is the key, and its first
    ``value_dim`` lanes are the value. One key head: no head mask, only
    the length's."""
    plane = plane_ref[0]
    total = total_ref[0]
    nbuf = c_scr.shape[0]
    o_ref[...] = jnp.zeros_like(o_ref)

    def copy(i, buf):
        start = pl.multiple_of(blk_ref[i] * block, block)
        return pltpu.make_async_copy(
            c_hbm.at[plane, row_ref[i], pl.ds(start, block), :],
            c_scr.at[buf], sem.at[buf])

    for j in range(nbuf - 1):
        @pl.when(j < total)
        def _prime():
            copy(j, j).start()

    def body(i, _):
        buf = i % nbuf

        @pl.when(i + nbuf - 1 < total)
        def _prefetch():  # into the buffer item i - 1 has finished with
            copy(i + nbuf - 1, (i + nbuf - 1) % nbuf).start()

        row = row_ref[i]
        live = len_ref[row] - blk_ref[i] * block

        @pl.when(blk_ref[i] == 0)
        def _begin_row():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        copy(i, buf).wait()

        @pl.when(live < block)
        def _hide_the_tail():  # 0 x NaN, as in ``_decode_kernel``
            pos = jax.lax.broadcasted_iota(jnp.int32, c_scr.shape[1:], 0)
            c_scr[buf] = jnp.where(
                pos < live, c_scr[buf].astype(jnp.float32),
                0.0).astype(c_scr.dtype)

        s = jax.lax.dot_general(q_ref[row], c_scr[buf],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < live, s * (scale * _LOG2E), _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(c_scr.dtype), c_scr[buf, :, :value_dim],
            preferred_element_type=jnp.float32)

        @pl.when(live <= block)
        def _end_row():
            o_ref[row] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                          ).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, total, body, 0)


def _latent_decode_attention_kernel(q, cache, lengths, plane, value_dim,
                                    scale):
    planes, b, s_max, _, lanes = cache.shape
    h = q.shape[1]
    block = decode_block(s_max)
    rows = -(-h // 16) * 16  # query heads, padded to a bf16 tile's sublanes
    lengths, ends, row_of, blk_of = _live_blocks(lengths, b, s_max, block)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, block=block,
                          value_dim=value_dim, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, rows, value_dim), q.dtype),
        in_specs=[smem] * 5 + [vmem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((_DECODE_BUFFERS, block, lanes), cache.dtype),
            pltpu.SemaphoreType.DMA((_DECODE_BUFFERS,)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, value_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="latent_decode_attention",
        interpret=_auto_interpret(),
    )(jnp.asarray(plane, jnp.int32).reshape(1), ends[-1:], row_of, blk_of,
      lengths, jnp.pad(q, ((0, 0), (0, rows - h), (0, 0))),
      cache.reshape(planes, b, s_max, lanes))
    return out[:, :h]


def latent_decode_attention(q, cache, lengths, plane, value_dim,
                            scale=None):
    """Single-query attention over a LATENT cache: every query head over
    ONE key head whose first ``value_dim`` lanes are also the value (the
    absorbed form of latent attention, models/latent_moe.py).

    q        [batch, heads, lanes]: the absorbed query, laid out as the
             cache's entries are (zeros where those hold padding)
    cache    the WHOLE cache [planes, batch, s_max, 1, lanes]; only the
             first ``lengths[b]`` positions of row b in plane ``plane``
             are real
    lengths  [batch] int32; a row of length 0 attends to nothing and its
             output means nothing
    scale    the softmax scale (default ``lanes ** -0.5``; a model whose
             heads had another width before the absorption passes its own)

    Returns [batch, heads, value_dim]: ``sum_t p_t c_t``, still in the
    latent's space. Two implementations of one contract, as
    ``decode_attention``: on one TPU chip a Mosaic kernel that takes the
    lengths as data and streams each row's blocks of ``DECODE_BLOCK``
    positions of the plane ONCE, in place, and none above its length;
    elsewhere an einsum over the whole plane under a length mask
    (``_latent_kernel_selected`` decides from the call; there is no
    option). fp32 softmax, products in the cache's dtype with fp32
    accumulation."""
    if q.ndim != 3 or cache.ndim != 5 or cache.shape[3] != 1 or \
            q.shape[2] != cache.shape[4]:
        raise ValueError(f"latent_decode_attention wants q [b, h, lanes] "
                         f"and a cache [planes, b, s, 1, lanes], got "
                         f"{q.shape} and {cache.shape}")
    s_max, lanes = cache.shape[2], cache.shape[4]
    scale = lanes ** -0.5 if scale is None else scale
    if _latent_kernel_selected(cache.shape, value_dim):
        return _latent_decode_attention_kernel(q, cache, lengths, plane,
                                               value_dim, scale)
    latent = cache[plane, :, :, 0]                       # [b, s, lanes]
    logits = jnp.einsum("bhw,bsw->bhs", q, latent,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s_max, dtype=jnp.int32)[None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, logits, _NEG_INF), axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", p.astype(cache.dtype),
                     latent[..., :value_dim],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# -- single-query attention over a cache of PACKED heads (models/sambay.py) ----

#: lanes of one packed head: a row of such a cache is ``groups`` of them
PACKED_LANES = 128
_PACKED_ROWS = 16  # a group's query heads, padded to a bf16 tile's sublanes


def _packed_kernel_selected(cache_shape, lanes=PACKED_LANES):
    """Whether ``packed_decode_attention`` over a cache of ``cache_shape``
    ``[planes, batch, s_max, 1, groups * lanes]`` runs as the Mosaic kernel:
    the conditions of ``_decode_kernel_selected`` for rows that are ONE
    run of whole lane tiles (20 key/value heads of 64 as ``[.., 20, 64]``
    or 10 pairs as ``[.., 10, 128]`` would be re-laid for every call: the
    tiled layout pads 20 or 10 sublanes to 32 or 16)."""
    if not _on_one_tpu_chip():
        return False
    _, _, s_max, one, width = cache_shape
    return lanes == PACKED_LANES and one == 1 and \
        s_max % DECODE_BLOCK == 0 and width % PACKED_LANES == 0


def _packed_decode_kernel(plane_ref, total_ref, row_ref, blk_ref, len_ref,
                          q_ref, k_hbm, v_hbm, o_ref, k_scr, v_scr, sem,
                          m_scr, l_scr, acc_scr, *, block, groups, scale):
    """``_decode_kernel`` for rows of ``groups`` packed heads: the same loop
    over the LIVE blocks of the plane, row after row, so a block above a
    row's length is never asked for. A block ``[block, groups * 128]`` of K
    and one of V come in as one DMA each; group ``g``'s query heads (its
    ``_PACKED_ROWS`` rows of ``q_ref``) meet lanes ``128 g .. 128 g + 127``
    of both and no others, so every head reads its own key/value head and
    the block is read from HBM once for all of them. Softmax statistics are
    fp32, in the exp2 domain as the forward kernels'."""
    plane = plane_ref[0]
    total = total_ref[0]
    nbuf = k_scr.shape[0]
    per = _PACKED_ROWS
    # a row of length 0 (a slot that does not decode) has no item
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(i, buf):
        start = pl.multiple_of(blk_ref[i] * block, block)
        return [pltpu.make_async_copy(
            hbm.at[plane, row_ref[i], pl.ds(start, block), :], scr.at[buf],
            sem.at[j, buf])
            for j, (hbm, scr) in enumerate(((k_hbm, k_scr), (v_hbm, v_scr)))]

    for j in range(nbuf - 1):
        @pl.when(j < total)
        def _prime():
            for c in copies(j, j):
                c.start()

    def body(i, _):
        buf = i % nbuf

        @pl.when(i + nbuf - 1 < total)
        def _prefetch():  # into the buffer item i - 1 has finished with
            for c in copies(i + nbuf - 1, (i + nbuf - 1) % nbuf):
                c.start()

        row = row_ref[i]
        live = len_ref[row] - blk_ref[i] * block

        @pl.when(blk_ref[i] == 0)
        def _begin_row():
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        for c in copies(i, buf):
            c.wait()

        @pl.when(live < block)
        def _hide_the_tail():  # 0 x NaN, as in ``_decode_kernel``
            pos = jax.lax.broadcasted_iota(jnp.int32, v_scr.shape[1:], 0)
            v_scr[buf] = jnp.where(
                pos < live, v_scr[buf].astype(jnp.float32),
                0.0).astype(v_scr.dtype)

        def lanes(g):
            return pl.ds(g * PACKED_LANES, PACKED_LANES)
        # every group's products first, then ONE softmax update over all
        # the rows: ten small products that wait for nothing of each other
        # and statistics a few registers wide, not ten chains of
        # product, reduction, product
        s = jnp.concatenate([
            jax.lax.dot_general(q_ref[row, pl.ds(g * per, per), :],
                                k_scr[buf, :, lanes(g)],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for g in range(groups)], axis=0)
        there = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < live
        s = jnp.where(there, s * (scale * _LOG2E), _NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new).astype(v_scr.dtype)
        m_scr[...] = m_new
        l_scr[...] = alpha * l_scr[...] + jnp.sum(
            p.astype(jnp.float32), axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.concatenate([
            jnp.dot(p[g * per:(g + 1) * per], v_scr[buf, :, lanes(g)],
                    preferred_element_type=jnp.float32)
            for g in range(groups)], axis=0)

        @pl.when(live <= block)
        def _end_row():
            o_ref[row] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                          ).astype(o_ref.dtype)
        return 0

    jax.lax.fori_loop(0, total, body, 0)


def _packed_decode_attention_kernel(q, k, v, lengths, plane, scale):
    planes, b, s_max, _, width = k.shape
    groups = width // PACKED_LANES
    per = q.shape[1] // groups
    block = decode_block(s_max)
    lengths, ends, row_of, blk_of = _live_blocks(lengths, b, s_max, block)
    # each group's query heads on sublanes of their own tile
    qg = jnp.pad(q.reshape(b, groups, per, PACKED_LANES),
                 ((0, 0), (0, 0), (0, _PACKED_ROWS - per), (0, 0)))
    rows = groups * _PACKED_ROWS
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    flat = (planes, b, s_max, width)
    out = pl.pallas_call(
        functools.partial(_packed_decode_kernel, block=block, groups=groups,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, rows, PACKED_LANES), jnp.float32),
        in_specs=[smem] * 5 + [vmem, hbm, hbm],
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((_DECODE_BUFFERS, block, width), k.dtype),
            pltpu.VMEM((_DECODE_BUFFERS, block, width), v.dtype),
            pltpu.SemaphoreType.DMA((2, _DECODE_BUFFERS)),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, PACKED_LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="packed_decode_attention",
        interpret=_auto_interpret(),
    )(jnp.asarray(plane, jnp.int32).reshape(1), ends[-1:], row_of, blk_of,
      lengths, qg.reshape(b, rows, PACKED_LANES), k.reshape(flat),
      v.reshape(flat))
    return out.reshape(b, groups, _PACKED_ROWS, PACKED_LANES)[:, :, :per] \
        .reshape(b, groups * per, PACKED_LANES)


def packed_decode_attention(q, k, v, lengths, plane, scale):
    """Single-query attention over a cache whose row is ``groups`` PACKED
    heads of ``lanes`` side by side (models/sambay.py: two key heads, or
    two value heads, are one packed head).

    q        [batch, heads, lanes], ``heads`` a multiple of ``groups``:
             query head i reads packed head ``i // (heads / groups)``
    k, v     the WHOLE cache [planes, batch, s_max, 1, groups * lanes]; only
             the first ``lengths[b]`` positions of row b in plane ``plane``
             are real
    lengths  [batch] int32; a row of length 0 attends to nothing and its
             output means nothing
    scale    the softmax scale (a packed head has no width of its own)

    Returns float32 [batch, heads, lanes]. Two implementations of one
    contract, as ``decode_attention``: on one TPU chip, at ``lanes`` =
    128, a Mosaic kernel that takes the lengths as data and streams each
    row's blocks of ``DECODE_BLOCK`` positions of the plane ONCE for all
    heads, in place, and none above its length; elsewhere an einsum over
    the whole plane under a length mask (``_packed_kernel_selected``
    decides from the call; there is no option). fp32 softmax, products in
    the cache's dtype with fp32 accumulation."""
    if q.ndim != 3 or k.ndim != 5 or k.shape != v.shape or \
            k.shape[3] != 1 or k.shape[4] % q.shape[2] or \
            q.shape[1] % (k.shape[4] // q.shape[2]):
        raise ValueError(f"packed_decode_attention wants q [b, h, lanes] "
                         f"and caches [planes, b, s, 1, groups * lanes], "
                         f"got {q.shape}, {k.shape} and {v.shape}")
    b, h, lanes = q.shape
    s_max = k.shape[2]
    groups = k.shape[4] // lanes
    if _packed_kernel_selected(k.shape, lanes):
        return _packed_decode_attention_kernel(q, k, v, lengths, plane,
                                               scale)
    kp = k[plane].reshape(b, s_max, groups, lanes)
    vp = v[plane].reshape(b, s_max, groups, lanes)
    qg = q.reshape(b, groups, h // groups, lanes)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg, kp,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(s_max, dtype=jnp.int32)[None, None, None, :] \
        < lengths.astype(jnp.int32)[:, None, None, None]
    p = jax.nn.softmax(jnp.where(valid, logits, _NEG_INF), axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p.astype(vp.dtype), vp,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, lanes)


# -- causal attention under a WINDOW (models/window_moe.py's prefill) ---------

def _band_span(qi, block_q, block_k, window):
    """(first, last) k tile that q tile ``qi`` (a number or a traced index)
    reads under the band ``0 <= i - j < window``: its first key is ``window
    - 1`` before its first query (``first`` may be negative: no such tile),
    its last the diagonal's."""
    return (qi * block_q - window + 1) // block_k, \
        ((qi + 1) * block_q - 1) // block_k


def band_tiles(s, block_q, block_k, window):
    """``_band_span`` of every q tile of a sequence of ``s``, as two lists
    (first, last)."""
    spans = [_band_span(qi, block_q, block_k, window)
             for qi in range(s // block_q)]
    return [lo for lo, _ in spans], [hi for _, hi in spans]


def _band_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 block_q, block_k, window, scale, steps):
    """The online forward (``_fwd_kernel``'s tile, statistic for statistic)
    over the k tiles of ONE q tile's band and no other: the grid's last
    axis walks them, ``steps`` a q tile, and the pipeline hands over tile
    ``first + t`` (``_band_index``). A step whose tile lies before the
    sequence or past the diagonal computes nothing; its block index is
    clamped onto a neighbour's, so nothing is fetched for it either."""
    qi, t = pl.program_id(1), pl.program_id(2)
    d = q_ref.shape[-1]
    lanes = math.gcd(128, block_k, d)
    first, last = _band_span(qi, block_q, block_k, window)
    kb = first + t

    @pl.when(t == 0)
    def _begin():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when((kb >= 0) & (kb <= last))
    def _tile():
        k, v = k_ref[0], v_ref[0]
        s = jnp.dot(q_ref[0], k.T, preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where((k_pos <= q_pos) & (q_pos - k_pos < window), s,
                      _NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp2(m - m_new)
        # a row that has met no key of its band yet (its keys begin in the
        # next tile) must not count this tile's masked ones: exp2(0) = 1
        p = jnp.where(s > _NEG_INF / 2,
                      jnp.exp2(s - jnp.tile(m_new, (1, block_k // lanes))),
                      0.0)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * jnp.tile(alpha, (1, d // lanes)) \
            + jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)

    @pl.when(t == steps - 1)
    def _end():
        l = jnp.clip(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / jnp.tile(l, (1, d // lanes))).astype(
            o_ref.dtype)


def _band_index(block_q, block_k, window, group):
    """The k/v block of grid step (head, q tile, t): the band's tile
    ``first + t`` of the key/value head that query head reads, clamped
    into the band (a step outside it names a tile that is there already)."""
    def index(i, j, t):
        first, last = _band_span(j, block_q, block_k, window)
        return i // group, jnp.clip(first + t, 0, last), 0
    return index


def window_attention(q, k, v, window, block=512, interpret=None,
                     scale=None):
    """Causal self-attention in which key ``j`` is visible to query ``i``
    iff ``0 <= i - j < window``: the forward alone (a serving prefill;
    there is no backward here).

    q [b, s, heads, d]; k, v [b, s, kv_heads, d], ``kv_heads`` dividing
    ``heads`` (query head i reads key/value head i // (heads / kv_heads),
    read as it lies and never repeated: the block index does the
    grouping). Numerically ``parallel.ring.full_attention`` under the band
    mask: fp32 softmax statistics, matmuls in the input dtype with fp32
    accumulation. The kernel visits the band's tiles only, at most
    ``(window + block - 2) // block + 2`` a q tile however long the
    sequence is (two at ``block == window``), through the pipeline's own
    blocks, so VMEM holds one tile of each operand. Sequences that no
    block divides are end-padded as ``flash_attention`` pads them (the
    causal mask hides the pad's keys), ``d`` to whole lane tiles when
    compiled. ``scale`` is the softmax scale where it is not ``d ** -0.5``."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    if k.shape != v.shape or k.shape[1] != s or h % hk or window < 1:
        raise ValueError(f"window_attention: q {q.shape}, k {k.shape}, "
                         f"v {v.shape}, window {window}")
    interpret = _auto_interpret() if interpret is None else interpret
    scale = d ** -0.5 if scale is None else scale
    blk = call_block(block, s, compiled=not interpret)
    pad_s = -s % blk
    pad_d = 0 if interpret else -d % 128
    if pad_s or pad_d:
        pads = ((0, 0), (0, pad_s), (0, 0), (0, pad_d))
        q, k, v = jnp.pad(q, pads), jnp.pad(k, pads), jnp.pad(v, pads)
    sp, dp = s + pad_s, d + pad_d
    first, last = band_tiles(sp, blk, blk, window)
    steps = max(hi - lo for lo, hi in zip(first, last)) + 1
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sp, dp)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, sp, dp)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, sp, dp)
    kv_spec = pl.BlockSpec((1, blk, dp),
                           _band_index(blk, blk, window, h // hk))
    lanes = math.gcd(128, blk, dp)
    out = pl.pallas_call(
        functools.partial(_band_kernel, block_q=blk, block_k=blk,
                          window=window, scale=scale, steps=steps),
        grid=(b * h, sp // blk, steps),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        in_specs=[pl.BlockSpec((1, blk, dp), lambda i, j, t: (i, j, 0)),
                  kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, blk, dp), lambda i, j, t: (i, j, 0)),
        out_shape=_out_struct((b * h, sp, dp), q.dtype, qf, kf, vf),
        scratch_shapes=[pltpu.VMEM((blk, lanes), jnp.float32),
                        pltpu.VMEM((blk, lanes), jnp.float32),
                        pltpu.VMEM((blk, dp), jnp.float32)],
        name="window_attention",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sp, dp).transpose(0, 2, 1, 3)[:, :s, :, :d]
