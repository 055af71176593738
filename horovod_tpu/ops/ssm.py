"""Selective state-space scan (Mamba-2) — the recurrence of a hybrid
block's mixer, in the two forms serving needs (docs/serving.md):

  * ``chunked_scan`` for prefill: the sequence is cut into chunks; inside
    a chunk the recurrence is a masked, decay-weighted [chunk, chunk]
    product (matrix products on the MXU), and only the [heads, head,
    state] state crosses chunks, one short ``lax.scan`` step a chunk.
  * ``decode_update`` for decode: the one-token update of the rows of a
    pass, in place in the cache's stacked state.

Both compute, per head with its group's B and C,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(the D x_t skip, the gate and the norm are the model's). A position with
``dt == 0`` holds the state and adds nothing — how a right-padded prompt
is kept out of the state it leaves behind. Decays, ``dt`` and the state
are float32; the matrix products take their operands in ``x``'s dtype
and accumulate in float32.

``literal_scan`` is the definition, position by position, built from
``state_step`` (one token, as written above); the tests hold the other
forms to it. The chunked form is plain jax.numpy: a handful of batched
matmuls XLA already maps to the MXU. The decode update has nothing to
compute and 4 MB a row and layer to move, so it is worth what it moves:
``state_step`` under XLA moves each layer's state THREE times a step (a
fusion that reads the old state and writes the new, and a second that
reads it again for ``y``), so on a TPU ``decode_update`` is a Mosaic
kernel that brings a row's state into VMEM once and takes ``y`` from it
there; ``state_step`` stays as the definition, and as what the CPU runs.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_interpret, _on_one_tpu_chip


def _per_head(t, heads):
    """[..., groups, n] -> [..., heads, n]: head j reads group
    j // (heads / groups)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def literal_scan(x, dt, a, b, c, state=None):
    """The recurrence as written, one position at a time, in float32.

    x [bt, s, h, p], dt [bt, s, h], a [h] (negative), b and c
    [bt, s, g, n]; ``state`` [bt, h, p, n] or None for zeros.
    Returns (y [bt, s, h, p], final state)."""
    bt, _, h, p = x.shape
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    b, c = _per_head(b, h), _per_head(c, h)
    if state is None:
        state = jnp.zeros((bt, h, p, b.shape[-1]), f32)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        return state_step(s, x_t, dt_t, a, b_t, c_t)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c))
    state, y = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state


def state_step(state, x, dt, a, b, c):
    """One token for every row: state [bt, h, p, n] float32, x [bt, h, p],
    dt [bt, h], a [h], b and c [bt, g, n] (or already per head).
    Returns (new state, y [bt, h, p] float32)."""
    f32 = jnp.float32
    h = x.shape[-2]
    x, dt = x.astype(f32), dt.astype(f32)
    b, c = _per_head(b.astype(f32), h), _per_head(c.astype(f32), h)
    decay = jnp.exp(dt * a)
    state = decay[..., None, None] * state + \
        (dt[..., None] * x)[..., None] * b[..., None, :]
    return state, jnp.einsum("bhpn,bhn->bhp", state, c)


#: the update kernel keeps two rows coming in and two going out in VMEM
_UPDATE_ROW_BYTES = 8 << 20


def _update_kernel_selected(shape, dtype):
    """Whether ``decode_update`` over a state of ``shape`` ``[layers, bt,
    heads, head, state]`` runs as the Mosaic kernel. Decided from what the
    call can see, as ``flash_attention._decode_kernel_selected`` decides
    (no option): a TPU backend with the program on one chip, a float32
    state whose ``[head, state]`` planes are whole tiles (8 sublanes, 128
    lanes), and rows that fit VMEM four at a time. Everything else takes
    ``state_step``: the CPU backend, a mesh of several chips."""
    _, _, h, p, n = shape
    return _on_one_tpu_chip() and dtype == jnp.float32 and \
        p % 8 == 0 and n % 128 == 0 and h * p * n * 4 <= _UPDATE_ROW_BYTES


def _update_kernel(layer_ref, total_ref, order_ref, decay_ref, dtx_ref,
                   b_ref, c_ref, was_hbm, now_hbm, y_ref, in_scr, out_scr,
                   sem):
    """Every decoding row's state through VMEM once: item i of the loop is
    row ``order_ref[i]`` (``total_ref[0]`` items: the rows of the mask, so
    a row outside it is neither read nor written). A row comes in as one
    DMA and goes out as one, each head's plane ``[head, state]`` updated
    and reduced against C between the two; the next row's read is started
    before this row is touched and a slot's write is awaited two rows
    later, so reads, arithmetic and writes overlap. (The arithmetic is
    hidden: a kernel that only copies takes the same time, whether a DMA
    is a row or an eighth of one - PERF.md §6, PR 34.)

    ``was_hbm`` and ``now_hbm`` are one buffer (the call aliases them).
    ``dtx_ref`` and ``y_ref`` are ``[bt, head, heads]``: a head's ``dt x``
    and its ``y`` vary along the plane's sublanes, so they are kept as
    columns; ``decay_ref`` is ``[bt * heads]`` scalars."""
    layer = layer_ref[0]
    total = total_ref[0]
    h = in_scr.shape[1]
    per_group = h // b_ref.shape[1]
    # a row outside the pass: its y means nothing, and is zero
    y_ref[...] = jnp.zeros_like(y_ref)

    def read(i):
        return pltpu.make_async_copy(was_hbm.at[layer, order_ref[i]],
                                     in_scr.at[i % 2], sem.at[0, i % 2])

    def write(i):
        return pltpu.make_async_copy(out_scr.at[i % 2],
                                     now_hbm.at[layer, order_ref[i]],
                                     sem.at[1, i % 2])

    @pl.when(total > 0)
    def _prime():
        read(0).start()

    def body(i, _):
        @pl.when(i + 1 < total)
        def _prefetch():  # into the slot row i - 1 has been computed from
            read(i + 1).start()

        read(i).wait()

        @pl.when(i >= 2)
        def _slot_is_free():  # row i - 2 has left this slot
            write(i - 2).wait()

        row, slot = order_ref[i], i % 2
        for head in range(h):
            g = head // per_group
            now = decay_ref[row * h + head] * in_scr[slot, head] + \
                dtx_ref[row, :, head:head + 1] * b_ref[row, g:g + 1, :]
            out_scr[slot, head] = now
            y_ref[row, :, head:head + 1] = jnp.sum(
                now * c_ref[row, g:g + 1, :], axis=1, keepdims=True)
        write(i).start()
        return 0

    jax.lax.fori_loop(0, total, body, 0)
    for back in (2, 1):
        @pl.when(total >= back)
        def _drain():
            write(total - back).wait()


def _decode_update_kernel(ssm, layer, x, dt, a, b, c, mask):
    """``decode_update`` as the Mosaic kernel. The stacked state goes in
    WHOLE (``memory_space=ANY``) with the layer as a scalar and comes out
    as the same buffer: a custom call cannot fuse a slice, and a sliced
    operand would be copied."""
    _, bt, h, p, n = ssm.shape
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    if mask is None:
        mask = jnp.ones((bt,), bool)
    # the rows of the mask first, in their order
    order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    ssm, y = pl.pallas_call(
        _update_kernel,
        out_shape=(jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((bt, p, h), f32)),
        in_specs=[smem] * 4 + [vmem] * 3 + [hbm],
        out_specs=(hbm, vmem),
        scratch_shapes=[pltpu.VMEM((2, h, p, n), f32),
                        pltpu.VMEM((2, h, p, n), f32),
                        pltpu.SemaphoreType.DMA((2, 2))],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="state_update",
        interpret=_auto_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.sum(mask, dtype=jnp.int32).reshape(1), order,
      jnp.exp(dt * a).reshape(bt * h),
      jnp.swapaxes(dt[..., None] * x, 1, 2), b.astype(f32), c.astype(f32),
      ssm)
    return ssm, jnp.swapaxes(y, 1, 2)


def decode_update(ssm, layer, x, dt, a, b, c, mask=None):
    """The decode step's one token for layer ``layer`` of the WHOLE state
    ``ssm`` ``[layers, bt, h, p, n]`` float32 (the cache's array, donated
    by the caller): ``state_step`` on the rows of ``mask`` [bt] bool (all
    rows when None), written back into ``ssm``. x [bt, h, p], dt [bt, h],
    a [h], b and c [bt, g, n]. Returns (ssm, y [bt, h, p] float32); the
    state of a row outside the mask is BIT-IDENTICAL, its y means nothing.

    Two implementations of one contract, picked from the call itself
    (``_update_kernel_selected``). The update is bound by moving the state,
    4 MB a row and layer at Falcon-H1's widths, and what decides its time
    is how often: ``state_step`` under XLA compiles to TWO fusions a layer,
    one that reads the old slab to write the new and one that reads it
    again to reduce ``y`` out of it (three slab moves where two are
    needed; however the two are written, the read-out is not fused into
    an in-place ``dynamic-update-slice``: PERF.md §6, PR 34). The Mosaic
    kernel (``_update_kernel``) brings each decoding row's state into VMEM
    once, updates it, reduces ``y`` from it there and sends it back."""
    if _update_kernel_selected(ssm.shape, ssm.dtype):
        return _decode_update_kernel(ssm, layer, x, dt, a, b, c, mask)
    was = ssm[layer]
    now, y = state_step(was, x, dt, a, b, c)
    if mask is not None:
        now = jnp.where(mask[:, None, None, None], now, was)
    return ssm.at[layer].set(now), y


def chunked_scan(x, dt, a, b, c, chunk, state=None):
    """The same recurrence over whole chunks of ``chunk`` positions (the
    sequence length must be a multiple; pad with ``dt == 0``).

    Shapes as ``literal_scan``. Returns (y [bt, s, h, p] float32, final
    state [bt, h, p, n] float32)."""
    bt, s, h, p = x.shape
    g, n = b.shape[-2:]
    if s % chunk:
        raise ValueError(f"chunked_scan: length {s} is not a multiple of "
                         f"the chunk {chunk}")
    nc, per = s // chunk, h // g
    f32, lo = jnp.float32, x.dtype
    dt = dt.astype(f32)
    # log-decay from the chunk's start up to and including position l
    cum = jnp.cumsum((dt * a).reshape(bt, nc, chunk, h), axis=2)
    xdt = (x.astype(f32) * dt[..., None]).astype(lo) \
        .reshape(bt, nc, chunk, g, per, p)
    bc = b.astype(lo).reshape(bt, nc, chunk, g, n)
    cc = c.astype(lo).reshape(bt, nc, chunk, g, n)
    # heads lead, positions last: [b, c, g, r, l]
    cum_h = jnp.moveaxis(cum.reshape(bt, nc, chunk, g, per), 2, -1)

    # within a chunk: y_l += sum_{m <= l} exp(cum_l - cum_m) (C_l . B_m)
    # dt_m x_m, a masked [chunk, chunk] product per head
    scores = jnp.einsum("bclgn,bcmgn->bcglm", cc, bc,
                        preferred_element_type=f32)
    seg = cum_h[..., :, None] - cum_h[..., None, :]    # [b,c,g,r,l,m]
    # masked before exp: above the diagonal cum_l - cum_m is positive
    weights = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)),
                                seg, -jnp.inf)) * scores[:, :, :, None]
    y = jnp.einsum("bcgrlm,bcmgrp->bclgrp", weights.astype(lo), xdt,
                   preferred_element_type=f32)

    # what each chunk adds to the state by its end, from a zero start
    to_end = jnp.exp(cum_h[..., -1:] - cum_h)          # [b,c,g,r,m]
    added = jnp.einsum("bcgrm,bcmgrp,bcmgn->bcgrpn", to_end.astype(lo),
                       xdt, bc, preferred_element_type=f32)
    whole = jnp.exp(cum_h[..., -1])                    # [b,c,g,r]
    if state is None:
        state = jnp.zeros((bt, h, p, n), f32)

    def carry(s, inp):
        add, dec = inp
        return dec[..., None, None] * s + add, s       # emits the state
    state, starts = jax.lax.scan(                       # at chunk START
        carry, state.reshape(bt, g, per, p, n),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                 # [b,c,g,r,p,n]
    # across chunks: y_l += exp(cum_l) C_l . S_start
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", cc, starts.astype(lo),
                       preferred_element_type=f32) \
        * jnp.moveaxis(jnp.exp(cum_h), -1, 2)[..., None]
    return y.reshape(bt, s, h, p), state.reshape(bt, h, p, n)


def causal_conv(x, taps, bias, window=None):
    """Depthwise causal convolution along the sequence: x [bt, s, ch],
    taps [k, ch] (tap j weighs the input k-1-j positions back), bias [ch];
    ``window`` [bt, k-1, ch] is what precedes x (zeros when None).
    Float32 result."""
    k = taps.shape[0]
    s = x.shape[1]
    if window is None:
        window = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    padded = jnp.concatenate([window.astype(x.dtype), x], axis=1) \
        .astype(jnp.float32)
    taps = taps.astype(jnp.float32)
    return sum(taps[j] * padded[:, j:j + s] for j in range(k)) \
        + bias.astype(jnp.float32)
