"""Selective state-space scan (Mamba-1, arXiv:2312.00752) - the recurrence
of models/sambay.py's mixer, in the two forms serving needs:

  * ``selective_scan`` for prefill: every position of a padded prompt, the
    state carried from position to position (on one TPU chip a Mosaic
    kernel, elsewhere one ``lax.scan``);
  * ``decode_update`` for decode: the one-token update of the rows of a
    pass, in place in the cache's stacked state (likewise a kernel there).

Both compute, for every channel ``c`` and state ``n`` (B and C are shared
by all channels, ``dt`` is the channel's own),

    S_t[n, c] = exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n S_t[n, c] C_t[n]

(the D x_t skip and the gate are the model's). Unlike Mamba-2 (ops/ssm.py:
one scalar decay a head, so that a chunk is a masked matrix product) the
decay here differs for every (state, channel) pair: a chunk has no matmul
form, and the recurrence is elementwise work over the state whatever is
done. A position with ``dt == 0`` holds the state and adds nothing: how a
right-padded prompt is kept out of the state it leaves behind. Decays,
``dt`` and the state are float32.

The state is held STATE-MAJOR, ``[.., n, channels]``: on a TPU the last two
dimensions of an array are tiled (8, 128), and ``[channels, 16]`` would pad
its 16 lanes to 128, eight times the memory and the traffic.

``literal_scan`` is the definition, position by position in a Python
loop, built from ``state_step`` (one token, as written above); the tests
hold the other forms to it.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_interpret, _on_one_tpu_chip


def state_step(state, x, dt, a, b, c):
    """One token for every row: state [bt, n, ch] float32, x and dt
    [bt, ch], a [n, ch] (negative), b and c [bt, n]. Returns (new state,
    y [bt, ch] float32)."""
    f32 = jnp.float32
    x, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    decay = jnp.exp(dt[:, None, :] * a)
    state = decay * state + (dt * x)[:, None, :] * b[:, :, None]
    return state, jnp.sum(state * c[:, :, None], axis=1)


def literal_scan(x, dt, a, b, c, state=None):
    """The recurrence as written, one position at a time, in float32.

    x and dt [bt, s, ch], a [n, ch] (negative), b and c [bt, s, n];
    ``state`` [bt, n, ch] or None for zeros. Returns (y [bt, s, ch],
    final state)."""
    if state is None:
        state = jnp.zeros((x.shape[0],) + a.shape, jnp.float32)
    ys = []
    for t in range(x.shape[1]):
        state, y = state_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), state


#: positions of the scan's loop body: the loop's own cost a position (a
#: few fusions launched for 80 vector registers of work) is what a prefill
#: pays for, so several positions share one trip
SCAN_UNROLL = 8
#: the scan kernel's blocks: channels (lanes) a program, positions a block
_SCAN_CHANNELS = 512
_SCAN_POSITIONS = 128
_LANES = 128


def _scan_kernel_selected(channels, states, dtype=jnp.float32):
    """Whether ``selective_scan`` runs as the Mosaic kernel. Decided from
    what the call can see, as ``flash_attention._decode_kernel_selected``
    decides (no option): a TPU backend with the program on one chip, and a
    state whose ``[states, channels]`` planes are whole (8, 128) tiles.
    Everything else takes the ``lax.scan``: the CPU backend, a mesh."""
    return _on_one_tpu_chip() and dtype == jnp.float32 and \
        channels % _LANES == 0 and states % 8 == 0


def _scan_kernel(dt_ref, dtx_ref, a_ref, b_ref, c_ref, was_ref, y_ref,
                 now_ref, state_scr, *, positions, copies):
    """One block of ``positions`` positions for one row's block of
    channels: the state ``[states, channels]`` stays in vector registers
    from position to position and in ``state_scr`` from block to block (the
    grid's last axis walks the sequence). ``b_ref`` and ``c_ref`` hold B_t
    and C_t along the SUBLANES, one lane tile wide (the state's own
    orientation, made outside: a kernel cannot turn a row of 16 lanes into
    a column for nothing); ``copies`` of it side by side are a block."""
    t_blk = pl.program_id(2)

    @pl.when(t_blk == 0)
    def _begin():
        state_scr[...] = was_ref[0]

    a = a_ref[...]

    def wide(ref, t):
        tile = ref[0, t]
        return tile if copies == 1 else jnp.concatenate([tile] * copies, 1)

    def trip(i, state):
        for j in range(SCAN_UNROLL):
            t = i * SCAN_UNROLL + j
            dt = dt_ref[0, pl.ds(t, 1), :]
            state = jnp.exp(dt * a) * state \
                + dtx_ref[0, pl.ds(t, 1), :] * wide(b_ref, t)
            y_ref[0, pl.ds(t, 1), :] = jnp.sum(
                state * wide(c_ref, t), axis=0, keepdims=True)
        return state
    state = jax.lax.fori_loop(0, positions // SCAN_UNROLL, trip,
                              state_scr[...])
    state_scr[...] = state

    @pl.when(t_blk == pl.num_programs(2) - 1)
    def _end():
        now_ref[0] = state


def _selective_scan_kernel(x, dt, a, b, c, state):
    """``selective_scan`` as the Mosaic kernel: grid (row, block of
    channels, block of positions). The sequence is end-padded to whole
    blocks with ``dt == 0``, which holds the state."""
    f32 = jnp.float32
    bt, s, ch = x.shape
    n = a.shape[0]
    cb = _SCAN_CHANNELS if ch % _SCAN_CHANNELS == 0 else _LANES
    tb = _SCAN_POSITIONS
    pad = -s % tb
    dt = dt.astype(f32)
    dtx = dt * x.astype(f32)
    # B_t and C_t down the sublanes, a lane tile wide
    b, c = (jnp.broadcast_to(t.astype(f32)[..., None], (bt, s, n, _LANES))
            for t in (b, c))
    if pad:
        dt, dtx, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (t.ndim - 2)) for t in (dt, dtx, b, c))
    sp = s + pad
    row = pl.BlockSpec((1, tb, cb), lambda i, j, t: (i, t, j))
    col = pl.BlockSpec((1, tb, n, _LANES), lambda i, j, t: (i, t, 0, 0))
    held = pl.BlockSpec((1, n, cb), lambda i, j, t: (i, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, positions=tb, copies=cb // _LANES),
        grid=(bt, ch // cb, sp // tb),
        in_specs=[row, row, pl.BlockSpec((n, cb), lambda i, j, t: (0, j)),
                  col, col, held],
        out_specs=(row, held),
        out_shape=(jax.ShapeDtypeStruct((bt, sp, ch), f32),
                   jax.ShapeDtypeStruct((bt, n, ch), f32)),
        scratch_shapes=[pltpu.VMEM((n, cb), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="selective_scan",
        interpret=_auto_interpret(),
    )(dt, dtx, a.astype(f32), b, c, state)
    return y[:, :s], state


def selective_scan(x, dt, a, b, c, state=None):
    """``literal_scan`` for a prefill (shapes and results as there). Two
    implementations of one contract, picked from the call itself
    (``_scan_kernel_selected``): on one TPU chip a Mosaic kernel that keeps
    a block of 512 channels' state in vector registers from position to
    position (``_scan_kernel``); elsewhere ONE ``lax.scan`` over the
    positions, ``SCAN_UNROLL`` a trip, which is the definition's loop and
    what the CPU runs (on the chip a trip is some thirty small fusions
    around 80 registers of work: the loop's own cost is most of it)."""
    f32 = jnp.float32
    if state is None:
        state = jnp.zeros((x.shape[0],) + a.shape, f32)
    if _scan_kernel_selected(x.shape[-1], a.shape[0], state.dtype):
        return _selective_scan_kernel(x, dt, a, b, c, state)

    def step(s, inp):
        return state_step(s, *inp[:2], a, *inp[2:])
    xs = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, b, c))
    state, y = jax.lax.scan(step, state, xs,
                            unroll=min(SCAN_UNROLL, x.shape[1]))
    return jnp.moveaxis(y, 0, 1), state


def _update_kernel_selected(shape, dtype):
    """Whether ``decode_update`` over a state of ``shape`` ``[planes, bt,
    states, channels]`` runs as the Mosaic kernel: as
    ``_scan_kernel_selected``, from what the call can see."""
    return _scan_kernel_selected(shape[3], shape[2], dtype)


def _update_kernel(layer_ref, total_ref, order_ref, dt_ref, dtx_ref, a_ref,
                   b_ref, c_ref, was_hbm, now_hbm, y_ref, in_scr, out_scr,
                   sem, *, piece):
    """Every decoding row's state through VMEM once (``ops/ssm.py``
    ``_update_kernel``'s loop: item i is row ``order_ref[i]``,
    ``total_ref[0]`` items, the rows of the mask, so a row outside it is
    neither read nor written; the next row's read is started before this
    row is touched and a slot's write is awaited two rows later). A row
    ``[states, channels]`` is updated and reduced against C ``piece``
    lanes at a time, a few registers each. ``was_hbm`` and ``now_hbm`` are
    one buffer (the call aliases them); ``b_ref`` and ``c_ref`` hold B and
    C down the sublanes, a lane tile wide, as the scan kernel's do."""
    layer = layer_ref[0]
    total = total_ref[0]
    channels = in_scr.shape[2]
    copies = piece // _LANES
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
        b = jnp.concatenate([b_ref[row]] * copies, axis=1)
        c = jnp.concatenate([c_ref[row]] * copies, axis=1)
        for j in range(channels // piece):
            cols = pl.ds(j * piece, piece)
            now = jnp.exp(dt_ref[pl.ds(row, 1), cols] * a_ref[:, cols]) \
                * in_scr[slot, :, cols] + dtx_ref[pl.ds(row, 1), cols] * b
            out_scr[slot, :, cols] = now
            y_ref[pl.ds(row, 1), cols] = jnp.sum(now * c, axis=0,
                                                 keepdims=True)
        write(i).start()
        return 0

    jax.lax.fori_loop(0, total, body, 0)
    for back in (2, 1):
        @pl.when(total >= back)
        def _drain():
            write(total - back).wait()


def _decode_update_kernel(ssm, layer, x, dt, a, b, c, mask):
    """``decode_update`` as the Mosaic kernel. The stacked state goes in
    WHOLE (``memory_space=ANY``) with the plane as a scalar and comes out
    as the same buffer: a custom call cannot fuse a slice, and a sliced
    operand would be copied."""
    _, bt, n, ch = ssm.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    if mask is None:
        mask = jnp.ones((bt,), bool)
    # the rows of the mask first, in their order
    order = jnp.argsort(~mask, stable=True).astype(jnp.int32)
    b, c = (jnp.broadcast_to(t.astype(f32)[..., None], (bt, n, _LANES))
            for t in (b, c))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    piece = _SCAN_CHANNELS if ch % _SCAN_CHANNELS == 0 else _LANES
    return pl.pallas_call(
        functools.partial(_update_kernel, piece=piece),
        out_shape=(jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((bt, ch), f32)),
        in_specs=[smem] * 3 + [vmem] * 5 + [hbm],
        out_specs=(hbm, vmem),
        scratch_shapes=[pltpu.VMEM((2, n, ch), f32),
                        pltpu.VMEM((2, n, ch), f32),
                        pltpu.SemaphoreType.DMA((2, 2))],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        name="mamba1_state_update",
        interpret=_auto_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.sum(mask, dtype=jnp.int32).reshape(1), order, dt,
      dt * x.astype(f32), a.astype(f32), b, c, ssm)


def decode_update(ssm, layer, x, dt, a, b, c, mask=None):
    """The decode step's one token for plane ``layer`` of the WHOLE state
    ``ssm`` ``[planes, bt, n, ch]`` float32 (the cache's array, donated by
    the caller): ``state_step`` on the rows of ``mask`` [bt] bool (all rows
    when None), written back into ``ssm``. Returns (ssm, y [bt, ch]
    float32); the state of a row outside the mask is BIT-IDENTICAL, its y
    means nothing.

    Two implementations of one contract, picked from the call itself
    (``_update_kernel_selected``). The update is bound by moving the state
    (327,680 B a row and layer at the published widths) and what decides
    its time is how often: under XLA ``state_step`` and the masked write
    back moved each slab more than twice over (the cell's first trace, PR
    48: 2.4 times the bytes the update needs, a third of the HBM peak); the
    Mosaic kernel brings each decoding row's state into VMEM once, updates
    it, reduces ``y`` from it there and sends it back
    (``ops/ssm.decode_update``'s finding for Mamba-2, PR 34)."""
    if _update_kernel_selected(ssm.shape, ssm.dtype):
        return _decode_update_kernel(ssm, layer, x, dt, a, b, c, mask)
    was = ssm[layer]
    now, y = state_step(was, x, dt, a, b, c)
    if mask is not None:
        now = jnp.where(mask[:, None, None], now, was)
    return ssm.at[layer].set(now), y
