"""The routed experts' grouped SwiGLU as ONE Mosaic kernel (models/moe.py
``experts`` calls it; docs/serving.md).

``t * k`` assignments lie sorted by expert, group e the rows of expert e,
and every row wants

    out = (silu(x gate_e) * (x up_e)) down_e

When 64 rows decode the rows are few (2-4 a group) and the weights are
everything; when one long prompt is prefilled at Laguna-XS.2's widths the
rows are 32,768, 128 a group, and their products take as long as the read.
The kernel is built around ONE read of the weights at every shape:

  * the stacks stay in HBM as they are held, ``[E, d, f]``, ``[E, d, f]``,
    ``[E, f, d]``; an expert's three matrices are three contiguous runs
    and come into VMEM as three DMAs, whole, into one of two slots;
  * the loop runs over the experts that HAVE rows (``order``, ``total``:
    an expert no row was routed to costs no read), and the next expert's
    three copies are started before this one's are awaited, so a copy is
    always queued behind the one in flight: only the very first expert's
    read is exposed;
  * an expert's rows are taken as WINDOWS of ``WINDOW`` rows from
    the 16-row tile its group starts in (a bfloat16 tile's sublanes: no
    unaligned slice), all three products and the SwiGLU happen on the
    window in VMEM, float32 accumulation, the hidden rounded to the rows'
    dtype once and never written to HBM. A group longer than a window
    takes another;
  * WHERE THE ROWS LIVE follows from the shape (``resident``): up to 32 MB
    of rows and outputs (4,096 assignments at a hidden width of 2,048)
    are in VMEM for the whole call. Past that they stay in HBM and are
    STREAMED through VMEM by expert: a window of rows is copied in while
    the window before it is multiplied (the next expert's first window
    too, as its matrices are), and a window of outputs is copied out
    behind the products, two slots each way;
  * a window is written WHOLE: its expert's rows, zeros behind them, and
    in front of them the rows that experts before it own in the tile the
    group starts in, which the kernel carries in VMEM from one expert to
    the next (``border``). So no output is read back, and the streamed
    writes, which are made one at a time and in order, may overlap: what
    a window writes behind its group is written again, later, by the
    expert that owns it. Rows behind the last group are zeroed first.

A window is whole wherever its group ends, so the last one may reach past
the last row: whoever gathers the sorted rows leaves ``room`` behind them
(``models/moe.experts`` gathers them so; the outputs' inverse gather never
reads it), and no padded copy of rows or outputs is ever made.

What a streamed call pays beside the read is the rows' own traffic, a
whole window each way an expert at least, and not the arithmetic: on the
v5e windows of 16 to 96 rows are within 3% of each other from 8,192 to
32,768 assignments, 32 the best or its equal at each, and resident calls
read the same at 32, 64 and 128, so ONE window length serves every shape
(docs/benchmarks.md). The matrices are copied at the BACKGROUND priority:
queued at the rows' own, a window of rows waits out the 6-19 MB of
matrices in front of it, and a group of several windows pays for each
(3.63 ms -> 2.74 ms a layer at 32,768 assignments and windows of 64).

``selected`` says from what a call can see whether this kernel or
``jax.lax.ragged_dot`` computes the products, ``resident`` where the rows
live: there is no option.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_interpret, _on_one_tpu_chip

#: rows of a bfloat16 tile: where a window may start
_TILE = 16
#: the most the rows and the outputs may take of VMEM together and stay
#: resident: 4,096 assignments at a hidden width of 2,048
_RESIDENT_BYTES = 32 << 20
#: two experts in VMEM: the most their six matrices may take
_EXPERTS_BYTES = 48 << 20
#: copies of zeros onto the rows behind the last group in flight at once
_ZEROING = 8
#: rows the three products are made on at once
WINDOW = 32


def selected(assignments, stack_shape, dtype):
    """Whether the grouped SwiGLU of ``assignments`` rows over stacks
    ``gate``/``up`` of ``stack_shape`` ``[E, d, f]`` (``down`` ``[E, f,
    d]``) and rows of ``dtype`` runs as the Mosaic kernel. Decided from
    what the call can see, as ``flash_attention._decode_kernel_selected``
    decides: a TPU backend with the program on ONE chip, bfloat16, ``d``
    and ``f`` in whole 128-lane tiles, two experts at once in VMEM. The
    number of rows decides nothing here (``grouped_swiglu`` streams what
    VMEM does not hold). Everything else takes ``jax.lax.ragged_dot``:
    the CPU backend, a mesh of several devices, float32, odd widths."""
    _, d, f = stack_shape
    return _on_one_tpu_chip() and dtype == jnp.bfloat16 and \
        d % 128 == 0 and f % 128 == 0 and assignments > 0 and \
        2 * 3 * d * f * 2 <= _EXPERTS_BYTES


def room(assignments):
    """Rows ``grouped_swiglu`` wants behind ``assignments`` sorted rows:
    up to a whole tile, then a window that no group reaches (a window is
    whole wherever its group ends, so the last may reach past the last
    row). Whoever gathers the rows gathers these with them, and a padded
    copy is never made."""
    return -assignments % _TILE + WINDOW


def resident(assignments, d, itemsize):
    """Whether all rows and all outputs stay in VMEM for the whole call
    (else they stay in HBM and windows of them pass through)."""
    return 2 * assignments * d * itemsize <= _RESIDENT_BYTES


def _kernel(order_ref, total_ref, start_ref, x_ref, gate_hbm, up_hbm,
            down_hbm, out_ref, gate_scr, up_scr, down_scr, sem, border,
            *stream):
    """Item j of the loop is expert ``order_ref[j]`` (``total_ref[0]``
    items: the experts with rows), its rows ``start_ref[e]`` up to
    ``start_ref[e + 1]``. ``sem`` is ``[matrix, slot]``. ``x_ref`` and
    ``out_ref`` end in a window of rows that no group reaches, so no
    window has to be pulled back. ``stream`` is empty where they are in
    VMEM; where they are in HBM it is two slots for a window of
    rows, two for a window of outputs, and a DMA semaphore a slot each
    way."""
    total = total_ref[0]
    last = start_ref.shape[0] - 2
    rows_end = out_ref.shape[0] - WINDOW
    d = out_ref.shape[1]
    if stream:
        x_scr, o_scr, x_sem, o_sem = stream

    # scalars go through jax.lax: every jnp operator on a traced value
    # (//, %, where, minimum) is a jit of its own to trace and to lower,
    # which nine serving programs a process would pay for (PERF.md §6)
    div, rem = jax.lax.div, jax.lax.rem

    def tiles(n):
        return div(n, _TILE) * _TILE

    def windows_over(n):
        return div(n + WINDOW - 1, WINDOW)

    def fetch(at, slot):
        return pltpu.make_async_copy(
            x_ref.at[pl.ds(pl.multiple_of(at, _TILE), WINDOW)],
            x_scr.at[slot], x_sem.at[slot])

    def flush(at, slot):
        return pltpu.make_async_copy(
            o_scr.at[slot],
            out_ref.at[pl.ds(pl.multiple_of(at, _TILE), WINDOW)],
            o_sem.at[slot])

    def copies(j):
        e, slot = order_ref[j], rem(j, 2)
        return [pltpu.make_async_copy(hbm.at[e], scr.at[slot],
                                      sem.at[i, slot])
                for i, (hbm, scr) in enumerate(((gate_hbm, gate_scr),
                                                (up_hbm, up_scr),
                                                (down_hbm, down_scr)))]

    # the matrices are copied in the BACKGROUND (priority 1): a window of
    # rows or outputs queued behind 6-19 MB of them would wait them out
    @pl.when(total > 0)
    def _prime():
        for c in copies(0):
            c.start(priority=1)
        if stream:
            fetch(tiles(start_ref[order_ref[0]]), 0).start()

    # rows behind the last group leave as zeros: from the first tile no
    # group reaches (the last group's last window zeroes its own tile)
    behind = tiles(start_ref[last + 1] + _TILE - 1)
    zeroings = windows_over(rows_end - behind)
    zeros = jnp.zeros((WINDOW, d), out_ref.dtype)
    if stream:  # while the first expert's matrices arrive
        o_scr[0] = zeros

        def zero(i, _):
            @pl.when(i >= _ZEROING)
            def _room():
                flush(0, 0).wait()
            flush(behind + i * WINDOW, 0).start()
            return 0

        jax.lax.fori_loop(0, zeroings, zero, 0)
        jax.lax.fori_loop(0, jax.lax.min(zeroings, _ZEROING),
                          lambda i, _: flush(0, 0).wait() or 0, 0)
    else:
        def zero(i, _):  # a loop: the stores unrolled are 1 MB of code
            out_ref[pl.ds(pl.multiple_of(behind + i * WINDOW, _TILE),
                          WINDOW), :] = zeros
            return 0

        jax.lax.fori_loop(0, zeroings, zero, 0)

    def expert(j, n):
        """``n`` counts the windows made so far: window n of the call
        passes through slot ``n % 2`` each way."""
        @pl.when(j + 1 < total)
        def _prefetch():  # into the slot expert j - 1 was computed from
            for c in copies(j + 1):
                c.start(priority=1)

        e, slot = order_ref[j], rem(j, 2)
        lo, hi = start_ref[e], start_ref[e + 1]
        first = tiles(lo)
        windows = windows_over(hi - first)
        if stream:  # the tile the next expert's group starts in
            after = tiles(start_ref[order_ref[jax.lax.min(j + 1, last)]])
        for c in copies(j):
            c.wait()

        def rows(w, n):
            at = pl.multiple_of(first + w * WINDOW, _TILE)
            if stream:
                more = w + 1 < windows

                here, there = rem(n, 2), rem(n + 1, 2)

                @pl.when(more | (j + 1 < total))
                def _next():  # behind the copy this window waits for
                    fetch(jax.lax.select(more, at + WINDOW, after),
                          there).start()

                fetch(at, here).wait()
                x, dest = x_scr[here], o_scr.at[here]
            else:
                x, dest = x_ref[pl.ds(at, WINDOW), :], \
                    out_ref.at[pl.ds(at, WINDOW)]
            g = jnp.dot(x, gate_scr[slot],
                        preferred_element_type=jnp.float32)
            u = jnp.dot(x, up_scr[slot], preferred_element_type=jnp.float32)
            hidden = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
            o = jnp.dot(hidden, down_scr[slot],
                        preferred_element_type=jnp.float32)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (WINDOW, 1), 0)
            dest[...] = jnp.where((row >= lo) & (row < hi), o,
                                  0).astype(dest.dtype)
            # in front of the group, in its first tile: the rows of the
            # experts before (no row of a later window lies in front)
            dest[:_TILE, :] = jnp.where(row[:_TILE] >= lo, dest[:_TILE, :],
                                        border[...])
            # the tile the next group starts in, as it is now (what a
            # window that is not the group's last keeps is never read)
            off = pl.multiple_of(
                jax.lax.min(tiles(hi) - at, WINDOW - _TILE), _TILE)
            border[...] = dest[pl.ds(off, _TILE), :]
            if stream:
                # one write at a time, in order: a window reaches into the
                # groups behind it, whose own windows have to land later
                flush(0, there).wait()
                flush(at, here).start()
            return n + 1

        return jax.lax.fori_loop(0, windows, rows, n)

    if stream:  # the write that window 0 waits for: into the spare rows
        flush(rows_end, 1).start()
    made = jax.lax.fori_loop(0, total, expert, 0)
    if stream:
        flush(0, rem(made + 1, 2)).wait()


@functools.partial(jax.jit, static_argnames="in_vmem")
def _call(rows, gate, up, down, load, *, in_vmem):
    """``grouped_swiglu`` with what it decides from the shapes handed in:
    whether rows and outputs are resident."""
    padded, d = rows.shape
    f = gate.shape[2]
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(load)])
    # the experts with rows first, in their order
    order = jnp.argsort(load == 0, stable=True).astype(jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    held = 2 * 3 * d * f * 2 + 8 * WINDOW * max(d, f) * 4
    if in_vmem:
        lives = pl.BlockSpec(memory_space=pltpu.VMEM)
        stream = []
        held += 2 * padded * d * rows.dtype.itemsize
    else:
        lives = hbm
        stream = [pltpu.VMEM((2, WINDOW, d), rows.dtype),
                  pltpu.VMEM((2, WINDOW, d), rows.dtype),
                  pltpu.SemaphoreType.DMA((2,)),
                  pltpu.SemaphoreType.DMA((2,))]
        held += 4 * WINDOW * d * rows.dtype.itemsize
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((padded, d), rows.dtype),
        in_specs=[smem] * 3 + [lives] + [hbm] * 3,
        out_specs=lives,
        scratch_shapes=[pltpu.VMEM((2, d, f), gate.dtype),
                        pltpu.VMEM((2, d, f), up.dtype),
                        pltpu.VMEM((2, f, d), down.dtype),
                        pltpu.SemaphoreType.DMA((3, 2)),
                        pltpu.VMEM((_TILE, d), rows.dtype)] + stream,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=held + (16 << 20)),
        name="grouped_swiglu",
        interpret=_auto_interpret(),
    )(order, jnp.sum(load > 0, dtype=jnp.int32).reshape(1), starts,
      rows, gate, up, down)


def grouped_swiglu(rows, gate, up, down, load):
    """``(silu(rows gate_e) * (rows up_e)) down_e`` for the rows of every
    group e, as the Mosaic kernel.

    rows [m + room(m), d]: the m assignments sorted by expert, and behind
    them ``room(m)`` rows of anything that no group reaches; gate, up [E,
    d, f]; down [E, f, d]; ``load`` [E] int32, the rows each expert has,
    in order (their sum at most m). Returns the same shape in rows' dtype:
    an assignment's output where its row is, zeros behind the last group,
    and anything in the last ``WINDOW`` rows.

    A jit of its own (``_call``), so that a program's expert layers, which
    call it at ONE shape, are one traced and one lowered kernel and not
    one a layer: lowering a Pallas kernel to Mosaic's MLIR is 0.1 s of
    Python, paid again by every process that loads the program from a
    warm cache (six layers x nine serving programs: 6 s of ``setup_s``,
    PERF.md §6, PR 43)."""
    m, d = rows.shape[0] - WINDOW, rows.shape[1]
    if m <= 0 or m % _TILE:
        raise ValueError(
            f"rows {rows.shape}: wanted the assignments and room(assignments) "
            f"rows behind them, a whole number of {_TILE}-row tiles")
    return _call(rows, gate, up, down, load,
                 in_vmem=resident(m, d, rows.dtype.itemsize))
