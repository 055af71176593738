"""The routed experts' grouped SwiGLU as ONE Mosaic kernel (models/moe.py
``experts`` calls it; docs/serving.md).

``t * k`` assignments lie sorted by expert, group e the rows of expert e,
and every row wants

    out = (silu(x gate_e) * (x up_e)) down_e

At serving shapes the rows are few (4 a group when 64 rows decode, 8-64
when one prompt is prefilled) and the weights are everything: three
matrices of 6.3 MB an expert at GLM-4.7-Flash's widths, each used once.
So the kernel is built around the READ of the weights and nothing else:

  * the stacks stay in HBM as they are held, ``[E, d, f]``, ``[E, d, f]``,
    ``[E, f, d]``; an expert's three matrices are three contiguous runs
    and come into VMEM as three DMAs, whole, into one of two slots;
  * the loop runs over the experts that HAVE rows (``order``, ``total``:
    an expert no row was routed to costs no read), and the next expert's
    three copies are started before this one's are awaited, so a copy is
    always queued behind the one in flight: only the very first expert's
    read is exposed;
  * all rows and all outputs are resident in VMEM (1 MB each way when 64
    rows decode, 16 MB at 4,096 assignments); an expert's rows are taken
    as WINDOWS of ``WINDOW`` rows from the 16-row tile its group starts
    in (a bfloat16 tile's sublanes: no unaligned slice), all three
    products and the SwiGLU happen on the window in VMEM, float32
    accumulation, the hidden rounded to the rows' dtype once, and only
    the window's rows that are the expert's are stored. A group longer
    than a window takes another. Rows behind the last group are never
    written: they leave as zeros.

The arithmetic hides behind the read at every serving shape, so ONE
window length serves them all: on the v5e windows of 32, 64 and 128 rows
are within 0.6% of each other from 256 assignments (4 a group) to 4,096
(64 a group), 87.5-90.5% of the HBM peak (PERF.md §6, PR 43).

``selected`` says from what a call can see whether this kernel or
``jax.lax.ragged_dot`` computes the products: there is no option.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_interpret, _on_one_tpu_chip

#: rows of a bfloat16 tile: where a window may start
_TILE = 16
#: rows the three products are made on at once
WINDOW = 64
#: the most assignments whose rows and outputs the kernel keeps in VMEM
MAX_ROWS = 4096
#: two experts in VMEM: the most their six matrices may take
_EXPERTS_BYTES = 48 << 20


def selected(assignments, stack_shape, dtype):
    """Whether the grouped SwiGLU of ``assignments`` rows over stacks
    ``gate``/``up`` of ``stack_shape`` ``[E, d, f]`` (``down`` ``[E, f,
    d]``) and rows of ``dtype`` runs as the Mosaic kernel. Decided from
    what the call can see, as ``flash_attention._decode_kernel_selected``
    decides: a TPU backend with the program on ONE chip, bfloat16, ``d``
    and ``f`` in whole 128-lane tiles, two experts and all the rows at
    once in VMEM. Everything else takes ``jax.lax.ragged_dot``: the CPU
    backend, a mesh of several devices, float32, odd widths."""
    _, d, f = stack_shape
    return _on_one_tpu_chip() and dtype == jnp.bfloat16 and \
        d % 128 == 0 and f % 128 == 0 and 0 < assignments <= MAX_ROWS and \
        2 * 3 * d * f * 2 <= _EXPERTS_BYTES


def _kernel(order_ref, total_ref, start_ref, x_ref, gate_hbm, up_hbm,
            down_hbm, out_ref, gate_scr, up_scr, down_scr, sem):
    """Item j of the loop is expert ``order_ref[j]`` (``total_ref[0]``
    items: the experts with rows), its rows ``start_ref[e]`` up to
    ``start_ref[e + 1]``. ``sem`` is ``[matrix, slot]``."""
    total = total_ref[0]
    m = x_ref.shape[0]

    def clear(w, _):  # a loop: 4,096 rows of stores unrolled are 1 MB of code
        out_ref[pl.ds(pl.multiple_of(w * WINDOW, WINDOW), WINDOW), :] = \
            jnp.zeros((WINDOW, out_ref.shape[1]), out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, m // WINDOW, clear, 0)

    def copies(j):
        e, slot = order_ref[j], j % 2
        return [pltpu.make_async_copy(hbm.at[e], scr.at[slot],
                                      sem.at[i, slot])
                for i, (hbm, scr) in enumerate(((gate_hbm, gate_scr),
                                                (up_hbm, up_scr),
                                                (down_hbm, down_scr)))]

    @pl.when(total > 0)
    def _prime():
        for c in copies(0):
            c.start()

    def expert(j, _):
        @pl.when(j + 1 < total)
        def _prefetch():  # into the slot expert j - 1 was computed from
            for c in copies(j + 1):
                c.start()

        e, slot = order_ref[j], j % 2
        lo, hi = start_ref[e], start_ref[e + 1]
        first = lo // _TILE * _TILE
        for c in copies(j):
            c.wait()

        def rows(w, _):
            # the last window is pulled back inside the rows: a row seen
            # twice is stored twice with the same value
            at = pl.multiple_of(
                jnp.minimum(first + w * WINDOW, m - WINDOW), _TILE)
            x = x_ref[pl.ds(at, WINDOW), :]
            g = jnp.dot(x, gate_scr[slot],
                        preferred_element_type=jnp.float32)
            u = jnp.dot(x, up_scr[slot], preferred_element_type=jnp.float32)
            hidden = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
            o = jnp.dot(hidden, down_scr[slot],
                        preferred_element_type=jnp.float32)
            row = at + jax.lax.broadcasted_iota(jnp.int32, (WINDOW, 1), 0)
            out_ref[pl.ds(at, WINDOW), :] = jnp.where(
                (row >= lo) & (row < hi), o.astype(out_ref.dtype),
                out_ref[pl.ds(at, WINDOW), :])
            return 0

        jax.lax.fori_loop(0, (hi - first + WINDOW - 1) // WINDOW, rows, 0)
        return 0

    jax.lax.fori_loop(0, total, expert, 0)


@jax.jit
def grouped_swiglu(rows, gate, up, down, load):
    """``(silu(rows gate_e) * (rows up_e)) down_e`` for the rows of every
    group e, as the Mosaic kernel.

    rows [m, d] sorted by expert; gate, up [E, d, f]; down [E, f, d];
    ``load`` [E] int32, the rows each expert has, in order. Rows behind
    the last group come back as zeros. Returns [m, d] in rows' dtype.

    A jit of its own, so that a program's expert layers, which call it at
    ONE shape, are one traced and one lowered kernel and not one a layer:
    lowering a Pallas kernel to Mosaic's MLIR is 0.1 s of Python, paid
    again by every process that loads the program from a warm cache (six
    layers x nine serving programs: 6 s of ``setup_s``, PERF.md §6, PR
    43)."""
    m, d = rows.shape
    f = gate.shape[2]
    padded = -(-m // WINDOW) * WINDOW
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(load)])
    # the experts with rows first, in their order
    order = jnp.argsort(load == 0, stable=True).astype(jnp.int32)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    held = 2 * 3 * d * f * 2 + 2 * padded * d * rows.dtype.itemsize \
        + 8 * WINDOW * max(d, f) * 4
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((padded, d), rows.dtype),
        in_specs=[smem] * 3 + [vmem] + [hbm] * 3,
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((2, d, f), gate.dtype),
                        pltpu.VMEM((2, d, f), up.dtype),
                        pltpu.VMEM((2, f, d), down.dtype),
                        pltpu.SemaphoreType.DMA((3, 2))],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=held + (16 << 20)),
        name="grouped_swiglu",
        interpret=_auto_interpret(),
    )(order, jnp.sum(load > 0, dtype=jnp.int32).reshape(1), starts,
      jnp.pad(rows, ((0, padded - m), (0, 0))), gate, up, down)
    return out[:m]
