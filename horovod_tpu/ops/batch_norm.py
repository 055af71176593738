"""Fused BatchNorm statistics as a Pallas TPU kernel + a flax module —
a MEASURED DEAD END on v5e, kept (tested, numerics-equal to flax) as the
record of the experiment and as building blocks for chips where the
trade flips.

Motivation was the round-3 ResNet-50 device profile (docs/benchmarks.md):
"convert_reduce_fusion" (BN statistics) at 25% of the step, apparently
~4× off the HBM roofline. Hypothesis: a Pallas kernel streaming [block,
C] tiles and accumulating per-channel sum/sum-of-products in VMEM would
reclaim the pass, in forward (sum x, sum x²) and backward (sum dy,
sum dy·x — the two reductions of the standard BN gradient, via the
custom VJP under ``TpuBatchNorm``).

Measured on v5e (chained-loop protocol, batch-256 ResNet-50 layer
shapes): XLA's own fused convert+reduce runs at 300-840 GB/s standalone
— the profile's "4× off roofline" was CONTEXT (serialization against
convs + µs-scale op-issue overhead at ~3,400 ops/step), not a bad
reduction — while this kernel's sequential accumulation grid tops out
at ~110-260 GB/s (per-step fixed cost; fatter blocks hit the 16 MB
scoped-VMEM wall). End-to-end, routing ResNet-50 through TpuBatchNorm
REGRESSED batch-256 throughput 2,350 → 1,372 img/s: the custom_vjp
boundary also denies XLA the conv-epilogue fusion of the normalize.
models/resnet.py therefore defaults to flax BatchNorm
(``norm_impl="flax"``); ``norm_impl="tpu"`` selects this module.

Reference analogue: none (the reference defers BN to cuDNN,
examples/pytorch_synthetic_benchmark.py's torchvision models).
On non-TPU backends the kernel runs in Pallas interpret mode.
"""

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _auto_interpret, _out_struct


# sequential grid: every step accumulates into the same [1, C] output
# blocks, which Mosaic keeps resident in VMEM across the whole grid
_SEQ = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


# a lone [rows, C] tile has no double-buffering; what bounds it is the
# ~16 MB scoped VMEM minus the fp32 intermediates of the reduction
# (input bf16 tile + ~2x for the f32 cast) — ~5 MB of input is safe
_SINGLE_TILE_LIMIT = 5 << 20


def _pick_block(rows, channels, budget_bytes=2 << 20, inputs=1,
                compiled=True):
    """Largest row-block that divides ``rows``, keeps ``inputs`` bf16
    [block, C] tiles within the VMEM budget, and stays a multiple of 8
    (the f32 sublane). Big blocks matter: the sequential accumulation
    grid pays a fixed per-step cost, so fewer/fatter DMA tiles win
    (measured on v5e). Non-8-aligned row counts fall back to one
    whole-array tile — unbounded in interpret mode (``compiled=False``),
    VMEM-capped when compiling for real hardware."""
    cap = max(8, budget_bytes // max(1, channels * 2 * inputs))
    block = 1 << max(3, (cap.bit_length() - 1))
    block = min(block, 65536)
    while block > 8 and rows % block:
        block //= 2
    if rows % block == 0:
        return block
    if not compiled or rows * channels * 2 * inputs <= _SINGLE_TILE_LIMIT:
        return rows
    raise ValueError(
        f"moments: {rows} rows (not a multiple of 8) x {channels} "
        "channels cannot tile for VMEM; pad rows to a multiple of 8")


def _moments1_kernel(x_ref, s_ref, ss_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    s = jnp.sum(x, axis=0, keepdims=True)
    ss = jnp.sum(x * x, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        s_ref[...] = s
        ss_ref[...] = ss

    @pl.when(i > 0)
    def _acc():
        s_ref[...] += s
        ss_ref[...] += ss


def _moments2_kernel(a_ref, b_ref, sa_ref, sab_ref):
    i = pl.program_id(0)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    sa = jnp.sum(a, axis=0, keepdims=True)
    sab = jnp.sum(a * b, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        sa_ref[...] = sa
        sab_ref[...] = sab

    @pl.when(i > 0)
    def _acc():
        sa_ref[...] += sa
        sab_ref[...] += sab


def _flat(x):
    return x.reshape(-1, x.shape[-1])


def moments(x, interpret=None):
    """Per-channel (sum, sum of squares) over all leading axes of ``x``
    [..., C], fp32 accumulation, one streaming HBM pass."""
    xf = _flat(x)
    rows, c = xf.shape
    interpret = interpret if interpret is not None else _auto_interpret()
    block = _pick_block(rows, c, compiled=not interpret)
    s, ss = pl.pallas_call(
        _moments1_kernel,
        grid=(rows // block,),
        compiler_params=_SEQ,
        in_specs=[pl.BlockSpec((block, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[_out_struct((1, c), jnp.float32, xf),
                   _out_struct((1, c), jnp.float32, xf)],
        interpret=interpret,
    )(xf)
    return s[0], ss[0]


def moments2(a, b, interpret=None):
    """Per-channel (sum a, sum a·b) for same-shape [..., C] arrays — the
    backward-pass pair (a=dy, b=x)."""
    af, bf = _flat(a), _flat(b)
    rows, c = af.shape
    interpret = interpret if interpret is not None else _auto_interpret()
    block = _pick_block(rows, c, inputs=2, compiled=not interpret)
    sa, sab = pl.pallas_call(
        _moments2_kernel,
        grid=(rows // block,),
        compiler_params=_SEQ,
        in_specs=[pl.BlockSpec((block, c), lambda i: (i, 0)),
                  pl.BlockSpec((block, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda i: (0, 0)),
                   pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_shape=[_out_struct((1, c), jnp.float32, af, bf),
                   _out_struct((1, c), jnp.float32, af, bf)],
        interpret=interpret,
    )(af, bf)
    return sa[0], sab[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_train(x, scale, bias, eps):
    """Returns (y, mean, var): the normalized output plus this batch's
    per-channel statistics, so the caller's running-average update reuses
    the kernel's single pass instead of recomputing moments."""
    (y, mean, var), _ = _bn_train_fwd(x, scale, bias, eps)
    return y, mean, var


def _bn_train_fwd(x, scale, bias, eps):
    n = x.size // x.shape[-1]
    s, ss = moments(x)
    mean = s / n
    var = jnp.maximum(ss / n - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = ((x.astype(jnp.float32) - mean) * (inv * scale) + bias)
    return (y.astype(x.dtype), mean, var), (x, scale, mean, inv)


def _bn_train_bwd(eps, res, cts):
    dy, _, _ = cts  # mean/var outputs feed running stats only: zero cts
    x, scale, mean, inv = res
    n = x.size // x.shape[-1]
    # the two per-channel reductions of the standard BN gradient, in one
    # streamed pass: sum(dy) and sum(dy·x)
    sum_dy, sum_dyx = moments2(dy, x)
    # sum(dy·x̂) with x̂ = (x-μ)·inv
    sum_dyxhat = (sum_dyx - mean * sum_dy) * inv
    dscale = sum_dyxhat
    dbias = sum_dy
    g = scale * inv
    xhat = (x.astype(jnp.float32) - mean) * inv
    dx = g * (dy.astype(jnp.float32) - sum_dy / n
              - xhat * (sum_dyxhat / n))
    return dx.astype(x.dtype), dscale, dbias


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


class TpuBatchNorm(nn.Module):
    """BatchNorm with Pallas-fused statistics (forward AND backward
    reductions); drop-in for ``flax.linen.BatchNorm`` on the surface the
    model zoo uses: ``use_running_average``, ``momentum``, ``epsilon``,
    ``dtype``, ``use_scale``/``use_bias`` + initializers, batch_stats
    collection with ``mean``/``var`` (biased, like flax)."""

    use_running_average: bool = False
    momentum: float = 0.99
    epsilon: float = 1e-5
    dtype: Any = None
    use_scale: bool = True
    use_bias: bool = True
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, use_running_average=None):
        use_ra = nn.merge_param(
            "use_running_average", self.use_running_average,
            use_running_average)
        c = x.shape[-1]
        scale = (self.param("scale", self.scale_init, (c,), jnp.float32)
                 if self.use_scale else jnp.ones((c,), jnp.float32))
        bias = (self.param("bias", self.bias_init, (c,), jnp.float32)
                if self.use_bias else jnp.zeros((c,), jnp.float32))
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((c,), jnp.float32))
        out_dtype = self.dtype or x.dtype

        if use_ra:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon)
            y = ((x.astype(jnp.float32) - ra_mean.value) * (inv * scale)
                 + bias)
            return y.astype(out_dtype)

        out, mean, var = _bn_train(x, scale, bias, self.epsilon)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = (m * ra_mean.value
                             + (1.0 - m) * jax.lax.stop_gradient(mean))
            ra_var.value = (m * ra_var.value
                            + (1.0 - m) * jax.lax.stop_gradient(var))
        return out.astype(out_dtype)
