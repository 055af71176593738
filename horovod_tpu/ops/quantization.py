"""Block-scaled quantized wire codecs for the allreduce data plane.

EQuARX-style (PAPERS.md) bandwidth compression: tensors cross the wire
as fixed-size blocks of a narrow dtype plus one f32 max-abs scale per
block, and every arithmetic step that ACCUMULATES runs in f32
(dequant -> sum -> requant), so the only precision loss is the two
quantization roundings — never a narrow-dtype accumulation. An
error-feedback residual (what the last encode dropped, added back
before the next one) turns that rounding into a zero-mean perturbation
over steps, which is what preserves convergence at int8/fp8 widths.

This module is the ONE sanctioned home for wire-dtype casts
(hvdlint HVD010): the codec registry in ops/compression.py fronts it
for the user API, the eager core calls it on fused buffers, and
ops/process_collectives.py runs its encode/decode inside the two-phase
shard_map collective. Everything here is pure jax + numpy — jit-cached
per (shape, codec, block), no host staging.

Wire format, per tensor (or fused buffer) of n elements:

  payload  [pad(n)]            int8 / float8_e4m3fn, block-contiguous
  scales   [pad(n) // block]   f32, scale b = max|x_block_b| / QMAX

``pad(n)`` rounds up to a block multiple (two-phase collectives round
to ``block * nproc`` so chunk boundaries land on block boundaries).
Dequant is ``payload * scales[block_of(i)]``; zeros pad the tail and
decode to exact zeros. Accounted wire size is ``payload.nbytes +
scales.nbytes`` — the scale overhead is 4/block per element (1.6% at
the default block of 256).
"""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import metrics as hvd_metrics

# Per-block element count. 256 keeps the f32-scale overhead at 1.6%
# while staying well inside one VPU tile; override via
# HOROVOD_QUANT_BLOCK (common/config.py).
BLOCK_DEFAULT = 256

# float8_e4m3fn landed in jax well before the pinned version, but the
# codec stays feature-gated so a build without ml_dtypes' fp8 falls
# back loudly at registry lookup instead of deep in a jit trace.
HAS_FP8 = hasattr(jnp, "float8_e4m3fn")

# Largest exactly-representable magnitude per codec: symmetric int8
# keeps -128 unused (symmetric quantization, same choice as EQuARX);
# e4m3fn's max normal is 448 and overflow converts to NaN, so encode
# clips to it.
_QMAX = {"int8": 127.0, "fp8": 448.0}

QUANTIZED_CODECS = ("int8", "fp8")
CAST_CODECS = ("fp16", "bf16")
WIRE_CODECS = QUANTIZED_CODECS + CAST_CODECS


def is_quantized(codec):
    return codec in QUANTIZED_CODECS


def is_wire(codec):
    """True when ``codec`` changes what crosses the wire (anything but
    none/unset)."""
    return codec in WIRE_CODECS


def wire_dtype(codec):
    if codec == "int8":
        return jnp.int8
    if codec == "fp8":
        if not HAS_FP8:
            raise ValueError(
                "codec 'fp8': this jax build has no float8_e4m3fn dtype; "
                "use HOROVOD_COMPRESSION=int8 instead")
        return jnp.float8_e4m3fn
    if codec == "fp16":
        return jnp.float16
    if codec == "bf16":
        return jnp.bfloat16
    raise ValueError(f"unknown wire codec {codec!r}")


def pad_to(n, multiple):
    """Smallest block-aligned size >= n."""
    return n + (-n) % multiple


# -- block kernels (shapes static inside jit; cached per shape/codec) --


def _block_encode(x32, block, codec):
    """[..., m] f32 with m % block == 0 -> (payload [..., m] wire dtype,
    scales [..., m // block] f32). Padding zeros encode to zeros."""
    shape = x32.shape
    blocks = x32.reshape(shape[:-1] + (shape[-1] // block, block))
    amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
    scale = amax / _QMAX[codec]
    # all-zero blocks (and the zero pad tail) get scale 0; divide by a
    # stand-in 1 so the quotient is a well-defined 0, not inf*0
    safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
    scaled = blocks / safe
    if codec == "int8":
        q = jnp.round(scaled).astype(jnp.int8)
    else:
        # clip: f32 rounding in the divide can land a hair above 448,
        # and e4m3fn overflows to NaN rather than saturating
        q = jnp.clip(scaled, -_QMAX["fp8"], _QMAX["fp8"]).astype(
            wire_dtype("fp8"))
    return (q.reshape(shape),
            scale.reshape(shape[:-1] + (shape[-1] // block,)))


def _block_decode(payload, scales, block):
    """Inverse of _block_encode, always f32."""
    shape = payload.shape
    blocks = payload.astype(jnp.float32).reshape(
        shape[:-1] + (shape[-1] // block, block))
    return (blocks * scales[..., None]).reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "codec", "multiple"))
def encode(x, block, codec, multiple=None):
    """Encode [..., n] (any float dtype) -> (payload, scales), padding
    the last axis to ``multiple`` (default: one block)."""
    m = pad_to(x.shape[-1], multiple or block)
    x32 = x.astype(jnp.float32)
    if m != x.shape[-1]:
        widths = [(0, 0)] * (x.ndim - 1) + [(0, m - x.shape[-1])]
        x32 = jnp.pad(x32, widths)
    return _block_encode(x32, block, codec)


@functools.partial(jax.jit, static_argnames=("block", "n"))
def decode(payload, scales, block, n):
    """Decode back to f32 [..., n] (drops the pad tail)."""
    return _block_decode(payload, scales, block)[..., :n]


@functools.partial(jax.jit,
                   static_argnames=("block", "codec", "average", "n"))
def stacked_wire_allreduce(stacked, block, codec, average, n):
    """Simulated quantized allreduce over the rows of a [world, n]
    buffer (the single-controller stacked path): encode each row as its
    own wire contribution, dequant to f32, sum in f32, requant the sum,
    dequant — byte-for-byte the math of the two-phase cross-process
    collective in process_collectives.py, so single- and multi-process
    runs of the same model see the same quantization error. Returns
    ([world, n] with identical rows, [world, n] f32 decode of each
    row's own wire payload — the error-feedback reference)."""
    q, s = encode(stacked, block, codec)
    dec = _block_decode(q, s, block)               # [world, m] f32
    q2, s2 = _block_encode(jnp.sum(dec, axis=0), block, codec)
    out = _block_decode(q2, s2, block)[:n]
    if average:
        out = out / stacked.shape[0]
    return (jnp.broadcast_to(out, (stacked.shape[0], n)),
            dec[..., :n])


@functools.partial(jax.jit, static_argnames=("block",))
def ef_update(comp, decoded, block):
    """New residual after encoding the compensated buffer ``comp``
    whose own-wire decode was ``decoded``; also returns its L2 norm
    (device scalar) for the hvd_ef_residual_norm gauge."""
    r = comp.astype(jnp.float32) - decoded
    return r, jnp.sqrt(jnp.sum(r * r))


class ErrorFeedback:
    """Per-tensor error-feedback residuals (1-bit SGD / EF-SGD
    lineage): whatever the encoder rounded away this step is added back
    before the next encode, so quantization error telescopes instead of
    accumulating. Keyed by the fused buffer's member names — stable
    across steps because the plan is — and reset on any shape change
    (elastic resize, recompiled model)."""

    def __init__(self):
        self._residuals = {}  # guarded_by: _lock
        self._lock = threading.Lock()

    def compensate(self, key, x):
        with self._lock:
            r = self._residuals.get(key)
        if r is None or r.shape != x.shape:
            return x
        # accumulate in f32: a bf16 gradient can't even represent the
        # small residuals EF exists to carry
        return x.astype(jnp.float32) + r

    def update(self, key, comp, decoded, block, anchor=None):
        """Store ``comp - decoded`` and export its norm. ``anchor``
        labels the gauge (first member tensor of the bucket)."""
        r, norm = ef_update(comp, decoded, block)
        with self._lock:
            self._residuals[key] = r
        reg = hvd_metrics.get_registry()
        if reg.enabled and anchor is not None:
            reg.gauge(
                "hvd_ef_residual_norm",
                "L2 norm of the error-feedback residual carried to the "
                "next step, by fused-bucket anchor tensor.",
                labels=("tensor",)).labels(tensor=anchor).set(float(norm))

    def peek(self, key, shape=None):
        """Current residual for ``key`` (or None), optionally requiring
        an exact shape match — the hierarchical path threads the
        residual into its jitted collective instead of adding it on the
        host, so it needs the raw buffer, not ``compensate``'s sum."""
        with self._lock:
            r = self._residuals.get(key)
        if r is not None and shape is not None and tuple(r.shape) != \
                tuple(shape):
            return None
        return r

    def reset(self):
        with self._lock:
            self._residuals.clear()


# -- selection + accounting ------------------------------------------


def config_fingerprint(config):
    """The codec knobs that MUST agree across ranks for the wire to be
    decodable — compared by the coordinator every cycle and failed
    loudly on mismatch (negotiation.py)."""
    name = getattr(config, "compression", "none") or "none"
    fp = "%s/b%d/min%d/ef%d" % (
        name, int(getattr(config, "quant_block", BLOCK_DEFAULT)),
        int(getattr(config, "quant_min_bytes", 0)),
        1 if getattr(config, "quant_ef", True) else 0)
    if getattr(config, "overlap_hierarchical", False):
        # The two-level split changes what crosses the inter-host wire
        # (per-host shards, requantized once per phase), so a rank
        # running flat cannot decode a hierarchical peer's stream. The
        # suffix only appears when the knob is on, keeping the
        # fingerprint byte-identical for every existing config.
        fp += "/h%d" % int(getattr(config, "overlap_local_size", 0))
    return fp


def select_codec(config, dtype, nbytes):
    """The wire codec for one tensor under this rank's config: the
    env-selected codec when the tensor is floating and big enough to be
    worth the encode, else none. Deterministic in (config, dtype,
    nbytes) only — every rank with the same config picks the same
    codec, which is what the negotiation fingerprint check enforces."""
    name = getattr(config, "compression", "none") or "none"
    if name == "none" or not is_wire(name):
        return None
    if dtype is None:
        # dtype-less (python scalar) input; np.dtype(None) would alias
        # float64 and quantize it
        return None
    try:
        np_dtype = np.dtype(dtype)
    except TypeError:
        return None
    if not np.issubdtype(np_dtype, np.floating):
        return None
    if nbytes < int(getattr(config, "quant_min_bytes", 0)):
        return None
    if name in CAST_CODECS and np_dtype == np.dtype(wire_dtype(name)):
        return None  # already at wire width; a cast would be a no-op
    return name


def encoded_nbytes(n, codec, block):
    """Wire bytes of one encoded n-element contribution: n at the wire
    width for cast codecs; pad(n) narrow bytes + one f32 scale per
    block for quantized codecs."""
    if codec in CAST_CODECS:
        return int(n) * 2
    m = pad_to(int(n), block)
    return m + (m // block) * 4


def wire_nbytes(payload, scales=None):
    nb = payload.size * payload.dtype.itemsize
    if scales is not None:
        nb += scales.size * scales.dtype.itemsize
    return int(nb)


def account(codec, raw_nbytes, wire_nb, axis="dp"):
    """Fold one executed collective into the wire metrics: encoded
    bytes by codec plus the live raw/wire compression ratio. ``axis``
    names the mesh axis the collective rode (the eager Horovod wire is
    the dp axis; the named-mesh data plane attributes tp/sp collectives
    separately via parallel.mesh.account_axis_bytes)."""
    reg = hvd_metrics.get_registry()
    if not reg.enabled:
        return
    reg.counter(
        "hvd_wire_bytes_total",
        "Encoded allreduce payload bytes that crossed (or would cross) "
        "the wire, by codec and mesh axis; 'none' counts full-width "
        "buffers.",
        labels=("codec", "axis")).labels(
            codec=codec or "none", axis=axis or "dp").inc(int(wire_nb))
    reg.counter(
        "hvd_wire_raw_bytes_total",
        "Full-width bytes of the same buffers before encoding, by "
        "codec and mesh axis — hvd_wire_bytes_total's denominator.",
        labels=("codec", "axis")).labels(
            codec=codec or "none", axis=axis or "dp").inc(
            int(raw_nbytes))
    if wire_nb:
        reg.gauge(
            "hvd_wire_compression_ratio",
            "raw/wire byte ratio of the most recent encoded collective "
            "(1.0 when no codec is active).").set(
                float(raw_nbytes) / float(wire_nb))


def account_leg(leg, codec, wire_nb):
    """Per-leg wire accounting for the two-level reduction: ``leg`` is
    'intra' (full-width shm traffic inside one host) or 'inter' (the
    scarce cross-host hop). tests/test_overlap.py reads this split to
    prove the quantized codec rides ONLY the inter-host leg — a nonzero
    {intra, int8} entry would mean narrow math leaked into the
    bandwidth-rich local reduction where it buys nothing."""
    reg = hvd_metrics.get_registry()
    if not reg.enabled:
        return
    reg.counter(
        "hvd_wire_leg_bytes_total",
        "Bytes moved per hierarchy leg of the two-level eager "
        "reduction, by leg (intra|inter) and codec.",
        labels=("leg", "codec")).labels(
            leg=leg, codec=codec or "none").inc(int(wire_nb))
