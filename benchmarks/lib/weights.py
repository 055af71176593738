"""Seeded weights, the benchmark's own: made on the device in one jitted
call, in the type they are used in, from ``{name: shape}`` tables that the
plain references publish.  Program and reference both get their weights
from here, so neither takes anything the other made.

Rules, by name: ``*.scale`` is 1 + 0.1 N(0,1) (norm gains),
``*.branch_scale`` is a fifth of that (the gain that closes a residual
branch: near 1 a deep batch-normalised net is chaotic at seeded weights, and
any rounding reads as a 30% change of its early gradients), ``*.bias`` is
0.1 N(0,1), ``embed`` is N(0,1), everything else N(0,1)/sqrt(fan_in) with
fan_in the product of all but the last dimension (unit-variance
activations and, behind a final norm, unit-variance logits).
"""

import math

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A PRNG key for any whole ``seed`` (the driver's pass 2**31)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


SMALL = 1 << 22  # leaves below this many elements share one draw


def _shape_noise(name, shape, noise, dtype):
    if name.endswith(".scale"):
        value = 1.0 + 0.1 * noise
    elif name.endswith(".branch_scale"):
        value = 0.2 * (1.0 + 0.1 * noise)
    elif name.endswith(".bias"):
        value = 0.1 * noise
    elif name == "embed":
        value = noise
    else:
        value = noise / math.sqrt(math.prod(shape[:-1]))
    return value.astype(dtype)


def make(shapes, key, dtype):
    """{name: array} for ``shapes`` - call it under ``jax.jit`` with the
    key as an argument, so every seed reuses one compiled program.  Small
    leaves (ResNet-50 has 161) are cut from one flat draw, which keeps the
    program small and quick to load; large ones get a draw each."""
    small = [(n, s) for n, s in shapes.items() if math.prod(s) < SMALL]
    flat = jax.random.normal(jax.random.fold_in(key, 0),
                             (sum(math.prod(s) for _, s in small),),
                             jnp.float32)
    out, at = {}, 0
    for name, shape in small:
        size = math.prod(shape)
        out[name] = _shape_noise(name, shape,
                                 flat[at:at + size].reshape(shape), dtype)
        at += size
    for i, (name, shape) in enumerate(shapes.items()):
        if name not in out:
            noise = jax.random.normal(jax.random.fold_in(key, i + 1), shape,
                                      jnp.float32)
            out[name] = _shape_noise(name, shape, noise, dtype)
    return {name: out[name] for name in shapes}
