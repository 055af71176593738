"""Selective state-space scan (Mamba-2) — the recurrence of a hybrid
block's mixer, in the two forms serving needs (docs/serving.md):

  * ``chunked_scan`` for prefill: the sequence is cut into chunks; inside
    a chunk the recurrence is a masked, decay-weighted [chunk, chunk]
    product (matrix products on the MXU), and only the [heads, head,
    state] state crosses chunks, one short ``lax.scan`` step a chunk.
  * ``state_step`` for decode: the one-token update of every row's state.

Both compute, per head with its group's B and C,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

(the D x_t skip, the gate and the norm are the model's). A position with
``dt == 0`` holds the state and adds nothing — how a right-padded prompt
is kept out of the state it leaves behind. Decays, ``dt`` and the state
are float32; the matrix products take their operands in ``x``'s dtype
and accumulate in float32.

Plain jax.numpy, no Pallas kernel: the chunked form is a handful of
batched matmuls XLA already maps to the MXU, and the decode update is one
elementwise pass over the state, bound by reading and writing it once.
``literal_scan`` is the definition, position by position; the tests hold
the other two to it.
"""

import jax
import jax.numpy as jnp


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
