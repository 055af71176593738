"""The Mamba-1 recurrence (ops/mamba1.py) in its serving forms against the
literal, position-by-position definition: the prefill scan in both its
implementations (one ``lax.scan``, several positions a trip; the Mosaic
kernel, interpreted), with a carried state and with right padding (``dt ==
0`` holds the state), and the one-token update of
the decode step in place in the cache's stacked state, rows outside the
mask bit for bit. float32 on both sides, the same arithmetic in the same
order: 1e-6 of values of order 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import mamba1

TOL = 1e-6


def case(bt, s, ch, n, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(bt, s, ch)), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3),
                                        (bt, s, ch))), jnp.float32)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32)[:, None],
                          (n, ch))
    b = jnp.asarray(rng.normal(size=(bt, s, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(bt, s, n)), jnp.float32)
    return x, dt, a, b, c


def by_hand(x, dt, a, b, c, state):
    """numpy, channel-major as the papers write it: S[c, n]."""
    x, dt, a, b, c = (np.asarray(t, np.float64) for t in (x, dt, a, b, c))
    bt, s, ch = x.shape
    state = np.zeros((bt, ch, a.shape[0])) if state is None \
        else np.asarray(state, np.float64).transpose(0, 2, 1)
    ys = np.zeros((bt, s, ch))
    for t in range(s):
        decay = np.exp(dt[:, t, :, None] * a.T[None])
        state = decay * state + (dt[:, t] * x[:, t])[:, :, None] \
            * b[:, t, None, :]
        ys[:, t] = (state * c[:, t, None, :]).sum(-1)
    return ys, state.transpose(0, 2, 1)


@pytest.mark.parametrize("s", [1, 5, 8, 19])
def test_the_literal_scan_is_the_equations(s):
    args = case(2, s, 24, 4, seed=s)
    y, state = mamba1.literal_scan(*args)
    want_y, want_state = by_hand(*args, None)
    assert np.abs(np.asarray(y) - want_y).max() < TOL
    assert np.abs(np.asarray(state) - want_state).max() < TOL


@pytest.mark.parametrize("s", [1, 7, 8, 9, 40])
@pytest.mark.parametrize("carried", [False, True])
def test_the_scan_is_the_literal_scan(s, carried):
    """Lengths under, at and over a trip of ``SCAN_UNROLL`` positions, from
    zeros and from a carried state."""
    args = case(2, s, 24, 4, seed=s)
    state = jnp.asarray(np.random.default_rng(1).normal(size=(2, 4, 24)),
                        jnp.float32) if carried else None
    y, last = jax.jit(mamba1.selective_scan)(*args, state)
    want_y, want_last = mamba1.literal_scan(*args, state)
    assert y.dtype == last.dtype == jnp.float32
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < TOL
    assert np.abs(np.asarray(last) - np.asarray(want_last)).max() < TOL


@pytest.fixture
def scan_kernel(monkeypatch):
    """The scan as a TPU backend picks it: the Mosaic kernel, here
    interpreted."""
    monkeypatch.setattr(mamba1, "_on_one_tpu_chip", lambda: True)


@pytest.mark.parametrize("s,ch,n", [
    (5, 128, 8),      # under a block of positions, one lane tile
    (128, 256, 16),   # a whole block, two programs of one lane tile
    (200, 1024, 16),  # over a block (padded with dt = 0), blocks of 512
])
@pytest.mark.parametrize("carried", [False, True])
def test_the_scan_kernel_is_the_literal_scan(scan_kernel, s, ch, n, carried):
    """The Mosaic kernel (a block of channels' state in registers from
    position to position, in scratch from block to block) against the
    ``lax.scan``, which the test above holds to the literal loop: bfloat16
    x, B and C as the model hands them, 2e-6 of values of order 1."""
    x, dt, a, b, c = case(2, s, ch, n, seed=s)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    state = jnp.asarray(np.random.default_rng(1).normal(size=(2, n, ch)),
                        jnp.float32) if carried else None
    assert mamba1._scan_kernel_selected(ch, n)
    y, last = jax.jit(mamba1.selective_scan)(x, dt, a, b, c, state)
    was = jnp.zeros((2, n, ch), jnp.float32) if state is None else state
    steps = jax.jit(lambda *t: jax.lax.scan(
        lambda st, inp: mamba1.state_step(st, inp[0], inp[1], a, inp[2],
                                          inp[3]), t[4],
        tuple(jnp.moveaxis(u, 1, 0) for u in t[:4])))
    want_last, want_y = steps(x, dt, b, c, was)
    assert y.shape == (2, s, ch) and last.shape == (2, n, ch)
    assert np.abs(np.asarray(y)
                  - np.moveaxis(np.asarray(want_y), 0, 1)).max() < 2 * TOL
    assert np.abs(np.asarray(last) - np.asarray(want_last)).max() < 2 * TOL


def test_the_scan_kernel_is_selected_from_the_call(monkeypatch):
    """On one TPU chip, a float32 state of whole (8, 128) tiles; never on
    the CPU."""
    assert not mamba1._scan_kernel_selected(5120, 16)
    monkeypatch.setattr(mamba1, "_on_one_tpu_chip", lambda: True)
    assert mamba1._scan_kernel_selected(5120, 16)
    assert not mamba1._scan_kernel_selected(5120, 4)
    assert not mamba1._scan_kernel_selected(96, 16)
    assert not mamba1._scan_kernel_selected(5120, 16, jnp.bfloat16)


def test_two_scans_carry_what_one_scan_holds():
    args = case(1, 24, 16, 4)
    x, dt, a, b, c = args
    whole, end = mamba1.selective_scan(*args)
    first, mid = mamba1.selective_scan(x[:, :10], dt[:, :10], a, b[:, :10],
                                       c[:, :10])
    second, last = mamba1.selective_scan(x[:, 10:], dt[:, 10:], a, b[:, 10:],
                                         c[:, 10:], mid)
    got = np.concatenate([np.asarray(first), np.asarray(second)], axis=1)
    assert np.abs(got - np.asarray(whole)).max() < TOL
    assert np.abs(np.asarray(last) - np.asarray(end)).max() < TOL


@pytest.mark.parametrize("real", [1, 6, 13])
def test_a_right_padded_position_leaves_the_state_untouched(real):
    """``dt == 0`` past the last real token: a step there returns the state
    bit for bit (exp(0) = 1, and it adds 0), so the state a scan leaves is
    the state after the real prefix (two programs: to TOL)."""
    x, dt, a, b, c = case(1, 16, 16, 4, seed=real)
    padded = jnp.where(jnp.arange(16)[None, :, None] < real, dt, 0.0)
    y, last = mamba1.selective_scan(x, padded, a, b, c)
    _, want = mamba1.selective_scan(x[:, :real], dt[:, :real], a,
                                    b[:, :real], c[:, :real])
    assert np.abs(np.asarray(last) - np.asarray(want)).max() < TOL
    assert np.isfinite(np.asarray(y)).all()
    held, _ = mamba1.state_step(want, x[:, real], 0.0 * dt[:, real], a,
                                b[:, real], c[:, real])
    np.testing.assert_array_equal(np.asarray(held), np.asarray(want))


@pytest.mark.parametrize("mask", [None, (True, False, True),
                                  (False, False, False)])
def test_the_decode_update_is_one_step_in_place(mask):
    """Plane 1 of a stacked state: the rows of the mask advance by
    ``state_step``; the others, and every other plane, are bit for bit what
    they were."""
    rng = np.random.default_rng(0)
    ssm = jnp.asarray(rng.normal(size=(3, 3, 4, 16)), jnp.float32)
    x, dt, a, b, c = case(3, 1, 16, 4)
    rows = None if mask is None else jnp.asarray(mask)
    now, y = jax.jit(mamba1.decode_update, static_argnums=1)(
        ssm, 1, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], rows)
    want, want_y = mamba1.state_step(ssm[1], x[:, 0], dt[:, 0], a, b[:, 0],
                                     c[:, 0])
    for plane in (0, 2):
        np.testing.assert_array_equal(np.asarray(now[plane]),
                                      np.asarray(ssm[plane]))
    for row in range(3):
        if mask is None or mask[row]:
            assert np.abs(np.asarray(now[1, row])
                          - np.asarray(want[row])).max() < TOL
            assert np.abs(np.asarray(y[row])
                          - np.asarray(want_y[row])).max() < TOL
        else:
            np.testing.assert_array_equal(np.asarray(now[1, row]),
                                          np.asarray(ssm[1, row]))


@pytest.mark.parametrize("mask", [None, (True, False, True, True, False),
                                  (False,) * 5])
@pytest.mark.parametrize("ch", [128, 1024])
def test_the_update_kernel_is_one_step_in_place(scan_kernel, mask, ch):
    """The Mosaic kernel (interpreted): each row of the mask through VMEM
    once, in blocks of 512 lanes or one lane tile; the others, and every
    other plane, bit for bit."""
    rng = np.random.default_rng(0)
    ssm = jnp.asarray(rng.normal(size=(3, 5, 16, ch)), jnp.float32)
    x, dt, a, b, c = case(5, 1, ch, 16)
    x, b, c = (t.astype(jnp.bfloat16) for t in (x, b, c))
    rows = None if mask is None else jnp.asarray(mask)
    assert mamba1._update_kernel_selected(ssm.shape, ssm.dtype)
    now, y = jax.jit(mamba1.decode_update, static_argnums=1)(
        ssm, 2, x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], rows)
    want, want_y = mamba1.state_step(ssm[2], x[:, 0], dt[:, 0], a, b[:, 0],
                                     c[:, 0])
    np.testing.assert_array_equal(np.asarray(now[:2]), np.asarray(ssm[:2]))
    for row in range(5):
        if mask is None or mask[row]:
            assert np.abs(np.asarray(now[2, row])
                          - np.asarray(want[row])).max() < 2 * TOL
            assert np.abs(np.asarray(y[row])
                          - np.asarray(want_y[row])).max() < 4 * TOL
        else:
            np.testing.assert_array_equal(np.asarray(now[2, row]),
                                          np.asarray(ssm[2, row]))
            assert not np.asarray(y[row]).any()


def test_the_state_is_held_state_major():
    """``[.., d_state, channels]``: the channels on the lanes. On a TPU
    the last two dimensions are tiled (8, 128), and 16 lanes of 128 would
    be eight times the memory and the traffic."""
    x, dt, a, b, c = case(2, 3, 256, 16)
    _, state = mamba1.selective_scan(x, dt, a, b, c)
    assert state.shape == (2, 16, 256) and a.shape == (16, 256)
