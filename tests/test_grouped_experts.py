"""The routed experts' grouped SwiGLU kernel (ops/grouped_matmul.py) as
``models/moe.py`` ``experts`` calls it, interpreted on the CPU, against a
plain loop over the experts in float32 and against the ``ragged_dot``
path of the same function, at reduced widths (hidden and expert width
128 or 256; the stacks bfloat16 as served). Both places the rows can
live are run: resident in VMEM, and (the cases named ``streamed_``) in HBM
with windows passing through, which at these widths takes the resident
bound brought down to the case's own.

Tolerances. The kernel keeps gate and up in float32 up to the hidden's one
rounding, ``ragged_dot`` hands them over in bfloat16: both are the plain
loop with bfloat16's error at different places, so each is held to the
loop by ``LOOP_TOL`` (outputs of order 1, a token's ``k`` expert outputs
each rounded to bfloat16: measured 0.004-0.012), and to each other by
twice that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import moe
from horovod_tpu.ops import grouped_matmul as gm

LOOP_TOL = 0.03


@pytest.fixture
def kernel(monkeypatch):
    """``experts`` takes the Mosaic kernel, interpreted: the predicate
    sees a TPU backend, the kernel still sees the CPU."""
    monkeypatch.setattr(gm, "_on_one_tpu_chip", lambda: True)


def _stacks(num, d, f, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)

    def mat(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / shape[-2] ** 0.5).astype(jnp.bfloat16)
    return mat(keys[0], num, d, f), mat(keys[1], num, d, f), \
        mat(keys[2], num, f, d)


def _loop(y, idx, weights, gate, up, down, mask):
    """``experts`` as written in its docstring, one assignment at a time,
    float32."""
    y, gate, up, down = (np.asarray(a, np.float32)
                         for a in (y, gate, up, down))
    out = np.zeros_like(y)
    load = np.zeros(gate.shape[0], np.int32)
    for t in range(y.shape[0]):
        if mask is not None and not mask[t]:
            continue
        for j, e in enumerate(np.asarray(idx[t])):
            g, u = y[t] @ gate[e], y[t] @ up[e]
            out[t] += float(weights[t, j]) * ((g / (1 + np.exp(-g)) * u)
                                              @ down[e])
            load[e] += 1
    return out, load


def _routing(tokens, k, experts, seed):
    """Each token ``k`` distinct experts of ``experts`` (a list of ids)."""
    rng = np.random.RandomState(seed)
    idx = np.stack([rng.choice(experts, k, replace=False)
                    for _ in range(tokens)]).astype(np.int32)
    weights = rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32)
    return idx, weights / weights.sum(-1, keepdims=True)


def _one_expert_takes_most(tokens, k, num, seed):
    """Expert 5 is every token's first choice: a group of ``tokens`` rows,
    longer than any window."""
    idx, weights = _routing(tokens, k, [e for e in range(num) if e != 5],
                            seed)
    idx[:, 0] = 5
    return idx, weights


# name: (tokens, k, experts, d, f, routing, mask[, the resident bound in
# rows: past it the rows are streamed])
CASES = {
    # 64 rows x 4 of 64 experts: 256 assignments, 4 a group
    "decode_like": (64, 4, 64, 128, 128,
                    lambda: _routing(64, 4, list(range(64)), 1), None),
    "eight_of_64_touched": (64, 4, 64, 128, 128, lambda: _routing(
        64, 4, [3, 4, 17, 30, 31, 32, 50, 62], 2), None),
    "no_row_at_start_middle_end": (24, 2, 16, 128, 256, lambda: _routing(
        24, 2, [2, 3, 4, 9, 10, 13], 3), None),
    "first_and_last_expert_only": (10, 2, 8, 128, 128,
                                   lambda: _routing(10, 2, [0, 7], 4), None),
    # 100 rows in one group where a window holds 32
    "a_group_longer_than_a_window": (
        100, 2, 16, 128, 128, lambda: _one_expert_takes_most(100, 2, 16, 5),
        None),
    "masked_rows_behind_the_last_group": (
        32, 2, 8, 128, 128, lambda: _routing(32, 2, list(range(8)), 6),
        np.arange(32) % 3 != 1),
    "no_row_in_the_mask": (8, 2, 8, 128, 128,
                           lambda: _routing(8, 2, list(range(8)), 7),
                           np.zeros(8, bool)),
    # 5 tokens x 2: 10 assignments, padded up to one window
    "fewer_rows_than_a_window": (5, 2, 8, 128, 128,
                                 lambda: _routing(5, 2, list(range(8)), 8),
                                 None),
    # a prompt of 512 padded positions of which 300 are there, 4 of 16:
    # 2,048 assignments, 75 a group: three windows each
    "prefill_like": (512, 4, 16, 128, 128,
                     lambda: _routing(512, 4, list(range(16)), 9),
                     np.arange(512) < 300),
    # the same past a resident bound of 1,024 rows: 128 a group, four or
    # five windows each through HBM
    "streamed_past_the_resident_bound": (
        512, 4, 16, 128, 128, lambda: _routing(512, 4, list(range(16)), 9),
        None, 1024),
    # 300 rows in one group where a window holds 32: ten windows
    "streamed_a_group_of_several_windows": (
        300, 2, 16, 128, 128, lambda: _one_expert_takes_most(300, 2, 16, 10),
        None, 0),
    # 4 rows a group: four groups share a tile and every border is carried
    "streamed_groups_that_start_off_a_tile": (
        64, 4, 64, 128, 128, lambda: _routing(64, 4, list(range(64)), 11),
        None, 0),
    "streamed_no_row_between_two_that_have_some": (
        24, 2, 16, 128, 256, lambda: _routing(
            24, 2, [2, 3, 4, 9, 10, 13], 12), None, 0),
    # 1,648 rows of zeros behind the groups: 52 windows, more than are in
    # flight at once
    "streamed_masked_rows_behind_the_last_group": (
        512, 4, 64, 128, 128, lambda: _routing(512, 4, list(range(64)), 13),
        np.arange(512) < 100, 0),
    "streamed_no_row_in_the_mask": (
        40, 2, 8, 128, 128, lambda: _routing(40, 2, list(range(8)), 14),
        np.zeros(40, bool), 0),
    # 74 assignments: no tile and no window divides them
    "streamed_a_length_no_window_divides": (
        37, 2, 8, 128, 128, lambda: _routing(37, 2, list(range(8)), 15),
        None, 0),
}


def _bound(monkeypatch, rows, d):
    """Rows and outputs of more than ``rows`` rows do not stay in VMEM."""
    monkeypatch.setattr(gm, "_RESIDENT_BYTES", 2 * rows * d * 2)


def _kernel_calls(monkeypatch):
    """The list that every call of the kernel adds (rows' shape, whether
    they are resident) to."""
    calls = []
    inner = gm._call
    monkeypatch.setattr(gm, "_call", lambda *a, **how: calls.append(
        (a[0].shape, how["in_vmem"])) or inner(*a, **how))
    return calls


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_loop_over_the_experts_and_the_ragged_dot(
        case, kernel, monkeypatch):
    tokens, k, num, d, f, routing, mask, *bound = CASES[case]
    if bound:
        _bound(monkeypatch, bound[0], d)
    idx, weights = routing()
    gate, up, down = _stacks(num, d, f, seed=tokens)
    y = jax.random.normal(jax.random.PRNGKey(tokens + 1), (tokens, d),
                          jnp.bfloat16)
    args = (y, jnp.asarray(idx), jnp.asarray(weights), gate, up, down,
            None if mask is None else jnp.asarray(mask))
    assert gm.selected(tokens * k, gate.shape, y.dtype)
    calls = _kernel_calls(monkeypatch)
    # a jit of its own each: the predicate is no part of a cache's key
    got, load = jax.jit(lambda *a: moe.experts(*a))(*args)
    held = tokens * k + gm.room(tokens * k)
    assert held % 16 == 0 and calls == [((held, d), not bound)]
    monkeypatch.setattr(gm, "selected", lambda *a: False)
    ragged, ragged_load = jax.jit(lambda *a: moe.experts(*a))(*args)
    assert calls == [((held, d), not bound)]         # the other path
    want, want_load = _loop(y, idx, weights, gate, up, down, mask)
    np.testing.assert_array_equal(np.asarray(load), want_load)
    np.testing.assert_array_equal(np.asarray(ragged_load), want_load)
    assert got.dtype == ragged.dtype == y.dtype
    got, ragged = (np.asarray(a, np.float32) for a in (got, ragged))
    assert np.abs(want).max() > 0.5 or (mask is not None and not mask.any())
    np.testing.assert_allclose(got, want, atol=LOOP_TOL, rtol=0)
    np.testing.assert_allclose(ragged, want, atol=LOOP_TOL, rtol=0)
    np.testing.assert_allclose(got, ragged, atol=2 * LOOP_TOL, rtol=0)
    if mask is not None:  # a token outside the mask gets zeros, exactly
        assert not got[~mask].any() and not ragged[~mask].any()


@pytest.mark.parametrize("rows_live", ["resident", "streamed"])
def test_rows_behind_the_last_group_leave_the_kernel_as_zeros(
        rows_live, kernel, monkeypatch):
    """The kernel's own contract, below ``experts``: 48 sorted rows of
    which the groups take 23, and the room for a last window behind them;
    the rest of the 48 come back zero, and a group's rows are its expert's
    whatever tile they start in."""
    if rows_live == "streamed":
        _bound(monkeypatch, 0, 128)
    gate, up, down = _stacks(8, 128, 128, seed=0)
    rows = jax.random.normal(jax.random.PRNGKey(1), (48 + gm.room(48), 128),
                             jnp.bfloat16)
    load = jnp.asarray([0, 7, 0, 11, 0, 0, 5, 0], jnp.int32)
    calls = _kernel_calls(monkeypatch)
    got = np.asarray(jax.jit(lambda *a: gm.grouped_swiglu(*a))(
        rows, gate, up, down, load), np.float32)
    assert calls == [(rows.shape, rows_live == "resident")]
    assert got.shape == rows.shape and not got[23:48].any()
    x = np.asarray(rows, np.float32)
    at = 0
    for e, n in enumerate(np.asarray(load)):
        g = x[at:at + n] @ np.asarray(gate[e], np.float32)
        u = x[at:at + n] @ np.asarray(up[e], np.float32)
        want = (g / (1 + np.exp(-g)) * u) @ np.asarray(down[e], np.float32)
        np.testing.assert_allclose(got[at:at + n], want, atol=LOOP_TOL,
                                   rtol=0)
        at += n


def test_the_kernel_is_chosen_from_what_the_call_sees(monkeypatch):
    from horovod_tpu.parallel import mesh as mesh_lib
    glm = (64, 2048, 1536)
    bf16 = jnp.dtype(jnp.bfloat16)
    assert not gm.selected(256, glm, bf16)                # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm.selected(256, glm, bf16)                    # 64 rows decode
    assert all(gm.selected(4 * s, glm, bf16)              # every prefill
               for s in range(128, 1025, 128))
    assert gm.selected(16, (8, 512, 256), bf16)           # chip_smoke's
    assert not gm.selected(256, glm, jnp.dtype(jnp.float32))
    assert not gm.selected(256, (64, 2048, 1500), bf16)   # odd widths
    assert not gm.selected(256, (64, 2000, 1536), bf16)
    assert not gm.selected(256, (8, 4096, 2048), bf16)    # two experts too
    laguna = (256, 2048, 512)
    assert all(gm.selected(8 * s, laguna, bf16)           # rows decide nothing
               for s in (64, 512, 1024, 4096, 32768))
    assert not gm.selected(0, laguna, bf16)
    # where the rows live follows from the shape
    assert gm.resident(4 * 1024, 2048, 2) and gm.resident(8 * 512, 2048, 2)
    assert not gm.resident(4 * 1152, 2048, 2)
    assert not gm.resident(8 * 1024, 2048, 2)
    mesh_lib.reset_global_mesh()
    try:
        mesh_lib.set_global_mesh(
            mesh_lib.build_mesh(devices=jax.devices()[:2], dp=2))
        assert not gm.selected(256, glm, bf16)            # several devices
        mesh_lib.reset_global_mesh()
        mesh_lib.set_global_mesh(
            mesh_lib.build_mesh(devices=jax.devices()[:1], dp=1))
        assert gm.selected(256, glm, bf16)                # a mesh of one
    finally:
        mesh_lib.reset_global_mesh()
