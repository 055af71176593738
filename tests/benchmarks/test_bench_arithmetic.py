"""The benchmark's own arithmetic, on hand-worked values."""

import json
import os
import statistics

import pytest

from benchmarks.lib import stats
from bench_tiny import REPO


def test_train_rate_carries_a_stall_and_the_median_block_beside_it_does_not():
    blocks = [1.0] * 29
    steady = stats.total_rate(29, 2560, sum(blocks), chips=1)
    assert steady == stats.block_rate(blocks, 2560, 1) == 2560.0
    stalled = blocks[:10] + [2.4] + blocks[11:]   # one block lost 1.4 s
    # the end-to-end rate is all the work over all the time: it moves
    rate = stats.total_rate(29, 2560, sum(stalled), 1)
    assert rate == pytest.approx(2560 * 29 / 30.4) and rate < 0.96 * steady
    # the per-layer statistics tell the stall from a slower step
    assert stats.block_rate(stalled, 2560, 1) == steady
    assert stats.stall_share(blocks, sum(blocks)) == pytest.approx(0.0)
    assert stats.stall_share(stalled, sum(stalled)) == \
        pytest.approx(1.4 / 30.4)
    assert rate == pytest.approx(
        steady * (1 - stats.stall_share(stalled, sum(stalled))))
    # a step that is slower throughout moves both
    slow = [1.05] * 29
    assert stats.block_rate(slow, 2560, 1) == pytest.approx(
        stats.total_rate(29, 2560, sum(slow), 1))


def test_a_stall_every_n_steps_shows_in_train_rate():
    blocks = ([1.0] * 4 + [1.5]) * 8              # every fifth block stalls
    assert stats.block_rate(blocks, 100, 1) == 100.0
    assert stats.total_rate(40, 100, sum(blocks), 1) == \
        pytest.approx(100 / 1.1)


def test_rates_are_per_chip():
    assert stats.block_rate([2.0, 2.0, 2.0], 32768, 4) == 4096.0
    assert stats.total_rate(3, 32768, 6.0, 4) == 4096.0


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 5.5), (90, 9.1),
                                    (100, 10.0)])
def test_percentile_interpolates_between_order_statistics(q, want):
    assert stats.percentile(range(1, 11), q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_tpot_is_last_minus_first_over_tokens_minus_one():
    assert stats.tpot_seconds(10.0, 13.2, 101) == pytest.approx(0.032)
    assert stats.tpot_seconds(10.0, 10.0, 1) is None


def test_quartile_spread_is_the_contracts():
    values = [100, 101, 99, 100.5, 99.5, 102]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartile_spread(values) == \
        pytest.approx((q3 - q1) / statistics.median(values))


def test_worst_leaf_gap_measures_against_the_median_leaf():
    want = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    got = {"a": 1.01, "b": 2.0, "tiny": 2e-9}
    gap, leaf = stats.worst_leaf_gap(got, want)
    # tiny's own norm is all but zero: it is held against the median (1.0)
    assert leaf == "a" and gap == pytest.approx(0.01)
    with pytest.raises(ValueError):
        stats.worst_leaf_gap({"a": 1.0}, want)
    assert stats.worst_leaf_gap({"a": float("nan"), "b": 2.0, "tiny": 0.0},
                                want)[0] == float("inf")


def test_worst_leaf_gap_over_named_leaves_keeps_the_floor_of_all():
    want = {"stage0.a": 1.0, "stage3.b": 2.0, "fc.kernel": 4.0,
            "fc.bias": 1e-9}
    got = {"stage0.a": 1.5, "stage3.b": 2.02, "fc.kernel": 4.0,
           "fc.bias": 3e-2}
    assert stats.worst_leaf_gap(got, want)[1] == "stage0.a"
    gap, leaf = stats.worst_leaf_gap(got, want, ["stage3.", "fc."])
    # fc.bias is held against the median of ALL leaves (1.5), not its own
    assert leaf == "fc.bias" and gap == pytest.approx(3e-2 / 1.5)


def test_histogram_counts_every_value_once():
    assert stats.histogram([1, 5, 5, 9, 10], [0, 5, 10]) == [1, 4]


# -- required operations and bytes ------------------------------------------

def _config(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name)) as f:
        return json.load(f)


def _counts(family):
    from benchmarks.lib.registry import Registry
    return Registry([REPO]).module("counts", family)


def test_baichuan_flops_per_token_by_hand():
    cfg, c = _config("baichuan-7b.json"), _counts("baichuan")
    assert c.layer_parameters(cfg) == 4 * 4096 ** 2 + 3 * 4096 * 11008 \
        == 202_375_168
    assert c.head_parameters(cfg) == 262_144_000
    # depth 1, s 4096: 6 x 464.5 M + 6 x 1 x 4096 x 4096 = 2.888 GFLOP
    assert c.train_flops_per_item(cfg, 1, {"seq_len": 4096}) == \
        6 * (202_375_168 + 262_144_000) + 6 * 4096 * 4096 == 2_887_778_304
    # the program's own count is unmasked attention: 12 L s d
    assert c.train_flops_per_item(cfg, 4, {"seq_len": 4096}) == \
        6 * (4 * 202_375_168 + 262_144_000) + 6 * 4 * 4096 * 4096


def test_flash_counts_are_causal_and_count_no_recomputation_twice():
    c = _counts("baichuan")
    flops, nbytes = c.flash_forward(64, 4096, 128)
    assert flops == 2 * 64 * 4096 * 4096 * 128        # half of 4 s^2 d
    assert nbytes == 4 * 64 * 4096 * 128 * 2
    flops_b, nbytes_b = c.flash_backward(64, 4096, 128)
    assert flops_b == 2.5 * flops and nbytes_b == 2 * nbytes
    # compute-bound on a v5e: 1024 FLOP/byte against a ridge of 240
    assert flops / nbytes == 1024


def test_decode_step_bytes_by_hand():
    cfg, c = _config("baichuan-7b.json"), _counts("baichuan")
    weights = (10 * 202_375_168 + 262_144_000) * 2
    kv = 2 * 10 * 6400 * 4096 * 2
    assert c.decode_step_bytes(cfg, 10, 6400) == weights + kv


def test_resnet50_forward_macs_by_hand():
    cfg, c = _config("resnet50.json"), _counts("resnet")
    stem = 112 * 112 * 64 * 7 * 7 * 3
    assert stem == 118_013_952
    # torchvision's resnet50 (v1.5) at 224 px: 4.09 G multiply-adds
    assert c.forward_macs(cfg) == 4_089_184_256
    assert c.train_flops_per_item(cfg, None, {}) == 6 * 4_089_184_256


# -- what a training cell is held to, and the readers beside train_rate -----

def test_compare_reads_a_limit_of_its_own_for_named_leaves():
    from benchmarks.lib.registry import Registry
    train = Registry([REPO]).module("generators", "train")
    with open(os.path.join(REPO, "benchmarks", "limits",
                           "resnet50-train-b256.json")) as f:
        limits = json.load(f)
    want = {"losses": [7.0, 6.9],
            "grad_norms": {"stage0.a": 1.0, "stage3.b": 1.0, "fc.kernel": 2.0},
            "delta_norms": {"stage0.a": 1.0, "stage3.b": 1.0,
                            "fc.kernel": 2.0}}
    # early leaves off by 8% (the seeded net's own noise), late ones by 5%
    # (what fp8 reads there, and bf16 never): only the late limit fails
    got = {"losses": [7.0005, 6.9005],
           "grad_norms": {"stage0.a": 1.08, "stage3.b": 1.05,
                          "fc.kernel": 2.0},
           "delta_norms": dict(want["delta_norms"])}
    checks = {n.split("[")[0]: (n, v, lim)
              for n, v, lim in train.compare(got, want, limits)}
    assert set(checks) == {"loss_rel.step1", "loss_rel.step2",
                           "grad_norm_gap", "delta_norm_gap",
                           "grad_norm_gap.late"}
    failed = {k for k, (_, v, lim) in checks.items() if v > lim}
    assert failed == {"grad_norm_gap.late"}
    assert checks["grad_norm_gap.late"][0].endswith("[stage3.b]")
    assert checks["grad_norm_gap"][0].endswith("[stage0.a]")
    # a part of the batch left out moves the loss past its limit
    got["losses"] = [7.0 * 1.001, 6.9]
    assert any(v > lim for n, v, lim in train.compare(got, want, limits)
               if n.startswith("loss_rel"))


def test_the_readers_beside_train_rate_tell_a_stall_from_a_slower_step():
    from benchmarks.lib.registry import Registry
    reg = Registry([REPO])
    blocks = [1.0] * 9 + [2.0]
    obs = {"window": {"block_s": blocks, "window_s": 11.0,
                      "items_per_block": 1000, "chips": 1,
                      "end_to_end": {"train_rate": 10 * 1000 / 11.0}}}
    p50 = reg.module("readers", "block_rate_p50").read(obs, {}, None)
    stall = reg.module("readers", "stall_share").read(obs, {}, None)
    assert p50 == 1000.0 and stall == pytest.approx(100 / 11.0)
    assert obs["window"]["end_to_end"]["train_rate"] == \
        pytest.approx(p50 * (1 - stall / 100))
    serve = {"window": {"kind": "serve"}}
    assert reg.module("readers", "block_rate_p50").read(serve, {}, None) \
        is None


@pytest.mark.parametrize("in_flight", [1, 2, 8])
def test_the_training_window_keeps_blocks_in_flight_and_drains_them(
        in_flight):
    """With ``blocks_in_flight`` n the host has sent n blocks before it
    waits for the first and never has more in flight (a host held up
    leaves the chip its queued blocks); the window closes only when every
    block sent has been read and ``--seconds`` have passed; the blocks'
    times add up to the window: ``train_rate`` is still all the items
    over all the time."""
    import contextlib
    import types
    from benchmarks.lib.registry import Registry
    train = Registry([REPO]).module("generators", "train")
    events = []

    class Loss:
        def __float__(self):
            events.append("read")
            return 1.0

    def step(params, opt_state, batch):
        events.append("sent")
        return params, opt_state, Loss()

    run = types.SimpleNamespace(
        traffic={"steps_per_block": 3, "blocks_in_flight": in_flight},
        span=lambda name: contextlib.nullcontext(),
        log=lambda line, **fields: None)
    gen = train.Generator(run)
    gen.compiles_before = 1
    gen.prog = types.SimpleNamespace(
        step=step, params=None, opt_state=None, batch=None,
        items_per_step=8, chips=1, compiles=lambda: 1)
    obs = gen.window(0.05)
    assert events[:3 * in_flight + 1] == ["sent"] * 3 * in_flight + ["read"]
    sent = read = most = 0
    for e in events:
        sent, read = sent + (e == "sent"), read + (e == "read")
        most = max(most, sent - 3 * read)
    assert most == 3 * in_flight            # blocks in flight, in steps
    assert sent == 3 * read == obs["steps"] == gen.steps_done
    assert obs["window_s"] >= 0.05
    assert sum(obs["block_s"]) == pytest.approx(obs["window_s"])
    assert obs["end_to_end"]["train_rate"] == pytest.approx(
        sent * 8 / obs["window_s"])
    # a window of no length still runs one block, and a second window
    # (the traced one) sends no more than fits it
    events.clear()
    gen.block_s = 1.0
    assert gen.window(0.0)["steps"] == 3 and events == ["sent"] * 3 + ["read"]


@pytest.mark.parametrize("seed", ["41", "42", "43"])
def test_resnet50s_fp8_control_as_read_on_the_chip_is_not_correct(seed):
    """The cell's limits against the norms recorded on the v5e at the
    cell's own size: the bf16 program passes every number; the fp8 control
    fails the late leaves' gradient gap, and only needs to fail one."""
    from benchmarks.lib.registry import Registry
    train = Registry([REPO]).module("generators", "train")
    with open(os.path.join(REPO, "benchmarks", "limits",
                           "resnet50-train-b256.json")) as f:
        limits = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "resnet50_control_v5e.json")) as f:
        got = json.load(f)["readings"][seed]
    sound = train.compare(got["program"], got["reference"], limits)
    assert all(v <= lim for _, v, lim in sound), sound
    control = {n.split("[")[0]: (v, lim) for n, v, lim in train.compare(
        got["control"], got["reference"], limits)}
    late, lim = control["grad_norm_gap.late"]
    assert late > 3 * lim
    # room on the sound side too: the program reads under a third of it
    assert dict((n.split("[")[0], v) for n, v, _ in sound)[
        "grad_norm_gap.late"] < lim / 3
