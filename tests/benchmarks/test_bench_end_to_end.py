"""The harness end to end at tiny sizes on the CPU, with its look for a
chip skipped: the result line, correctness against the plain reference,
the control that has to fail, a timed path broken underneath, and a tree
that adds one of everything without editing a file."""

import json
import os
import subprocess
import sys

import pytest

import bench_tiny
from bench_tiny import REPO


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="module")
def train_run(roots):
    return bench_tiny.run_cell(roots, "tiny-lm-train")


@pytest.fixture(scope="module")
def serve_run(roots):
    return bench_tiny.run_cell(roots, "tiny-lm-serve", seconds=0.5)


@pytest.fixture(autouse=True, scope="module")
def _shutdown():
    yield
    import horovod_tpu as hvd
    hvd.shutdown()


def _line(lines, tag):
    return [x for x in lines if x["line"] == tag]


@pytest.mark.parametrize("which", ["train_run", "serve_run"])
def test_the_last_line_has_the_contracts_keys(which, request):
    result, _ = request.getfixturevalue(which)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["failed"] == 0 and result["attempted"] > 0
    json.dumps(result)


def test_training_agrees_with_the_plain_reference(train_run):
    result, lines = train_run
    assert result["correct"] is True
    compared = {x["name"].split("[")[0]: x for x in _line(lines, "compared")}
    assert set(compared) == {"loss_rel.step1", "loss_rel.step2",
                             "loss_rel.step3", "grad_norm_gap",
                             "delta_norm_gap"}
    assert all(x["value"] <= x["limit"] and x["ok"]
               for x in compared.values())
    # every number compared is printed beside its limit; set-up is itemised
    items = _line(lines, "setup")[0]["items"]
    assert {"weights", "build", "compile_or_cache_load_and_first_step",
            "probes"} <= set(items)
    blocks = _line(lines, "blocks")[0]
    assert blocks["count"] == len(blocks["rates"]) >= 1


def test_train_rate_is_all_items_over_the_window_and_the_first_steps_fed_it(
        train_run):
    result, lines = train_run
    blocks = _line(lines, "blocks")[0]
    items = 2 * 64 * blocks["steps_per_block"]     # batch x seq x steps
    assert result["metrics"]["train_rate"]["value"] == \
        pytest.approx(blocks["count"] * items / blocks["window_s"])
    assert blocks["total_rate"] <= max(blocks["rates"])
    # attempted counts the three checked steps and every step of the window
    assert result["attempted"] == 3 + blocks["count"] * 2


def test_serving_agrees_with_the_plain_reference(serve_run):
    result, lines = serve_run
    assert result["correct"] is True
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p90",
                                      "tpot_p90", "setup_s"}
    window = _line(lines, "window")[0]
    assert window["ttft_samples"] == window["finished"] > 0
    assert window["tokens"] > 0 and window["in_flight"] == 4
    lengths = _line(lines, "lengths")[0]
    assert sum(lengths["prompt_histogram"].values()) == 8
    ref = _line(lines, "reference")[0]
    assert ref["requests"] >= 1 and ref["served_tokens"] > 0


def test_four_devices_run_the_sharded_step_against_the_reference(roots):
    result, lines = bench_tiny.run_cell(roots, "tiny-lm-train4")
    assert result["correct"] is True, _line(lines, "compared")
    assert _line(lines, "program")[0]["mesh"] == {"dp": 2, "tp": 2}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        roots, monkeypatch):
    import jax
    from benchmarks.lib.registry import Registry
    adapter = Registry(roots).module("programs", "baichuan")
    build = adapter.build_train

    def broken(run):
        prog = build(run)
        real = prog.step

        def step(params, opt_state, batch):
            copy = jax.tree_util.tree_map(lambda a: a + 0, (params,
                                                            opt_state))
            _, _, loss = real(*copy, batch)
            return params, opt_state, loss
        step._cache_size = real._cache_size
        step.lower = real.lower
        prog.step = step
        return prog
    monkeypatch.setattr(adapter, "build_train", broken)
    monkeypatch.setattr(Registry, "module", lambda self, kind, name, _m=
                        Registry.module: adapter if (kind, name) ==
                        ("programs", "baichuan") else _m(self, kind, name))
    result, lines = bench_tiny.run_cell(roots, "tiny-lm-train")
    assert result["correct"] is False
    failed = {x["name"].split("[")[0] for x in _line(lines, "compared")
              if not x["ok"]}
    assert {"grad_norm_gap", "delta_norm_gap"} <= failed


def test_a_token_altered_where_it_is_produced_is_not_correct(
        roots, monkeypatch):
    from horovod_tpu.serving import engine as engine_mod
    real = engine_mod.sample_tokens
    monkeypatch.setattr(engine_mod, "sample_tokens",
                        lambda rng, logits, temps:
                        (real(rng, logits, temps) + 1) % logits.shape[-1])
    for fn in (engine_mod._decode_jit, engine_mod._prefill_jit):
        fn.clear_cache()
    try:
        result, lines = bench_tiny.run_cell(roots, "tiny-lm-serve",
                                            seconds=0.3)
    finally:
        for fn in (engine_mod._decode_jit, engine_mod._prefill_jit):
            fn.clear_cache()
    assert result["correct"] is False
    assert not _line(lines, "compared")[0]["ok"]


def test_the_lower_precision_control_comes_out_not_correct(roots):
    """The control at a size a test run can hold: the reference computed
    in fp8 in the program's place fails the training limits that the
    program itself (bf16) meets."""
    from benchmarks import control
    got = control.main(["--workload", "tiny-lm-train", "--seeds", "3",
                        "--controls", "1"], roots=roots,
                       require_chip=False)[0]
    assert all(v <= lim for _, v, lim in got["sound"]), got["sound"]
    assert any(v > lim for _, v, lim in got["control"]), got["control"]


def test_a_spent_budget_starts_no_control_and_programs_are_built_once(roots):
    from benchmarks import control
    from benchmarks.lib import train_reference as tref
    built = len(tref._PROGRAMS)
    got = control.main(["--workload", "tiny-lm-train", "--seeds", "4,5",
                        "--controls", "2", "--budget-seconds", "0"],
                       roots=roots, require_chip=False)
    assert [set(g) >= {"sound", "seed", "seconds"} and "control" not in g
            for g in got] == [True, True]
    # two seeds, one set of reference programs (at most one new: float32)
    assert len(tref._PROGRAMS) - built <= 1


def test_a_new_metric_and_reader_need_no_edit(roots):
    """The temporary tree's own per-layer metric (``test.steps``, reader
    ``window_field``) is found by name beside the repository's files."""
    from benchmarks.lib import registry as registry_mod
    reg = registry_mod.Registry(roots)
    spec = reg.data("metrics", "test.steps")
    reader = reg.module("readers", spec["reader"])
    assert reader.read({"window": {"steps": 12}}, spec["args"], None) == 12
    assert reg.path("readers", "window_field.py").startswith(roots[0])
    assert reg.path("generators", "train.py").startswith(REPO)
    assert [m["name"] for m in registry_mod.metrics_of(
        reg.benchmark(), "per_layer", "tiny-lm-train")] == ["test.steps"]


def test_run_py_refuses_a_cpu_with_nothing_under_a_metrics_name():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "resnet50-train-b256", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "metrics" not in proc.stdout and "train_rate" not in proc.stdout


def test_an_unknown_chip_is_an_error_not_a_default(roots, monkeypatch):
    import jax
    from benchmarks import run as run_mod
    from benchmarks.lib import registry as registry_mod

    class Fake:
        platform, device_kind = "tpu", "TPU v9"
    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    reg = registry_mod.Registry(roots)
    bench = reg.benchmark()
    run = run_mod.Run(reg, bench, bench["workloads"][0], 1, 1, 0, sys.stdout)
    with pytest.raises(SystemExit, match="no row for device_kind"):
        run_mod.find_devices(run, require_chip=True)


def test_resnet_reference_and_program_agree_in_float32():
    """The weight table of reference/resnet.py maps onto the program's
    ResNet-50 tree; with both in float32 the logits agree."""
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import weights
    from benchmarks.lib.registry import Registry
    from horovod_tpu import models
    reg = Registry([REPO])
    ref = reg.module("reference", "resnet")
    prog = reg.module("programs", "resnet")
    cfg = {"image_size": 32, "num_classes": 10}
    w = jax.jit(lambda k: weights.make(ref.weight_shapes(cfg), k,
                                       jnp.float32))(weights.seed_key(2 ** 31))
    model = models.build("resnet50", num_classes=10, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3),
                          jnp.bfloat16)
    stats = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), x[:2], train=False))["batch_stats"]
    stats = jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                   stats)
    got, _ = jax.jit(lambda p, x: model.apply(
        {"params": p, "batch_stats": stats}, x, train=True,
        mutable=["batch_stats"]))(prog.to_tree(w), x)
    want = jax.jit(lambda w, x: ref.logits(w, x, cfg))(w, x)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-3 * float(
        jnp.max(jnp.abs(want)))
    assert prog.from_tree(prog.to_tree(w)).keys() == w.keys()
