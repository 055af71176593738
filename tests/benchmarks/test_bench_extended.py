"""What a ``model_config`` PR adds, as added files alone: the extended
benchmark of ``bench_tiny.make_extended_root`` (a second serving family
with its program adapter, reference and counts, traffic, limits, a cell, a
per-layer metric on a new layer) run through the harness on the CPU; the
served check on the model's own bf16 leaves against a float32 copy of
them; and the decode roofline's count told the rows that decode."""

import hashlib
import io
import json
import os
import statistics
import types

import numpy as np
import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks import run as run_mod
from benchmarks.lib import xplane
from benchmarks.lib.registry import Registry, cell_of

ADDED = "tiny-wrapped-serve-closed"


def _digest():
    """Every file of the checkout that the benchmark reads."""
    h = hashlib.sha256()
    tops = [os.path.join(REPO, "BENCHMARK.json")]
    for top in ("benchmarks", "tests/benchmarks"):
        for folder, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = sorted(d for d in dirs if d != "__pycache__")
            tops += [os.path.join(folder, f) for f in sorted(files)
                     if not f.endswith(".pyc")]
    for path in tops:
        with open(path, "rb") as f:
            h.update(path.encode() + b"\0" + f.read())
    return h.hexdigest(), len(tops)


@pytest.fixture(scope="module")
def checkout_before():
    return _digest()


@pytest.fixture(scope="module")
def ext_roots(tmp_path_factory, checkout_before):
    return bench_tiny.make_extended_root(
        str(tmp_path_factory.mktemp("extended")))


@pytest.fixture(scope="module")
def tiny_roots(tmp_path_factory):
    return bench_tiny.make_root(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture(scope="module")
def added_run(ext_roots):
    return bench_tiny.run_cell(ext_roots, ADDED, seconds=0.5)


@pytest.fixture(autouse=True, scope="module")
def _shutdown():
    yield
    import horovod_tpu as hvd
    hvd.shutdown()


def _line(lines, tag):
    return [x for x in lines if x["line"] == tag]


def _run(roots, workload, seed=11):
    reg = Registry(roots)
    bench = reg.benchmark()
    run = run_mod.Run(reg, bench, cell_of(bench, workload), seed, 1.0, 0,
                      io.StringIO())
    run_mod.find_devices(run, False)
    return run


def _sample(vocab=256):
    """Finished requests as the generator books them, made up: the
    longest the tiny mixes allow, the shortest, one between."""
    rng = np.random.default_rng(5)
    return [{"id": f"r{i}",
             "prompt": tuple(int(t) for t in rng.integers(0, vocab, p)),
             "tokens": [int(t) for t in rng.integers(0, vocab, n)]}
            for i, (p, n) in enumerate([(64, 24), (4, 2), (30, 9)])]


# -- the added cell, through the harness --------------------------------------

def test_the_added_cell_runs_to_a_correct_result_on_the_cpu(added_run):
    result, lines = added_run
    assert result["correct"] is True, _line(lines, "compared")
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "ttft_p90",
                                      "tpot_p90", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    start = _line(lines, "start")[0]
    assert (start["config"], start["traffic"]) == ("tiny-wrapped",
                                                   "tiny-wrapped-closed4")
    compared = _line(lines, "compared")
    assert len(compared) == 1 and compared[0]["ok"] and \
        compared[0]["name"].startswith("served_logit_gap[c")
    ref = _line(lines, "reference")[0]
    assert ref["requests"] >= 1 and ref["served_tokens"] > 0


def test_the_added_family_is_found_by_name_in_files_of_its_own(ext_roots):
    reg = Registry(ext_roots)
    traffic = reg.data("traffic", "tiny-wrapped-closed4")
    assert traffic["family"] == "wrapped"
    for kind in ("programs", "reference", "counts"):
        assert reg.path(kind, "wrapped.py").startswith(ext_roots[0])
        assert reg.path(kind, "baichuan.py").startswith(REPO)
    assert reg.path("generators", "serve-closed.py").startswith(REPO)
    assert reg.path("readers", "row_state_share.py").startswith(ext_roots[0])
    assert reg.path("readers", "decode_roofline.py").startswith(REPO)
    # the family's reference names its leaves its own way, in the order
    # and under the naming rules of lib/weights.py
    ref = reg.module("reference", "wrapped")
    base = reg.module("reference", "baichuan")
    cfg = json.load(open(reg.path("configs", "tiny-wrapped.json")))
    mine, theirs = ref.weight_shapes(cfg, 2), base.weight_shapes(cfg, 2)
    assert list(mine.values()) == list(theirs.values())
    assert [ref.to_base(n) for n in mine] == list(theirs)
    assert set(mine) & set(theirs) == {"embed"}
    assert all(n.endswith(".scale") == m.endswith(".scale")
               for n, m in zip(mine, theirs))


def test_the_check_logs_its_own_device_bytes(added_run):
    _, lines = added_run
    mem = _line(lines, "reference_memory")[0]
    program = _line(lines, "program")[0]
    d, f, v, layers = 64, 128, 256, program["layers"]
    parameters = 2 * v * d + d + layers * (4 * d * d + 3 * d * f + 2 * d)
    # the leaves are held once, in bfloat16: two bytes a parameter
    assert mem["leaf_bytes"] == 2 * parameters
    assert mem["quant"] is None
    # arguments (the leaves, one row of tokens, the positions), the
    # logits and the program's temporaries
    assert mem["peak_bytes"] >= mem["leaf_bytes"] + mem["temp_bytes"] + \
        4 * (128 + 24) + 4 * 24 * v


# -- B: the served check reads the model's bf16 leaves -------------------------

CHECKED = [("tiny", "tiny-lm-serve"), ("extended", ADDED)]


@pytest.fixture(scope="module")
def roots_of(tiny_roots, ext_roots):
    return {"tiny": tiny_roots, "extended": ext_roots}


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("which,workload", CHECKED)
def test_the_reference_on_bf16_leaves_equals_it_on_their_float32_copy(
        which, workload, quant, roots_of, monkeypatch):
    import jax.numpy as jnp
    run = _run(roots_of[which], workload)
    gen = run.registry.module("generators", "serve-closed")
    sample = _sample()
    now = gen.reference_logits(run, sample, quant=quant)
    real = gen.make_leaves
    monkeypatch.setattr(gen, "make_leaves", lambda shapes, key: {
        n: a.astype(jnp.float32) for n, a in real(shapes, key).items()})
    before = gen.reference_logits(run, sample, quant=quant)
    assert [x.shape for x in now] == [(24, 256), (2, 256), (9, 256)]
    for a, b in zip(now, before):
        assert a.dtype == b.dtype == np.float32
        assert np.array_equal(a, b)
    assert np.isfinite(now[0]).all() and float(np.ptp(now[0])) > 1.0
    lines = [json.loads(x) for x in run.out.getvalue().splitlines()]
    new, old = _line(lines, "reference_memory")
    assert old["leaf_bytes"] == 2 * new["leaf_bytes"]
    assert new["quant"] == old["quant"] == quant


@pytest.mark.parametrize("which,workload", CHECKED)
def test_the_reference_is_handed_the_served_leaves_and_no_float32_copy(
        which, workload, roots_of, monkeypatch):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import train_reference as tref
    run = _run(roots_of[which], workload)
    gen = run.registry.module("generators", "serve-closed")
    ref = run.registry.module("reference", run.traffic["family"])
    adapter = run.registry.module("programs", run.traffic["family"])
    shapes = ref.weight_shapes(run.config,
                               adapter.depth(run.config, run.traffic))
    leaves = gen.make_leaves(shapes, tref.weights_key(run.seed))
    assert {a.dtype for a in leaves.values()} == {jnp.dtype(jnp.bfloat16)}
    assert {n: a.shape for n, a in leaves.items()} == shapes
    seen = []
    real = ref.logits_at

    def spy(w, *args, **kw):
        seen.append({n: a.dtype for n, a in w.items()})
        out = real(w, *args, **kw)
        assert out.dtype == jnp.float32
        return out
    monkeypatch.setattr(ref, "logits_at", spy)
    made = []
    real_make = gen.make_leaves
    monkeypatch.setattr(gen, "make_leaves", lambda s, k: made.append(
        real_make(s, k)) or made[-1])
    gen.reference_logits(run, _sample()[:1])
    assert len(seen) == 1 and set(seen[0]) == set(shapes)
    assert set(seen[0].values()) == {jnp.dtype(jnp.bfloat16)}
    # and they are freed when the check is done
    assert all(a.is_deleted() for a in made[0].values())
    assert not [a for a in jax.live_arrays()
                if a.dtype == jnp.float32 and a.size >= 256 * 64]


def test_training_still_differentiates_float32_leaves_unchanged():
    """``matmul`` and ``rms_norm`` widen their weight operand: for the
    float32 leaves of the training check that is the identity."""
    import jax
    import jax.numpy as jnp
    ref = Registry([REPO]).module("reference", "baichuan")
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 5), jnp.float32)
    assert np.array_equal(ref.matmul(x, w, None), jnp.matmul(x, w))
    g = jax.grad(lambda w: ref.matmul(x, w, None).sum())(w)
    assert g.dtype == jnp.float32 and np.array_equal(
        g, jnp.broadcast_to(x.sum(0)[:, None], w.shape))
    lowered = jax.jit(lambda x, w: ref.matmul(x, w, None)).lower(x, w)
    assert "convert" not in lowered.as_text()
    wb = w.astype(jnp.bfloat16)
    assert np.array_equal(ref.matmul(x, wb, None),
                          jnp.matmul(x, wb.astype(jnp.float32)))
    s = 1.0 + 0.1 * w[0]
    assert np.array_equal(
        ref.rms_norm(x[:, :5], s.astype(jnp.bfloat16)),
        ref.rms_norm(x[:, :5], s.astype(jnp.bfloat16).astype(jnp.float32)))


# -- C: the decode roofline's count is told the rows ---------------------------

def _decode_obs(steps, durations):
    """Observations of a traced window: ``steps`` as the generator books
    them and one ``jit__decode_jit`` module event a duration."""
    t, mods = 0.1, []
    for d in durations:
        mods.append(xplane.Event("jit__decode_jit(7)", t, t + d))
        t += d + 0.001
    trace = xplane.Trace(ops={0: [xplane.Event(
        "%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop", e.start, e.end)
        for e in mods]}, modules={0: mods},
        spans=[xplane.Event("bench.window", 0.0, t + 0.1)])
    return {"trace": trace, "traced": {"steps": steps,
                                       "model": {"layers": 2}}}


def _reader_run(roots, family, config):
    logged = []
    reg = Registry(roots)
    with open(reg.path("configs", config + ".json")) as f:
        cfg = json.load(f)
    run = types.SimpleNamespace(
        registry=reg, traffic={"family": family}, config=cfg,
        peaks={"hbm_bytes_per_s": 819e9},
        log=lambda line, **kw: logged.append(dict(kw, line=line)))
    return run, logged


STEPS = [(0.0, 0.01, 0, 4, 3, 100), (0.01, 0.02, 1, 5, 4, 140),
         (0.02, 0.03, 0, 3, 2, 90)]


def test_the_reader_tells_the_count_the_mean_of_the_steps_live_rows(
        ext_roots, monkeypatch):
    run, logged = _reader_run(ext_roots, "wrapped", "tiny-wrapped")
    counts = run.registry.module("counts", "wrapped")
    reader = run.registry.module("readers", "decode_roofline")
    calls = []
    real = counts.decode_step_bytes
    monkeypatch.setattr(counts, "decode_step_bytes",
                        lambda *a, **kw: calls.append((a, kw)) or
                        real(*a, **kw))
    durations = [0.004, 0.002, 0.003]
    value = reader.read(_decode_obs(STEPS, durations),
                        {"module": "jit__decode_jit"}, run)
    (args, kw), = calls
    assert kw == {"rows": 3.0} and args[1:] == (2, 110.0)
    nbytes = real(run.config, 2, 110.0, rows=3.0)
    assert value == pytest.approx(
        100.0 * nbytes / 819e9 / statistics.median(durations))
    line, = logged
    assert line["line"] == "decode_roofline" and line["rows"] == 3.0 and \
        line["live_tokens"] == 110.0 and line["bytes"] == nbytes
    # nothing to read gives nothing, never a 0
    assert reader.read(_decode_obs([], durations),
                       {"module": "jit__decode_jit"}, run) is None
    assert reader.read(_decode_obs(STEPS, durations),
                       {"module": "jit__other"}, run) is None


def test_a_family_with_per_row_state_counts_it_and_needs_the_rows(ext_roots):
    reg = Registry(ext_roots)
    cfg = json.load(open(reg.path("configs", "tiny-wrapped.json")))
    mine = reg.module("counts", "wrapped")
    base = reg.module("counts", "baichuan")
    plain = base.decode_step_bytes(cfg, 2, 110)
    assert mine.decode_step_bytes(cfg, 2, 110, rows=0) == plain
    # 16 float32 values a row a layer, read and written
    assert mine.decode_step_bytes(cfg, 2, 110, rows=3) == \
        plain + 2 * 4 * 16 * 2 * 3
    with pytest.raises(TypeError, match="needs rows"):
        mine.decode_step_bytes(cfg, 2, 110)


@pytest.mark.parametrize("rows", [None, 0, 16, 15.86])
def test_baichuans_bytes_are_the_same_whatever_the_rows(rows):
    reg = Registry([REPO])
    cfg = json.load(open(reg.path("configs", "baichuan-7b.json")))
    c = reg.module("counts", "baichuan")
    weights = (10 * 202_375_168 + 262_144_000) * 2
    kv = 2 * 10 * 6400 * 4096 * 2
    assert c.decode_step_bytes(cfg, 10, 6400, rows=rows) == weights + kv
    assert c.decode_step_bytes(cfg, 10, 6400, rows, 1) == (weights + kv) // 2


def test_baichuans_roofline_reads_what_it_read(monkeypatch):
    """The accepted serving cell's number, from the same steps and the
    same trace, with and without the rows: the same."""
    run, logged = _reader_run([REPO], "baichuan", "baichuan-7b")
    reader = run.registry.module("readers", "decode_roofline")
    steps = [(0.0, 0.016, 0, 16, 16, 7800), (0.016, 0.032, 0, 16, 15, 7700)]
    obs = _decode_obs(steps, [0.0122, 0.0121, 0.0123])
    obs["traced"]["model"]["layers"] = 10
    value = reader.read(obs, {"module": "jit__decode_jit"}, run)
    nbytes = ((10 * 202_375_168 + 262_144_000) + 2 * 10 * 7750 * 4096) * 2
    assert value == pytest.approx(100.0 * nbytes / 819e9 / 0.0122)
    assert logged[0]["rows"] == 15.5 and logged[0]["bytes"] == nbytes


def test_the_added_layers_reader_reads_the_share_of_per_row_state(ext_roots):
    run, _ = _reader_run(ext_roots, "wrapped", "tiny-wrapped")
    spec = run.registry.data("metrics", "state.row_bytes_share")
    reader = run.registry.module("readers", spec["reader"])
    counts = run.registry.module("counts", "wrapped")
    obs = {"window": {"steps": STEPS, "model": {"layers": 2}}}
    state = counts.row_state_bytes(run.config, 2, 3.0)
    total = counts.decode_step_bytes(run.config, 2, 110.0, rows=3.0)
    assert reader.read(obs, spec["args"], run) == \
        pytest.approx(100.0 * state / total)
    assert reader.read({"window": {"steps": []}}, spec["args"], run) is None
    # a family that keeps no per-row state has nothing to read
    other, _ = _reader_run([REPO], "baichuan", "baichuan-7b")
    assert reader.read(obs, spec["args"], other) is None


# -- and no file of the checkout was touched ------------------------------------

def test_the_extended_benchmark_touched_no_file_of_the_checkout(
        checkout_before, ext_roots, added_run):
    assert ext_roots[1] == REPO and not ext_roots[0].startswith(REPO)
    assert _digest() == checkout_before
    assert checkout_before[1] > 80
