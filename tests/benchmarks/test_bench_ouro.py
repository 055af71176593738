"""The Ouro family's benchmark files at a size the CPU runs: the published
configuration file with every width made tiny (the four passes kept),
through the harness (``serve-closed`` generator, served check, int8
control), its counts, the two per-layer metrics its cell lists, and what
the generator offers the cell's seeds."""

import itertools
import json
import os

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import xplane

CELL = "tiny-ouro-serve-closed"
REAL = "ouro2.6b-serve-closed"


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "ouro-2.6b.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = published()
    cfg.update(name="tiny-ouro", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               vocab_size=256, max_position_embeddings=256,
               num_hidden_layers={"serve_1chip": 2}, reduced=[])
    return cfg


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The repository's benchmark with one more cell, of the tiny
    configuration: new files in a root of its own, nothing edited."""
    root = str(tmp_path_factory.mktemp("ouro"))
    traffic = dict(bench_tiny.TRAFFIC["tiny-serve"], family="ouro",
                   model_overrides={"attention_impl": "full"})
    # the check scores up to output_tokens.max positions a request
    traffic["prompt_tokens"] = dict(traffic["prompt_tokens"], max=24)
    traffic["output_tokens"] = dict(traffic["output_tokens"], max=100)
    bench_tiny._dump(root, "configs", "tiny-ouro", tiny_config())
    bench_tiny._dump(root, "traffic", "tiny-ouro-closed4", traffic)
    # at these widths on the CPU the served model (bfloat16) reads 0-0.034
    # over the 40-70 tokens a short window serves (6 seeds), the int8
    # control 0.134-0.229 over 1,200 (3 seeds): 0.07 lies between
    bench_tiny._dump(root, "limits", CELL, {"served_logit_gap": 0.07})
    add = {"configs": [{"name": "tiny-ouro", "source": "self-test",
                        "file": "benchmarks/configs/tiny-ouro.json",
                        "reduced": [], "why": "tiny"}],
           "workloads": [{"name": CELL, "config": "tiny-ouro",
                          "traffic": "tiny-ouro-closed4", "chips": 1,
                          "why": "tiny"}],
           "per_layer": []}
    bench = bench_tiny._grow(bench_tiny.repo_benchmark(), add, CELL)
    for m in bench["per_layer"]:
        if m["name"].startswith("loop."):
            m["workloads"].append(CELL)
    return bench_tiny._write_benchmark(root, bench, (REPO,))


def test_the_tiny_family_is_correct_through_the_harness(roots):
    from horovod_tpu.utils import tracing as hvd_tracing
    result, lines = bench_tiny.run_cell(roots, CELL, seconds=0.5)
    assert result["correct"] is True, [x for x in lines
                                       if x["line"] == "compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"} <= \
        set(result["metrics"])
    program, = (x for x in lines if x["line"] == "program")
    # the counts are handed the WEIGHT layers; the cache holds the planes
    assert (program["layers"], program["passes"], program["planes"]) == \
        (2, 4, 8)
    assert set(program["state_bytes"]) == {"k", "v"}
    assert program["state_bytes"]["k"] == 8 * 4 * 128 * 4 * 16 * 2
    compared, = (x for x in lines if x["line"] == "compared")
    assert compared["name"].startswith("served_logit_gap[")
    assert 0 <= compared["value"] < compared["limit"]
    # the step record's count of the configured passes, on decoding steps
    # (the ring's newest records: this run's engine wrote them)
    records = [r for r in hvd_tracing.get_tracer().steps()
               if "passes" in r][-20:]
    assert records and {r["passes"] for r in records} == {4}


def test_the_int8_control_reads_not_correct(roots):
    """The control as ``control.py`` reads it (the reference itself in
    int8 in the program's place; the gap, under the float32 reference, of
    the token it puts first), on a made-up sample of 1,200 positions: the
    same sample on every machine, so one number."""
    import sys

    import numpy as np
    from benchmarks import run as run_mod
    reg = registry_mod.Registry(roots)
    bench = reg.benchmark()
    serve = reg.module("generators", "serve-closed")
    run = run_mod.Run(reg, bench, registry_mod.cell_of(bench, CELL), 6, 1, 0,
                      sys.stdout)
    rng = np.random.default_rng(6)
    sample = [{"id": f"m{i}", "prompt": tuple(rng.integers(0, 256, 8 + i)),
               "tokens": tuple(rng.integers(0, 256, 100))}
              for i in range(12)]
    want = serve.reference_logits(run, sample)
    low = serve.reference_logits(run, sample, quant="int8")
    first = [lg.argmax(axis=-1) for lg in low]
    gap, _, scored = serve.widest_gap(sample, want, tokens=first)
    assert scored == 1200
    assert gap > 1.5 * run.limits["served_logit_gap"]    # measured 0.144
    best = [lg.argmax(axis=-1) for lg in want]
    assert serve.widest_gap(sample, want, tokens=best)[0] == 0.0


def test_the_configuration_file_holds_the_published_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    cfg = published()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == {"source": 48, "serve_1chip": 6}
    assert cfg["total_ut_steps"] == 4 and cfg["early_exit_threshold"] == 1
    assert {"sandwich_norms", "norm_between_passes", "exit_gate",
            "kv_per_pass", "weights", "compute_dtype"} <= set(cfg["assumed"])
    assert "init" not in cfg["assumed"]          # no gain was needed


def test_the_cell_and_its_traffic_are_the_issues():
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    cell = registry_mod.cell_of(bench, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro-2.6b", "serve-closed32-ouro", 1)
    traffic = reg.data("traffic", cell["traffic"])
    # the generator and every parameter are the ones ISSUE 37 named
    assert (traffic["generator"], traffic["family"], traffic["callers"]) == \
        ("serve-closed", "ouro", 32)
    assert set(traffic) == set(reg.data("traffic", "serve-closed32-h1"))
    assert traffic["engine"] == {"num_slots": 32, "max_len": 1536,
                                 "kv_block": 128,
                                 "admission_timeout_s": 1200.0}
    h1 = reg.data("traffic", "serve-closed32-h1")
    for law in ("prompt_tokens", "output_tokens", "requests_per_cycle",
                "temperature", "preroll_s", "check_requests",
                "trace_seconds"):
        assert traffic[law] == h1[law]
    e2e = {m["name"] for m in
           registry_mod.metrics_of(bench, "end_to_end", REAL)}
    assert e2e == {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"}
    layer = {m["name"] for m in
             registry_mod.metrics_of(bench, "per_layer", REAL)}
    assert {"loop.passes_per_token", "loop.decode_attn_roofline",
            "model.decode_roofline", "attn.kv_bytes_per_step",
            "entry.compiles.serve", "device.idle_share.serve",
            "device.peak_hbm.serve", "engine.occupancy"} <= layer
    # the four that read nothing since PR 28 stay with the cells they had
    assert not {m for m in layer if m.startswith(("engine.idle.", "mixer.",
                                                  "cache."))}
    for other in ("baichuan7b-serve-closed", "falconh1-34b-serve-closed"):
        names = {m["name"] for m in
                 registry_mod.metrics_of(bench, "per_layer", other)}
        assert "engine.idle.in_readback" in names
        assert not {m for m in names if m.startswith("loop.")}


# -- what the generator offers the cell's seeds ------------------------------

def _kv_work(requests):
    """Tokens of K/V the server streams for these requests: each step of
    a request reads its prompt and what it has written so far."""
    return sum(o * len(p) + o * (o + 1) // 2 for p, o in requests)


def _cycle(seed, k=1):
    reg = registry_mod.Registry([REPO])
    gen = reg.module("generators", "serve-closed")
    mix = reg.data("traffic", "serve-closed32-ouro")
    n = k * mix["requests_per_cycle"]
    return list(itertools.islice(gen.request_stream(mix, 49152, seed), n))


@pytest.mark.parametrize("seed", [1, 3700000204, 2 ** 31 + 5])
def test_every_seed_offers_the_same_two_multisets_of_lengths(seed):
    got, first = _cycle(seed, k=2), _cycle(1)
    n = len(first)
    for cycle in (got[:n], got[n:]):
        assert sorted(len(p) for p, _ in cycle) == \
            sorted(len(p) for p, _ in first)
        assert sorted(o for _, o in cycle) == sorted(o for _, o in first)
    assert _cycle(seed, k=2) == got                          # same seed
    assert 16 <= min(len(p) for p, _ in got) and \
        max(len(p) + o for p, o in got) <= 1536


def test_which_prompt_meets_which_output_depends_on_the_seed():
    """What the cell's seeds differ by (PERF.md §6, PR 37): the two
    multisets are permuted independently, so the K/V a cycle makes the
    server stream is another sum for another seed; K/V is half of this
    cell's decode step."""
    work = [_kv_work(_cycle(s)) for s in (1, 2, 3)]
    assert max(work) / min(work) > 1.05


def test_the_counts_follow_the_shapes():
    counts = registry_mod.Registry([REPO]).module("counts", "ouro")
    cfg = published()
    # ISSUE 37's arithmetic, from the row's keys
    assert counts.layer_parameters(cfg) == \
        4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51388416
    assert counts.head_parameters(cfg) == 2048 * 49152
    assert counts.kv_bytes_per_token(cfg, 6) == 196608
    assert counts.kv_bytes_per_token(cfg, 48) == 4 * 48 * 2 * 16 * 128 * 2
    weights = counts.decode_step_bytes(cfg, 6, 0)
    assert weights == (4 * 6 * 51388416 + 2048 * 49152) * 2
    assert round(weights / 1e9, 2) == 2.67
    # the stack's weights are read once a PASS, the head once
    one = dict(cfg, total_ut_steps=1)
    assert weights - counts.decode_step_bytes(one, 6, 0) == \
        3 * 6 * 51388416 * 2
    live = 16384
    assert counts.decode_attention_bytes(cfg, 6, live) == live * 196608
    assert round(counts.decode_attention_bytes(cfg, 6, live) / 1e9, 2) \
        == 3.22
    assert counts.decode_step_bytes(cfg, 6, live, rows=32) == \
        weights + live * 196608
    assert counts.decode_step_bytes(cfg, 6, live, rows=32, bytes_per=1) == \
        (weights + live * 196608) // 2


# -- the new reader, on events named as the compiled program names them ------

KERNEL = ('%decode_attention.{n} = bf16[32,16,128]{{2,1,0:T(8,128)(2,1)}} '
          'custom-call(s32[1]{{0:T(128)S(6)}} %reshape.{n}, bf16[24,32,24576,'
          '128]{{3,2,1,0:T(8,128)(2,1)}} %bitcast.1, bf16[24,32,24576,128]'
          '{{3,2,1,0:T(8,128)(2,1)}} %bitcast.2), '
          'custom_call_target="tpu_custom_call"')
MATMUL = ("%fusion.{n} = bf16[32,2048]{{1,0}} fusion(bf16[32,5632]{{1,0}} "
          "%x, bf16[5632,2048]{{1,0}} %w), kind=kOutput")
FLASH = ('%flash.3 = bf16[16,1024,128]{2,1,0} custom-call(bf16[16,1024,128]'
         '{2,1,0} %q), custom_call_target="tpu_custom_call"')


class FakeRun:
    def __init__(self, lines, family="ouro"):
        self.registry = registry_mod.Registry([REPO])
        self.config = published()
        self.traffic = {"family": family}
        self.peaks = {"hbm_bytes_per_s": 819e9}
        self.lines = lines

    def log(self, line, **fields):
        self.lines.append(dict(line=line, **fields))


def decode_trace(kernel_s, calls=3, planes=24, kernel=KERNEL):
    """``calls`` decode programs of ``planes`` (pass, layer) bodies: the
    attention kernel and a matmul each; and one prefill program whose
    flash kernel is Mosaic too."""
    ops, mods, t = [], [], 0.0
    for _ in range(calls):
        start = t
        for n in range(planes):
            for name, d in ((kernel.format(n=n), kernel_s), (MATMUL.format(n=99), 1e-4)):
                ops.append(xplane.Event(name, t, t + d))
                t += d
        mods.append(xplane.Event("jit__decode_jit(7)", start, t))
        t += 1e-3
    ops.append(xplane.Event(FLASH, t, t + 5e-3))
    mods.append(xplane.Event("jit__prefill_jit(3)", t, t + 5e-3))
    return xplane.Trace({0: ops}, {0: mods}, [])


def steps(live, n=5):
    return [(0.0, 0.0, 0, 32, 32, live) for _ in range(n)]


def test_decode_attn_roofline_reads_the_kernels_events():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "loop.decode_attn_roofline")
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": decode_trace(kernel_s=2.5e-4),
           "traced": {"steps": steps(14000), "model": {"layers": 6}}}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    least = 14000 * 196608 / 819e9           # a call, all 24 planes
    assert value == pytest.approx(100 * least / (24 * 2.5e-4))
    assert 0 < value < 100
    said, = lines
    assert (said["calls"], said["events"]) == (3, 72)    # not the flash call
    assert said["kernel_ms_per_call"] == pytest.approx(6.0)
    # fewer live tokens: the least time falls with them
    obs["traced"]["steps"] = steps(7000)
    assert reader.read(obs, spec["args"], FakeRun([])) == \
        pytest.approx(value / 2)


def test_decode_attn_roofline_reports_nothing_where_there_is_nothing():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "loop.decode_attn_roofline")
    reader = reg.module("readers", spec["reader"])
    traced = {"steps": steps(14000), "model": {"layers": 6}}
    # the einsum path: no kernel of that name in the decode program
    obs = {"trace": decode_trace(2.5e-4, kernel=MATMUL), "traced": traced}
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    # a program that keeps no step records, a family without the count
    obs = {"trace": decode_trace(2.5e-4), "traced": {"model": {"layers": 6}}}
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    obs["traced"] = traced
    assert reader.read(obs, spec["args"], FakeRun([], "baichuan")) is None


@pytest.mark.parametrize("planes, layers, want", [
    (24, 6, 4),      # the cell: four passes of six layers
    (18, 6, 3),      # a program that skips a pass reads less
    (6, 6, 1),       # a stack that runs once
])
def test_passes_per_token_counts_the_kernels_that_ran(planes, layers, want):
    """``loop.passes_per_token`` is read from the trace, not from the
    configuration: kernel events a decode call over the weight layers."""
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "loop.passes_per_token")
    assert spec["source"] == "device_trace"
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": decode_trace(2.5e-4, planes=planes),
           "traced": {"model": {"layers": layers}}}
    assert reader.read(obs, spec["args"], FakeRun(lines)) == want
    said, = lines
    assert (said["calls"], said["events"], said["layers"]) == \
        (3, 3 * planes, layers)


def test_passes_per_token_reports_nothing_without_the_kernel():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "loop.passes_per_token")
    reader = reg.module("readers", spec["reader"])
    obs = {"trace": decode_trace(2.5e-4, kernel=MATMUL),
           "traced": {"model": {"layers": 6}}}
    assert reader.read(obs, spec["args"], FakeRun([])) is None


def test_the_selection_rule_on_events_recorded_on_the_chip():
    """``fixtures/ouro_events_v5e.json``: instruction texts of the cell's
    own traced run on a v5e, the passes one loop.  In the decode program
    the readers select the SIX ``decode_attention`` custom calls, one a
    weight layer, each seen once a pass a call and handed the WHOLE
    24-plane cache twice (K and V) and no slice of it, and nothing else
    (not the ``while`` that encloses them); in the prefill program, whose
    flash kernels are Mosaic calls too, nothing."""
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "ouro_events_v5e.json")) as f:
        recorded = json.load(f)
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "loop.decode_attn_roofline")
    reader = reg.module("readers", spec["reader"])
    kernel = spec["args"]["kernel"]
    decode = recorded["events"]["jit__decode_jit"]
    picked = [(name, seen, took) for name, seen, took in decode
              if reader.is_kernel(name, kernel)]
    assert len(picked) == 6 < len(decode)
    cache = ("bf16", (24, 32, 1536 * 16, 128))
    for name, seen, took in picked:
        assert seen == 4 * recorded["calls_sampled"]      # once a pass
        operands = xplane.shapes(
            name.partition("custom-call(")[2].partition("), custom_call")[0])
        assert operands.count(cache) == 2
        assert 0.1e-3 < took / seen < 0.3e-3      # 0.19 ms a plane
    # ``loop.passes_per_token``: events a call over the weight layers
    assert sum(s for _, s, _ in picked) / recorded["calls_sampled"] / 6 == 4
    mosaic = [n for n, _, _ in decode if xplane.op_class(n) == "mosaic"]
    assert sorted(mosaic) == sorted(n for n, _, _ in picked)
    loops = [n for n, _, _ in decode if xplane.opcode(n) == "while"]
    assert len(loops) == 1 and not reader.is_kernel(loops[0], kernel)
    prefill = recorded["events"]["jit__prefill_jit"]
    flash = [n for n, _, _ in prefill if xplane.op_class(n) == "mosaic"]
    assert len(flash) >= 6
    assert not [n for n in flash if reader.is_kernel(n, kernel)]
    # what the kernel took a call in that run, against what the cache of
    # the traced window's live tokens needs (the result line read 78.9%)
    counts = reg.module("counts", "ouro")
    took = sum(t for _, _, t in picked) / recorded["calls_sampled"]
    least = counts.decode_attention_bytes(published(), 6, 14796.43) / 819e9
    assert 70 < 100 * least / took < 85
