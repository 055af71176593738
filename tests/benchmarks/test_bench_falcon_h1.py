"""The Falcon-H1 family's benchmark files at a size the CPU runs: the
published configuration file with every width made tiny, through the
harness (``serve-closed`` generator, served check, int8 control), its
counts, and the three per-layer readers this family's cell lists."""

import json
import os

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import xplane

CELL = "tiny-h1-serve-closed"
SSM = "f32[6,32,32,128,256]{4,3,2,1,0:T(8,128)}"


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "falcon-h1-34b.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = published()
    cfg.update(name="tiny-h1", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               mamba_n_heads=4, mamba_d_head=8, mamba_d_ssm=32,
               mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=16,
               vocab_size=256, max_position_embeddings=256,
               num_hidden_layers={"serve_1chip": 2}, reduced=[])
    return cfg


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The repository's benchmark with one more cell, of the tiny
    configuration: new files in a root of its own, nothing edited."""
    root = str(tmp_path_factory.mktemp("h1"))
    traffic = dict(bench_tiny.TRAFFIC["tiny-serve"], family="falcon_h1",
                   model_overrides={"attention_impl": "full"})
    # the check scores up to output_tokens.max positions a request
    traffic["prompt_tokens"] = dict(traffic["prompt_tokens"], max=24)
    traffic["output_tokens"] = dict(traffic["output_tokens"], max=100)
    bench_tiny._dump(root, "configs", "tiny-h1", tiny_config())
    bench_tiny._dump(root, "traffic", "tiny-h1-closed4", traffic)
    # at these widths on the CPU the served model (bfloat16) reads 0-0.009
    # over the 50-110 tokens a short window serves (4 seeds), the int8
    # control 0.043-0.097 over 1,200 (6 seeds): 0.03 lies between
    bench_tiny._dump(root, "limits", CELL, {"served_logit_gap": 0.03})
    add = {"configs": [{"name": "tiny-h1", "source": "self-test",
                        "file": "benchmarks/configs/tiny-h1.json",
                        "reduced": [], "why": "tiny"}],
           "workloads": [{"name": CELL, "config": "tiny-h1",
                          "traffic": "tiny-h1-closed4", "chips": 1,
                          "why": "tiny"}],
           "per_layer": []}
    bench = bench_tiny._grow(bench_tiny.repo_benchmark(), add, CELL)
    for m in bench["per_layer"]:
        if m["name"].startswith(("mixer.", "cache.")):
            m["workloads"].append(CELL)
    return bench_tiny._write_benchmark(root, bench, (REPO,))


def test_the_tiny_family_is_correct_through_the_harness(roots):
    result, lines = bench_tiny.run_cell(roots, CELL, seconds=0.5)
    assert result["correct"] is True, [x for x in lines
                                       if x["line"] == "compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"} <= \
        set(result["metrics"])
    program, = (x for x in lines if x["line"] == "program")
    assert set(program["state_bytes"]) == {"k", "v", "ssm", "conv"}
    compared, = (x for x in lines if x["line"] == "compared")
    assert compared["name"].startswith("served_logit_gap[")
    assert 0 <= compared["value"] < compared["limit"]


def test_the_int8_control_reads_not_correct(roots):
    """The control as ``control.py`` reads it (the reference itself in
    int8 in the program's place; the gap, under the float32 reference, of
    the token it puts first), on a made-up sample of 1,200 positions
    rather than on whatever a window of wall-clock time served: the same
    sample on every machine, so one number."""
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
    assert gap > 2 * run.limits["served_logit_gap"]     # measured 0.097
    # and the float32 reference's own first tokens read 0
    best = [lg.argmax(axis=-1) for lg in want]
    assert serve.widest_gap(sample, want, tokens=best)[0] == 0.0


def test_the_configuration_file_holds_the_published_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    cfg = published()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == {"source": 72, "serve_1chip": 6}
    assert set(cfg["assumed"]["init"]["gains_log2"]) <= {
        "q", "k", "v", "o", "in_proj", "out_proj", "gate", "up", "down",
        "head"}


def test_the_counts_follow_the_shapes():
    counts = registry_mod.Registry([REPO]).module("counts", "falcon_h1")
    cfg = published()
    # ISSUE 27's arithmetic, from the row's keys
    assert round(counts.layer_parameters(cfg) / 1e6, 2) == 430.12
    assert counts.head_parameters(cfg) == 261120 * 5120
    assert counts.ssm_state_bytes(cfg) == 32 * 128 * 256 * 4
    assert counts.conv_state_bytes(cfg) == 3 * 5120 * 2
    assert counts.kv_bytes_per_token(cfg) == 2048
    layers, live = 6, 20000
    row = counts.ssm_state_bytes(cfg) + counts.conv_state_bytes(cfg)
    none = counts.decode_step_bytes(cfg, layers, live, rows=0)
    for rows in (1, 7.5, 32):
        assert counts.decode_step_bytes(cfg, layers, live, rows=rows) \
            - none == 2 * rows * layers * row
    assert counts.decode_step_bytes(cfg, layers, live + 1, rows=3) \
        - counts.decode_step_bytes(cfg, layers, live, rows=3) \
        == layers * 2048
    assert counts.state_update_bytes(cfg, layers, 32) == \
        2 * 32 * layers * counts.ssm_state_bytes(cfg)
    with pytest.raises(TypeError, match="needs rows"):
        counts.decode_step_bytes(cfg, layers, live)
    flops, nbytes = counts.prefill_scan(cfg, 1024)
    assert flops > 0 and nbytes > counts.ssm_state_bytes(cfg)


# -- the readers, on events named as the compiled programs name them ---------

class FakeRun:
    def __init__(self, lines):
        self.registry = registry_mod.Registry([REPO])
        self.config = published()
        self.traffic = {"family": "falcon_h1"}
        self.peaks = {"hbm_bytes_per_s": 819e9}
        self.lines = lines

    def log(self, line, **fields):
        self.lines.append(dict(line=line, **fields))


def decode_trace(update_s, reduce_s, calls=3, state=SSM):
    """``calls`` decode programs of six layers each: per layer the fusion
    that reduces S C from the state and the one that rewrites it in place
    (the text is the v5e compile's, ahead of time), and a matmul."""
    ops, mods, t = [], [], 0.0
    for _ in range(calls):
        start = t
        for i in range(6):
            for name, d in (
                    (f"%fusion.{36 - 3 * i} = f32[32,32,128]{{2,1,0}} fusion("
                     f"{state} %state__ssm__.1, f32[32,32,256]{{2,1,0}} "
                     f"%bitcast.584), kind=kLoop", reduce_s),
                    (f"%select_dynamic-update-slice_fusion.{i} = {state} "
                     f"fusion({state} %state__ssm__.1, f32[32,32,256]"
                     f"{{2,1,0}} %bitcast.575, pred[32]{{0}} %mask.1), "
                     f"kind=kLoop", update_s),
                    ("%fusion.9 = bf16[32,5120]{1,0} fusion(bf16[32,21504]"
                     "{1,0} %x, bf16[21504,5120]{1,0} %w), kind=kOutput",
                     1e-3)):
                ops.append(xplane.Event(name, t, t + d))
                t += d
        mods.append(xplane.Event("jit__decode_jit(7)", start, t))
        t += 2e-3
    return xplane.Trace({0: ops}, {0: mods}, [])


def steps(rows, n=5):
    return [(0.0, 0.0, 0, rows, rows, 1000) for _ in range(n)]


def test_state_update_roofline_reads_the_events_of_the_states_shape():
    reader = registry_mod.Registry([REPO]).module(
        "readers", "state_update_roofline")
    lines = []
    run = FakeRun(lines)
    obs = {"trace": decode_trace(update_s=4e-4, reduce_s=2e-4),
           "traced": {"steps": steps(32), "model": {"layers": 6}}}
    value = reader.read(obs, {"module": "jit__decode_jit"}, run)
    slab = 32 * 32 * 128 * 256 * 4
    least = 2 * 6 * slab / 819e9            # a call, all 32 rows
    assert value == pytest.approx(100 * least / (6 * 6e-4))
    assert 0 < value < 100
    said, = lines
    assert said["calls"] == 3 and said["events"] == 36
    assert said["events_move_bytes"] == 3 * 6 * 3 * slab   # 2 reads, 1 write
    # fewer rows decode: the least time falls with them
    obs["traced"]["steps"] = steps(16)
    assert reader.read(obs, {"module": "jit__decode_jit"}, run) == \
        pytest.approx(value / 2)


def test_state_update_roofline_refuses_events_that_move_too_little():
    """A state the compiler kept elsewhere: the events of its shape move
    fewer bytes than the update needs, and nothing is reported (not a
    share over 100%)."""
    reader = registry_mod.Registry([REPO]).module(
        "readers", "state_update_roofline")
    lines = []
    trace = decode_trace(update_s=1e-5, reduce_s=1e-5)
    # only one layer's events keep the state's shape
    trace.ops[0] = [e for e in trace.ops[0] if "fusion.36" in e.name
                    or "kOutput" in e.name]
    obs = {"trace": trace,
           "traced": {"steps": steps(32), "model": {"layers": 6}}}
    assert reader.read(obs, {"module": "jit__decode_jit"},
                       FakeRun(lines)) is None
    assert lines[0]["events_move_bytes"] < lines[0]["need_bytes"]
    # no decode program in the window, or a family without the count
    obs["trace"].modules[0] = []
    assert reader.read(obs, {"module": "jit__decode_jit"},
                       FakeRun([])) is None
    run = FakeRun([])
    run.traffic = {"family": "baichuan"}
    assert reader.read(obs, {"module": "jit__decode_jit"}, run) is None


@pytest.mark.parametrize("text,scan", [
    # per-chunk states and the carried state
    ("%fusion.7 = f32[1,8,2,16,128,256]{5,4,3,2,1,0} fusion(bf16[1,8,128,2,"
     "16,128]{5,4,3,2,1,0} %x), kind=kOutput", True),
    ("%while.3 = f32[1,2,16,128,256]{4,3,2,1,0} fusion(f32[1,2,16,128,256]"
     "{4,3,2,1,0} %s), kind=kLoop", True),
    # the masked decay product within a chunk
    ("%fusion.2 = bf16[1,8,2,16,128,128]{5,4,3,2,1,0} fusion(f32[1,8,2,16,"
     "128]{4,3,2,1,0} %cum), kind=kLoop", True),
    # projections, K/V, the flash kernel's blocks at one chunk's length
    ("%fusion.9 = bf16[1024,9248]{1,0} fusion(bf16[1024,5120]{1,0} %h, "
     "bf16[5120,9248]{1,0} %w), kind=kOutput", False),
    ("%copy.4 = bf16[1,1024,4,128]{3,2,1,0} copy(bf16[1,1024,4,128]"
     "{3,1,2,0} %k)", False),
    ("%flash = bf16[20,128,128]{2,1,0} custom-call(bf16[20,128,128]{2,1,0} "
     "%q), custom_call_target=\"tpu_custom_call\"", False),
    ("%fusion.1 = bf16[1,1024,32,128]{3,2,1,0} fusion(bf16[1024,4096]{1,0} "
     "%x), kind=kLoop", False),
])
def test_the_scans_events_are_told_by_their_shapes(text, scan):
    reader = registry_mod.Registry([REPO]).module("readers",
                                                  "prefill_scan_share")
    assert reader.is_scan(text, 32, 128, 256, 128) is scan


def test_prefill_scan_share_is_the_scans_part_of_the_programs_time():
    reader = registry_mod.Registry([REPO]).module("readers",
                                                  "prefill_scan_share")
    scan = ("%fusion.7 = f32[1,8,2,16,128,256]{5,4,3,2,1,0} fusion(bf16[1,8,"
            "128,2,16,128]{5,4,3,2,1,0} %x), kind=kOutput")
    other = ("%fusion.9 = bf16[1024,9248]{1,0} fusion(bf16[1024,5120]{1,0} "
             "%h, bf16[5120,9248]{1,0} %w), kind=kOutput")
    loop = "%while.1 = (s32[], f32[1,2,16,128,256]{4,3,2,1,0}) while(%t)"
    ops = [xplane.Event(other, 0.0, 0.03), xplane.Event(loop, 0.03, 0.04),
           xplane.Event(scan, 0.03, 0.04),
           # a decode program's events are not the prefill's
           xplane.Event(scan, 0.05, 0.06)]
    mods = [xplane.Event("jit__prefill_jit(3)", 0.0, 0.045),
            xplane.Event("jit__decode_jit(7)", 0.05, 0.07)]
    lines = []
    obs = {"trace": xplane.Trace({0: ops}, {0: mods}, [])}
    value = reader.read(obs, {"module": "jit__prefill_jit"}, FakeRun(lines))
    assert value == pytest.approx(25.0)
    assert lines[0]["calls"] == 1
    run = FakeRun([])
    run.config = {"hidden_size": 4096}          # a family with no mixer
    assert reader.read(obs, {"module": "jit__prefill_jit"}, run) is None


def test_step_count_median_reads_the_records_own_count(monkeypatch):
    from benchmarks.lib import step_phases
    reader = registry_mod.Registry([REPO]).module("readers",
                                                  "step_count_median")
    decode = [["decode_prepare", 0, 1], ["decode_dispatch", 1, 2]]
    records = [dict(phases=decode, state_bytes=b) for b in (10, 30, 20)] \
        + [dict(phases=[["prefill", 0, 1]] + decode, state_bytes=99)]
    monkeypatch.setattr(step_phases, "analysis",
                        lambda obs, run: {"window": records})
    args = {"count": "state_bytes", "scale": 0.5}
    assert reader.read({}, args, None) == 10.0
    # a program whose records lack the count (the parent commit)
    for r in records:
        del r["state_bytes"]
    assert reader.read({}, args, None) is None
    monkeypatch.setattr(step_phases, "analysis", lambda obs, run: None)
    assert reader.read({}, args, None) is None


def test_the_selection_rules_on_events_recorded_on_the_chip():
    """``fixtures/falconh1_events_v5e.json``: instruction texts of the
    cell's own traced run on a v5e.  The state reader selects two events
    a layer in the decode program (the fusion that reduces S C from the
    state, the one that rewrites it in place) and nothing else; the scan
    rule selects no projection, head, copy of K/V or flash kernel."""
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "falconh1_events_v5e.json")) as f:
        recorded = json.load(f)
    reg = registry_mod.Registry([REPO])
    state = reg.module("readers", "state_update_roofline")
    scan = reg.module("readers", "prefill_scan_share")
    tail, slab = (32, 128, 256), 32 * 32 * 128 * 256 * 4
    decode = recorded["events"]["jit__decode_jit"]
    hits = {name.split(" = ")[0]: state.slabs(name, tail)
            for name, _, _ in decode if state.slabs(name, tail)}
    rewrites = {k: v for k, v in hits.items() if "update-slice" in k}
    reduces = {k: v for k, v in hits.items() if k not in rewrites}
    assert len(rewrites) == len(reduces) == 6            # one a layer
    assert set(rewrites.values()) == {2 * slab}          # read + write
    assert set(reduces.values()) == {slab}               # a second read
    assert len(decode) > len(hits)                       # and others none
    prefill = recorded["events"]["jit__prefill_jit"]
    picked = [name for name, _, _ in prefill
              if scan.is_scan(name, 32, 128, 256, 128)]
    assert picked and len(picked) < len(prefill)
    for name in picked:
        assert "128,256]" in name or "128,128]" in name
        assert "custom-call(" not in name and "261120" not in name
    mosaic = [n for n, _, _ in prefill if xplane.op_class(n) == "mosaic"]
    assert mosaic and not set(mosaic) & set(picked)
