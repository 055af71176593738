"""The Phi-4-mini-flash family's benchmark files (``phi4flash``) at a size
the CPU runs: the published configuration file with every width made tiny
(the layer plan, eps, the biases and the init rules kept), through the
harness (``serve-closed``, unedited), its counts against hand arithmetic
(the numbers of ISSUE 48), the entries of ``BENCHMARK.json`` looked up BY
NAME, the per-layer metrics its cell lists and their readers on the events
a v5e recorded."""

import json
import os
import sys

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import xplane

sys.path.insert(0, os.path.join(REPO, "tests"))
import test_sambay_model as sm  # noqa: E402

CELL = "tiny-phi4flash-serve"
REAL = "phi4miniflash-serve-reasoning"
CONFIG = "phi-4-mini-flash"
FAMILY = "phi4flash"
METRICS = ("yoco.decode_attn_roofline", "yoco.shared_kv_bytes_per_step",
           "yoco.prefill_cross_share", "mamba1.state_update_roofline",
           "mamba1.prefill_scan_share", "mamba1.state_bytes_per_step")
CATALOG_ROW = {  # /opt/skills/guides/model-configs/architectures.jsonl
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
published = sm.published


def tiny_config():
    return dict(sm.tiny_config(max_position_embeddings=256),
                name="tiny-phi4flash", reduced=[])


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The repository's benchmark with one more cell, of the tiny
    configuration: new files in a root of its own, nothing edited."""
    root = str(tmp_path_factory.mktemp("phi4flash"))
    traffic = dict(bench_tiny.TRAFFIC["tiny-serve"], family=FAMILY,
                   model_overrides={"attention_impl": "full"})
    traffic["prompt_tokens"] = dict(traffic["prompt_tokens"], max=24)
    traffic["output_tokens"] = dict(traffic["output_tokens"], max=100)
    bench_tiny._dump(root, "configs", "tiny-phi4flash", tiny_config())
    bench_tiny._dump(root, "traffic", "tiny-phi4flash-closed4", traffic)
    # bfloat16 through 8 tiny layers on the CPU reads 0-0.05
    bench_tiny._dump(root, "limits", CELL, {"served_logit_gap": 0.08})
    add = {"configs": [{"name": "tiny-phi4flash", "source": "self-test",
                        "file": "benchmarks/configs/tiny-phi4flash.json",
                        "reduced": [], "why": "tiny"}],
           "workloads": [{"name": CELL, "config": "tiny-phi4flash",
                          "traffic": "tiny-phi4flash-closed4", "chips": 1,
                          "why": "tiny"}],
           "per_layer": []}
    bench = bench_tiny._grow(bench_tiny.repo_benchmark(), add, CELL)
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"].append(CELL)
    return bench_tiny._write_benchmark(root, bench, (REPO,))


def test_the_familys_files_are_found_by_name():
    reg = registry_mod.Registry([REPO])
    for kind in ("programs", "reference", "counts"):
        assert reg.module(kind, FAMILY)
    assert reg.data("traffic", "serve-closed96-phi4flash")["family"] == FAMILY
    assert published()["family"] == FAMILY
    ref = reg.module("reference", FAMILY)
    with open(ref.__file__) as f:
        assert "horovod_tpu" not in f.read()
    for name in ("one_token_share", "scan_loop_share",
                 "slab_update_roofline", "mixed_attn_roofline"):
        assert reg.module("readers", name)


def test_the_configuration_is_the_catalogs_row_uncut():
    cfg = published()
    for key, value in CATALOG_ROW.items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["reduced"] == [] and cfg["name"] == CONFIG
    assert cfg["source"] == "https://huggingface.co/microsoft/" \
        "Phi-4-mini-flash-reasoning/blob/main/config.json"
    assumed = cfg["assumed"]
    assert assumed["mamba"]["dt_rank"] == cfg["hidden_size"] // 16
    assert {"layer_plan", "memory", "attention_bias", "window",
            "differential_attention", "positional_encoding",
            "compute_dtype", "init"} <= set(assumed)
    assert assumed["init"]["embed_gain_log2"] == -6


def test_the_benchmark_names_the_cell_and_its_metrics():
    """By NAME: the configuration, the cell with ISSUE 48's traffic letter
    for letter, its name in two end-to-end lists, the six per-layer metrics
    with that one cell; 8 cells, none on four chips."""
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    cell = registry_mod.cell_of(bench, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "serve-closed96-phi4flash", 1)
    assert len(cell["why"]) <= 200
    conf, = (c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == [] and conf["source"] == published()["source"]
    assert conf["file"] == "benchmarks/configs/phi-4-mini-flash.json"
    assert len(bench["workloads"]) == 8
    assert not [c for c in bench["workloads"] if c["chips"] != 1]
    e2e = {m["name"] for m in registry_mod.metrics_of(bench, "end_to_end",
                                                      REAL)}
    # not ``ttft_p90``: most requests that finish in a window were admitted
    # in the burst that opens the run, and their p90 spreads 3.7% (PERF §6)
    assert e2e == {"serve_tokens_per_s", "tpot_p90", "setup_s"}
    mine = {m["name"]: m for m in registry_mod.metrics_of(bench, "per_layer",
                                                          REAL)}
    assert set(METRICS) <= set(mine)
    for name in METRICS:
        assert mine[name]["workloads"] == [REAL]
        spec = reg.data("metrics", name)
        assert (spec["layer"], spec["unit"], spec["moves"]) == \
            (mine[name]["layer"], mine[name]["unit"], mine[name]["moves"])
        assert reg.module("readers", spec["reader"])
    assert {"model.decode_roofline", "attn.kv_bytes_per_step",
            "device.peak_hbm.serve", "launch.idle.host"} <= set(mine)
    assert not {n for n in mine if n.startswith(("engine.idle.", "moe",
                                                 "mixer.", "cache."))}
    traffic = reg.data("traffic", cell["traffic"])
    assert traffic["generator"] == "serve-closed"
    assert traffic["engine"] == {"num_slots": 96, "max_len": 3072,
                                 "kv_block": 128,
                                 "admission_timeout_s": 1200.0}
    assert (traffic["callers"], traffic["requests_per_cycle"]) == (96, 64)
    assert traffic["prompt_tokens"] == {"law": "lognormal", "median": 192,
                                        "sigma": 0.8, "min": 32, "max": 1024}
    assert traffic["output_tokens"] == {"law": "lognormal", "median": 1024,
                                        "sigma": 0.35, "min": 512,
                                        "max": 2048}
    assert (traffic["temperature"], traffic["preroll_s"],
            traffic["check_requests"], traffic["trace_seconds"]) == \
        (0.0, 8, 6, 4)
    serve = reg.module("generators", "serve-closed")
    prompts = serve.stratified_lengths(traffic["prompt_tokens"], 64)
    assert sorted({serve.padded(p, 128, 3072) for p in prompts}) == \
        [128 * i for i in range(1, 9)]    # eight prefill programs
    assert 0 < reg.data("limits", REAL)["served_logit_gap"] < 1


def test_the_counts_are_the_issues_arithmetic():
    """3.853 B parameters by layer kind; a decode step at 96 rows of 870
    live tokens: weights 7.71 GB, the one plane read eight times 3.42 GB,
    eight rings 2.01 GB, the state read and written 0.57 GB."""
    reg = registry_mod.Registry([REPO])
    counts = reg.module("counts", FAMILY)
    cfg = published()
    assert [counts.mixer_parameters(cfg, k) for k in
            ("mamba", "window", "gmu", "cross")] == \
        [41241600, 19668864, 26214400, 13112704]
    assert counts.parameters(cfg, 32) == 3852562944
    assert counts.plane_readers(cfg, 32) == 8
    assert counts.state_tail(cfg) == (16, 5120)
    live = 96 * 870
    assert counts.kv_bytes_per_token(cfg) == 5120
    assert counts.shared_plane_bytes(cfg, 32, live) == 8 * live * 5120
    assert counts.decode_attention_bytes(cfg, 32, live, 96) == \
        8 * live * 5120 + 8 * 96 * 512 * 5120
    assert counts.state_update_bytes(cfg, 32, 96) == \
        2 * 96 * 9 * 16 * 5120 * 4
    step = counts.decode_step_bytes(cfg, 32, live, rows=96)
    assert step == 2 * 3852562944 + 8 * live * 5120 \
        + 8 * 96 * 512 * 5120 + 2 * 96 * 9 * 16 * 5120 * 4
    assert 13.6e9 < step < 13.8e9
    # a row shorter than the window reads what it holds
    assert counts.decode_attention_bytes(cfg, 32, 96 * 100, 96) == \
        16 * 96 * 100 * 5120
    # a prefill: the cross-decoder and the head over ONE token
    short, long = (counts.prefill_flops(cfg, 32, s) for s in (128, 1024))
    self_params = sum(counts.layer_parameters(cfg, i) for i in range(18))
    assert 7.5 < (long - short) / (896 * 2 * self_params) * 7.5 < 8.5
    assert counts.prefill_flops(cfg, 32, 128) > 2 * 128 * self_params


def test_the_program_counts_what_the_counts_count():
    """The cache manager at the published widths (one slot of 1,024): the
    step record's blocks are the counts' bytes, the plane once a reader."""
    from horovod_tpu.serving.kv_cache import KVCache
    reg = registry_mod.Registry([REPO])
    adapter = reg.module("programs", FAMILY)
    counts = reg.module("counts", FAMILY)
    cfg = published()
    kv = KVCache(adapter.sambay_config(cfg, 32), 1, max_len=1024,
                 block_size=128)
    assert kv.readers == 8 and kv.planes == 9
    assert kv.kv_block_bytes(128) == 128 * 5120          # held ONCE
    assert kv.ring_block_bytes(128) == 128 * 8 * 5120

    class Rec(dict):
        def count(self, name, n):
            self[name] = self.get(name, 0) + n
    rec = Rec()
    kv.count_reads(rec, [896])
    assert rec["shared_kv_bytes"] == counts.shared_plane_bytes(cfg, 32, 896)
    assert rec["kv_bytes"] == counts.decode_attention_bytes(cfg, 32, 896, 1)
    assert rec["window_kv_bytes"] == 4 * 128 * 8 * 5120
    assert kv.row_state_bytes() * 2 == \
        counts.state_update_bytes(cfg, 32, 1) + 2 * 9 * 3 * 5120 * 2


def test_the_tiny_family_is_correct_through_the_harness(roots):
    from horovod_tpu.utils import tracing as hvd_tracing
    result, lines = bench_tiny.run_cell(roots, CELL, seconds=0.5)
    assert result["correct"] is True, [x for x in lines
                                       if x["line"] == "compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"} <= \
        set(result["metrics"])
    program, = (x for x in lines if x["line"] == "program")
    assert (program["layers"], program["planes"],
            program["plane_readers"]) == (8, 3, 2)
    assert program["layer_kinds"] == ["mamba", "window", "mamba", "window",
                                      "mamba", "full", "gmu", "cross"]
    assert (program["window"], program["ring_len"]) == (8, 9)
    assert program["decode_attention"] == "einsum"      # the CPU
    # ONE plane of 128 a row, two rings of 8 + 1, three states
    full, ring = 4 * 128 * 32 * 2, 2 * 4 * 9 * 32 * 2
    assert program["state_bytes"] == {
        "k": full, "v": full, "k_ring": ring, "v_ring": ring,
        "ssm": 3 * 4 * 4 * 128 * 4, "conv": 3 * 4 * 3 * 128 * 2}
    assert program["state_bytes_total"] == sum(
        program["state_bytes"].values())
    compared, = (x for x in lines if x["line"] == "compared")
    assert compared["name"].startswith("served_logit_gap[")
    assert 0 <= compared["value"] < compared["limit"], compared
    steps = hvd_tracing.get_tracer().steps()
    decoded = [r for r in steps if "shared_kv_bytes" in r][-20:]
    assert decoded and all(
        0 < r["shared_kv_bytes"] < r["kv_bytes"] and
        r["kv_bytes"] == r["shared_kv_bytes"] + r["window_kv_bytes"] and
        r["state_rows"] == r["active"] for r in decoded)
    admitted = [r for r in steps if r.get("self_tokens")][-20:]
    assert admitted and all(
        r["cross_tokens"] == r["admitted"] and
        r["self_tokens"] >= r["prompt_tokens"] for r in admitted)


def test_the_int8_control_reads_not_correct(roots):
    """The control as ``control.py`` reads it, on a made-up sample whose
    contexts run past the window: the reference one precision step down
    serves other tokens, and its best choices lie further below the float32
    reference's best than the cell's limit."""
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
              for i in range(4)]
    want = serve.reference_logits(run, sample)
    low = serve.reference_logits(run, sample, quant="int8")
    first = [lg.argmax(axis=-1) for lg in low]
    gap, _, scored = serve.widest_gap(sample, want, tokens=first)
    assert scored == 400
    assert gap > run.limits["served_logit_gap"]


# -- the readers, on the events a v5e recorded --------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(REPO, "benchmarks", "fixtures",
                        "phi4flash_events_v5e.json")
    with open(path) as f:
        return json.load(f)


def _trace(recorded, module):
    """A ``Trace`` of the ONE sampled call of ``module`` (the fixture was
    recorded with ``--calls 1``: each entry is an instruction of that call,
    how often it ran in it and how long in all): the events laid end to
    end in the order they first ran; an instruction that ran more than
    once ran in a loop, so a ``while`` spans the entries after it up to the
    next that ran once."""
    assert recorded["calls"][module] == 1
    at, ops, loop = 0.0, [], None
    for text, n, took in recorded["events"][module]:
        if loop is not None and n == 1:
            ops[loop] = xplane.Event(ops[loop].name, ops[loop].start, at)
            loop = None
        if xplane.opcode(text) == "while":
            loop = len(ops)
            ops.append(xplane.Event(text, at, at))
            continue
        ops.append(xplane.Event(text, at, at + took))
        at += took
    if loop is not None:
        ops[loop] = xplane.Event(ops[loop].name, ops[loop].start, at)
    call = xplane.Event(module + "(1)", 0.0, at)
    return xplane.Trace({0: ops}, {0: [call]}, [])


def test_the_recorded_events_name_the_kernels(recorded):
    """What the chip ran (``tools/record_events.py --calls 1`` on the
    cell): the decode program holds sixteen calls of the packed kernel
    (eight rings, the plane eight times), nine of the state's update and
    no plain decode kernel; the prefill holds the banded forward eight
    times, the packed kernel for the seven cross layers' one query, nine
    scans (one a bare custom call, eight inside the fusion that writes
    the state on) and no loop."""
    reg = registry_mod.Registry([REPO])
    decode = recorded["events"]["jit__decode_jit"]
    prefill = recorded["events"]["jit__prefill_jit"]
    is_kernel = reg.module("readers", "decode_attn_roofline").is_kernel
    named = reg.module("readers", "scan_loop_share").named

    def ran(events, kernel, rule=is_kernel):
        return sum(n for t, n, _ in events if rule(t, kernel))
    assert ran(decode, "packed_decode_attention") == 16
    assert ran(decode, "mamba1_state_update", named) == 9
    assert ran(prefill, "packed_decode_attention") == 7
    assert ran(prefill, "window_attention") == 8
    assert ran(prefill, "selective_scan", named) == 9
    assert 1 <= ran(prefill, "selective_scan") <= 9
    for events in (decode, prefill):
        assert not [t for t, _, _ in events if xplane.opcode(t) == "while"]
    assert 0 < recorded["metrics"]["yoco.decode_attn_roofline"]["value"] \
        <= 100


def test_the_new_readers_read_the_recorded_events(recorded):
    """``scan_loop_share``, ``one_token_share`` and ``slab_update_roofline``
    on the recorded call of each program, against sums taken here by
    hand."""
    from benchmarks import run as run_mod
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    null = open(os.devnull, "w")
    run = run_mod.Run(reg, bench, registry_mod.cell_of(bench, REAL), 1, 1, 1,
                      null)
    run.peaks = reg.peaks("TPU v5 lite")
    prefill = recorded["events"]["jit__prefill_jit"]
    ops = [(t, n, took) for t, n, took in prefill
           if xplane.opcode(t) != "while"]
    whole = sum(took for _, _, took in ops)
    obs = {"trace": _trace(recorded, "jit__prefill_jit"),
           "traced": {"steps": [], "model": {"layers": 32}}}
    scan = reg.module("readers", "scan_loop_share").read(
        obs, {"module": "jit__prefill_jit", "kernel": "selective_scan"}, run)
    assert scan == pytest.approx(100 * sum(
        took for t, _, took in ops
        if "selective_scan" in t.partition(" = ")[0]) / whole)
    cross = reg.module("readers", "one_token_share").read(
        obs, {"module": "jit__prefill_jit",
              "kernel": "packed_decode_attention"}, run)
    assert 0 < cross < 50 and 0 < scan < 100 and scan + cross < 100
    # by hand: the seven kernels, and what ran once with one token's arrays
    mod = reg.module("readers", "one_token_share")
    s = mod.padded_length(obs["trace"].ops[0], 2560)
    assert s in range(128, 1025, 128)
    one = [took for t, n, took in ops
           if "packed_decode_attention" in t.partition(" = ")[0] or
           (n == 1 and not mod.carries(t, s) and "200064" not in t)]
    assert cross == pytest.approx(100 * sum(one) / whole)
    # the cross-decoder's seven gated units and seven SwiGLUs are among them
    assert sum("bf16[2560,10240]" in t for t, n, _ in ops
               if n == 1 and not mod.carries(t, s)) >= 14
    # a program without the family's counts or keys gives nothing
    other = run_mod.Run(reg, bench, registry_mod.cell_of(
        bench, "baichuan7b-serve-closed"), 1, 1, 1, null)
    steps = [(0.0, 0.0, 0, 96, 96, 96 * 870)]
    for name in ("scan_loop_share", "one_token_share",
                 "slab_update_roofline"):
        assert reg.module("readers", name).read(
            dict(obs, traced={"steps": steps, "model": {"layers": 10}}),
            {"module": "jit__prefill_jit", "kernel": "x"}, other) is None
    decode = recorded["events"]["jit__decode_jit"]
    slabs = reg.module("readers", "slab_update_roofline").slabs
    moved = [(slabs(t, (16, 5120)), took) for t, _, took in decode]
    got = reg.module("readers", "slab_update_roofline").read(
        {"trace": _trace(recorded, "jit__decode_jit"),
         "traced": {"steps": steps, "model": {"layers": 32}}},
        {"module": "jit__decode_jit"}, run)
    need = 2 * 96 * 9 * 16 * 5120 * 4
    assert sum(b for b, _ in moved) >= need
    assert got == pytest.approx(
        100 * need / 819e9 / sum(took for b, took in moved if b))
    assert 0 < got <= 100
