"""The Laguna-XS.2 family's benchmark files (``laguna``) at a size the CPU
runs: the published configuration file with every width made tiny (the
pattern of layers, both head counts, the routing keys and eps kept),
through the harness (``serve-closed-routed``, unedited: ``serve-closed``'s
loop with the served check's statistic a quantile, int8 control), its
counts against hand arithmetic (the numbers of ISSUE 44), the per-layer
metrics its cell lists, the ``assumed.init`` gain and the weights' draw."""

import json
import math
import os
import sys

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import xplane

sys.path.insert(0, os.path.join(REPO, "tests"))
import test_window_moe_model as wm  # noqa: E402

CELL = "tiny-laguna-serve-mixed"
REAL = "laguna-xs2-serve-mixed"
FAMILY = "laguna"
GENERATOR = "serve-closed-routed"
published = wm.published


def tiny_config():
    return dict(wm.tiny_config(max_position_embeddings=256), name="tiny-laguna",
                num_hidden_layers={"serve_1chip": 5}, reduced=[])


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The repository's benchmark with one more cell, of the tiny
    configuration: new files in a root of its own, nothing edited."""
    root = str(tmp_path_factory.mktemp("laguna"))
    traffic = dict(bench_tiny.TRAFFIC["tiny-serve"], family=FAMILY,
                   generator=GENERATOR,
                   model_overrides={"attention_impl": "full"})
    traffic["prompt_tokens"] = dict(traffic["prompt_tokens"], max=24)
    traffic["output_tokens"] = dict(traffic["output_tokens"], max=100)
    bench_tiny._dump(root, "configs", "tiny-laguna", tiny_config())
    bench_tiny._dump(root, "traffic", "tiny-laguna-closed4", traffic)
    # the real cell's generator and statistic (the gap that 85% of the
    # served tokens stay within): at these widths on the CPU 0-0.02
    bench_tiny._dump(root, "limits", CELL, {"served_logit_gap": 0.12})
    add = {"configs": [{"name": "tiny-laguna", "source": "self-test",
                        "file": "benchmarks/configs/tiny-laguna.json",
                        "reduced": [], "why": "tiny"}],
           "workloads": [{"name": CELL, "config": "tiny-laguna",
                          "traffic": "tiny-laguna-closed4", "chips": 1,
                          "why": "tiny"}],
           "per_layer": []}
    bench = bench_tiny._grow(bench_tiny.repo_benchmark(), add, CELL)
    for m in bench["per_layer"]:
        if m["name"].startswith(("moe256.", "mixed.", "swa.")):
            m["workloads"].append(CELL)
    return bench_tiny._write_benchmark(root, bench, (REPO,))


def test_the_familys_files_are_found_by_name():
    reg = registry_mod.Registry([REPO])
    for kind in ("programs", "reference", "counts"):
        assert reg.module(kind, FAMILY)
    assert reg.data("traffic", "serve-closed64-laguna")["family"] == FAMILY
    assert published()["family"] == FAMILY
    ref = reg.module("reference", FAMILY)
    with open(ref.__file__) as f:
        assert "horovod_tpu" not in f.read().replace(
            "nothing imported from the program", "")
    for name in ("stack_roofline", "mixed_attn_roofline",
                 "band_flash_roofline"):
        assert reg.module("readers", name)


def test_the_tiny_family_is_correct_through_the_harness(roots):
    from horovod_tpu.utils import tracing as hvd_tracing
    result, lines = bench_tiny.run_cell(roots, CELL, seconds=0.5)
    assert result["correct"] is True, [x for x in lines
                                       if x["line"] == "compared"]
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"} <= \
        set(result["metrics"])
    program, = (x for x in lines if x["line"] == "program")
    assert (program["layers"], program["expert_layers"], program["planes"]) \
        == (5, 4, 5)
    assert program["layer_types"] == ["full", "window", "window", "window",
                                      "full"]
    assert program["heads_per_layer"] == [3, 4, 4, 4, 3]
    assert (program["window"], program["ring_len"]) == (8, 9)
    assert (program["experts"], program["experts_per_tok"]) == (16, 4)
    # TWO classes: two planes of 128 a row, three rings of 8 + 1
    full, ring = 2 * 4 * 128 * 16 * 2, 3 * 4 * 9 * 16 * 2
    assert program["state_bytes"] == {"k": full, "v": full, "k_ring": ring,
                                      "v_ring": ring}
    compared, = (x for x in lines if x["line"] == "compared")
    assert compared["name"].startswith("served_logit_gap[p85 of ")
    assert 0 <= compared["value"] < compared["limit"]
    records = [r for r in hvd_tracing.get_tracer().steps()
               if "experts_touched" in r][-20:]
    assert records
    assert all(4 <= r["experts_touched"] <= 64 and
               1 <= r["expert_tokens_max"] <= 4 for r in records)
    decoded = [r for r in hvd_tracing.get_tracer().steps()
               if "window_kv_bytes" in r][-20:]
    assert decoded and all(0 < r["window_kv_bytes"] < r["kv_bytes"]
                           for r in decoded)


def test_the_int8_control_reads_not_correct(roots):
    """The control as ``control.py`` reads it, on a made-up sample of
    1,200 positions whose contexts run to 120 (fifteen windows). At a
    vocabulary of 256 int8 changes a few tokens in a hundred, so the
    cell's quantile reads 0 here and it is the WIDEST gap,
    ``serve-closed``'s own statistic, that tells the control at this
    size (at the real cell's widths, on the chip, the quantile does:
    PERF.md section 6)."""
    import numpy as np
    from benchmarks import run as run_mod
    reg = registry_mod.Registry(roots)
    bench = reg.benchmark()
    serve = reg.module("generators", GENERATOR)
    run = run_mod.Run(reg, bench, registry_mod.cell_of(bench, CELL), 6, 1, 0,
                      sys.stdout)
    rng = np.random.default_rng(6)
    sample = [{"id": f"m{i}", "prompt": tuple(rng.integers(0, 256, 8 + i)),
               "tokens": tuple(rng.integers(0, 256, 100))}
              for i in range(12)]
    want = serve.reference_logits(run, sample)
    low = serve.reference_logits(run, sample, quant="int8")
    first = [lg.argmax(axis=-1) for lg in low]
    gap, _, scored = serve.closed.widest_gap(sample, want, tokens=first)
    assert scored == 1200
    assert gap > 1.5 * run.limits["served_logit_gap"]
    changed = sum(int(np.sum(f != lg.argmax(axis=-1)))
                  for f, lg in zip(first, want))
    assert 0 < changed < 0.25 * scored
    best = [lg.argmax(axis=-1) for lg in want]
    assert serve.closed.widest_gap(sample, want, tokens=best)[0] == 0.0


def test_the_configuration_file_holds_the_published_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    cfg = published()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == {"source": 40, "serve_1chip": 5}
    # the three per-layer lists stand as published; the stage runs the
    # first five entries of each
    assert [len(cfg[k]) for k in ("layer_types", "mlp_layer_types",
                                  "num_attention_heads_per_layer")] == [40] * 3
    assert cfg["layer_types"][:5] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert (cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["moe_intermediate_size"], cfg["sliding_window"]) == \
        (256, 8, 512, 512)
    assert "FIRST of eight pipeline stages" in cfg["layouts"]["serve_1chip"]
    assert {"gate_granularity", "gate_activation", "router_score",
            "shared_expert", "qk_norm", "yarn", "rotary", "attention",
            "weights", "compute_dtype", "init"} <= set(cfg["assumed"])
    # the gain is an exact power of two, and the one the stacks' law needs
    assert 2.0 ** cfg["assumed"]["init"]["expert_gain_log2"] == \
        math.sqrt(cfg["num_experts"]) == 16.0
    bench = registry_mod.Registry([REPO]).benchmark()
    entry = bench["configs"][-1]
    assert (entry["name"], entry["file"], entry["reduced"]) == \
        ("laguna-xs.2", "benchmarks/configs/laguna-xs.2.json",
         ["num_hidden_layers"])
    assert entry["source"] == row["source_url"]


def test_the_init_gain_gives_an_expert_its_own_fan_in():
    """``lib/weights.py`` scales a stack ``[256, rows, cols]`` by 1 /
    sqrt(256 x rows); times the gain each expert is N(0,1) / sqrt(rows),
    as every other matrix. Reference and adapter each apply it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import weights
    reg = registry_mod.Registry([REPO])
    ref, prog = reg.module("reference", FAMILY), reg.module("programs",
                                                            FAMILY)
    cfg = dict(published(), hidden_size=128, moe_intermediate_size=128,
               shared_expert_intermediate_size=128, intermediate_size=256,
               vocab_size=64, head_dim=16)
    shapes = ref.weight_shapes(cfg, 2)
    assert shapes["layers.1.attn.gate"] == (128, 64)     # per head
    assert shapes["layers.0.attn.q"] == (128, 48 * 16)
    w = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(3))
    gate = np.asarray(w["layers.1.experts.gate"], np.float32)
    assert gate.shape == (256, 128, 128)
    assert gate.std() * math.sqrt(256 * 128) == pytest.approx(1.0, abs=0.02)
    assert ref.expert_gain(cfg) == 16.0
    assert gate.std() * 16 * math.sqrt(128) == pytest.approx(1.0, abs=0.02)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 128)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        by_ref = ref.matmul(x, w["layers.1.experts.gate"][5], None, 16.0)
        scaled = w["layers.1.experts.gate"] * jnp.asarray(16.0, jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(by_ref),
            np.asarray(jnp.matmul(x, scaled[5].astype(jnp.float32))))
    layer = prog.to_tree(w, 2, cfg)["layer_1"]
    np.testing.assert_array_equal(
        np.asarray(layer["experts"]["gate"], np.float32), gate * 16)
    assert set(layer["router"]) == {"kernel"}            # no selection bias


def test_the_weights_draw_fits_the_chip_by_arithmetic():
    from benchmarks.lib import weights
    reg = registry_mod.Registry([REPO])
    ref = reg.module("reference", FAMILY)
    cfg = published()
    shapes = ref.weight_shapes(cfg, 5)
    sizes = {k: math.prod(s) for k, s in shapes.items()}
    # ISSUE 44: this stage 3,869.9 M parameters = 7.74 GB
    assert sum(sizes.values()) == pytest.approx(3869.9e6, rel=2e-5)
    assert round(2 * sum(sizes.values()) / 1e9, 2) == 7.74
    flat = sum(n for n in sizes.values() if n < weights.SMALL)
    largest = max(sizes.values())
    assert largest == 256 * 2048 * 512         # a stack, over the head
    assert sizes["embed"] == sizes["head"] == 100352 * 2048
    peak = 2 * sum(sizes.values()) + 4 * flat + 4 * largest
    assert flat * 4 < 0.5e9 and peak < 16e9 * 0.75
    # the whole model by the same table: 33.44 B against the published 33.4
    whole = sum(math.prod(s) for s in ref.weight_shapes(cfg, 40).values())
    assert round(whole / 1e9, 2) == 33.44


def test_the_cell_and_its_traffic_are_the_issues():
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    cell = registry_mod.cell_of(bench, REAL)
    assert cell == bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("laguna-xs.2", "serve-closed64-laguna", 1)
    traffic = reg.data("traffic", cell["traffic"])
    assert (traffic["generator"], traffic["family"], traffic["callers"],
            traffic["layout"], traffic["requests_per_cycle"]) == \
        (GENERATOR, FAMILY, 64, "serve_1chip", 64)
    assert traffic["engine"] == {"num_slots": 64, "max_len": 5120,
                                 "kv_block": 512,
                                 "admission_timeout_s": 1200.0}
    assert traffic["prompt_tokens"] == {"law": "lognormal", "median": 1024,
                                        "sigma": 0.9, "min": 64, "max": 4096}
    assert traffic["output_tokens"] == {"law": "lognormal", "median": 192,
                                        "sigma": 0.7, "min": 32, "max": 768}
    glm = reg.data("traffic", "serve-closed64-glm")
    assert set(traffic) == set(glm)
    for key in ("temperature", "preroll_s", "check_requests",
                "trace_seconds"):
        assert traffic[key] == glm[key]
    assert "NOT fitted to a public trace" in traffic["source"]
    # eight prefill programs and one decode program: nine, as every cell
    closed = reg.module("generators", "serve-closed")
    prompts = closed.stratified_lengths(traffic["prompt_tokens"], 64)
    assert sorted({closed.padded(p, 512, 5120) for p in prompts}) == \
        [512 * i for i in range(1, 9)]
    outputs = closed.stratified_lengths(traffic["output_tokens"], 64)
    assert max(prompts) + max(outputs) <= 5120
    # contexts on both sides of the window among the requests
    assert min(prompts) + min(outputs) < 512 < max(prompts)
    e2e = {m["name"] for m in
           registry_mod.metrics_of(bench, "end_to_end", REAL)}
    # ``ttft_p90`` too: over six seeds on the chip it spread 0.45% where
    # half its bound is 1.5% (62.10-62.54 ms; GLM's cell read 3.8% and
    # leaves it out): eight prefill lengths spread the admissions, and
    # the 90th percentile lies on no shoulder (PERF.md section 6)
    assert e2e == {"serve_tokens_per_s", "ttft_p90", "tpot_p90", "setup_s"}
    layer = {m["name"] for m in
             registry_mod.metrics_of(bench, "per_layer", REAL)}
    assert {"entry.compiles.serve", "engine.queue_wait_p90",
            "engine.prefill_p90"} <= layer
    new = ["moe256.expert_roofline", "moe256.experts_touched_share",
           "moe256.prefill_expert_roofline", "mixed.decode_attn_roofline",
           "mixed.window_kv_bytes_per_step", "swa.prefill_flash_roofline"]
    assert [m["name"] for m in bench["per_layer"][-6:]] == new
    assert all(m["workloads"] == [REAL] for m in bench["per_layer"][-6:])
    assert set(new) | {"model.decode_roofline", "attn.kv_bytes_per_step",
                       "engine.decode_step_p50", "device.idle_share.serve",
                       "device.peak_hbm.serve", "engine.occupancy"} <= layer
    assert not {m for m in layer if m.startswith((
        "engine.idle.", "mixer.", "cache.", "loop.", "moe.", "mla."))}
    # nothing that was there changed hands
    for other in ("baichuan7b-serve-closed", "falconh1-34b-serve-closed",
                  "ouro2.6b-serve-closed", "glm4.7flash-serve-closed"):
        names = {m["name"] for m in
                 registry_mod.metrics_of(bench, "per_layer", other)}
        assert not {m for m in names
                    if m.startswith(("moe256.", "mixed.", "swa."))}
    share = reg.data("metrics", "moe256.experts_touched_share")
    assert share["args"]["scale"] == pytest.approx(100 / (4 * 256))
    for m in bench["per_layer"][-6:]:
        spec = reg.data("metrics", m["name"])
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == \
            {k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert reg.module("readers", spec["reader"])


def test_the_counts_follow_the_shapes():
    """The arithmetic of ISSUE 44, from the row's keys (the issue rounds)."""
    counts = registry_mod.Registry([REPO]).module("counts", FAMILY)
    cfg = published()
    full = 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48 + 48 * 128 * 2048
    window = 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64 + 64 * 128 * 2048
    assert counts.attention_parameters(cfg, 0) == full == \
        counts.attention_parameters(cfg, 4)
    assert counts.attention_parameters(cfg, 1) == window
    assert (round(full / 1e6, 2), round(window / 1e6, 2)) == (29.46, 37.88)
    assert counts.expert_parameters(cfg) == 3 * 2048 * 512 == 3145728
    assert counts.fixed_parameters(cfg, 0) == full + 3 * 2048 * 8192 + 4096
    fixed = window + 2048 * 256 + 3145728 + 4096
    assert counts.fixed_parameters(cfg, 2) == fixed
    assert round((fixed + 256 * 3145728) / 1e6, 2) == 846.86
    assert round((counts.fixed_parameters(cfg, 4) + 256 * 3145728) / 1e6,
                 2) == 838.44
    assert counts.expert_layers(cfg, 5) == 4 and counts.planes(cfg, 5) == (2, 3)
    assert counts.planes(cfg, 40) == (10, 30)
    assert counts.head_parameters(cfg) == 2048 * 100353
    assert counts.kv_bytes_per_token(cfg) == 4096
    assert counts.stack_shapes(cfg) == {(256, 2048, 512), (256, 512, 2048)}
    # uniform routing: 64 rows x 8 distinct experts of 256 leave an expert
    # untouched with probability (248/256)^64
    assert counts.expected_touched(cfg, 64) == \
        pytest.approx(256 * (1 - (248 / 256) ** 64))
    assert counts.expected_touched(cfg, 64) == pytest.approx(222.4, abs=0.1)
    assert counts.expected_touched(cfg, 64) / 256 == \
        pytest.approx(0.869, abs=1e-3)
    assert counts.expected_touched(cfg, 1) == pytest.approx(8)
    assert counts.expected_touched(cfg, 512) == pytest.approx(256, abs=1e-4)
    assert round(counts.expert_bytes(cfg, 4 * 222) / 1e9, 2) == 5.59
    # K/V: every live token in the 2 full planes, min(length, 512) a row in
    # the 3 rings
    live = 64 * 1500
    assert counts.window_tokens(cfg, live, 64) == 64 * 512
    assert counts.window_tokens(cfg, 64 * 300, 64) == 64 * 300
    attn = counts.decode_attention_bytes(cfg, 5, live, 64)
    assert attn == 2 * live * 4096 + 3 * 64 * 512 * 4096
    assert (round(2 * live * 4096 / 1e9, 2),
            round(3 * 64 * 512 * 4096 / 1e9, 2)) == (0.79, 0.40)
    assert round(3 * live * 4096 / 1e9, 2) == 1.18      # without the window
    every = counts.decode_step_bytes(cfg, 5, 0)
    # the whole stage less the embedding, which a step gathers by row
    assert round(every / 1e9, 2) == round((3869.9e6 - 205.52e6) * 2 / 1e9, 2)
    step = counts.decode_step_bytes(cfg, 5, live, rows=64)
    touched = 4 * counts.expected_touched(cfg, 64)
    assert step == pytest.approx(
        every - (4 * 256 - touched) * 3145728 * 2 + attn)
    assert round(step / 1e9, 1) == 7.7
    assert 0.71 < counts.expert_bytes(cfg, touched) / step < 0.74
    assert 0.15 < attn / step < 0.165
    assert round(step / 819e9 * 1e3, 1) == 9.4
    # the band: min(i + 1, window) keys a query
    assert counts.band_pairs(4, 512) == 10
    assert counts.band_pairs(512, 512) == 512 * 513 // 2
    assert counts.band_pairs(4096, 512) == 512 * 513 // 2 + 3584 * 512
    assert counts.band_tiles(4096, 512, 512) == 15 and \
        counts.band_tiles(512, 512, 512) == 1
    flops, nbytes = counts.band_forward(64, 8, 4096, 128, 512)
    assert flops == 4 * 64 * counts.band_pairs(4096, 512) * 128
    assert nbytes == 2 * 72 * 4096 * 128 * 2
    # a kernel that visits whole tiles does at most 15 x 512^2 pairs
    assert counts.band_pairs(4096, 512) < 15 * 512 * 512 \
        < 2 * counts.band_pairs(4096, 512)


# -- the readers, on events named as the compiled program names them ---------

GROUPED = ('%grouped_swiglu.{n} = bf16[{rows},2048]{{1,0:T(8,128)(2,1)}} '
           'custom-call(s32[256]{{0:T(256)}} %a.{n}, s32[1]{{0:T(128)}} %b.{n}, '
           's32[257]{{0:T(512)}} %c.{n}, bf16[{rows},2048]{{1,0:T(8,128)(2,1)}} '
           '%x.{n}, bf16[256,2048,512]{{2,1,0:T(8,128)(2,1)}} %p.{n}, '
           'bf16[256,2048,512]{{2,1,0:T(8,128)(2,1)}} %q.{n}, bf16[256,512,2048]'
           '{{2,1,0:T(8,128)(2,1)}} %r.{n}), custom_call_target="tpu_custom_call"')
RAGGED = ('%ragged-dot.{n} = bf16[32768,512]{{1,0:T(8,128)(2,1)}} custom-call('
          's32[257]{{0:T(512)}} %g.{n}, bf16[32768,2048]{{1,0:T(8,128)(2,1)}} '
          '%x.{n}, bf16[256,2048,512]{{2,1,0:T(8,128)(2,1)}} %p.{n}), '
          'custom_call_target="tpu_custom_call"')
DECODE_ATTN = ('%decode_attention.{n} = bf16[64,{rows},128]{{2,1,0:T(8,128)'
               '(2,1)}} custom-call(s32[1]{{0:T(128)}} %l.{n}, bf16[64,{rows},'
               '128]{{2,1,0:T(8,128)(2,1)}} %q.{n}, bf16[{cache}]{{3,2,1,0:'
               'T(8,128)(2,1)}} %k.{n}, bf16[{cache}]{{3,2,1,0:T(8,128)(2,1)}} '
               '%v.{n}), custom_call_target="tpu_custom_call"')
BAND = ('%window_attention.{n} = bf16[64,{s},128]{{2,1,0:T(8,128)(2,1)}} '
        'custom-call(bf16[64,{s},128]{{2,1,0:T(8,128)(2,1)}} %q.{n}, '
        'bf16[8,{s},128]{{2,1,0:T(8,128)(2,1)}} %k.{n}, bf16[8,{s},128]'
        '{{2,1,0:T(8,128)(2,1)}} %v.{n}), custom_call_target="tpu_custom_call"')
FLASH = ('%flash.{n} = bf16[48,{s},128]{{2,1,0:T(8,128)(2,1)}} custom-call('
         'bf16[48,{s},128]{{2,1,0}} %q.{n}, bf16[48,{s},128]{{2,1,0}} %k.{n}, '
         'bf16[48,{s},128]{{2,1,0}} %v.{n}), '
         'custom_call_target="tpu_custom_call"')


class FakeRun:
    def __init__(self, lines, family=FAMILY):
        self.registry = registry_mod.Registry([REPO])
        self.config = published()
        self.traffic = {"family": family}
        self.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
        self.lines = lines

    def log(self, line, **fields):
        self.lines.append(dict(line=line, **fields))


def trace(expert_s=2e-3, attn_s=1e-4, band_s=1e-3, calls=3, s=4096):
    """``calls`` decode programs (a layer: the decode kernel over its
    class's planes, then from layer 1 on the grouped SwiGLU) and one
    prefill program (three banded kernels, two flash kernels, four grouped
    products by ``ragged_dot``: three an expert layer)."""
    ops, mods, t = [], [], 0.0

    def op(name, d):
        nonlocal t
        ops.append(xplane.Event(name, t, t + d))
        t += d
    for _ in range(calls):
        start = t
        for n, (rows, cache) in enumerate(
                [(48, "2,64,40960,128")] + [(64, "3,64,5120,128")] * 3
                + [(48, "2,64,40960,128")]):
            op(DECODE_ATTN.format(n=n, rows=rows, cache=cache), attn_s)
            if n:
                op(GROUPED.format(n=n, rows=512), expert_s)
        mods.append(xplane.Event("jit__decode_jit(7)", start, t))
        t += 1e-3
    start = t
    for n in range(5):
        op((BAND if n in (1, 2, 3) else FLASH).format(n=n, s=s), band_s)
        if n:
            for j in range(3):
                op(RAGGED.format(n=3 * n + j), expert_s)
    mods.append(xplane.Event("jit__prefill_jit(3)", start, t))
    return xplane.Trace({0: ops}, {0: mods}, [])


def steps(live, n=5, rows=64):
    return [(0.0, 0.0, 0, rows, rows, live) for _ in range(n)]


def records(touched, prompts=()):
    return {"traced": [{"experts_touched": t, "expert_tokens_max": 9,
                        "active": 64} for t in touched]
            + [{"admitted": 1, "prompt_tokens": p, "active": 64}
               for p in prompts]}


def test_the_decode_products_against_the_experts_a_pass_touched():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "moe256.expert_roofline")
    assert spec["source"] == "device_trace" and spec["moves"] == "tpot_p90"
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": trace(expert_s=2e-3),
           "traced": {"steps": steps(96000), "model": {"layers": 5}},
           "step_phases": records([886, 888, 890])}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    least = 888 * 3145728 * 2 / 819e9          # a call, four layers
    assert value == pytest.approx(100 * least / (4 * 2e-3))
    said, = lines
    # the four grouped calls of each decode program, not the prefill's
    assert (said["calls"], said["events"], said["records"]) == (3, 12, 3)
    assert said["experts_touched"] == 888
    # a program whose records lack the count, or a family without stacks
    obs["step_phases"] = {"traced": [{"active": 64}]}
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    obs["step_phases"] = records([888])
    assert reader.read(obs, spec["args"], FakeRun([], "ouro")) is None
    obs["step_phases"] = None
    assert reader.read(obs, spec["args"], FakeRun([])) is None


def test_the_prefill_products_against_one_read_of_every_expert():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "moe256.prefill_expert_roofline")
    assert spec["moves"] == "serve_tokens_per_s"
    assert spec["args"] == {"module": "jit__prefill_jit", "prompts": True}
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": trace(expert_s=2e-3),
           "traced": {"steps": steps(96000), "model": {"layers": 5}},
           "step_phases": records([888], prompts=[2000, 1000])}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    # 1,500 tokens a prompt touch every expert of the four layers
    least = 4 * 256 * 3145728 * 2 / 819e9
    assert value == pytest.approx(100 * least / (12 * 2e-3), rel=1e-6)
    said, = lines
    assert (said["calls"], said["events"], said["prompt_tokens"]) == \
        (1, 12, 1500)
    # a short prompt touches fewer: 64 tokens 222 of 256 a layer
    obs["step_phases"] = records([888], prompts=[64])
    assert reader.read(obs, spec["args"], FakeRun([])) == \
        pytest.approx(value * 222.4 / 256, rel=1e-3)
    # a window that admitted nothing
    obs["step_phases"] = records([888])
    assert reader.read(obs, spec["args"], FakeRun([])) is None


def test_decode_attention_against_both_classes_live_bytes():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "mixed.decode_attn_roofline")
    assert spec["args"] == {"module": "jit__decode_jit",
                            "kernel": "decode_attention"}
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": trace(attn_s=4e-4),
           "traced": {"steps": steps(96000), "model": {"layers": 5}}}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    least = (2 * 96000 + 3 * 64 * 512) * 4096 / 819e9
    assert value == pytest.approx(100 * least / (5 * 4e-4))
    said, = lines
    assert (said["calls"], said["events"], said["rows"]) == (3, 15, 64)
    # rows shorter than the window: the rings hold what the rows hold
    obs["traced"]["steps"] = steps(64 * 200)
    assert reader.read(obs, spec["args"], FakeRun([])) == pytest.approx(
        100 * 5 * 64 * 200 * 4096 / 819e9 / (5 * 4e-4))
    # a family whose counts have no such function
    assert reader.read(obs, spec["args"], FakeRun([], "resnet")) is None


def test_the_banded_forward_against_the_bands_pairs():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "swa.prefill_flash_roofline")
    assert spec["args"] == {"kernel": "window_attention"}
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": trace(band_s=2e-3)}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    pairs = 512 * 513 // 2 + 3584 * 512
    least = 4 * 64 * pairs * 128 / 197e12
    assert value == pytest.approx(100 * least / 2e-3)
    said, = lines
    # the three window layers' calls, not the two full layers' flash kernel
    assert (said["calls"], said["bound"]) == (3, "compute")
    assert reader.read(obs, spec["args"], FakeRun([], "ouro")) is None
    obs = {"trace": trace(calls=1, s=512)}
    obs["trace"].ops[0] = [e for e in obs["trace"].ops[0]
                           if "window_attention" not in e.name]
    assert reader.read(obs, spec["args"], FakeRun([])) is None


def test_the_two_counts_are_read_from_decode_only_steps():
    reg = registry_mod.Registry([REPO])
    window = [{"phases": [["decode_readback", 0, 1]], "experts_touched": t,
               "window_kv_bytes": b} for t, b in
              ((886, 390e6), (890, 400e6), (888, 395e6))]
    window.append({"phases": [["prefill", 0, 1], ["decode_readback", 1, 2]],
                   "experts_touched": 100, "window_kv_bytes": 1e6})
    obs = {"step_phases": {"window": window}}
    share = reg.data("metrics", "moe256.experts_touched_share")
    reader = reg.module("readers", share["reader"])
    assert reader.read(obs, share["args"], FakeRun([])) == \
        pytest.approx(100 * 888 / 1024)
    ring = reg.data("metrics", "mixed.window_kv_bytes_per_step")
    assert reader.read(obs, ring["args"], FakeRun([])) == \
        pytest.approx(0.395)
    # a program without the counts reports nothing and raises nothing
    obs = {"step_phases": {"window": [{"phases": [], "kv_bytes": 5}]}}
    assert reader.read(obs, ring["args"], FakeRun([])) is None


def test_the_selection_rules_on_events_recorded_on_the_chip():
    """``fixtures/laguna_events_v5e.json``: instruction texts of the cell's
    own traced run on a v5e (``tools/record_events.py``).  In the decode
    program ``moe256.expert_roofline`` selects the FOUR ``grouped_swiglu``
    calls (one an expert layer, each handed the three stacks of 256
    experts as they lie, 512 assignments) and nothing else;
    ``mixed.decode_attn_roofline`` the FIVE ``decode_attention`` calls:
    two over the full planes (48 query heads, the cache ``[2, 64, 5120 x
    8, 128]`` whole, twice: K and V) and three over the rings (64 heads,
    ``[3, 64, 640 x 8, 128]``); no other Mosaic call is in the program and
    no loop.  In the prefill programs ``moe256.prefill_expert_roofline``
    selects the kernel at 512 tokens (4,096 assignments, its last) and
    XLA's ``ragged-dot`` custom calls, three an expert layer, from 1,024
    tokens on; ``swa.prefill_flash_roofline`` the three
    ``window_attention`` calls a prefill, with K and V at their own 8
    heads, and not the full layers' flash kernel."""
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "laguna_events_v5e.json")) as f:
        recorded = json.load(f)
    reg = registry_mod.Registry([REPO])
    experts = reg.module("readers", "expert_roofline")
    attn = reg.module("readers", "decode_attn_roofline")
    counts = reg.module("counts", FAMILY)
    cfg = published()
    stacks = counts.stack_shapes(cfg)
    n = recorded["calls_sampled"]

    def operands(name):
        return xplane.shapes(name.partition("custom-call(")[2]
                             .partition("), custom_call")[0])
    decode = recorded["events"]["jit__decode_jit"]
    grouped = [e for e in decode if experts.reads_a_stack(e[0], stacks)]
    assert len(grouped) == 4 < len(decode)
    for name, seen, took in grouped:
        assert seen == n and name.startswith("%grouped_swiglu")
        assert xplane.op_class(name) == "mosaic"
        ops = operands(name)
        assert sum(dims in stacks for _, dims in ops) == 3
        assert ("bf16", (512, 2048)) in ops        # 64 rows x 8, sorted
        assert 1.5e-3 < took / seen < 2.2e-3       # 1.85 ms a layer
    kernel = reg.data("metrics", "mixed.decode_attn_roofline")["args"][
        "kernel"]
    reads = [e for e in decode if attn.is_kernel(e[0], kernel)]
    assert len(reads) == 5
    full = [e for e in reads if ("bf16", (64, 48, 128)) in operands(e[0])]
    ring = [e for e in reads if ("bf16", (64, 64, 128)) in operands(e[0])]
    assert (len(full), len(ring)) == (2, 3)
    for name, seen, took in full:
        assert operands(name).count(("bf16", (2, 64, 5120 * 8, 128))) == 2
        assert seen == n and 0.4e-3 < took / seen < 0.9e-3   # 0.63 ms
    for name, seen, took in ring:
        assert operands(name).count(("bf16", (3, 64, 640 * 8, 128))) == 2
        assert seen == n and 0.1e-3 < took / seen < 0.3e-3   # 0.18 ms
    mosaic = [e[0] for e in decode if xplane.op_class(e[0]) == "mosaic"]
    assert sorted(mosaic) == sorted(e[0] for e in grouped + reads)
    assert not [e for e in decode if xplane.opcode(e[0]) == "while"]
    # that run's result line, from the same events: 92.6% and 89.4%
    took = sum(t for _, _, t in grouped) / n
    least = counts.expert_bytes(cfg, 888.0) / 819e9
    assert 88 < 100 * least / took < 96
    assert recorded["metrics"]["moe256.expert_roofline"]["value"] == \
        pytest.approx(92.6, abs=0.1)
    took = sum(t for _, _, t in reads) / n
    least = counts.decode_attention_bytes(cfg, 5, 106839.1, 63.7) / 819e9
    assert 85 < 100 * least / took < 95
    prefill = recorded["events"]["jit__prefill_jit"]
    products = [e for e in prefill if experts.reads_a_stack(e[0], stacks)]
    kernels = [e for e in products if e[0].startswith("%grouped_swiglu")]
    ragged = [e for e in products if e[0].startswith("%ragged-dot")]
    assert len(kernels) == 4 and len(kernels) + len(ragged) == len(products)
    assert all(("bf16", (4096, 2048)) in operands(e[0]) for e in kernels)
    rows = {dims[0] for e in ragged for _, dims in operands(e[0])
            if len(dims) == 2}
    assert rows and min(rows) == 8192 and max(rows) == 32768
    assert all(sum(dims in stacks for _, dims in operands(e[0])) == 1
               for e in ragged)
    band = [e for e in prefill if attn.is_kernel(e[0], "window_attention")]
    assert band and not [e for e in decode
                         if attn.is_kernel(e[0], "window_attention")]
    for name, seen, took in band:
        (_, q), (_, k), (_, v) = [o for o in operands(name) if len(o[1]) == 3]
        assert q[0] == 64 and k == v == (8, q[1], 128)
        flops, _ = counts.band_forward(64, 8, q[1], 128, 512)
        # a tile's worth of work at best: under the compute roofline
        assert flops / 197e12 < took / seen
    flash = [e for e in prefill if xplane.op_class(e[0]) == "mosaic"
             and e not in band and e not in kernels and e not in ragged
             and "ragged-dot" not in e[0]]
    assert flash and all(("bf16", (48, operands(e[0])[0][1][1], 128))
                         in operands(e[0]) for e in flash)
