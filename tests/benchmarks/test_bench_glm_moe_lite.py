"""The GLM-4.7-Flash family's benchmark files (``glm_moe_lite``) at a size
the CPU runs: the published configuration file with every width made tiny
(the routing keys, the dense first layer and eps kept), through the
harness (``serve-closed-routed`` generator: ``serve-closed``'s loop with
the served check's statistic a quantile, int8 control), its
counts against hand arithmetic, the per-layer metrics its cell lists, the
``assumed.init`` gain and the weights' draw."""

import json
import math
import os

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import xplane

CELL = "tiny-glm-serve-closed"
REAL = "glm4.7flash-serve-closed"
FAMILY = "glm_moe_lite"
GENERATOR = "serve-closed-routed"


def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        return json.load(f)


def tiny_config():
    cfg = published()
    cfg.update(name="tiny-glm", hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
               kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
               v_head_dim=32, moe_intermediate_size=32, n_routed_experts=8,
               num_experts_per_tok=2, vocab_size=256,
               max_position_embeddings=256,
               num_hidden_layers={"serve_1chip": 3}, reduced=[])
    # 8 experts: the stacks join the flat draw scaled by 1 / sqrt(8 rows)
    cfg["assumed"] = dict(cfg["assumed"], init=dict(
        cfg["assumed"]["init"], expert_gain_log2=1))
    return cfg


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """The repository's benchmark with one more cell, of the tiny
    configuration: new files in a root of its own, nothing edited."""
    root = str(tmp_path_factory.mktemp("glm"))
    traffic = dict(bench_tiny.TRAFFIC["tiny-serve"], family=FAMILY,
                   generator=GENERATOR,
                   model_overrides={"attention_impl": "full"})
    traffic["prompt_tokens"] = dict(traffic["prompt_tokens"], max=24)
    traffic["output_tokens"] = dict(traffic["output_tokens"], max=100)
    bench_tiny._dump(root, "configs", "tiny-glm", tiny_config())
    bench_tiny._dump(root, "traffic", "tiny-glm-closed4", traffic)
    # the real cell's generator and its statistic, the gap that 85% of the
    # served tokens stay within: at these widths on the CPU 0 on six seeds
    # (40-70 served tokens, of which none or one is routed elsewhere than
    # float32 routes it: the WIDEST gap, which ``serve-closed`` reads, is
    # 0-0.007 on 10 of 11 seeds and 0.17-0.31 where one flipped)
    bench_tiny._dump(root, "limits", CELL, {"served_logit_gap": 0.12})
    add = {"configs": [{"name": "tiny-glm", "source": "self-test",
                        "file": "benchmarks/configs/tiny-glm.json",
                        "reduced": [], "why": "tiny"}],
           "workloads": [{"name": CELL, "config": "tiny-glm",
                          "traffic": "tiny-glm-closed4", "chips": 1,
                          "why": "tiny"}],
           "per_layer": []}
    bench = bench_tiny._grow(bench_tiny.repo_benchmark(), add, CELL)
    for m in bench["per_layer"]:
        if m["name"].startswith(("moe.", "mla.")):
            m["workloads"].append(CELL)
    return bench_tiny._write_benchmark(root, bench, (REPO,))


def test_the_familys_files_are_found_by_name():
    reg = registry_mod.Registry([REPO])
    for kind in ("programs", "reference", "counts"):
        assert reg.module(kind, FAMILY)
    assert reg.data("traffic", "serve-closed64-glm")["family"] == FAMILY
    assert published()["family"] == FAMILY
    # between the two readings of the chip (PERF.md section 6): 85% of the
    # served tokens within 0.026 on 22 sound seeds, 0.545 under int8
    assert 0.026 < reg.data("limits", REAL)["served_logit_gap"] < 0.545
    ref = reg.module("reference", FAMILY)
    with open(ref.__file__) as f:
        assert "horovod_tpu" not in f.read().replace(
            "nothing imported from the program", "")


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
        == (3, 2, 3)
    assert (program["experts"], program["experts_per_tok"],
            program["shared_experts"]) == (8, 2, 1)
    # ONE kind: 32 + 8 numbers a token a plane, in one 128-lane tile
    assert (program["latent_numbers"], program["latent_lanes"]) == (40, 128)
    assert program["state_bytes"] == {"latent": 3 * 4 * 128 * 128 * 2}
    compared, = (x for x in lines if x["line"] == "compared")
    assert compared["name"].startswith("served_logit_gap[p85 of ")
    assert 0 <= compared["value"] < compared["limit"]
    # the widest gap is logged beside it, and is one flip's at most
    scored, = (x for x in lines if x["line"] == "reference")
    assert compared["value"] <= scored["widest_gap"] < 0.5
    # what a pass routed, in the records of the steps that read one
    records = [r for r in hvd_tracing.get_tracer().steps()
               if "experts_touched" in r][-20:]
    assert records
    assert all(2 <= r["experts_touched"] <= 16 and
               1 <= r["expert_tokens_max"] <= 4 for r in records)


def test_the_int8_control_reads_not_correct(roots):
    """The control as ``control.py`` reads it, on a made-up sample of
    1,200 positions: the same sample on every machine, so one number.
    At a vocabulary of 256 and three layers int8 changes 4 tokens in a
    hundred (42-47 at the real cell's widths, on the chip: PERF.md
    section 6), so the cell's quantile reads 0 here and it is the WIDEST
    gap, ``serve-closed``'s own statistic, that tells the control at this
    size."""
    import sys

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
    assert 0 < changed < 0.15 * scored
    assert serve.widest_gap(sample, want, tokens=first) == \
        (0.0, "p85 of 12 requests", 1200)
    best = [lg.argmax(axis=-1) for lg in want]
    assert serve.closed.widest_gap(sample, want, tokens=best)[0] == 0.0


def test_the_quantile_reads_the_bulk_and_not_one_token():
    """``serve-closed-routed``'s statistic on made-up logits: a token's
    gap is its own reference logit under the reference's best; the number
    is the lowest gap with 85% of the scored tokens at or under it."""
    import numpy as np
    serve = registry_mod.Registry([REPO]).module("generators", GENERATOR)
    assert serve.QUANTILE == 0.85
    assert serve.reference_logits is serve.closed.reference_logits

    def scored(gaps):
        """Two requests whose served token 0 lies ``gaps`` under token 1."""
        gaps = np.asarray(gaps, np.float32)
        logits = np.zeros((len(gaps), 4), np.float32)
        logits[:, 1] = gaps
        half = len(gaps) // 2
        sample = [{"id": "a", "tokens": [0] * half},
                  {"id": "b", "tokens": [0] * (len(gaps) - half)}]
        return sample, [logits[:half], logits[half:]]

    # 10 tokens in 100 two logits under the best (routed elsewhere at a
    # near tie): the widest gap reads 2, the quantile nothing
    flips = np.zeros(100)
    flips[::10] = 2.0
    assert serve.widest_gap(*scored(flips)) == (0.0, "p85 of 2 requests", 100)
    assert serve.closed.widest_gap(*scored(flips))[0] == 2.0
    # 15 in 100 are still within the 85; the 16th is read
    flips[:15] = 1.0
    assert serve.widest_gap(*scored(flips))[0] == 1.0
    # a third of the tokens half a logit under (a lower precision): read
    low = np.zeros(99)
    low[::3] = 0.5
    assert serve.widest_gap(*scored(low))[0] == 0.5
    # every token a little under: read, however little
    assert serve.widest_gap(*scored(np.full(50, 0.25)))[0] == 0.25
    # ``tokens`` scores others than the served ones; nothing finite, nothing
    sample, logits = scored(np.full(20, 0.25))
    assert serve.widest_gap(sample, logits,
                            tokens=[[1] * 10, [1] * 10])[0] == 0.0
    logits[0][3, 1] = np.nan
    assert serve.widest_gap(sample, logits)[0] == math.inf
    assert serve.widest_gap([], []) == (0.0, "p85 of 0 requests", 0)


def test_the_configuration_file_holds_the_published_keys():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    cfg = published()
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == {"source": 47, "serve_1chip": 7}
    assert (cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"], cfg["first_k_dense_replace"]) == \
        (64, 4, 1, 1)
    assert cfg["num_nextn_predict_layers"] == 1      # kept, not held
    assert "NOT held" in cfg["layouts"]["serve_1chip"]
    assert {"inner_norms", "router_bias", "router_epsilon", "router_dtype",
            "softmax_scale", "rotary", "mtp", "weights", "compute_dtype",
            "init"} <= set(cfg["assumed"])
    # the gain is an exact power of two, and the one the stacks' law needs
    assert 2.0 ** cfg["assumed"]["init"]["expert_gain_log2"] == \
        math.sqrt(cfg["n_routed_experts"]) == 8.0


def test_the_init_gain_gives_an_expert_its_own_fan_in():
    """``lib/weights.py`` scales a stack ``[64, rows, cols]`` by 1 /
    sqrt(64 x rows); times the gain each expert is N(0,1) / sqrt(rows),
    as every other matrix. Reference and adapter each apply it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.lib import weights
    reg = registry_mod.Registry([REPO])
    ref, prog = reg.module("reference", FAMILY), reg.module("programs",
                                                            FAMILY)
    cfg = dict(published(), hidden_size=256, moe_intermediate_size=128,
               intermediate_size=512, vocab_size=64)
    shapes = ref.weight_shapes(cfg, 2)
    w = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(
        weights.seed_key(3))
    gate = np.asarray(w["layers.1.experts.gate"], np.float32)
    assert gate.shape == (64, 256, 128)
    assert gate.std() * math.sqrt(64 * 256) == pytest.approx(1.0, abs=0.02)
    assert ref.expert_gain(cfg) == 8.0
    assert gate.std() * 8 * math.sqrt(256) == pytest.approx(1.0, abs=0.02)
    # the reference widens then scales; the adapter scales the leaf: the
    # same numbers (a power of two is exact in bfloat16)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 256)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        by_ref = ref.matmul(x, w["layers.1.experts.gate"][5], None, 8.0)
        scaled = w["layers.1.experts.gate"] * jnp.asarray(8.0, jnp.bfloat16)
        np.testing.assert_array_equal(
            np.asarray(by_ref),
            np.asarray(jnp.matmul(x, scaled[5].astype(jnp.float32))))
    layer = prog.to_tree(w, 2, cfg)["layer_1"]
    np.testing.assert_array_equal(
        np.asarray(layer["experts"]["gate"], np.float32), gate * 8)
    # and the selection bias by its own gain, 1/16, on both sides
    assert ref.bias_gain(cfg) == 1 / 16
    np.testing.assert_array_equal(
        np.asarray(layer["router"]["bias"], np.float32),
        np.asarray(w["layers.1.router.bias"], np.float32) / 16)


def test_the_weights_draw_fits_the_chip_by_arithmetic():
    """``lib/weights.py`` cuts every leaf under 4.2 M elements from ONE
    flat float32 draw and gives every other leaf a draw of its own. With
    the experts published as three stacks a layer the flat draw is small
    and the largest single draw is a stack's; 1,152 leaves of one expert
    each would all join the flat draw."""
    from benchmarks.lib import weights
    reg = registry_mod.Registry([REPO])
    ref = reg.module("reference", FAMILY)
    cfg = published()
    shapes = ref.weight_shapes(cfg, 7)
    sizes = {k: math.prod(s) for k, s in shapes.items()}
    assert sum(sizes.values()) == pytest.approx(4530.9e6, rel=1e-4)
    flat = sum(n for n in sizes.values() if n < weights.SMALL)
    largest = max(sizes.values())
    assert largest == 154880 * 2048        # embedding and head
    assert sizes["layers.1.experts.gate"] == 64 * 2048 * 1536 > weights.SMALL
    # the leaves in bfloat16, the flat draw and the largest draw in
    # float32 live together at worst
    peak = 2 * sum(sizes.values()) + 4 * flat + 4 * largest
    assert flat * 4 < 0.5e9 and peak < 16e9 * 0.75
    one_expert = 2048 * 1536
    assert one_expert < weights.SMALL
    assert 6 * 64 * 3 * one_expert * 4 == pytest.approx(14.5e9, rel=0.01)


def test_the_cell_and_its_traffic_are_the_issues():
    reg = registry_mod.Registry([REPO])
    bench = reg.benchmark()
    cell = registry_mod.cell_of(bench, REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("glm-4.7-flash", "serve-closed64-glm", 1)
    traffic = reg.data("traffic", cell["traffic"])
    assert (traffic["generator"], traffic["family"], traffic["callers"],
            traffic["layout"]) == (GENERATOR, FAMILY, 64, "serve_1chip")
    assert traffic["engine"] == {"num_slots": 64, "max_len": 1536,
                                 "kv_block": 128,
                                 "admission_timeout_s": 1200.0}
    ouro = reg.data("traffic", "serve-closed32-ouro")
    assert set(traffic) == set(ouro)
    for law in ("prompt_tokens", "output_tokens", "requests_per_cycle",
                "temperature", "preroll_s", "check_requests",
                "trace_seconds"):
        assert traffic[law] == ouro[law]
    e2e = {m["name"] for m in
           registry_mod.metrics_of(bench, "end_to_end", REAL)}
    # not ``ttft_p90``: over six seeds on the chip it spread 3.8% where
    # half its bound is 1.5% (a step that admits two requests makes the
    # second wait out the first one's 27 ms prefill, one request in ten
    # does, and the 90th percentile lies on that shoulder: PERF.md §6); in
    # a loop at capacity the tails belong with the per-layer metrics
    assert e2e == {"serve_tokens_per_s", "tpot_p90", "setup_s"}
    layer = {m["name"] for m in
             registry_mod.metrics_of(bench, "per_layer", REAL)}
    assert {"moe.expert_roofline", "moe.experts_touched_share",
            "moe.expert_tokens_max", "mla.decode_attn_roofline",
            "model.decode_roofline", "attn.kv_bytes_per_step",
            "engine.decode_step_p50", "device.idle_share.serve",
            "device.peak_hbm.serve", "engine.occupancy",
            "launch.idle.dispatch", "engine.admit_ahead_share"} <= layer
    assert not {m for m in layer if m.startswith(("engine.idle.", "mixer.",
                                                  "cache.", "loop."))}
    # nothing that was there changed hands
    for other in ("baichuan7b-serve-closed", "falconh1-34b-serve-closed",
                  "ouro2.6b-serve-closed"):
        names = {m["name"] for m in
                 registry_mod.metrics_of(bench, "per_layer", other)}
        assert not {m for m in names if m.startswith(("moe.", "mla."))}
    share = reg.data("metrics", "moe.experts_touched_share")
    assert share["args"]["scale"] == pytest.approx(100 / (6 * 64))


def test_the_counts_follow_the_shapes():
    counts = registry_mod.Registry([REPO]).module("counts", FAMILY)
    cfg = published()
    # ISSUE 42's arithmetic, from the row's keys (the issue rounds)
    attn = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512
            + 512 * 20 * 448 + 20 * 256 * 2048)
    assert counts.attention_parameters(cfg) == attn
    assert round(attn / 1e6, 2) == 21.76
    assert counts.expert_parameters(cfg) == 3 * 2048 * 1536 == 9437184
    assert counts.dense_layer_parameters(cfg) == \
        attn + 3 * 2048 * 10240 + 4096
    assert round(counts.dense_layer_parameters(cfg) / 1e6, 2) == 84.68
    fixed = attn + 2048 * 64 + 64 + 9437184 + 4096
    assert counts.expert_layer_fixed_parameters(cfg) == fixed
    assert round((fixed + 64 * 9437184) / 1e6, 2) == 635.31
    assert counts.head_parameters(cfg) == 2048 * 154881
    assert counts.latent_bytes_per_token(cfg, 7) == 7 * 576 * 2 == 8064
    assert counts.latent_bytes_per_token(cfg, 47) == 47 * 1152
    # ONE read of the latent: key and value at once
    live = 64 * 450
    assert counts.decode_attention_bytes(cfg, 7, live) == live * 8064
    assert round(counts.decode_attention_bytes(cfg, 7, live) / 1e9, 2) \
        == 0.23
    assert counts.expert_bytes(cfg, 378) == 378 * 9437184 * 2
    # uniform routing: 64 rows x 4 distinct experts of 64 leave an expert
    # untouched with probability (60/64)^64
    assert counts.expected_touched(cfg, 64) == \
        pytest.approx(64 * (1 - (60 / 64) ** 64))
    assert counts.expected_touched(cfg, 64) / 64 == \
        pytest.approx(0.984, abs=5e-4)
    assert counts.expected_touched(cfg, 1) == pytest.approx(4)
    assert counts.expected_touched(cfg, 8) == pytest.approx(25.8, abs=0.1)
    every = counts.decode_step_bytes(cfg, 7, 0)
    assert every == (counts.dense_layer_parameters(cfg) + 6 * fixed
                     + 2048 * 154881 + 6 * 64 * 9437184) * 2
    # the whole stage less the embedding, which a step gathers by row
    assert round(every / 1e9, 2) == round((4530.9e6 - 317.19e6) * 2 / 1e9, 2)
    step = counts.decode_step_bytes(cfg, 7, live, rows=64)
    touched = 6 * counts.expected_touched(cfg, 64)
    assert step == pytest.approx(
        every - (6 * 64 - touched) * 9437184 * 2 + live * 8064)
    assert round(step / 1e9, 1) == 8.5
    assert 0.83 < counts.expert_bytes(cfg, touched) / step < 0.85
    # fewer rows touch fewer experts: the step has to read less
    assert counts.decode_step_bytes(cfg, 7, live, rows=8) < 0.55 * step


# -- the readers, on events named as the compiled program names them ---------

RAGGED = ('%ragged-dot-none.{n} = bf16[256,1536]{{1,0:T(8,128)(2,1)S(1)}} '
          'custom-call(s32[1]{{0:T(128)}} %get-tuple-element.2, s32[65]'
          '{{0:T(128)S(1)}} %get-tuple-element.3, bf16[256,2048]{{1,0:T(8,128)'
          '(2,1)}} %fusion.{n}, bf16[64,2048,1536]{{2,1,0:T(8,128)(2,1)}} '
          '%param.{n}), custom_call_target="tpu_custom_call"')
RAGGED_DOWN = RAGGED.replace("bf16[64,2048,1536]", "bf16[64,1536,2048]") \
    .replace("= bf16[256,1536]", "= bf16[256,2048]")
LATENT = ('%latent_decode_attention.{n} = bf16[64,32,512]{{2,1,0:T(8,128)'
          '(2,1)S(1)}} custom-call(s32[1]{{0:T(128)S(6)}} %constant.{n}, '
          'bf16[64,32,640]{{2,1,0:T(8,128)(2,1)}} %pad.{n}, bf16[7,64,1536,'
          '640]{{3,2,1,0:T(8,128)(2,1)}} %fusion.9), '
          'custom_call_target="tpu_custom_call"')
MATMUL = ("%fusion.{n} = bf16[64,2048]{{1,0}} fusion(bf16[64,1536]{{1,0}} "
          "%x, bf16[1536,2048]{{1,0}} %w), kind=kOutput")
LOOP = ("%while.3 = (s32[], bf16[64,2048,1536]{2,1,0}) while((s32[], "
        "bf16[64,2048,1536]{2,1,0}) %tuple.1), condition=%c, body=%b")


class FakeRun:
    def __init__(self, lines, family=FAMILY):
        self.registry = registry_mod.Registry([REPO])
        self.config = published()
        self.traffic = {"family": family}
        self.peaks = {"hbm_bytes_per_s": 819e9}
        self.lines = lines

    def log(self, line, **fields):
        self.lines.append(dict(line=line, **fields))


def decode_trace(expert_s, latent_s=1e-4, calls=3, layers=6, loop=False):
    """``calls`` decode programs: a layer is the latent kernel, a shared
    expert's matmul and the three grouped products; one prefill program
    holds grouped products too."""
    ops, mods, t = [], [], 0.0

    def op(name, d):
        nonlocal t
        ops.append(xplane.Event(name, t, t + d))
        t += d
    for _ in range(calls):
        start = t
        if loop:
            ops.append(xplane.Event(LOOP, t, t + 1.0))
        for n in range(layers):
            op(LATENT.format(n=n), latent_s)
            op(MATMUL.format(n=n), 5e-5)
            op(RAGGED.format(n=3 * n), expert_s)
            op(RAGGED.format(n=3 * n + 1), expert_s)
            op(RAGGED_DOWN.format(n=3 * n + 2), expert_s)
        mods.append(xplane.Event("jit__decode_jit(7)", start, t))
        t += 1e-3
    start = t
    op(RAGGED.format(n=77).replace("[256,", "[1024,"), 3e-3)
    mods.append(xplane.Event("jit__prefill_jit(3)", start, t))
    return xplane.Trace({0: ops}, {0: mods}, [])


def steps(live, n=5, rows=64):
    return [(0.0, 0.0, 0, rows, rows, live) for _ in range(n)]


def records(touched):
    return {"traced": [{"experts_touched": t, "expert_tokens_max": 9}
                       for t in touched] + [{"active": 64}]}


def test_expert_roofline_reads_the_events_that_read_a_stack():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "moe.expert_roofline")
    assert spec["source"] == "device_trace" and spec["moves"] == "tpot_p90"
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": decode_trace(expert_s=8e-4),
           "traced": {"steps": steps(28000), "model": {"layers": 7}},
           "step_phases": records([376, 378, 380])}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    least = 378 * 9437184 * 2 / 819e9          # a call, six layers
    assert value == pytest.approx(100 * least / (18 * 8e-4))
    assert 55 < value < 65
    said, = lines
    # not the latent kernel, not the shared expert, not the prefill's
    assert (said["calls"], said["events"], said["records"]) == (3, 54, 3)
    assert said["experts_touched"] == 378
    assert said["events_ms_per_call"] == pytest.approx(14.4)
    # a loop that carries a stack is no read of it
    obs["trace"] = decode_trace(expert_s=8e-4, loop=True)
    assert reader.read(obs, spec["args"], FakeRun([])) == \
        pytest.approx(value)
    # fewer experts touched: the least time falls with them
    obs["step_phases"] = records([189])
    assert reader.read(obs, spec["args"], FakeRun([])) == \
        pytest.approx(value / 2)


def test_expert_roofline_reports_nothing_where_there_is_nothing():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "moe.expert_roofline")
    reader = reg.module("readers", spec["reader"])
    traced = {"steps": steps(28000), "model": {"layers": 7}}
    # a program whose records lack the count (the parent), or none at all
    obs = {"trace": decode_trace(8e-4), "traced": traced,
           "step_phases": {"traced": [{"active": 64}]}}
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    obs["step_phases"] = None
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    # no event reads a stack: a family without experts
    obs = {"trace": decode_trace(8e-4, layers=0), "traced": traced,
           "step_phases": records([378])}
    assert reader.read(obs, spec["args"], FakeRun([])) is None
    obs["trace"] = decode_trace(8e-4)
    assert reader.read(obs, spec["args"], FakeRun([], "ouro")) is None


def test_the_latent_kernels_roofline_is_the_accepted_reader_by_name():
    reg = registry_mod.Registry([REPO])
    spec = reg.data("metrics", "mla.decode_attn_roofline")
    assert spec["reader"] == "decode_attn_roofline"
    assert spec["args"] == {"module": "jit__decode_jit",
                            "kernel": "latent_decode_attention"}
    reader = reg.module("readers", spec["reader"])
    lines = []
    obs = {"trace": decode_trace(8e-4, latent_s=8e-5),
           "traced": {"steps": steps(28000), "model": {"layers": 7}}}
    value = reader.read(obs, spec["args"], FakeRun(lines))
    least = 28000 * 7 * 1152 / 819e9       # ONE read of 576 numbers
    assert value == pytest.approx(100 * least / (6 * 8e-5))
    said, = lines
    assert (said["calls"], said["events"]) == (3, 18)
    # the einsum path: nothing of that name
    obs["trace"] = decode_trace(8e-4, layers=0)
    assert reader.read(obs, spec["args"], FakeRun([])) is None


def test_the_two_counts_are_read_from_decode_only_steps():
    reg = registry_mod.Registry([REPO])
    window = [{"phases": [["decode_readback", 0, 1]], "experts_touched": t,
               "expert_tokens_max": m} for t, m in
              ((376, 9), (380, 11), (378, 10))]
    window.append({"phases": [["prefill", 0, 1], ["decode_readback", 1, 2]],
                   "experts_touched": 100, "expert_tokens_max": 40})
    window.append({"phases": [["decode_dispatch", 0, 1]]})   # ran ahead
    obs = {"step_phases": {"window": window}}
    share = reg.data("metrics", "moe.experts_touched_share")
    reader = reg.module("readers", share["reader"])
    assert reader.read(obs, share["args"], FakeRun([])) == \
        pytest.approx(100 * 378 / 384)
    fullest = reg.data("metrics", "moe.expert_tokens_max")
    assert reader.read(obs, fullest["args"], FakeRun([])) == 10
    # a program without the counts reports nothing and raises nothing
    obs = {"step_phases": {"window": [{"phases": [], "kv_bytes": 5}]}}
    assert reader.read(obs, share["args"], FakeRun([])) is None


def test_the_selection_rules_on_events_recorded_on_the_chip():
    """``fixtures/glm_events_v5e.json``: instruction texts of the cell's
    own traced run on a v5e.  In the decode program ``moe.expert_roofline``
    selects the EIGHTEEN grouped products (three an expert layer: XLA's
    ``ragged-dot`` custom calls, each handed ONE whole stack of 64
    experts as it lies) and nothing else; ``mla.decode_attn_roofline`` the
    SEVEN ``latent_decode_attention`` calls, one a plane, each handed the
    whole 640-lane cache once; no other Mosaic call is in the program.
    The prefill program's grouped products read the stacks too and are no
    part of either metric (another module)."""
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "glm_events_v5e.json")) as f:
        recorded = json.load(f)
    reg = registry_mod.Registry([REPO])
    experts = reg.module("readers", "expert_roofline")
    attn = reg.module("readers", "decode_attn_roofline")
    kernel = reg.data("metrics", "mla.decode_attn_roofline")["args"]["kernel"]
    stacks = experts.stack_shapes(published())
    assert stacks == {(64, 2048, 1536), (64, 1536, 2048)}
    n = recorded["calls_sampled"]
    decode = recorded["events"]["jit__decode_jit"]
    grouped = [(name, seen, took) for name, seen, took in decode
               if experts.reads_a_stack(name, stacks)]
    assert len(grouped) == 18 < len(decode)
    for name, seen, took in grouped:
        assert seen == n and name.startswith("%ragged-dot")
        assert xplane.op_class(name) == "mosaic"
        operands = xplane.shapes(name.partition("custom-call(")[2]
                                 .partition("), custom_call")[0])
        assert sum(dims in stacks for _, dims in operands) == 1
        assert ("bf16", (256, 2048)) in operands or \
            ("bf16", (256, 1536)) in operands       # 64 rows x 4, sorted
        assert 0.5e-3 < took / seen < 1.1e-3         # 0.79 ms a product
    latent = [(name, seen, took) for name, seen, took in decode
              if attn.is_kernel(name, kernel)]
    assert len(latent) == 7
    cache = ("bf16", (7, 64, 1536, 640))
    for name, seen, took in latent:
        assert seen == n
        operands = xplane.shapes(name.partition("custom-call(")[2]
                                 .partition("), custom_call")[0])
        assert operands.count(cache) == 1            # key and value at once
        assert ("bf16", (64, 32, 640)) in operands   # 20 heads in 32 rows
        assert 0.05e-3 < took / seen < 0.3e-3        # 0.14 ms a plane
    mosaic = [name for name, _, _ in decode
              if xplane.op_class(name) == "mosaic"]
    assert sorted(mosaic) == sorted([g[0] for g in grouped]
                                    + [k[0] for k in latent])
    assert not [name for name, _, _ in decode
                if xplane.opcode(name) == "while"]
    prefill = recorded["events"]["jit__prefill_jit"]
    assert [name for name, _, _ in prefill
            if experts.reads_a_stack(name, stacks)]
    assert not [name for name, _, _ in prefill
                if attn.is_kernel(name, kernel)]
    # what the products took a call in that run, against what the experts
    # that window's passes touched need (the result line read 61.4%), and
    # the kernel against one read of the live latent (28.3%)
    counts = reg.module("counts", FAMILY)
    took = sum(t for _, _, t in grouped) / n
    least = counts.expert_bytes(published(), 378.06) / 819e9
    assert 55 < 100 * least / took < 68
    took = sum(t for _, _, t in latent) / n
    least = counts.decode_attention_bytes(published(), 7, 28861.8) / 819e9
    assert 20 < 100 * least / took < 40
