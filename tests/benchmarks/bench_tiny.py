"""A tiny benchmark tree in a temporary directory, for the self-tests.

It adds one of each kind of thing a later PR may add (two configurations,
four traffic mixes, limits, a per-layer metric and its reader) as NEW
files under a root of its own, and the harness runs them with the
repository's generators, programs and references: nothing that is there is
edited.
"""

import io
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ADAMW = {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9, "b2": 0.999,
         "eps": 1e-8, "weight_decay": 1e-4, "mu_dtype": "bfloat16"}
FULL = {"attention_impl": "full"}  # no interpreted Pallas kernel: quicker

CONFIGS = {
    "tiny-lm": {"family": "baichuan", "hidden_size": 64,
                "intermediate_size": 128, "num_attention_heads": 4,
                "num_hidden_layers": {"train_1chip": 2, "train_4dev": 2,
                                      "serve_1chip": 2},
                "vocab_size": 256, "max_position_embeddings": 256},
}
TRAFFIC = {
    "tiny-train": {
        "generator": "train", "family": "baichuan", "layout": "train_1chip",
        "mesh": {"dp": 1}, "global_batch": 2, "seq_len": 64,
        "optimizer": ADAMW, "steps_per_block": 2, "blocks_in_flight": 3,
        "check_steps": 3,
        "trace_seconds": 1, "model_overrides": FULL,
        "reference": {"rows_per_block": 1, "head_block": 2,
                      "position_block": 32, "remat_layers": True}},
    "tiny-train4": {
        "generator": "train", "family": "baichuan", "layout": "train_4dev",
        "mesh": {"dp": 2, "tp": 2}, "global_batch": 4, "seq_len": 64,
        "optimizer": ADAMW, "steps_per_block": 1, "check_steps": 2,
        "trace_seconds": 1, "model_overrides": FULL,
        "reference": {"rows_per_block": 2}},
    "tiny-serve": {
        "generator": "serve-closed", "family": "baichuan",
        "layout": "serve_1chip", "callers": 4, "model_overrides": FULL,
        "engine": {"num_slots": 4, "max_len": 128, "kv_block": 16,
                   "admission_timeout_s": 600.0},
        "requests_per_cycle": 8,
        "prompt_tokens": {"law": "lognormal", "median": 24, "sigma": 1.0,
                          "min": 4, "max": 64},
        "output_tokens": {"law": "lognormal", "median": 8, "sigma": 0.7,
                          "min": 2, "max": 24},
        "temperature": 0.0, "preroll_s": 0.2, "check_requests": 4,
        "trace_seconds": 1},
}
CELLS = [("tiny-lm-train", "tiny-lm", "tiny-train", 1),
         ("tiny-lm-train4", "tiny-lm", "tiny-train4", 4),
         ("tiny-lm-serve", "tiny-lm", "tiny-serve", 1)]
# tiny-size limits: the CPU's bf16 against float32 at these widths reads
# 1e-3 (gradients) and 3e-3 (parameter change); fp8 reads 8e-3 and 1.6e-2
LIMITS = {"train": {"loss_rel": 0.01, "grad_norm_gap": 0.004,
                    "delta_norm_gap": 0.008},
          "serve": {"served_logit_gap": 0.2}}

READER = '''"""A reader a later PR might add: steps the window ran."""


def read(obs, args, run):
    return obs["window"].get(args["field"])
'''


def make_root(root):
    bdir = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "limits", "metrics", "readers"):
        os.makedirs(os.path.join(bdir, d), exist_ok=True)
    for name, body in CONFIGS.items():
        with open(os.path.join(bdir, "configs", name + ".json"), "w") as f:
            json.dump(body, f)
    for name, body in TRAFFIC.items():
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(body, f)
    for cell, _, traffic, _ in CELLS:
        kind = "serve" if "serve" in traffic else "train"
        with open(os.path.join(bdir, "limits", cell + ".json"), "w") as f:
            json.dump(LIMITS[kind], f)
    with open(os.path.join(bdir, "readers", "window_field.py"), "w") as f:
        f.write(READER)
    with open(os.path.join(bdir, "metrics", "test.steps.json"), "w") as f:
        json.dump({"name": "test.steps", "reader": "window_field",
                   "args": {"field": "steps"}}, f)
    e2e = [{"name": n, "unit": u, "better": b, "bound": 0.1,
            "source": "host_clock"}
           for n, u, b in (("train_rate", "items/s/chip", "higher"),
                           ("serve_tokens_per_s", "tokens/s", "higher"),
                           ("ttft_p90", "ms", "lower"),
                           ("tpot_p90", "ms", "lower"),
                           ("setup_s", "s", "lower"))]
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "self-test",
                     "file": f"benchmarks/configs/{n}.json", "reduced": [],
                     "why": "tiny"} for n in CONFIGS],
        "workloads": [{"name": a, "config": b, "traffic": c, "chips": d,
                       "why": "tiny"} for a, b, c, d in CELLS],
        "end_to_end": e2e,
        "per_layer": [{"name": "test.steps", "unit": "count",
                       "better": "higher", "source": "program_counter",
                       "layer": "test", "moves": "train_rate",
                       "workloads": ["tiny-lm-train"]}]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return (root, REPO)


def run_cell(roots, workload, seed=7, seconds=0.3):
    """One run through the harness with its look for a chip skipped;
    returns (result, earlier lines)."""
    from benchmarks import run as run_mod
    out = io.StringIO()
    result = run_mod.execute(workload, seed, seconds, 0, roots=roots,
                             require_chip=False, out=out)
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    assert lines[-1] == result
    return result, lines[:-1]
