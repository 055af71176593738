"""Benchmark trees in a temporary directory, for the self-tests.

Each builder adds what a later PR may add as NEW files under a root of its
own, placed before the roots it builds on; the harness runs them with the
generators, programs and references that are there, and nothing that is
there is edited.

``make_root``: a tiny benchmark of its own (a configuration, three traffic
mixes and cells, limits, a per-layer metric and its reader) over the
repository's generators and Baichuan family.
``make_grown_root``: the repository's ``BENCHMARK.json`` after a PR that
added a cell, a configuration and a per-layer metric on a new layer (the
tiny serving cell of the above): what the accepted benchmark may look like
when the next PR arrives.
``make_extended_root``: a benchmark (the repository's, or the grown one)
with what a ``model_config`` PR brings (``second_family/``): a
configuration of a second serving family with its ``programs/``,
``reference/`` and ``counts/`` files, a traffic mix, a limits file, a cell,
and a per-layer metric on a new layer with its reader.  The contract tests
run on it as on the repository's benchmark, and the harness runs the added
cell on the CPU.
"""

import copy
import io
import json
import os
import shutil

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


METRIC = {"name": "test.steps", "unit": "count", "better": "higher",
          "source": "program_counter", "layer": "test"}


def _dump(root, kind, name, body):
    folder = os.path.join(root, "benchmarks", kind)
    os.makedirs(folder, exist_ok=True)
    with open(os.path.join(folder, name + ".json"), "w") as f:
        json.dump(body, f)


def _tiny_files(root, cells, moves):
    """The files of ``cells`` (of ``CELLS``) and of the metric, all new."""
    for cell, config, traffic, _ in cells:
        _dump(root, "configs", config, dict(CONFIGS[config], reduced=[]))
        _dump(root, "traffic", traffic, TRAFFIC[traffic])
        _dump(root, "limits", cell,
              LIMITS["serve" if "serve" in traffic else "train"])
    _dump(root, "metrics", METRIC["name"],
          dict(METRIC, moves=moves, reader="window_field",
               args={"field": "steps"}))
    os.makedirs(os.path.join(root, "benchmarks", "readers"), exist_ok=True)
    with open(os.path.join(root, "benchmarks", "readers",
                           "window_field.py"), "w") as f:
        f.write(READER)


def _entries(cells, moves, metric_cell):
    """``BENCHMARK.json``'s entries for ``cells`` and the metric."""
    return {
        "configs": [{"name": n, "source": "self-test",
                     "file": f"benchmarks/configs/{n}.json", "reduced": [],
                     "why": "tiny"} for n in dict.fromkeys(
                         c[1] for c in cells)],
        "workloads": [{"name": a, "config": b, "traffic": c, "chips": d,
                       "why": "tiny"} for a, b, c, d in cells],
        "per_layer": [dict(METRIC, moves=moves, workloads=[metric_cell])]}


def make_root(root):
    _tiny_files(root, CELLS, "train_rate")
    e2e = [{"name": n, "unit": u, "better": b, "bound": 0.1,
            "source": "host_clock"}
           for n, u, b in (("train_rate", "items/s/chip", "higher"),
                           ("serve_tokens_per_s", "tokens/s", "higher"),
                           ("ttft_p90", "ms", "lower"),
                           ("tpot_p90", "ms", "lower"),
                           ("setup_s", "s", "lower"))]
    bench = dict({"command": ["python3", "benchmarks/run.py"],
                  "paths": ["benchmarks"], "run_seconds": 1,
                  "end_to_end": e2e},
                 **_entries(CELLS, "train_rate", "tiny-lm-train"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return (root, REPO)


SECOND_FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "second_family")
SERVING = ("serve_tokens_per_s", "ttft_p90", "tpot_p90")


def repo_benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _grow(bench, add, cell):
    """``bench`` with ``add``'s entries appended: nothing that is there
    changes but the ``workloads`` lists of the serving metrics, which the
    added cell reports."""
    bench = copy.deepcopy(bench)
    for section in ("configs", "workloads", "per_layer"):
        bench[section] += add[section]
    for m in bench["end_to_end"]:
        if m["name"] in SERVING and "workloads" in m:
            m["workloads"].append(cell)
    return bench


def grown_benchmark():
    """The repository's benchmark after a PR that added the tiny serving
    cell, its configuration and a metric on the layer ``test``."""
    cell = CELLS[2]
    return _grow(repo_benchmark(), _entries([cell], "tpot_p90", cell[0]),
                 cell[0])


def extended_benchmark(base=None):
    """``base`` (the repository's ``BENCHMARK.json`` unless given) with
    ``second_family/``'s entries appended."""
    with open(os.path.join(SECOND_FAMILY, "additions.json")) as f:
        add = json.load(f)
    return _grow(base or repo_benchmark(), add, add["workloads"][0]["name"])


def _write_benchmark(root, bench, below):
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return (root,) + tuple(below)


def make_grown_root(root):
    _tiny_files(root, CELLS[2:], "tpot_p90")
    return _write_benchmark(root, grown_benchmark(), (REPO,))


def make_extended_root(root, below=(REPO,), base=None):
    """Writes the extended benchmark under ``root``, before the roots
    ``below`` that hold ``base``'s files; returns the roots."""
    shutil.copytree(os.path.join(SECOND_FAMILY, "benchmarks"),
                    os.path.join(root, "benchmarks"))
    return _write_benchmark(root, extended_benchmark(base), below)


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
