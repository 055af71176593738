#!/usr/bin/env python3
"""The readings a cell's limits are set from, taken on the chip at the
cell's own size, several seeds in one process:

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3 [--controls 3] [--seconds 10] [--budget-seconds 1200]

For each seed it reads the SOUND numbers (the program's timed path
against the float32 reference, exactly what ``run.py`` compares) and, for
the first ``--controls`` seeds, the CONTROL's: the reference itself put in
the program's place and computed one precision step below the
configuration (fp8 operands under bf16 compute for training; int8 weights
and activations under a bf16 served model, where at each position of the
same prompts and tokens the gap is read of the token that the lower
precision puts first).  The control has to come out as not correct; the
benchmark's own runs never run it.  ``tests/benchmarks`` keeps a small
copy of this as a test.

The references' programs are built once and reused for every seed
(``lib/train_reference._programs``).  Once ``--budget-seconds`` have
passed, no further control is started and the remaining seeds give sound
readings only, so that a slow control (ResNet-50's emulated fp8 took nine
minutes a seed on the v5e) cannot eat a cell's chip budget unseen.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as run_mod  # noqa: E402
from benchmarks.lib import registry as registry_mod  # noqa: E402


def train_readings(run, gen, with_control, dump_dir=None):
    """{"sound": [(name, value, limit)], "control": [...]} for one seed."""
    from benchmarks.lib import train_reference as tref
    train = run.registry.module("generators", "train")
    traffic = run.traffic
    gen.setup()
    gen.prog.free()
    ref = run.registry.module("reference", traffic["family"])
    adapter = run.registry.module("programs", traffic["family"])
    layers = adapter.depth(run.config, traffic)
    steps = traffic["check_steps"]
    want = tref.run(ref, run.config, layers, traffic, run.seed, run.devices,
                    steps=steps)
    out = {"sound": train.compare(gen.first, want, run.limits)}
    leaves = {"program": gen.first, "reference": want}
    if with_control:
        low = tref.run(ref, run.config, layers, traffic, run.seed,
                       run.devices, quant=traffic.get("control", "fp8"),
                       steps=steps)
        out["control"] = train.compare(low, want, run.limits)
        leaves["control"] = low
    if dump_dir:  # every leaf's norms, to choose a number from
        os.makedirs(dump_dir, exist_ok=True)
        with open(os.path.join(
                dump_dir, f"leaves_{run.cell['name']}_{run.seed}.json"),
                "w") as f:
            json.dump(leaves, f)
    return out


def serve_readings(run, gen, with_control, seconds):
    import numpy as np
    serve = run.registry.module("generators", run.traffic["generator"])
    gen.setup()
    gen.window(seconds)
    sample = gen.sample()
    gen.prog.free()
    logits = serve.reference_logits(run, sample)
    limit = run.limits["served_logit_gap"]
    gap, where, n = serve.widest_gap(sample, logits)
    out = {"sound": [(f"served_logit_gap[{where}]", gap, limit)],
           "served_tokens": n}
    if with_control:
        low = serve.reference_logits(
            run, sample, quant=run.traffic.get("control", "int8"))
        first = [lg.argmax(axis=-1) for lg in low]
        gap, where, _ = serve.widest_gap(sample, logits, tokens=first)
        out["control"] = [(f"served_logit_gap[{where}]", gap, limit)]
        out["control_tokens_changed"] = int(sum(
            int(np.sum(f != np.asarray(r["tokens"])))
            for f, r in zip(first, sample)))
    return out


def main(argv=None, roots=(ROOT,), require_chip=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--budget-seconds", type=float, default=1200.0,
                    help="start no control once this much time has passed")
    ap.add_argument("--dump-leaves", default=None, metavar="DIR",
                    help="training cells: write every leaf's norms there")
    args = ap.parse_args(argv)
    registry = registry_mod.Registry(roots)
    bench = registry.benchmark()
    cell = registry_mod.cell_of(bench, args.workload)
    if require_chip:
        from horovod_tpu.utils import compile_cache
        compile_cache.configure()
    results, t_start = [], time.perf_counter()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t_seed = time.perf_counter()
        with_control = i < args.controls and \
            t_seed - t_start < args.budget_seconds
        run = run_mod.Run(registry, bench, cell, seed, args.seconds, 0,
                          sys.stdout)
        run_mod.find_devices(run, require_chip)
        gen = registry.module("generators", run.traffic["generator"]) \
            .Generator(run)
        if run.traffic["generator"] == "train":
            got = train_readings(run, gen, with_control, args.dump_leaves)
        else:
            got = serve_readings(run, gen, with_control, args.seconds)
        got["seed"] = seed
        got["seconds"] = time.perf_counter() - t_seed
        print(json.dumps({"line": "readings", **got}), flush=True)
        results.append(got)
    return results


if __name__ == "__main__":
    main()
