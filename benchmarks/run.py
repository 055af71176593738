#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything else by name
(``lib/registry.py``), builds the system under test through the program's
normal entry points, warms the cell's own shapes (set-up), measures for
``--seconds``, then checks what the timed path produced against the
family's plain reference.  The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``.  Earlier lines are JSON objects too
(``{"line": ...}``): set-up items, per-block rates, sample counts, length
histograms, every number compared beside its limit.

There is no CPU mode: without a TPU, with fewer chips than the cell asks
for, or on a chip that ``peaks.json`` does not know, the run exits
non-zero and prints nothing under a metric's name.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import registry as registry_mod  # noqa: E402


class Run:
    """What one run knows: the cell, its files, the devices, the clock."""

    def __init__(self, registry, bench, cell, seed, seconds, trace, out):
        self.registry, self.bench, self.cell = registry, bench, cell
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.out = out
        conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
        with open(registry.top(conf["file"])) as f:
            self.config = json.load(f)
        self.traffic = registry.data("traffic", cell["traffic"])
        self.limits = registry.data("limits", cell["name"])
        self.devices = None
        self.peaks = None
        self.setup_items = {}
        self._annotation = None

    def log(self, line, **fields):
        print(json.dumps({"line": line, **fields}), file=self.out,
              flush=True)

    @contextlib.contextmanager
    def setup_item(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.setup_items[name] = self.setup_items.get(name, 0.0) + \
                time.perf_counter() - t0

    def span(self, name):
        """A harness span around a call into a layer; lands in the
        profiler's trace as ``bench.<name>`` when one is being taken."""
        if self._annotation is None:
            import jax
            self._annotation = jax.profiler.TraceAnnotation
        return self._annotation("bench." + name)


def find_devices(run, require_chip):
    import jax
    devices = jax.devices()
    chips = run.cell["chips"]
    if require_chip and devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmarks/run.py: needs a TPU, JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}); there is "
            f"no CPU mode")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmarks/run.py: cell {run.cell['name']} asks for {chips} "
            f"chip(s), JAX found {len(devices)}")
    run.devices = devices[:chips]
    if require_chip:
        try:
            run.peaks = run.registry.peaks(devices[0].device_kind)
        except KeyError as e:
            raise SystemExit(f"benchmarks/run.py: {e.args[0]}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(devices):
    """Peak bytes on the fullest chip.  On this runtime
    ``peak_bytes_in_use`` counts arrays (arguments, results) and leaves out
    the scratch that loaded programs reserve for their temporaries, which
    ``peak_bytes_reserved`` counts: the LM step reads 7.29 GB + 3.58 GB
    where ``memory_analysis()`` declares 7.27 GB of arguments and 3.64 GB
    of temporaries (chip run, PR 23).  The peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) +
                     stats.get("peak_bytes_reserved", 0))
    return max(peaks)


def traced_window(run, gen, tracedir):
    """A second, short window under the profiler; returns (obs, Trace)."""
    import jax
    from benchmarks.lib import xplane
    shutil.rmtree(tracedir, ignore_errors=True)
    jax.profiler.start_trace(tracedir)
    try:
        with run.span("window"):
            obs = gen.window(run.traffic["trace_seconds"])
    finally:
        jax.profiler.stop_trace()
    trace = xplane.load(xplane.find(tracedir))
    shutil.rmtree(tracedir, ignore_errors=True)
    return obs, trace


def execute(workload, seed, seconds, trace, roots=(ROOT,), require_chip=True,
            out=None):
    """Run one cell; returns the result object (and prints it last)."""
    out = out or sys.stdout
    # set-up counts from the top of this file; a self-test that skips the
    # look for a chip runs many cells in one process and counts from here
    t0 = T0 if require_chip else time.perf_counter()
    registry = registry_mod.Registry(roots)
    bench = registry.benchmark()
    cell = registry_mod.cell_of(bench, workload)
    run = Run(registry, bench, cell, seed, seconds, trace, out)
    if require_chip:
        # the program's own placement of JAX's persistent compile cache:
        # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
        from horovod_tpu.utils import compile_cache
        cache_dir = compile_cache.configure()
    else:
        cache_dir = None
    device = find_devices(run, require_chip)
    run.setup_items["imports_and_backend"] = time.perf_counter() - t0
    run.log("start", workload=workload, seed=seed, seconds=seconds,
            trace=trace, device=device, compile_cache=cache_dir,
            config=cell["config"], traffic=cell["traffic"])

    gen = registry.module("generators", run.traffic["generator"]) \
        .Generator(run)
    gen.setup()
    setup_s = time.perf_counter() - t0
    run.log("setup", setup_s=setup_s,
            items={k: round(v, 3) for k, v in run.setup_items.items()})

    obs = gen.window(seconds)
    observations = {"window": obs}
    checks = []
    if obs.get("compiled_in_window"):
        checks.append(("compiles_in_window", obs["compiled_in_window"], 0))
    if trace:
        tracedir = os.path.join(roots[-1], ".bench_trace", workload)
        observations["traced"], observations["trace"] = \
            traced_window(run, gen, tracedir)
        observations["memory_analysis"] = gen.memory_analysis()
    run.log("memory_stats", **{k: v for k, v in (
        run.devices[0].memory_stats() or {}).items()})
    device["memory_peak_bytes"] = peak_bytes(run.devices)
    observations["memory_peak_bytes"] = device["memory_peak_bytes"]

    more, attempted, failed = gen.check()
    checks += more
    for name, value, limit in checks:
        run.log("compared", name=name, value=value, limit=limit,
                ok=bool(value <= limit))
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)

    metrics = {}
    if trace:
        from benchmarks.lib import xplane
        tr = observations["trace"]
        busy = xplane.busy_seconds(tr)
        t0, t1 = xplane.window_of(tr)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = t1 - t0
        for m in registry_mod.metrics_of(bench, "per_layer", workload):
            spec = registry.data("metrics", m["name"])
            reader = registry.module("readers", spec["reader"])
            value = reader.read(observations, spec.get("args", {}), run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(obs["end_to_end"], setup_s=setup_s)
        for m in registry_mod.metrics_of(bench, "end_to_end", workload):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = xplane.breakdown(observations["trace"])
    print(json.dumps(result), file=out, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    execute(args.workload, args.seed, args.seconds, args.trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
