#!/usr/bin/env python3
"""Record, from ONE traced run of a cell on the chip, the instruction texts
that the per-layer readers select from: for each of the serving programs
(``jit__decode_jit``, ``jit__prefill_jit``) every distinct device event
inside the module's calls in the traced window, with how often it ran and
how long it took in all, over the first ``calls_sampled`` calls.

    python3 benchmarks/tools/record_events.py --workload <cell> --seed <n> [--seconds 40] [--calls 40]

It is ``benchmarks/run.py --trace 1`` itself (the result line is printed
as ever) with the trace kept a moment longer; the fixture goes to
``chiprun_out/events/<cell>.json`` and is then committed under
``benchmarks/fixtures/`` for the readers' self-tests, which hold the
selection rules to what the chip really named.
"""

import argparse
import bisect
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as run_mod  # noqa: E402
from benchmarks.lib import xplane  # noqa: E402

MODULES = ("jit__decode_jit", "jit__prefill_jit")


def events_by_module(trace, calls_sampled):
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    out, calls = {}, {}
    for module in MODULES:
        mods = [m for m in trace.modules.get(dev, [])
                if xplane.module_name(m.name) == module
                and m.start >= t0 and m.end <= t1][:calls_sampled]
        starts = [m.start for m in mods]
        seen = collections.OrderedDict()
        for e in trace.ops[dev]:
            i = bisect.bisect_right(starts, e.start) - 1
            if i >= 0 and e.end <= mods[i].end:
                n, took = seen.get(e.name, (0, 0.0))
                seen[e.name] = (n + 1, took + e.end - e.start)
        out[module] = [[name, n, took] for name, (n, took) in seen.items()]
        calls[module] = len(mods)
    return out, calls


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--calls", type=int, default=40)
    args = ap.parse_args(argv)
    kept = []
    load = xplane.load

    def keeping(path):
        kept.append(load(path))
        return kept[-1]
    xplane.load = keeping
    result = run_mod.execute(args.workload, args.seed, args.seconds, 1)
    events, calls = events_by_module(kept[-1], args.calls)
    outdir = os.path.join(ROOT, "chiprun_out", "events")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, args.workload + ".json"), "w") as f:
        json.dump({"source": f"benchmarks/tools/record_events.py --workload "
                             f"{args.workload} --seed {args.seed} --seconds "
                             f"{args.seconds:g} on a {result['device']['kind']}",
                   "calls": calls, "calls_sampled": args.calls,
                   "metrics": result["metrics"], "events": events}, f,
                  indent=0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
