"""Median over the untraced window's steps that admitted nothing (no
``prefill`` phase) of one count of the program's own step record
(``args.count``), times ``args.scale``.  ``None`` where the program keeps
no step records or its records lack the count (an older commit)."""
from benchmarks.lib import stats
from benchmarks.lib import step_phases


def read(obs, args, run):
    got = step_phases.analysis(obs, run)
    steps = step_phases.decode_only((got or {}).get("window", []))
    values = [r[args["count"]] for r in steps if args["count"] in r]
    if not values:
        return None
    return stats.median(values) * args.get("scale", 1)
