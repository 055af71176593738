"""Share, in %, of one count of the program's own step record in another
over the untraced window's steps: ``sum(args.count) / sum(args.over)``.
``None`` where the program keeps no step records, its records lack either
count (an older commit), or the window holds nothing of ``args.over``."""
from benchmarks.lib import step_phases


def read(obs, args, run):
    got = step_phases.analysis(obs, run)
    steps = (got or {}).get("window", [])
    if not steps or any(args["count"] not in r or args["over"] not in r
                        for r in steps):
        return None
    over = sum(r[args["over"]] for r in steps)
    if not over:
        return None
    return 100.0 * sum(r[args["count"]] for r in steps) / over
