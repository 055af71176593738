"""Median, in ms, over the untraced window's steps that admitted nothing
(no ``prefill`` phase) of a part of the program's own step record:
``args.sum`` names the phases to add up, ``args.less`` those to take from
the whole step.  ``None`` where the program keeps no step records."""
from benchmarks.lib import stats
from benchmarks.lib import step_phases


def read(obs, args, run):
    got = step_phases.analysis(obs, run)
    steps = step_phases.decode_only((got or {}).get("window", []))
    if not steps:
        return None
    if "sum" in args:
        values = [step_phases.phase_ms(r, args["sum"]) for r in steps]
    else:
        values = [step_phases.step_ms(r) - step_phases.phase_ms(
            r, args["less"]) for r in steps]
    return stats.median(values)
