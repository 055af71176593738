"""A percentile, in ms, over the requests the untraced window admitted of
``prefill`` + ``prefill_readback`` in the program's own step records: the
host arrays, the upload, both launches and the wait for the first token.
``None`` where the program keeps no step records."""
from benchmarks.lib import stats
from benchmarks.lib import step_phases


def read(obs, args, run):
    got = step_phases.analysis(obs, run)
    values = step_phases.prefill_ms((got or {}).get("window", []))
    if not values:
        return None
    run.log("percentile", field="prefill_ms", q=args["q"],
            samples=len(values))
    return stats.percentile(values, args["q"])
