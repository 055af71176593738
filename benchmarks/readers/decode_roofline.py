"""Roofline share of the decode step, in %: the bytes a step has to read
(every weight once, K/V of the live tokens, per-row state of the rows that
decode where the family keeps any) over the HBM peak, over the median
device time of ``jit__decode_jit`` in the trace.  Memory-bound."""
from benchmarks.lib import stats
from benchmarks.lib import xplane


def read(obs, args, run):
    durations = xplane.module_seconds(obs["trace"]).get(args["module"])
    steps = obs["traced"].get("steps")
    if not durations or not steps:
        return None
    counts = run.registry.module("counts", run.traffic["family"])
    layers = obs["traced"]["model"]["layers"]
    live = sum(s[5] for s in steps) / len(steps)
    rows = sum(s[4] for s in steps) / len(steps)
    nbytes = counts.decode_step_bytes(run.config, layers, live, rows=rows)
    least = nbytes / run.peaks["hbm_bytes_per_s"]
    run.log("decode_roofline", bound="memory", live_tokens=live,
            rows=rows, bytes=nbytes, least_ms=least * 1e3,
            median_ms=stats.median(durations) * 1e3, calls=len(durations))
    return 100.0 * least / stats.median(durations)
