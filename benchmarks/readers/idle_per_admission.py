"""The traced window's idle time over its admissions, in ms: the window
less the union of the device's op intervals (mean over the chips used),
over the sum of the traced step records' ``admitted``.  All of a closed
loop's idle lies around admissions, so this is what one costs the chip.
``None`` without a trace, where the program keeps no launch ledger, or
where the window admitted nothing."""
from benchmarks.lib import launches
from benchmarks.lib import xplane


def read(obs, args, run):
    got = launches.analysis(obs, run)
    trace = obs.get("trace")
    if not got or trace is None or "traced" not in got:
        return None
    admitted = sum(r["admitted"] for r in got["traced"])
    if not admitted:
        return None
    busy = xplane.busy_seconds(trace)
    t0, t1 = xplane.window_of(trace)
    return (t1 - t0 - sum(busy.values()) / len(busy)) * 1e3 / admitted
