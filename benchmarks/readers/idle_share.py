"""1 - union of device op intervals over the traced window, in %, mean
over the chips used."""
from benchmarks.lib import xplane


def read(obs, args, run):
    trace = obs["trace"]
    busy = xplane.busy_seconds(trace)
    t0, t1 = xplane.window_of(trace)
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / (t1 - t0))
