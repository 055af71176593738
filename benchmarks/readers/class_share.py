"""Device time of some op classes over device busy time, in %."""
from benchmarks.lib import xplane


def read(obs, args, run):
    trace = obs["trace"]
    classes = xplane.class_seconds(trace)
    busy = xplane.busy_seconds(trace)
    mean_busy = sum(busy.values()) / len(busy)
    run.log("op_classes", seconds={k: round(v, 6)
                                   for k, v in classes.items()})
    return 100.0 * sum(classes.get(c, 0.0) for c in args["classes"]) \
        / mean_busy
