"""Median, in ms, of a list of host-clock seconds the window recorded."""
from benchmarks.lib import stats


def read(obs, args, run):
    values = obs["window"].get(args["field"])
    return stats.median(values) * 1e3 if values else None
