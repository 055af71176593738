"""Items per second per chip from the median block: the steady step's
rate, which one stalled block does not move (``train_rate`` carries the
stall; ``step.stall_share`` is the gap between the two)."""
from benchmarks.lib import stats


def read(obs, args, run):
    w = obs["window"]
    if "block_s" not in w:
        return None
    return stats.block_rate(w["block_s"], w["items_per_block"], w["chips"])
