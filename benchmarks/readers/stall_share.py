"""What the median block leaves out: 1 - blocks x median / window, in %:
the share of ``train_rate`` that stalls cost, measured against
``step.block_rate_p50``."""
from benchmarks.lib import stats


def read(obs, args, run):
    w = obs["window"]
    if "block_s" not in w:
        return None
    return 100.0 * stats.stall_share(w["block_s"], w["window_s"])
