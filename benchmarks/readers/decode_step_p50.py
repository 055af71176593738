"""Median host time, in ms, of an ``engine.step()`` that admitted no
prefill: one fused decode over every slot and its one readback."""
from benchmarks.lib import stats


def read(obs, args, run):
    steps = obs["window"].get("steps")
    if not isinstance(steps, list):
        return None
    pure = [s[1] - s[0] for s in steps if s[2] == 0]
    run.log("decode_steps", pure=len(pure), with_prefill=len(steps) - len(pure))
    return stats.median(pure) * 1e3 if pure else None
