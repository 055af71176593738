"""The benchmark's own arithmetic: percentiles, block rates, spreads.

Kept free of JAX so that the self-tests run it in microseconds.
"""

import math
import statistics


def percentile(values, q):
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), of a non-empty sequence."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return statistics.median(values)


def total_rate(blocks, items_per_block, window_seconds, chips):
    """Items per second per chip over ALL the work and ALL the time of the
    window: the end-to-end rate.  A stall inside the window lowers it."""
    return blocks * items_per_block / window_seconds / chips


def block_rate(block_seconds, items_per_block, chips):
    """Items per second per chip from the MEDIAN block: one stalled block
    moves one reading and not this rate.  A per-layer statistic beside the
    total rate, never in its place."""
    return items_per_block / median(block_seconds) / chips


def stall_share(block_seconds, window_seconds):
    """Share of the window (0..1) that the median block does not account
    for: 1 - blocks x median block / window; what the total rate lost
    against the median-block rate."""
    return 1.0 - len(block_seconds) * median(block_seconds) / window_seconds


def tpot_seconds(first_token_ts, last_token_ts, tokens):
    """Time per output token after the first: (last - first)/(tokens - 1);
    None for a single-token answer."""
    if tokens < 2:
        return None
    return (last_token_ts - first_token_ts) / (tokens - 1)


def quartile_spread(values):
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``), the contract's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def histogram(values, edges):
    """Counts of ``values`` in [edges[i], edges[i+1]); the last bin is
    closed on the right."""
    counts = [0] * (len(edges) - 1)
    for v in values:
        for i in range(len(counts)):
            last = i == len(counts) - 1
            if edges[i] <= v < edges[i + 1] or (last and v == edges[-1]):
                counts[i] += 1
                break
    return counts


def worst_leaf_gap(got, want, prefixes=None):
    """Largest gap between two {leaf: norm} maps: |got - want| measured
    against the reference's norm of that leaf or of its median leaf (over
    all leaves), whichever is larger (some gradients are all but zero).
    ``prefixes`` keeps the search to leaves whose name starts with one of
    them.  Returns (gap, leaf)."""
    if set(got) != set(want):
        raise ValueError(f"leaf sets differ: {sorted(set(got) ^ set(want))}")
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for name, ref in want.items():
        if prefixes and not name.startswith(tuple(prefixes)):
            continue
        gap = abs(got[name] - ref) / max(ref, floor, 1e-30)
        if not math.isfinite(gap):
            return math.inf, name
        if gap >= worst:
            worst, where = gap, name
    return worst, where
