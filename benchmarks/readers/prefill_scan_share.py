"""Share of the prefill program's device time spent in the chunked scan,
in %: over the calls of ``args.module`` in the trace, the time of the
events that carry a shape only the scan has, over the time of all their
events.  The selection rule (``is_scan``): an array whose last two
dimensions are the recurrent state's ``[head, state]`` (the per-chunk
states, the carried state) or ``[chunk, chunk]`` (the masked decay-weighted
product within a chunk), with the dimensions before them a whole number of
mixer heads.  K/V, the flash kernel's blocks and the projections carry no
such shape.  ``while`` events are left out of both sums: their bodies'
events are listed too."""
import bisect
import math

from benchmarks.lib import xplane


def is_scan(text, heads, head, state, chunk):
    for _, dims in xplane.shapes(text):
        if len(dims) >= 3 and dims[-2:] in ((head, state), (chunk, chunk)) \
                and math.prod(dims[:-2]) % heads == 0:
            return True
    return False


def read(obs, args, run):
    trace, cfg = obs["trace"], run.config
    if "mamba_n_heads" not in cfg:
        return None
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    calls = [m for m in trace.modules.get(dev, [])
             if xplane.module_name(m.name) == args["module"]
             and m.start >= t0 and m.end <= t1]
    if not calls:
        return None
    starts = [m.start for m in calls]
    scan = whole = 0.0
    for e in trace.ops[dev]:
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.end > calls[i].end or \
                xplane.opcode(e.name) == "while":
            continue
        whole += e.end - e.start
        if is_scan(e.name, cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"], cfg["mamba_chunk_size"]):
            scan += e.end - e.start
    run.log("prefill_scan_share", calls=len(calls), scan_seconds=scan,
            op_seconds=whole,
            module_seconds=sum(m.end - m.start for m in calls))
    return 100.0 * scan / whole if whole else None
