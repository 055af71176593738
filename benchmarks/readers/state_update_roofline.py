"""Roofline share of the recurrent state's update in the decode step, in
%: the least time the chip could take to read and write the state of the
rows that decode (``counts.state_update_bytes`` over the HBM peak, per
call) over the time of the device events inside ``args.module`` that read
or write an array of the state's shape (float32 ``[.., heads, head,
state]``: a layer's slab of every slot, or the stacked array of which an
event touches one layer's slab).  Memory-bound.

A state that the compiler fused away or kept elsewhere would leave events
that move fewer bytes than the update needs, and the share would read
over 100%: the events' own shapes are held to the count first (each
appearance of the shape in an event, result or operand, is one slab
moved), and where they move less the reader says so and reports nothing.
Nothing either where the family's count has no ``state_update_bytes``."""
import bisect

from benchmarks.lib import xplane


def slabs(text, tail):
    """Bytes of the state-shaped float32 arrays in an event's text, the
    leading (layer) dimension of a stacked array left out."""
    total = 0
    for dtype, dims in xplane.shapes(text):
        if dtype == "f32" and len(dims) >= 4 and dims[-3:] == tail:
            slots = dims[-4]
            total += 4 * slots * tail[0] * tail[1] * tail[2]
    return total


def inside(calls, starts, ev):
    i = bisect.bisect_right(starts, ev.start) - 1
    return i >= 0 and ev.end <= calls[i].end


def read(obs, args, run):
    trace, steps = obs["trace"], obs["traced"].get("steps")
    counts = run.registry.module("counts", run.traffic["family"])
    if not steps or not hasattr(counts, "state_update_bytes"):
        return None
    cfg = run.config
    tail = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"])
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    calls = [m for m in trace.modules.get(dev, [])
             if xplane.module_name(m.name) == args["module"]
             and m.start >= t0 and m.end <= t1]
    if not calls:
        return None
    starts = [m.start for m in calls]
    took = moved = 0.0
    events = 0
    for e in trace.ops[dev]:
        if xplane.opcode(e.name) == "while" or not inside(calls, starts, e):
            continue
        nbytes = slabs(e.name, tail)
        if nbytes:
            took += e.end - e.start
            moved += nbytes
            events += 1
    layers = obs["traced"]["model"]["layers"]
    rows = sum(s[4] for s in steps) / len(steps)
    need = counts.state_update_bytes(cfg, layers, rows) * len(calls)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("state_update_roofline", calls=len(calls), events=events,
            rows=rows, need_bytes=need, events_move_bytes=moved,
            event_seconds=took, least_seconds=least)
    if not events or moved < need:
        return None
    return 100.0 * least / took
