"""``state_update_roofline`` for a recurrent state whose shape the family's
counts give (``counts.state_tail``: the last dimensions of one row's state
in one layer, as the program holds it): the least time the chip could take
to read and write the state of the rows that decode
(``counts.state_update_bytes`` over the HBM peak, a call) over the time of
the device events inside ``args.module`` that read or write a float32
array ending in that tail with a slots dimension before it (a layer's slab
of every slot, or the stacked array of which an event touches one layer's
slab), in %.  Memory-bound.

As there, the events' own shapes are held to the count first (each
appearance of the shape in an event, result or operand, is one slab
moved): where they move less than the update needs the reader says so and
reports nothing.  Nothing either where the family's counts have no
``state_tail``."""
import math

from benchmarks.lib import xplane


def slabs(text, tail):
    """Bytes of the state-shaped float32 arrays in an event's text, the
    leading (layer) dimension of a stacked array left out."""
    total = 0
    for dtype, dims in xplane.shapes(text):
        if dtype == "f32" and len(dims) > len(tail) and \
                dims[-len(tail):] == tail:
            total += 4 * dims[-len(tail) - 1] * math.prod(tail)
    return total


def read(obs, args, run):
    trace, steps = obs["trace"], obs["traced"].get("steps")
    counts = run.registry.module("counts", run.traffic["family"])
    if not steps or not hasattr(counts, "state_tail"):
        return None
    tail = tuple(counts.state_tail(run.config))
    inside = run.registry.module("readers", "state_update_roofline").inside
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
    need = counts.state_update_bytes(run.config, layers, rows) * len(calls)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("slab_update_roofline", calls=len(calls), events=events,
            rows=rows, need_bytes=need, events_move_bytes=moved,
            event_seconds=took, least_seconds=least)
    if not events or moved < need:
        return None
    return 100.0 * least / took
