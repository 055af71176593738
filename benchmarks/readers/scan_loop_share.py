"""Share of the prefill program's device time spent in the Mamba-1 scan,
in %: over the calls of ``args.module`` in the trace, the time of the
events that run INSIDE a scan's loop over the time of all their events.  A
scan's loop is a ``while`` whose carried values hold the recurrent state,
a float32 array that ends in the family's ``counts.state_tail`` (``[..,
d_state, d_inner]``: nothing else in the program has that shape); an
instruction named after the Mosaic kernel ``args.kernel`` is the scan too
(``named``), which is what the program runs on the chip.  ``while`` events themselves are left out of both sums: their
bodies' events are listed too.  Nothing where the family's counts have no
``state_tail`` or the module no call."""
import bisect

from benchmarks.lib import xplane


def named(text, kernel):
    """An instruction named after ``kernel``: the Mosaic custom call, or
    the fusion the compiler wraps round it where the call's result is
    written on into a larger array (eight of the nine scans of a prefill
    are ``%selective_scan.<n> = ... fusion(...)``: chip run, PR 48)."""
    return kernel in text.partition(" = ")[0]


def holds_state(text, tail):
    return any(dtype == "f32" and dims[-len(tail):] == tail
               for dtype, dims in xplane.shapes(text))


def read(obs, args, run):
    trace = obs["trace"]
    counts = run.registry.module("counts", run.traffic["family"])
    if not hasattr(counts, "state_tail"):
        return None
    tail = tuple(counts.state_tail(run.config))
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    calls = [m for m in trace.modules.get(dev, [])
             if xplane.module_name(m.name) == args["module"]
             and m.start >= t0 and m.end <= t1]
    if not calls:
        return None
    starts = [m.start for m in calls]

    def inside(e):
        i = bisect.bisect_right(starts, e.start) - 1
        return i >= 0 and e.end <= calls[i].end
    events = [e for e in trace.ops[dev] if inside(e)]
    loops = sorted((e.start, e.end) for e in events
                   if xplane.opcode(e.name) == "while"
                   and holds_state(e.name, tail))
    begins = [a for a, _ in loops]
    scan = whole = 0.0
    for e in events:
        if xplane.opcode(e.name) == "while":
            continue
        whole += e.end - e.start
        i = bisect.bisect_right(begins, e.start) - 1
        if (i >= 0 and e.end <= loops[i][1]) or \
                named(e.name, args["kernel"]):
            scan += e.end - e.start
    run.log("scan_loop_share", calls=len(calls), loops=len(loops),
            scan_seconds=scan, op_seconds=whole,
            module_seconds=sum(m.end - m.start for m in calls))
    return 100.0 * scan / whole if whole else None
