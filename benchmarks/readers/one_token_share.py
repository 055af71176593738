"""Share of a prefill program's device time that its ONE-TOKEN layers
take, in %: a prefill of two token extents (``horovod_tpu/models/
sambay.py``) runs its self-decoder over the padded prompt and its
cross-decoder over the last real token alone, so over the calls of
``args.module`` in the trace this is the time of the cross-decoder's
events over the time of all their events.

The trace names an event by its instruction's text and not by its scope,
so the rule is by shape, a call at a time: the call's padded length ``s``
is the extent that most of its ``[1, s, hidden]`` arrays have; an event is
the SELF-decoder's if one of its arrays has ``s`` among its dimensions
before the last, or if it runs inside a ``while`` (the scan's loop, whose
steps carry one position each); the head's events carry the vocabulary.
Every other event is the cross-decoder's, and so is the Mosaic kernel
``args.kernel`` (the cross layers' attention reads all ``s`` keys of the
one plane).  The last position's gathers, a few microseconds, fall to the
cross-decoder.  ``while`` events are left out of both sums: their bodies'
events are listed too.  Nothing where the configuration has no
``mb_per_layer`` or the module no call."""
import bisect
import collections

from benchmarks.lib import xplane


def padded_length(events, hidden):
    """The extent most ``[1, s, hidden]`` arrays of ``events`` have."""
    seen = collections.Counter()
    for e in events:
        for _, dims in xplane.shapes(e.name):
            if len(dims) == 3 and dims[0] == 1 and dims[1] > 1 \
                    and dims[2] == hidden:
                seen[dims[1]] += 1
    return seen.most_common(1)[0][0] if seen else None


def carries(text, extent):
    """Whether an array of the event has ``extent`` among its dimensions
    before the last."""
    return any(extent in dims[:-1] for _, dims in xplane.shapes(text))


def has_dimension(text, extent):
    return any(extent in dims for _, dims in xplane.shapes(text))


def read(obs, args, run):
    trace, cfg = obs["trace"], run.config
    if "mb_per_layer" not in cfg:
        return None
    named = run.registry.module("readers", "scan_loop_share").named
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    calls = [m for m in trace.modules.get(dev, [])
             if xplane.module_name(m.name) == args["module"]
             and m.start >= t0 and m.end <= t1]
    if not calls:
        return None
    starts = [m.start for m in calls]
    by_call = collections.defaultdict(list)
    for e in trace.ops[dev]:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= calls[i].end:
            by_call[i].append(e)
    one = whole = 0.0
    for events in by_call.values():
        s = padded_length(events, cfg["hidden_size"])
        loops = [(e.start, e.end) for e in events
                 if xplane.opcode(e.name) == "while"]
        for e in events:
            if xplane.opcode(e.name) == "while":
                continue
            took = e.end - e.start
            whole += took
            looped = any(a <= e.start and e.end <= b for a, b in loops)
            if named(e.name, args["kernel"]) or (
                    s is not None and not looped and
                    not carries(e.name, s) and
                    not has_dimension(e.name, cfg["vocab_size"])):
                one += took
    run.log("one_token_share", calls=len(calls), one_token_seconds=one,
            op_seconds=whole)
    return 100.0 * one / whole if whole else None
