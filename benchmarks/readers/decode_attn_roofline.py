"""Roofline share of decode attention in the decode step, in %: the least
time the chip could take to read K and V of the live tokens in every plane
(``counts.decode_attention_bytes`` at the traced window's mean live
tokens, over the HBM peak, a call) over the time of the Mosaic kernel
``args.kernel``'s events inside ``args.module``, a call.  Memory-bound.

Nothing where the kernel's events are absent (the einsum path, or a
program that has no such kernel) or the family's counts have no
``decode_attention_bytes``.  The kernel streams whole blocks of each row,
so it reads more than the live tokens and the share stays under 100%."""
import bisect

from benchmarks.lib import xplane


def is_kernel(text, kernel):
    """A Mosaic custom call whose instruction is named after ``kernel``
    (``%decode_attention.7 = ... custom-call(...)``)."""
    return xplane.op_class(text) == "mosaic" and \
        kernel in text.partition(" = ")[0]


def kernel_events(obs, args):
    """(calls of ``args.module`` inside the traced window, events of the
    kernel ``args.kernel`` inside them, the events' summed time)."""
    trace = obs["trace"]
    t0, t1 = xplane.window_of(trace)
    dev = sorted(trace.ops)[0]
    calls = [m for m in trace.modules.get(dev, [])
             if xplane.module_name(m.name) == args["module"]
             and m.start >= t0 and m.end <= t1]
    starts = [m.start for m in calls]
    took, events = 0.0, 0
    for e in trace.ops[dev]:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= calls[i].end and \
                is_kernel(e.name, args["kernel"]):
            took += e.end - e.start
            events += 1
    return len(calls), events, took


def read(obs, args, run):
    steps = obs["traced"].get("steps")
    counts = run.registry.module("counts", run.traffic["family"])
    if not steps or not hasattr(counts, "decode_attention_bytes"):
        return None
    calls, events, took = kernel_events(obs, args)
    if not events:
        return None
    layers = obs["traced"]["model"]["layers"]
    live = sum(s[5] for s in steps) / len(steps)
    need = counts.decode_attention_bytes(run.config, layers, live)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("decode_attn_roofline", bound="memory", calls=calls,
            events=events, live_tokens=live, bytes_per_call=need,
            least_ms=least * 1e3, kernel_ms_per_call=took / calls * 1e3)
    return 100.0 * least * calls / took
