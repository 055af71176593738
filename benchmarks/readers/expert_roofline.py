"""Roofline share of the routed experts' grouped products in the decode
step, in %: the least time the chip could take to read the weights of the
experts that the traced window's decode passes were routed to
(``counts.expert_bytes`` of the mean of the step records' ``args.count``,
over the HBM peak, a call) over the time of the device events inside
``args.module`` that read a STACK of experts: an operand of the shape
``[experts, hidden, width]`` or ``[experts, width, hidden]`` of the
configuration.  Memory-bound at decode.

Selected by shape, so it reads the same work whether ``jax.lax.ragged_dot``
(on this chip a custom call of XLA's own) or a Pallas kernel computes the
grouped product, and a copy that re-lays a stack before it is counted with
it.  Nothing where the program keeps no such count (an older commit), the
family's counts have no ``expert_bytes``, or no event reads a stack."""
import bisect

from benchmarks.lib import step_phases
from benchmarks.lib import xplane


def stack_shapes(cfg):
    e, d = cfg["n_routed_experts"], cfg["hidden_size"]
    f = cfg["moe_intermediate_size"]
    return {(e, d, f), (e, f, d)}


def reads_a_stack(text, stacks):
    """An executed instruction with a whole stack among its operands (its
    text after the opcode), and no loop that merely carries one."""
    if xplane.opcode(text) == "while":
        return False
    operands = text.partition(" = ")[2].partition("(")[2]
    return any(dims in stacks for _, dims in xplane.shapes(operands))


def stack_events(obs, args, stacks):
    """(calls of ``args.module`` inside the traced window, events in them
    that read a stack, their summed time)."""
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
                reads_a_stack(e.name, stacks):
            took += e.end - e.start
            events += 1
    return len(calls), events, took


def read(obs, args, run):
    counts = run.registry.module("counts", run.traffic["family"])
    got = step_phases.analysis(obs, run)
    touched = [r[args["count"]] for r in (got or {}).get("traced", [])
               if args["count"] in r]
    if not touched or not hasattr(counts, "expert_bytes"):
        return None
    calls, events, took = stack_events(obs, args, stack_shapes(run.config))
    if not events:
        return None
    mean = sum(touched) / len(touched)
    need = counts.expert_bytes(run.config, mean)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("expert_roofline", bound="memory", calls=calls, events=events,
            experts_touched=mean, records=len(touched),
            bytes_per_call=need, least_ms=least * 1e3,
            events_ms_per_call=took / calls * 1e3)
    return 100.0 * least * calls / took
