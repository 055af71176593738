"""``expert_roofline`` for a family whose configuration names its experts
otherwise, and for the prefill programs: the least time the chip could take
to read the experts' weights that the calls of ``args.module`` in the
traced window had to read, over the time of the device events inside them
that read a STACK of experts (``expert_roofline.stack_events``, selected by
the operand's shape: ``counts.stack_shapes`` of the family), in %.

What a call had to read: with ``args.count``, the step records' count of
touched (layer, expert) pairs, as ``expert_roofline`` takes it (a decode
pass); with ``args.prompts`` instead, ONE read of the experts that a
prompt of the traced window's mean length is expected to touch in every
expert layer (``counts.expected_touched`` at the records' ``prompt_tokens
/ admitted``: every expert, from a few hundred tokens on), whichever
product computes it: the Mosaic kernel, or ``jax.lax.ragged_dot`` where a
prompt's assignments pass the kernel's rows.

Nothing where the program keeps no such records (an older commit), the
family's counts name no stacks, or no event reads one."""
from benchmarks.lib import step_phases


def read(obs, args, run):
    counts = run.registry.module("counts", run.traffic["family"])
    events_of = run.registry.module("readers", "expert_roofline").stack_events
    got = step_phases.analysis(obs, run)
    records = (got or {}).get("traced", [])
    if not records or not hasattr(counts, "stack_shapes"):
        return None
    if "prompts" in args:
        admitted = sum(r.get("admitted", 0) for r in records)
        if not admitted:
            return None
        tokens = sum(r.get("prompt_tokens", 0) for r in records) / admitted
        layers = counts.expert_layers(run.config,
                                      obs["traced"]["model"]["layers"])
        touched = layers * counts.expected_touched(run.config, tokens)
        said = {"prompt_tokens": tokens}
    else:
        seen = [r[args["count"]] for r in records if args["count"] in r]
        if not seen:
            return None
        touched, said = sum(seen) / len(seen), {"records": len(seen)}
    calls, events, took = events_of(obs, args,
                                    counts.stack_shapes(run.config))
    if not events:
        return None
    need = counts.expert_bytes(run.config, touched)
    least = need / run.peaks["hbm_bytes_per_s"]
    run.log("stack_roofline", module=args["module"], bound="memory",
            calls=calls, events=events, experts_touched=touched,
            bytes_per_call=need, least_ms=least * 1e3,
            events_ms_per_call=took / calls * 1e3, **said)
    return 100.0 * least * calls / took
