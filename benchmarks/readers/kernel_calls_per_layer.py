"""Times the program ran its layer stack in one call of ``args.module``,
read from what ran: the events of the Mosaic kernel ``args.kernel`` (one
a layer a pass) inside the module's calls in the traced window, a call,
over the model's weight layers.  1 for a stack that runs once, ``passes``
for a looped one whether its passes are unrolled or one loop; a program
that skips a pass reads less.  Nothing where the kernel's events are
absent (the einsum path, or a program that has no such kernel)."""


def read(obs, args, run):
    calls, events, _ = run.registry.module(
        "readers", "decode_attn_roofline").kernel_events(obs, args)
    if not events:
        return None
    layers = obs["traced"]["model"]["layers"]
    run.log("kernel_calls_per_layer", calls=calls, events=events,
            layers=layers)
    return events / calls / layers
