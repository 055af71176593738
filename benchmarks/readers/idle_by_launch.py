"""Device idle in the traced window, in % of it, by what the host was
doing (``args.part``), from the program's own launch ledger
(``lib/launches.py``): every gap in device 0's busy time ends at a program
whose launch the step record names, and is cut at the start of that
launch's call: ``dispatch`` from there on; before it ``readback`` (under a
``prefill_readback`` or ``decode_readback`` phase), ``host`` (under any
other phase of a step) or ``caller`` (under no step); ``unmatched`` where
no launch is known.  The five metrics that share this reader add up to
``device.idle_share.serve``.  ``None`` where the program keeps no launch
ledger or its launches could not be laid on the device's programs."""
from benchmarks.lib import launches


def read(obs, args, run):
    got = launches.analysis(obs, run)
    if not got or "idle" not in got:
        return None
    return 100.0 * got["idle"][args["part"]] / got["traced_window_s"]
