"""Device idle in the traced window, in % of it, while the program was in
one of the phases ``args.phases`` of its own step records
(``unattributed``: under no phase).  The metrics that share this reader
split ``idle_share``'s number.  ``None`` where the program keeps no
step records or the two clocks could not be joined."""
from benchmarks.lib import step_phases


def read(obs, args, run):
    got = step_phases.analysis(obs, run)
    if not got or "idle" not in got:
        return None
    return 100.0 * sum(got["idle"].get(p, 0.0) for p in args["phases"]) \
        / got["traced_window_s"]
