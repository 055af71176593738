"""Share of the untraced window, in %, in which the host KNEW the chip had
nothing queued: every launch the engine had made was read back and the
next was not yet made (``lib/launches.starved``), from the program's own
launch ledger, with no profiler.  A lower bound of the device's idle
share.  ``None`` where the program keeps no launch ledger."""
from benchmarks.lib import launches


def read(obs, args, run):
    got = launches.analysis(obs, run)
    return (got or {}).get("starved")
