"""What stalled steps cost the untraced window, in % of it: a step is
stalled when it ran more than 100 ms longer than the median of the
window's steps with as many admissions, and the excesses are added up
(``lib/launches.stalls``; the ``stalls`` line says where each sat).  0
for a window without one; ``None`` where the program keeps no launch
ledger."""
from benchmarks.lib import launches


def read(obs, args, run):
    got = launches.analysis(obs, run)
    return (got or {}).get("stall_share")
