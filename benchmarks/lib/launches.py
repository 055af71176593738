"""Which program each of the serving engine's launches became on the chip,
and what the host was doing while the chip had nothing to run.

Since PR 40 a step record (``horovod_tpu/serving/tracing.py`` ``StepTrace``)
holds, beside the phases that tile the step, ``launches``
(``[n, program, call_start_us, call_end_us]``, one entry a dispatch of a
device program, where it is made; ``n`` runs on across steps) and ``reads``
(``[n, start_us, end_us]``: the read-back that returned launch ``n``'s
result, with the times of the readback phase entry it lies in).  The chip
runs launches in the order of their numbers, so the k-th launch of a traced
window IS the k-th run of one of those programs on device 0's ``XLA
Modules`` line: the two name sequences are laid side by side (``align``; a
pass in flight when the trace began may lead the device's list, and the
irregular places of the admissions make the alignment unique), and no
program is matched to the NEAREST dispatch, which is what broke
``lib/step_phases.launch_margins`` when PR 28 put a pass in flight across
the step boundary.  With the match, causality is exact: a program starts
after its own launch's call began and ends before its own read-back's end,
and ``step_phases.causal_shift`` places the host's times in the middle of
what that allows, as it did until PR 28.

Then every gap in device 0's busy time ends at a program whose launch is
known, and is cut in two at the start of that launch's call: from there on
the host was dispatching (``dispatch``), before it the host had not begun
to, and that part goes to the phase it lies under (``readback``: the chip
is done and the host still in ``device_get``; ``host``: any other phase of
a step) or to ``caller`` where no step was running.  ``unmatched`` is the
tracing's coverage.

Two readings need no trace.  ``starved``: the host KNOWS the chip is idle
from the end of a read-back that leaves no launch unread to the start of
the next launch's call; added up over the untraced window, ten times the
admissions a trace holds.  ``stalls``: the steps that ran 100 ms longer
than their like, and which phase, launch or read they sat in.

A program whose records have no ``launches`` (an older commit) gives
``None`` everywhere.  Pure functions first (the self-tests call them on
made-up records and a made-up trace), the run's own reading last.
"""

import bisect
import collections

from benchmarks.lib import stats
from benchmarks.lib import step_phases
from benchmarks.lib import xplane

PARTS = ("dispatch", "readback", "host", "caller", "unmatched")
READBACK = ("prefill_readback", "decode_readback")
CALLER = "caller"
MAX_LEAD = 4          # programs that may run before the first traced launch
MAX_TAIL = 4          # launches the trace may have ended before
STALL_MS = 100.0      # a prefill is 10-40 ms, the stalls seen 113-3,218 ms


def has_ledger(records):
    return bool(records) and all("launches" in r and "reads" in r
                                 for r in records)


def launches_of(records):
    return [lc for r in records for lc in r["launches"]]


def reads_of(records):
    return [rd for r in records for rd in r["reads"]]


def program_of(records):
    """{launch number: program} over ``records``."""
    return {n: program for n, program, _, _ in launches_of(records)}


def modules_of(trace):
    """Device 0's ``XLA Modules`` events by start, and their programs:
    ``jit__decode_jit(123)`` -> ``_decode_jit``."""
    if not trace.modules:
        return [], []
    events = sorted(trace.modules[sorted(trace.modules)[0]],
                    key=lambda e: e.start)
    return events, [xplane.module_name(e.name)[len("jit_"):] for e in events]


def align(launched, ran):
    """How the launches' programs ``launched`` lie on the programs ``ran``
    that the device shows, both in order: ``(lead, matched)`` with
    ``launched[:matched] == ran[lead:lead + matched]``, for the least
    ``lead`` (at most ``MAX_LEAD``) under which the two agree as far as
    the shorter goes; the launches may outrun the device's list by
    ``MAX_TAIL`` (the trace ended first).  Else ``(None, where)`` with the
    first place the two differ under the lead that agrees longest."""
    best = (-1, None)
    for lead in range(min(MAX_LEAD, len(ran)) + 1):
        m = min(len(launched), len(ran) - lead)
        i = next((i for i in range(m) if launched[i] != ran[lead + i]), m)
        if i == m and m and len(launched) - m <= MAX_TAIL:
            return lead, m
        if i > best[0]:
            best = (i, {"lead": lead, "at": i,
                        "launched": launched[i] if i < len(launched)
                        else None,
                        "ran": ran[lead + i] if lead + i < len(ran)
                        else None})
    return None, best[1]


def margins(pairs, reads, place):
    """(leads, lags) in seconds: for every matched launch its program's
    start less the start of its call, for every read-back of a matched
    launch its end less the program's end.  ``pairs``: {n: (call start us,
    event)}; ``place`` puts a host microsecond on the trace's clock."""
    leads = [ev.start - place(c0) for c0, ev in pairs.values()]
    lags = [place(end) - pairs[n][1].end for n, _, end in reads
            if n in pairs]
    return leads, lags


def _under(a, b, starts, rest):
    """{phase: length} of [a, b] under the placed phases, and what is left
    under none."""
    out, left = collections.Counter(), b - a
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(starts) and starts[i] < b:
        end, name = rest[i]
        part = min(end, b) - max(starts[i], a)
        if part > 0:
            out[name] += part
            left -= part
        i += 1
    return out, left


def idle_by_launch(trace, calls, placed):
    """({part: idle seconds}, {``<program>/<where>``: seconds}) of device
    0 inside the traced window.  ``calls``: {index of a module event of
    ``modules_of``: the start of its launch's call on the trace's clock};
    ``placed``: ``step_phases.place`` of the traced records.  A gap that
    ends at the first operation of a program with a known call is cut at
    the call's start: ``dispatch`` after it, and before it ``readback``,
    ``host`` or ``caller`` by the phase each piece lies under.  A gap that
    ends at no program (the window's end), at one with no known call, or
    that lies inside one program's extent is ``unmatched``.  The parts
    add up to the window less the busy time."""
    starts, rest = placed
    t0, t1 = xplane.window_of(trace)
    events, programs = modules_of(trace)
    begins = [e.start for e in events]
    dev = sorted(trace.ops)[0]
    busy = xplane.merge(xplane.clip(trace.ops[dev], t0, t1))
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    parts = dict.fromkeys(PARTS, 0.0)
    detail = collections.Counter()
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        j = bisect.bisect_right(begins, b) - 1
        inside = j >= 0 and events[j].start < a and b <= events[j].end
        if b >= t1 or j < 0 or inside or b > events[j].end or \
                j not in calls:
            parts["unmatched"] += b - a
            detail[("-" if j < 0 or b >= t1 else programs[j]) +
                   ("/inside" if inside else "/unmatched")] += b - a
            continue
        cut = min(max(calls[j], a), b)
        parts["dispatch"] += b - cut
        detail[programs[j] + "/dispatch"] += b - cut
        under, left = _under(a, cut, starts, rest)
        for name, part in under.items():
            parts["readback" if name in READBACK else "host"] += part
            detail[programs[j] + "/" + name] += part
        parts[CALLER] += left
        detail[programs[j] + "/" + CALLER] += left
    return parts, dict(detail)


def starved(records):
    """[(start_us, end_us)]: the stretches in which every launch made had
    been read back, from the end of the read-back that left none unread
    to the start of the next launch's call.  The chip runs launches in
    order, so it is certainly idle then; it may be idle longer (while the
    host is still in a read-back, or dispatching): a lower bound.  A
    launch nothing reads (a slot write) counts as in flight until a later
    launch is read."""
    launches, reads = launches_of(records), reads_of(records)
    if not launches:
        return []
    events = sorted([(end, 0, n) for n, _, end in reads] +
                    [(c0, 1, n) for n, _, c0, _ in launches])
    launched, read, since, out = launches[0][0] - 1, -1, None, []
    for t, is_launch, n in events:
        if is_launch:
            if since is not None:
                out.append((since, t))
                since = None
            launched = max(launched, n)
        else:
            read = max(read, n)
            if read >= launched and since is None:
                since = t
    return out


def span_us(records):
    return records[-1]["end_us"] - records[0]["start_us"]


def starved_summary(records):
    """Of ``records`` (in order): the starved share of their span in %,
    the starved milliseconds an admission, and the seconds by where they
    lay (phase name, or ``caller`` between two steps)."""
    starts, rest = step_phases.place(records, 0.0, 0.0)
    where = collections.Counter()
    for a, b in starved(records):
        under, left = _under(a / 1e6, b / 1e6, starts, rest)
        where.update(under)
        where[CALLER] += left
    total = sum(where.values())
    admitted = sum(r.get("admitted", 0) for r in records)
    return {"share": 100.0 * total / (span_us(records) / 1e6),
            "seconds": total, "admitted": admitted,
            "ms_per_admission": total * 1e3 / admitted if admitted else None,
            "where_s": {k: round(v, 6) for k, v in sorted(
                where.items(), key=lambda kv: -kv[1]) if v > 0}}


def typical_ms(records):
    """{``admitted``: median step ms} over ``records``."""
    by = collections.defaultdict(list)
    for r in records:
        by[r.get("admitted", 0)].append(step_phases.step_ms(r))
    return {k: stats.median(v) for k, v in by.items()}


def stalls(records, typical):
    """[(record, excess ms)]: the steps that ran more than ``STALL_MS``
    longer than ``typical`` says a step with as many admissions takes."""
    out = []
    for r in records:
        base = typical.get(r.get("admitted", 0))
        if base is not None and step_phases.step_ms(r) - base > STALL_MS:
            out.append((r, step_phases.step_ms(r) - base))
    return out


def describe_stall(record, excess_ms, programs):
    """Where a stalled step sat: its longest phase entry and the launch
    call or read-back inside it, with its program."""
    name, start, end = max(record["phases"], key=lambda p: p[2] - p[1])
    out = {"seq": record["seq"], "ms": round(step_phases.step_ms(record), 3),
           "excess_ms": round(excess_ms, 3),
           "admitted": record.get("admitted", 0),
           "phase": name, "phase_ms": round((end - start) / 1e3, 3)}
    reads = [rd for rd in record["reads"] if start <= rd[1] and rd[2] <= end]
    calls = [lc for lc in record["launches"]
             if start <= lc[2] and lc[3] <= end]
    if reads:
        out["read"] = programs.get(reads[-1][0])
    elif calls:
        n, program, c0, c1 = max(calls, key=lambda lc: lc[3] - lc[2])
        out["launch"], out["call_ms"] = program, round((c1 - c0) / 1e3, 3)
    return out, (start, end)


def busy_share(trace, a, b):
    """Share of [a, b] (trace seconds) in which device 0 ran something."""
    ops = trace.ops[sorted(trace.ops)[0]]
    return xplane.total(xplane.merge(xplane.clip(ops, a, b))) / (b - a)


# -- the run's own reading -----------------------------------------------------

def analysis(obs, run):
    """Everything the launch readers share, computed once a run and kept
    on ``obs``: ``window`` and ``traced`` (the records, where they carry
    the ledger), ``starved`` and ``stall_share`` of the untraced window,
    and for a traced run whose launches could be matched ``idle`` (seconds
    by part) and ``traced_window_s``.  Logs the ``starved``, ``stalls``,
    ``launch_join`` and ``idle_by_launch`` lines.  ``None`` where the
    program keeps no launch ledger."""
    if "launches" not in obs:
        obs["launches"] = _analyse(obs, run)
    return obs["launches"]


def _analyse(obs, run):
    got = step_phases.analysis(obs, run)
    out = {key: got[key] for key in ("window", "traced")
           if got and has_ledger(got.get(key))}
    if not out:
        return None
    trace = obs.get("trace")
    join = None
    if trace is not None and "traced" in out and got.get("traced_complete"):
        join = _join(obs, run, out["traced"], trace)
    if join is not None:
        place, placed, calls = join
        t0, t1 = xplane.window_of(trace)
        out["idle"], detail = idle_by_launch(trace, calls, placed)
        out["traced_window_s"] = t1 - t0
        run.log("idle_by_launch", window_s=t1 - t0,
                seconds={k: round(v, 6) for k, v in out["idle"].items()},
                by_program_and_place={k: round(v, 6) for k, v in sorted(
                    detail.items(), key=lambda kv: -kv[1])[:24]})
    line = {key: starved_summary(out[key]) for key in ("window", "traced")
            if key in out}
    if trace is not None and "traced" in line:
        line["traced"]["idle_share"] = 100.0 * (
            1.0 - busy_share(trace, *xplane.window_of(trace)))
    run.log("starved", **line)
    if "window" in out:
        out["starved"] = line["window"]["share"]
        out["stall_share"] = _stalls(run, out, trace, join)
    return out


def _join(obs, run, records, trace):
    """(place, placed phases, {module index: call start}) for the traced
    window, or ``None`` with the reason on the ``launch_join`` line."""
    base = step_phases.program_records()[1]
    joined = step_phases.clock_join(trace.span(step_phases.STEP_SPAN),
                                    obs["traced"]["steps"])
    launches = launches_of(records)
    events, programs = modules_of(trace)
    known = {lc[1] for lc in launches}
    index = [j for j, p in enumerate(programs) if p in known]
    line = {"launches": len(launches), "programs": len(index)}
    if joined is None or joined[1] > step_phases.MAX_JOIN_SPREAD_S:
        run.log("launch_join", ok=False, why="step spans", **line)
        return None
    lead, m = align([lc[1] for lc in launches], [programs[j] for j in index])
    if lead is None:
        where = dict(m, launch=launches[m["at"]][:2]
                     if m["at"] < len(launches) else None)
        run.log("launch_join", ok=False, why="order", differ=where, **line)
        return None
    offset = joined[0]
    matched = list(zip(launches, index[lead:lead + m]))
    pairs = {lc[0]: (lc[2], events[j]) for lc, j in matched}
    leads, lags = margins(pairs, reads_of(records),
                          lambda us: base + us / 1e6 + offset)
    shift = step_phases.causal_shift(min(leads), min(lags)) \
        if lags else None
    run.log("launch_join", ok=shift is not None, lead=lead, matched=m,
            tail=len(launches) - m, offset_s=offset,
            residual_spread_ms=joined[1] * 1e3,
            lead_ms={"min": min(leads) * 1e3,
                     "p50": stats.median(leads) * 1e3},
            lag_ms={"min": min(lags) * 1e3, "p50": stats.median(lags) * 1e3}
            if lags else None,
            phases_moved_ms=None if shift is None else shift * 1e3,
            # what may lie on the other side of a call's start or a
            # read-back's end
            uncertain_ms=None if shift is None
            else (min(leads) + min(lags)) / 2 * 1e3, **line)
    if shift is None:
        return None

    def place(us):
        return base + us / 1e6 + offset + shift
    calls = {j: place(lc[2]) for lc, j in matched}
    return place, step_phases.place(records, base, offset + shift), calls


def _stalls(run, out, trace, join):
    """The untraced window's stall share in %, and the ``stalls`` line:
    each stalled step of either window, where it sat, and for one the
    trace holds whether the chip was busy meanwhile."""
    window = out["window"]
    typical = typical_ms(window)
    found = stalls(window, typical)
    lines = {"window": [describe_stall(r, x, program_of(window))[0]
                        for r, x in found]}
    if "traced" in out:
        programs = program_of(window + out["traced"])
        lines["traced"] = []
        for r, x in stalls(out["traced"], typical):
            told, (start, end) = describe_stall(r, x, programs)
            if join is not None:
                told["chip_busy_share"] = round(busy_share(
                    trace, join[0](start), join[0](end)), 4)
            lines["traced"].append(told)
    share = 100.0 * sum(x for _, x in found) / (span_us(window) / 1e3)
    run.log("stalls", over_ms=STALL_MS, share=share,
            typical_ms={str(k): round(v, 3)
                        for k, v in sorted(typical.items())}, **lines)
    return share
