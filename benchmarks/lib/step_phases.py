"""What the serving engine's own step records say of a window, and where
their phases lie on the device's timeline.

The program keeps one record per ``ServeEngine.step()`` in its tracer's
step ring (``horovod_tpu/serving/tracing.py`` ``StepTrace``): ``seq``,
``start_us``, ``end_us``, ``phases`` (``[name, start_us, end_us]``, tiling
the step) and counts, on the tracer's clock, for which
``clock.base + ts_us / 1e6`` is ``time.monotonic`` - the clock the serving
generator stamps its ``steps`` with.  So a window's records are those that
lie inside its steps' stamps, one each.

The profiler's clock is another.  The harness's ``bench.engine.step``
spans are the same steps on it: the k-th span and the k-th stamped step
differ by the clocks' offset and a few microseconds of Python, so the
median difference of their middles is the offset and the residuals say
how far to trust it.  The device's events are on a third clock, which the
profiler has laid on its own only to about a millisecond (decode programs
seem to start 0.8-1 ms before their dispatch began): the phases are moved
to the middle of where every program follows its dispatch and precedes its
readback's end (``causal_shift``; the interval is logged, and half its
width is the error of the split between dispatch and readback).  So every
phase gets a place in the trace, and every gap in the device's busy time
is cut at the phases' boundaries and charged, piece by piece, to the phase
each piece lies in.  (Charged whole to the phase at its middle, a decode
step's ONE gap - from the end of its program to the start of the next -
would go to a single phase.)

A program that keeps no step records (an older commit, or
``HVD_SERVE_TRACE=0``) gives ``None`` everywhere: the readers then report
nothing.  Pure functions first (the self-tests call them on made-up
records), the run's own reading last.
"""

import bisect

from benchmarks.lib import stats
from benchmarks.lib import xplane

# must equal horovod_tpu.serving.tracing.STEP_PHASES (a test holds it to)
PHASES = ("control", "admit", "prefill", "prefill_readback",
          "decode_prepare", "decode_dispatch", "decode_readback",
          "bookkeeping", "telemetry")
UNATTRIBUTED = "unattributed"
STEP_SPAN = "engine.step"
DECODE_MODULE = "jit__decode_jit"
SLACK_S = 5e-6          # ts_us is cut to whole microseconds
MAX_JOIN_SPREAD_S = 1e-3


def select(records, base, steps, slack=SLACK_S):
    """The record of each stamped step ``(t0, t1, ...)``, in order; ``None``
    where the ring no longer holds it.  Both lists are in time order."""
    out, i = [], 0
    for step in steps:
        t0, t1 = step[0], step[1]
        while i < len(records) and \
                base + records[i]["start_us"] / 1e6 < t0 - slack:
            i += 1
        if i < len(records) and \
                base + records[i]["end_us"] / 1e6 <= t1 + slack:
            out.append(records[i])
            i += 1
        else:
            out.append(None)
    return out


def phase_ms(record, names):
    """Milliseconds of ``record`` under the phases ``names``."""
    return sum(e - s for n, s, e in record["phases"] if n in names) / 1e3


def step_ms(record):
    return (record["end_us"] - record["start_us"]) / 1e3


def decode_only(records):
    """Steps that admitted nothing: no ``prefill`` phase."""
    return [r for r in records
            if not any(p[0] == "prefill" for p in r["phases"])]


def prefill_ms(records):
    """One number per admitted request: its ``prefill`` and the
    ``prefill_readback`` that follows it."""
    out = []
    for r in records:
        for name, s, e in r["phases"]:
            if name == "prefill":
                out.append((e - s) / 1e3)
            elif name == "prefill_readback" and out:
                out[-1] += (e - s) / 1e3
    return out


def summary(records):
    """Per phase the median and 90th percentile of its milliseconds a
    step, over the steps it occurs in, and the window's counts."""
    phases = {}
    for name in PHASES:
        ms = [phase_ms(r, (name,)) for r in records
              if any(p[0] == name for p in r["phases"])]
        if ms:
            phases[name] = {"steps": len(ms),
                            "p50": round(stats.median(ms), 4),
                            "p90": round(stats.percentile(ms, 90), 4)}
    whole = [step_ms(r) for r in records]
    out = {"steps": len(records), "decode_only": len(decode_only(records)),
           "phase_ms": phases}
    if whole:
        out["step_ms"] = {"p50": round(stats.median(whole), 4),
                          "p90": round(stats.percentile(whole, 90), 4)}
    for key in ("admitted", "active", "retired", "cohorts",
                "prompt_tokens"):
        out[key] = sum(r.get(key, 0) for r in records)
    return out


def clock_join(spans, steps):
    """(offset, spread) in seconds between the profiler's clock and the
    stamps': the median over k of middle(k-th span) - middle(k-th step),
    and the distance between the 5th and 95th percentile of the residuals.
    ``None`` if the two do not count the same steps."""
    if not spans or len(spans) != len(steps):
        return None
    diffs = [(s.start + s.end) / 2 - (st[0] + st[1]) / 2
             for s, st in zip(spans, steps)]
    offset = stats.median(diffs)
    spread = stats.percentile(diffs, 95) - stats.percentile(diffs, 5)
    return offset, spread


def place(records, base, offset):
    """Every phase of ``records`` on the trace's timeline, in order:
    ([start seconds], [(end seconds, name)])."""
    starts, rest = [], []
    for r in records:
        for name, s, e in r["phases"]:
            starts.append(base + s / 1e6 + offset)
            rest.append((base + e / 1e6 + offset, name))
    return starts, rest


def idle_by_phase(trace, placed):
    """{phase or ``UNATTRIBUTED``: idle seconds} inside the traced window,
    mean over the chips used.  A gap in a chip's busy time is cut at the
    phases' boundaries and each piece goes to the phase it lies in; what
    lies under no phase (between two steps, outside them all) is
    ``UNATTRIBUTED``.  The values add up to the window less the busy
    time, the numerator of ``idle_share``."""
    starts, rest = placed
    t0, t1 = xplane.window_of(trace)
    out = {}
    for dev in trace.ops:
        busy = xplane.merge(xplane.clip(trace.ops[dev], t0, t1))
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            left = b - a
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(starts) and starts[i] < b:
                end, name = rest[i]
                part = min(end, b) - max(starts[i], a)
                if part > 0:
                    out[name] = out.get(name, 0.0) + part / len(trace.ops)
                    left -= part
                i += 1
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + \
                left / len(trace.ops)
    return out


def _nearest(sorted_times, t):
    i = bisect.bisect_left(sorted_times, t)
    near = sorted_times[max(i - 1, 0):i + 1]
    return min(near, key=lambda x: abs(x - t))


def launch_margins(trace, placed):
    """How the device's clock sits against the host's, from causality:
    a decode program cannot start before its ``decode_dispatch`` began nor
    end after its ``decode_readback`` ended.  Returns the least of
    (program start - nearest dispatch start) and of (nearest readback end
    - program end) in seconds over the window's decode programs; a
    negative one says the two clocks are that far apart."""
    starts, rest = placed
    dispatch = [s for s, (_, n) in zip(starts, rest)
                if n == "decode_dispatch"]
    readback = [e for e, n in rest if n == "decode_readback"]
    if not dispatch or not readback:
        return None, None
    lead, lag = [], []
    for dev in sorted(trace.modules)[:1]:
        for ev in trace.modules[dev]:
            if xplane.module_name(ev.name) == DECODE_MODULE and \
                    dispatch[0] <= ev.end and ev.start <= readback[-1]:
                lead.append(ev.start - _nearest(dispatch, ev.start))
                lag.append(_nearest(readback, ev.end) - ev.end)
    return (min(lead) if lead else None, min(lag) if lag else None)


def causal_shift(lead, lag):
    """The shift (seconds, added to the host phases' places) that puts
    them in the middle of where causality allows: any shift in
    [-lag, lead] lets every decode program start after its dispatch began
    and end before its readback ended, and the trace cannot tell which is
    true.  The middle errs by at most half the interval's width whichever
    it is, and does not depend on how the profiler laid the device's clock
    on the host's (``lead + lag`` does not).  ``None`` where no shift is
    causal.  On the v5e, trivial round trips in the same profile put the
    truth inside the interval and within 0.3 ms of its middle, in two
    sessions whose intervals lay 0.9 ms apart (PERF.md §6, PR 24)."""
    if lead is None or lag is None:
        return 0.0
    if lead + lag < 0:
        return None
    return (lead - lag) / 2


# -- the run's own reading -----------------------------------------------------

def program_records():
    """(records, clock base) from the program's tracer, or ``None`` where
    the program keeps no step records."""
    from horovod_tpu.utils import tracing as hvd_tracing
    tracer = hvd_tracing.get_tracer()
    steps = getattr(tracer, "steps", None)
    if steps is None:
        return None
    return steps(), tracer.clock.base


def analysis(obs, run):
    """Everything the step-phase readers share, computed once a run and
    kept on ``obs``: the records of the untraced window (``window``) and
    of the traced one (``traced``), and for the traced one the idle
    seconds by phase (``idle``) when the clocks could be joined.  Logs the
    ``step_phases`` and ``step_clock_join`` lines.  ``None`` if the
    program keeps no step records."""
    if "step_phases" not in obs:
        obs["step_phases"] = _analyse(obs, run)
    return obs["step_phases"]


def _analyse(obs, run):
    src = program_records()
    if src is None or not src[0]:
        return None
    records, base = src
    out, line = {}, {}
    for key in ("window", "traced"):
        steps = (obs.get(key) or {}).get("steps")
        if not isinstance(steps, list) or not steps:
            continue
        got = select(records, base, steps)
        held = [r for r in got if r is not None]
        out[key] = held
        out[key + "_complete"] = len(held) == len(steps)
        line[key] = dict(summary(held), window_steps=len(steps),
                         held=len(held))
    if not line:
        return None
    run.log("step_phases", ring=len(records), **line)
    trace = obs.get("trace")
    if trace is not None and out.get("traced_complete"):
        joined = clock_join(trace.span(STEP_SPAN), obs["traced"]["steps"])
        if joined is None:
            run.log("step_clock_join", ok=False,
                    spans=len(trace.span(STEP_SPAN)),
                    steps=len(obs["traced"]["steps"]))
        else:
            offset, spread = joined
            lead, lag = launch_margins(
                trace, place(out["traced"], base, offset))
            shift = causal_shift(lead, lag)
            ok = spread <= MAX_JOIN_SPREAD_S and shift is not None
            run.log("step_clock_join", ok=ok, offset_s=offset,
                    residual_spread_ms=spread * 1e3,
                    steps=len(out["traced"]),
                    launch_to_start_ms_min=None if lead is None
                    else lead * 1e3,
                    end_to_wake_ms_min=None if lag is None else lag * 1e3,
                    phases_moved_ms=None if shift is None else shift * 1e3,
                    # what may lie on the other side of a dispatch or a
                    # readback's end, per decode program
                    uncertain_ms=None if shift is None or lead is None
                    else (lead + lag) / 2 * 1e3)
            if ok:
                placed = place(out["traced"], base, offset + shift)
                out["idle"] = idle_by_phase(trace, placed)
                t0, t1 = xplane.window_of(trace)
                out["traced_window_s"] = t1 - t0
                run.log("idle_by_phase", window_s=t1 - t0, seconds={
                    k: round(v, 6) for k, v in sorted(
                        out["idle"].items(), key=lambda kv: -kv[1])})
    return out
