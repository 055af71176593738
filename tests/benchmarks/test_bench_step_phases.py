"""The program's own step records as the benchmark reads them
(``benchmarks/lib/step_phases.py`` and its three readers): on made-up
records and a made-up trace, where every number is known, and on the
observations of the tiny serve cell driven untraced on the CPU."""

import io

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import step_phases as sp
from benchmarks.lib import xplane
from benchmarks.lib.registry import Registry, cell_of

REG = Registry([REPO])
IDLE = ["engine.idle.before_launch", "engine.idle.in_readback",
        "engine.idle.after_readback", "engine.idle.unattributed"]
HOST = ["engine.host_exposed_ms", "engine.telemetry_ms",
        "engine.prefill_p90"]

BASE = 1000.0          # the program clock's base on time.monotonic
OFFSET = 1234.567891   # profiler's clock less time.monotonic


def _record(seq, start_us, parts, **counts):
    """A step record whose phases follow one another from ``start_us``;
    ``parts`` is [(name, microseconds)]."""
    phases, t = [], start_us
    for name, us in parts:
        phases.append([name, t, t + us])
        t += us
    rec = {"seq": seq, "start_us": start_us, "end_us": t, "phases": phases,
           "admitted": 0, "active": 4, "retired": 0, "cohorts": 1,
           "prompt_tokens": 0}
    rec.update(counts)
    return rec


DECODE = [("control", 100), ("admit", 100), ("decode_prepare", 300),
          ("decode_dispatch", 500), ("decode_readback", 8000),
          ("telemetry", 200), ("bookkeeping", 600), ("telemetry", 200)]
PREFILL = [("control", 100), ("admit", 100), ("prefill", 2000),
           ("prefill_readback", 5000), ("bookkeeping", 300)] + DECODE[2:]


def _made_up(steps=6, period_us=20_000):
    """Records, the harness's stamps of the same steps (20 us of Python on
    either side) and a trace of them: each step's device program runs from
    its dispatch's end to 1 ms before its readback's end, so the chip
    idles over the whole host part of every step and 1 ms of each
    readback."""
    records, stamps, spans, ops, mods = [], [], [], [], []
    for k in range(steps):
        start = 50_000 + k * period_us
        rec = _record(k + 1, start, PREFILL if k == 2 else DECODE,
                      **({"admitted": 1, "prompt_tokens": 24}
                         if k == 2 else {}))
        records.append(rec)
        t0 = BASE + rec["start_us"] / 1e6 - 20e-6
        t1 = BASE + rec["end_us"] / 1e6 + 20e-6
        stamps.append((t0, t1, int(k == 2), 4, 4, 100))
        spans.append(xplane.Event("bench.engine.step", t0 + 5e-6 + OFFSET,
                                  t1 - 5e-6 + OFFSET))
        by = {(n, s): e for n, s, e in rec["phases"]}
        launch = next(e for (n, _), e in by.items()
                      if n == "decode_dispatch")
        wake = next(e for (n, _), e in by.items() if n == "decode_readback")
        dev0 = BASE + launch / 1e6 + OFFSET
        dev1 = BASE + (wake - 1000) / 1e6 + OFFSET
        ops.append(xplane.Event("%fusion.1 = f32[8] fusion()", dev0, dev1))
        mods.append(xplane.Event("jit__decode_jit(1)", dev0, dev1))
    window = xplane.Event("bench.window", spans[0].start - 1e-3,
                          spans[-1].end + 1e-3)
    trace = xplane.Trace({0: ops}, {0: mods}, [window] + spans)
    return records, stamps, trace


class Log:
    def __init__(self):
        self.lines = []

    def log(self, line, **fields):
        self.lines.append(dict(fields, line=line))

    def of(self, tag):
        return [x for x in self.lines if x["line"] == tag]


def test_the_phase_names_are_the_programs_letter_for_letter():
    from horovod_tpu.serving import tracing as serve_tracing
    assert sp.PHASES == serve_tracing.STEP_PHASES
    named = {p for m in IDLE for p in REG.data("metrics", m)["args"]["phases"]}
    assert named == set(sp.PHASES) | {sp.UNATTRIBUTED}
    for m in HOST[:2]:
        args = REG.data("metrics", m)["args"]
        assert set(args.get("sum", []) + args.get("less", [])) <= \
            set(sp.PHASES)


def test_a_windows_records_are_those_inside_its_steps_stamps():
    records, stamps, _ = _made_up()
    assert sp.select(records, BASE, stamps) == records
    # a later window; the ring has lost the two oldest steps
    got = sp.select(records[2:], BASE, stamps[1:5])
    assert got == [None] + records[2:5]
    assert sp.select([], BASE, stamps) == [None] * 6


def test_the_records_parts_in_milliseconds():
    rec = _record(1, 0, PREFILL)
    assert sp.step_ms(rec) == pytest.approx(17.3)
    assert sp.phase_ms(rec, ("telemetry",)) == pytest.approx(0.4)
    assert sp.phase_ms(rec, ("decode_readback", "prefill_readback")) == \
        pytest.approx(13.0)
    records = [_record(1, 0, DECODE), rec]
    assert sp.decode_only(records) == records[:1]
    # one number per admitted request: prefill and its readback
    two = _record(3, 0, PREFILL[:5] + PREFILL[1:])
    assert sp.prefill_ms(records + [two]) == pytest.approx([7.0, 7.0, 7.0])
    s = sp.summary(records)
    assert s["steps"] == 2 and s["decode_only"] == 1
    assert s["phase_ms"]["prefill"] == {"steps": 1, "p50": 2.0, "p90": 2.0}
    assert s["phase_ms"]["telemetry"]["p50"] == 0.4
    assert s["active"] == 8 and set(s["phase_ms"]) == set(sp.PHASES)


def test_the_clocks_offset_is_recovered_to_the_microsecond():
    _, stamps, trace = _made_up()
    offset, spread = sp.clock_join(trace.span("engine.step"), stamps)
    assert offset == pytest.approx(OFFSET, abs=1e-6)
    assert spread < 1e-6
    # one span held up by half a millisecond moves the spread, not the offset
    spans = trace.span("engine.step")
    spans[3] = xplane.Event(spans[3].name, spans[3].start + 1e-3,
                            spans[3].end)
    offset, spread = sp.clock_join(spans, stamps)
    assert offset == pytest.approx(OFFSET, abs=1e-6) and spread > 1e-4
    # another count of steps: no join
    assert sp.clock_join(spans[:-1], stamps) is None
    assert sp.clock_join([], []) is None


def test_a_gap_is_cut_at_the_phases_and_each_piece_charged_where_it_lies():
    records, _, trace = _made_up(steps=3)
    placed = sp.place(records, BASE, OFFSET)
    idle = sp.idle_by_phase(trace, placed)
    t0, t1 = xplane.window_of(trace)
    assert sum(idle.values()) == pytest.approx(
        t1 - t0 - xplane.busy_seconds(trace)[0], rel=1e-9)
    # a step's one gap runs from 1 ms before its readback's end to the
    # next step's dispatch's end, over every phase between; the third step
    # prefills; 10 ms lie between two steps, 1.015 at the window's ends
    want = {"decode_readback": 3000, "telemetry": 1200,
            "bookkeeping": 1800 + 300, sp.UNATTRIBUTED: 2 * 10000 + 2 * 1015,
            "control": 300, "admit": 300, "prefill": 2000,
            "prefill_readback": 5000, "decode_prepare": 900,
            "decode_dispatch": 1500}
    assert {k: round(v * 1e6, 3) for k, v in idle.items()} == want
    # a gap wholly inside one phase is that phase's
    inside = xplane.Trace({0: [
        xplane.Event("%a = f32[8] fusion()", t0, placed[0][4] + 1e-4),
        xplane.Event("%b = f32[8] fusion()", placed[0][4] + 3e-4, t1)]},
        {}, trace.spans)
    assert sp.idle_by_phase(inside, placed) == {
        "decode_readback": pytest.approx(2e-4), sp.UNATTRIBUTED: 0.0}
    lead, lag = sp.launch_margins(trace, placed)
    assert lead == pytest.approx(500e-6, abs=1e-9)
    assert lag == pytest.approx(1e-3, abs=1e-9)


def _obs(records, stamps, trace):
    return {"window": {"steps": stamps}, "traced": {"steps": stamps},
            "trace": trace}


def _read(metric, obs, run):
    spec = REG.data("metrics", metric)
    return REG.module("readers", spec["reader"]).read(obs, spec["args"], run)


def test_the_four_idle_shares_add_up_to_idle_shares_own_number(monkeypatch):
    records, stamps, trace = _made_up()
    monkeypatch.setattr(sp, "program_records", lambda: (records, BASE))
    obs, run = _obs(records, stamps, trace), Log()
    shares = {m: _read(m, obs, run) for m in IDLE}
    whole = _read("device.idle_share.serve", obs, run)
    assert sum(shares.values()) == pytest.approx(whole, rel=1e-9)
    # in microseconds: six steps' gaps, the third step prefills (and
    # leaves 2.7 ms, not 10, before the fourth)
    unit = shares["engine.idle.unattributed"] / (
        4 * 10000 + 2700 + 2 * 1015)
    assert shares["engine.idle.in_readback"] == pytest.approx(
        (6 * 1000 + 5000) * unit)
    assert shares["engine.idle.after_readback"] == pytest.approx(
        (6 * 1000 + 300) * unit)
    assert shares["engine.idle.before_launch"] == pytest.approx(
        (6 * 1000 + 2000) * unit)
    # read once a run, logged once
    (line,) = run.of("step_phases")
    assert line["ring"] == 6
    for key in ("window", "traced"):
        assert line[key]["held"] == line[key]["window_steps"] == 6
        assert line[key]["admitted"] == 1 and line[key]["decode_only"] == 5
        assert line[key]["phase_ms"]["decode_readback"]["p50"] == 8.0
    (join,) = run.of("step_clock_join")
    assert join["ok"] and join["residual_spread_ms"] < 1e-3
    assert join["launch_to_start_ms_min"] == pytest.approx(0.5)
    # the host's numbers, from the untraced window's records
    assert _read("engine.host_exposed_ms", obs, run) == pytest.approx(2.0)
    assert _read("engine.telemetry_ms", obs, run) == pytest.approx(0.4)
    assert _read("engine.prefill_p90", obs, run) == pytest.approx(7.0)


def test_the_phases_go_to_the_middle_of_where_causality_allows(monkeypatch):
    assert sp.causal_shift(0.5e-3, 1e-3) == pytest.approx(-0.25e-3)
    assert sp.causal_shift(-0.3e-3, 1.8e-3) == pytest.approx(-1.05e-3)
    assert sp.causal_shift(1e-3, -0.2e-3) == pytest.approx(0.6e-3)
    assert sp.causal_shift(-1e-3, 0.5e-3) is None     # no shift is causal
    assert sp.causal_shift(None, None) == 0.0
    # so the split does not depend on how the profiler laid the device's
    # clock on the host's: 0.7 ms early or 0.6 ms late, the same numbers
    records, stamps, trace = _made_up()
    monkeypatch.setattr(sp, "program_records", lambda: (records, BASE))
    got = {}
    for skew in (0.0, -7e-4, 6e-4):
        moved = xplane.Trace(
            {0: [xplane.Event(e.name, e.start + skew, e.end + skew)
                 for e in trace.ops[0]]},
            {0: [xplane.Event(e.name, e.start + skew, e.end + skew)
                 for e in trace.modules[0]]}, trace.spans)
        obs, run = _obs(records, stamps, moved), Log()
        got[skew] = [_read(m, obs, run) for m in IDLE]
        (join,) = run.of("step_clock_join")
        assert join["ok"] and join["uncertain_ms"] == pytest.approx(0.75)
        assert join["launch_to_start_ms_min"] == pytest.approx(
            0.5 + skew * 1e3)
        assert join["end_to_wake_ms_min"] == pytest.approx(1.0 - skew * 1e3)
        assert join["phases_moved_ms"] == pytest.approx(-0.25 + skew * 1e3)
    for skew in (-7e-4, 6e-4):
        assert got[skew][:3] == pytest.approx(got[0.0][:3], rel=1e-6)
    # each program now starts 0.75 ms after its dispatch began (0.5 ms
    # long): 0.25 ms at every readback's start, and 0.75 at its end, idle
    idle = sp.analysis(obs, run)["idle"]
    assert idle["decode_dispatch"] == pytest.approx(6 * 0.5e-3, rel=1e-6)
    assert idle["decode_readback"] == pytest.approx(6 * 1e-3, rel=1e-6)


@pytest.mark.parametrize("fault", ["count", "spread", "lost_step",
                                   "no_records", "older_program"])
def test_what_cannot_be_placed_is_not_reported(fault, monkeypatch):
    records, stamps, trace = _made_up()
    if fault == "count":
        trace.spans.pop()
    elif fault == "spread":
        trace.spans[1:] = [
            xplane.Event(s.name, s.start + 2e-3 * (i % 2), s.end + 2e-3 *
                         (i % 2)) for i, s in enumerate(trace.spans[1:])]
    elif fault == "lost_step":
        records = records[1:]
    elif fault == "no_records":
        records = []
    if fault == "older_program":
        from horovod_tpu.utils import tracing as hvd_tracing

        class Older:
            clock = hvd_tracing.get_tracer().clock
        monkeypatch.setattr(hvd_tracing, "get_tracer", lambda: Older())
    else:
        monkeypatch.setattr(sp, "program_records", lambda: (records, BASE))
    obs, run = _obs(records, stamps, trace), Log()
    assert [_read(m, obs, run) for m in IDLE] == [None] * 4
    host = [_read(m, obs, run) for m in HOST]
    if fault in ("no_records", "older_program"):
        assert host == [None] * 3 and not run.lines
    else:
        assert all(v is not None for v in host)
        assert len(run.of("step_phases")) == 1


@pytest.fixture(scope="module")
def serve_obs(tmp_path_factory):
    """The tiny serve cell's set-up and one untraced window, as
    ``run.execute`` drives them (the self-tests never trace)."""
    from benchmarks import run as run_mod
    roots = bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    reg = Registry(roots)
    bench = reg.benchmark()
    run = run_mod.Run(reg, bench, cell_of(bench, "tiny-lm-serve"), 11, 0.4,
                      0, io.StringIO())
    run_mod.find_devices(run, require_chip=False)
    gen = reg.module("generators", run.traffic["generator"]).Generator(run)
    gen.setup()
    obs = {"window": gen.window(0.4)}
    yield obs, run
    gen.prog.free()
    import horovod_tpu as hvd
    hvd.shutdown()


@pytest.mark.parametrize("metric", HOST)
def test_the_host_readers_give_a_number_on_the_tiny_serve_cell(
        metric, serve_obs):
    obs, run = serve_obs
    value = _read(metric, obs, run)
    assert isinstance(value, float) and 0 < value < 60_000
    got = sp.analysis(obs, run)
    steps = obs["window"]["steps"]
    # every step of the window has its record, and the record agrees with
    # what the generator counted from outside
    assert got["window_complete"] and len(got["window"]) == len(steps)
    assert [r["admitted"] for r in got["window"]] == [s[2] for s in steps]
    for rec, s in zip(got["window"], steps):
        assert sp.step_ms(rec) <= (s[1] - s[0]) * 1e3 + 0.01
    # untraced: nothing to place on a device's timeline
    assert "idle" not in got
    assert [_read(m, obs, run) for m in IDLE] == [None] * 4
