"""The launch ledger as the benchmark reads it (``benchmarks/lib/launches.py``
and its four readers, PR 40): on made-up records and a made-up trace, where
every number is known, and on the tiny serve cell driven untraced on the
CPU.  The made-up engine runs ahead as the real one does since PR 28: a
decode program starts when the one before it ends, milliseconds after its
own dispatch and just before the NEXT step's, which is the case that broke
``lib/step_phases.launch_margins``."""

import io
import json
import os

import pytest

import bench_tiny
from bench_tiny import REPO
from benchmarks.lib import launches as ln
from benchmarks.lib import registry as registry_mod
from benchmarks.lib import step_phases as sp
from benchmarks.lib import xplane

REG = registry_mod.Registry([REPO])
IDLE = ["launch.idle." + p for p in ln.PARTS]
TRACED = IDLE + ["launch.admit_idle_ms"]
UNTRACED = ["launch.starved_share", "engine.stall_share"]

BASE = 1000.0          # the program clock's base on time.monotonic
OFFSET = 1234.567891   # profiler's clock less time.monotonic
LATENCY = 200          # us from a call's start to the program's, chip idle
WAKE = 100             # us from a program's end to its read-back's
CALLER = 300           # us between two steps
FOLD, PREFILL, WRITE, DECODE = ("_threefry_fold_in", "_prefill_jit",
                                "_write_slot", "_decode_jit")
RUN_US = {FOLD: 5, PREFILL: 10_000, WRITE: 50, DECODE: 8_000}


class Sim:
    """A host that steps and a chip that runs what it is sent, in order:
    records, the harness's stamps and the device's programs."""

    def __init__(self, t=50_000):
        self.t, self.free, self.n, self.seq = t, 0, 0, 0
        self.unread = None
        self.ends, self.programs = {}, []   # n -> end us; (name, s, e, n)
        self.records, self.idle_launches = [], 0

    def step(self, admits=0, due=False, stall_us=0):
        phases, launches, reads = [], [], []
        start = t = self.t

        def phase(name, us):
            nonlocal t
            phases.append([name, t, t + us])
            t += us

        def launch(program, at, call_us):
            self.n += 1
            begin = max(at + LATENCY, self.free)
            self.idle_launches += begin == at + LATENCY
            self.free = self.ends[self.n] = begin + RUN_US[program]
            self.programs.append((program, begin, self.free, self.n))
            launches.append([self.n, program, at, at + call_us])
            return self.n

        def read(name, n, more_us=0):
            nonlocal t
            end = max(t + 20, self.ends[n] + WAKE) + more_us
            phases.append([name, t, end])
            reads.append([n, t, end])
            t = end

        phase("control", 100)
        firsts = []
        for _ in range(admits):
            phase("admit", 100)
            launch(FOLD, t + 300, 200)
            firsts.append(launch(PREFILL, t + 600, 600))
            launch(WRITE, t + 1500, 300)
            phase("prefill", 2000)
        phase("decode_prepare", 300)
        own = launch(DECODE, t + 50, 400)
        phase("decode_dispatch", 500)
        if self.unread is not None:
            read("decode_readback", self.unread, stall_us)
            phase("telemetry", 200)
            phase("bookkeeping", 2800)
        for n in firsts:
            read("prefill_readback", n)
            phase("bookkeeping", 300)
        self.unread = None if due else own
        if due:
            read("decode_readback", own)
            phase("bookkeeping", 600)
        phase("telemetry", 200)
        self.seq += 1
        self.records.append({
            "seq": self.seq, "start_us": start, "end_us": t,
            "phases": phases, "launches": launches, "reads": reads,
            "admitted": admits, "active": 4, "retired": int(due),
            "cohorts": 1, "prompt_tokens": 24 * admits})
        self.t = t + CALLER
        return self

    def run(self, plan):
        for kind in plan:
            self.step(admits=int(kind) if kind.isdigit() else 0,
                      due=kind == "d")
        return self

    def observed(self, skew=0.0, lead=(), hole=None, first=0):
        """(records, stamps, trace) of the steps from ``first`` on.
        ``skew`` moves the device's clock; ``lead`` are programs the
        device shows before the first; ``hole``: the n of a program whose
        one operation becomes two with 1 ms between them."""
        records = self.records[first:]
        stamps, spans = [], []
        for r in records:
            t0 = BASE + r["start_us"] / 1e6 - 20e-6
            t1 = BASE + r["end_us"] / 1e6 + 20e-6
            stamps.append((t0, t1, r["admitted"], 4, 4, 100))
            spans.append(xplane.Event("bench.engine.step", t0 + 5e-6 + OFFSET,
                                      t1 - 5e-6 + OFFSET))
        seen = {lc[0] for r in records for lc in r["launches"]}
        programs = list(lead) + [p for p in self.programs if p[3] in seen]
        ops, mods = [], []
        for name, s, e, n in programs:
            d0 = BASE + s / 1e6 + OFFSET + skew
            d1 = BASE + e / 1e6 + OFFSET + skew
            mods.append(xplane.Event(f"jit_{name}({n})", d0, d1))
            cuts = [(d0, d1)] if n != hole else [(d0, d0 + 2e-3),
                                                 (d0 + 3e-3, d1)]
            ops += [xplane.Event("%fusion.1 = f32[8] fusion()", a, b)
                    for a, b in cuts]
        window = xplane.Event("bench.window", spans[0].start - 1e-3,
                              spans[-1].end + 1e-3)
        return records, stamps, xplane.Trace({0: ops}, {0: mods},
                                             [window] + spans)


# ahead, ahead, a row finishes; one joins; ...; two join; ...
PLAN = "aad1aad2aaad1ad"


class Log:
    def __init__(self):
        self.lines = []

    def log(self, line, **fields):
        self.lines.append(dict(fields, line=line))

    def of(self, tag):
        return [x for x in self.lines if x["line"] == tag]


def _obs(records, stamps, trace, monkeypatch, window=None):
    window = window or (records, stamps)
    held = window[0] + [r for r in records if r not in window[0]]
    monkeypatch.setattr(sp, "program_records", lambda: (held, BASE))
    return {"window": {"steps": window[1]}, "traced": {"steps": stamps},
            "trace": trace}


def _read(metric, obs, run):
    spec = REG.data("metrics", metric)
    return REG.module("readers", spec["reader"]).read(obs, spec["args"], run)


def test_the_launches_are_matched_by_order_where_nearest_failed(monkeypatch):
    records, stamps, trace = Sim().run(PLAN).observed()
    # the old join: a program that runs ahead starts 4 ms after its own
    # dispatch and 4 ms before the next step's, so the nearest dispatch is
    # as often the wrong one and no shift is causal
    lead, lag = sp.launch_margins(trace, sp.place(records, BASE, OFFSET))
    assert lead == pytest.approx(-4e-3, abs=1e-6)
    assert sp.causal_shift(lead, lag) is None
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    assert all(_read(m, obs, run) is not None for m in TRACED)
    (old,) = run.of("step_clock_join")
    assert old["ok"] is False
    (join,) = run.of("launch_join")
    n = sum(len(r["launches"]) for r in records)
    assert join["ok"] and join["lead"] == 0 and join["tail"] == 0
    assert join["launches"] == join["programs"] == join["matched"] == n
    assert join["lead_ms"]["min"] == pytest.approx(LATENCY / 1e3)
    assert join["lag_ms"]["min"] == pytest.approx(WAKE / 1e3)
    assert join["lead_ms"]["p50"] > 1.0     # most run behind another
    assert join["phases_moved_ms"] == pytest.approx(0.05)
    assert join["uncertain_ms"] == pytest.approx(0.15)


@pytest.mark.parametrize("launched,ran,want", [
    ("DDFPWD", "DDFPWD", (0, 6)),
    ("DDFPWD", "DDDFPWD", (1, 6)),         # a pass in flight leads
    ("DDFPWD", "DDFPW", (0, 5)),           # the trace ended first
    ("DDFPWD", "DDFPWDD", (0, 6)),
    ("DD", "DDD", (0, 2)),                 # the least lead that agrees
], ids=str)
def test_two_name_sequences_that_agree_give_their_alignment(
        launched, ran, want):
    assert ln.align(list(launched), list(ran)) == want


@pytest.mark.parametrize("launched,ran,at", [
    ("DDFPWD", "DDPWD", 2), ("DFPWD", "DFWPD", 2), ("DFPWD", "", 0),
    ("DDFPWD", "DDDDDDDFPWD", 2)], ids=str)
def test_two_that_do_not_agree_say_where(launched, ran, at):
    more = "DDFPWDDD" if ran else ""   # a window is longer than its slack
    launched, ran = launched + more, ran + more
    lead, where = ln.align(list(launched), list(ran))
    assert lead is None and where["at"] == at
    assert where["launched"] == launched[at]


def test_a_pass_in_flight_when_the_trace_begins_is_skipped(monkeypatch):
    sim = Sim().run(PLAN)
    # the trace begins with the second step: the first step's pass runs
    # on into it and leads the device's list
    flying = [p for p in sim.programs if p[3] == 1]
    records, stamps, trace = sim.observed(lead=flying, first=1)
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    assert all(_read(m, obs, run) is not None for m in TRACED)
    (join,) = run.of("launch_join")
    assert join["ok"] and join["lead"] == 1 and join["tail"] == 0
    assert join["programs"] == join["launches"] + 1
    # the read of the pass that flew in names a launch no record holds:
    # it bounds nothing, and the rest still give the two margins
    assert records[0]["reads"][0][0] == 1
    assert join["lag_ms"]["min"] == pytest.approx(WAKE / 1e3)


def test_a_missing_launch_reports_nothing_and_says_where(monkeypatch):
    records, stamps, trace = Sim().run(PLAN).observed()
    lost = records[3]["launches"].pop(1)       # the first prefill
    assert lost[1] == PREFILL
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    assert [_read(m, obs, run) for m in IDLE] == [None] * 5
    (join,) = run.of("launch_join")
    at = sum(len(r["launches"]) for r in records[:3]) + 1
    assert join["ok"] is False and join["why"] == "order"
    assert join["differ"]["at"] == at
    assert join["differ"]["launched"] == WRITE
    assert join["differ"]["ran"] == PREFILL
    assert join["differ"]["launch"] == [lost[0] + 1, WRITE]
    # what needs no match is still read
    assert _read("launch.admit_idle_ms", obs, run) is not None
    assert _read("launch.starved_share", obs, run) is not None


def _parts(sim, monkeypatch, **kw):
    records, stamps, trace = sim.observed(**kw)
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    got = ln.analysis(obs, run)
    return got, obs, run, trace


def test_the_five_parts_add_up_to_the_window_less_the_busy_time(
        monkeypatch):
    sim = Sim().run(PLAN)
    got, obs, run, trace = _parts(sim, monkeypatch)
    idle, window_s = got["idle"], got["traced_window_s"]
    t0, t1 = xplane.window_of(trace)
    assert sum(idle.values()) == pytest.approx(
        t1 - t0 - xplane.busy_seconds(trace)[0], rel=1e-9)
    shares = {m: _read(m, obs, run) for m in IDLE}
    whole = _read("device.idle_share.serve", obs, run)
    assert sum(shares.values()) == pytest.approx(whole, rel=1e-9)
    # the host's times sit 50 us late (the middle of what causality
    # allows): a launch onto an idle chip idles it 150 us from its call's
    # start, and a read-back that nothing is queued behind 150 us
    # (the plan's last step reads its own pass too, into the window's end)
    due = PLAN.count("d") - 1
    assert idle["dispatch"] == pytest.approx(150e-6 * sim.idle_launches)
    assert idle["readback"] == pytest.approx(150e-6 * due)
    # the trace's last program to the window's end; nothing else
    last = max(e.end for e in trace.modules[0])
    assert idle["unmatched"] == pytest.approx(t1 - last)
    # between two steps the caller has the thread: under a gap wherever a
    # step read its own pass, and before the first record
    first = BASE + sim.records[0]["start_us"] / 1e6 + OFFSET + 50e-6
    assert idle["caller"] == pytest.approx(
        CALLER * 1e-6 * due + first - t0, rel=1e-6)
    assert idle["host"] > idle["caller"]
    # an admission: the idle over the admissions
    per = _read("launch.admit_idle_ms", obs, run)
    assert per == pytest.approx(sum(idle.values()) * 1e3 / 4)
    (line,) = run.of("idle_by_launch")
    assert line["window_s"] == window_s
    assert sum(line["by_program_and_place"].values()) == pytest.approx(
        sum(idle.values()), abs=1e-5)
    assert line["by_program_and_place"][FOLD + "/decode_readback"] == \
        pytest.approx(150e-6 * 3, abs=1e-6)


@pytest.mark.parametrize("skew", [-7e-4, 6e-4, 3e-3])
def test_the_split_does_not_depend_on_how_the_devices_clock_was_laid(
        skew, monkeypatch):
    sim = Sim().run(PLAN)
    straight = _parts(sim, monkeypatch)[0]["idle"]
    got, _, run, _ = _parts(sim, monkeypatch, skew=skew)
    (join,) = run.of("launch_join")
    assert join["ok"] and join["phases_moved_ms"] == pytest.approx(
        0.05 + skew * 1e3)
    for part in ("dispatch", "readback", "host"):
        assert got["idle"][part] == pytest.approx(straight[part], rel=1e-6)


def test_a_gap_inside_one_programs_extent_is_unmatched(monkeypatch):
    sim = Sim().run(PLAN)
    whole = _parts(sim, monkeypatch)[0]["idle"]
    holed, _, run, _ = _parts(sim, monkeypatch, hole=2)
    assert holed["idle"]["unmatched"] == pytest.approx(
        whole["unmatched"] + 1e-3)
    for part in ("dispatch", "readback", "host", "caller"):
        assert holed["idle"][part] == pytest.approx(whole[part])
    (line,) = run.of("idle_by_launch")
    assert line["by_program_and_place"][DECODE + "/inside"] == \
        pytest.approx(1e-3)


def _rec(seq, start, end, launches=(), reads=(), phases=None, admitted=0):
    return {"seq": seq, "start_us": start, "end_us": end,
            "phases": phases or [["control", start, end]],
            "launches": [list(x) for x in launches],
            "reads": [list(x) for x in reads], "admitted": admitted}


def test_starved_is_from_the_read_that_leaves_none_unread_to_the_next_call():
    records = [
        # runs ahead: launches 6, reads 5; 6 is in flight, not starved
        _rec(1, 0, 1000, [(6, DECODE, 100, 200)], [(5, 300, 900)]),
        # launches 7, reads 6, then its own: starved from 1900 ...
        _rec(2, 1100, 2000, [(7, DECODE, 1200, 1300)],
             [(6, 1400, 1600), (7, 1700, 1900)]),
        # ... to the fold's call at 2400. The first token (9) is read,
        # but the slot write (10) and the pass (11) are not: in flight.
        # Then the pass is: starved from 3300 to the next call at 3700
        _rec(3, 2100, 3500,
             [(8, FOLD, 2400, 2450), (9, PREFILL, 2500, 2600),
              (10, WRITE, 2700, 2750), (11, DECODE, 2800, 2900)],
             [(9, 3000, 3100), (11, 3200, 3300)],
             phases=[["control", 2100, 2200], ["prefill", 2200, 2800],
                     ["decode_dispatch", 2800, 3000],
                     ["prefill_readback", 3000, 3100],
                     ["decode_readback", 3100, 3300],
                     ["bookkeeping", 3300, 3500]], admitted=1),
        _rec(4, 3600, 4000, [(12, DECODE, 3700, 3800)], [(12, 3850, 3950)]),
    ]
    assert ln.starved(records) == [(1900, 2400), (3300, 3700)]
    got = ln.starved_summary(records)
    assert got["share"] == pytest.approx(100.0 * 900 / 4000)
    assert got["ms_per_admission"] == pytest.approx(0.9)
    assert got["where_s"] == {
        "control": pytest.approx(300e-6),     # 1900-2000, 2100-2200, 3600-
        "caller": pytest.approx(200e-6), "prefill": pytest.approx(200e-6),
        "bookkeeping": pytest.approx(200e-6)}
    # a window that opens on the read of a pass launched before it: the
    # numbers run on, so the first launch says what was in flight
    assert ln.starved(records[1:])[0] == (1900, 2400)
    late = [_rec(1, 0, 500, [], [(5, 100, 200)]),
            _rec(2, 600, 900, [(6, DECODE, 700, 800)], [])]
    assert ln.starved(late) == [(200, 700)]
    assert ln.starved([_rec(1, 0, 500)]) == []


def test_starved_never_exceeds_the_idle_share_of_the_same_window(
        monkeypatch):
    sim = Sim().run(PLAN)
    got, obs, run, trace = _parts(sim, monkeypatch)
    (line,) = run.of("starved")
    idle = _read("device.idle_share.serve", obs, run)
    for key in ("window", "traced"):
        assert 0 < line[key]["share"] < idle
        assert line[key]["admitted"] == 4
    assert line["traced"]["idle_share"] == pytest.approx(idle)
    assert _read("launch.starved_share", obs, run) == line["window"]["share"]
    # what the host knows is the part before the call, less the read-back
    # and less what waits behind a launch that nothing reads
    known = got["idle"]["host"] + got["idle"]["caller"]
    assert line["traced"]["seconds"] < known
    assert line["traced"]["seconds"] > known - 4 * 200e-6 - 2e-3
    assert set(line["window"]["where_s"]) >= {"caller", "bookkeeping",
                                              "prefill", "control"}


def test_a_stalled_step_is_found_and_named(monkeypatch):
    sim = Sim().run(PLAN[:6])
    sim.step(stall_us=150_000)        # held in a read of the pass before
    sim.run(PLAN[6:])
    records, stamps, trace = sim.observed()
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    share = _read("engine.stall_share", obs, run)
    (line,) = run.of("stalls")
    (stall,) = line["window"]
    typical = line["typical_ms"]["0"]
    assert stall["seq"] == 7 and stall["admitted"] == 0
    assert stall["phase"] == "decode_readback" and stall["read"] == DECODE
    assert stall["excess_ms"] == pytest.approx(stall["ms"] - typical)
    assert 140 < stall["excess_ms"] < 151
    span_ms = (records[-1]["end_us"] - records[0]["start_us"]) / 1e3
    assert share == pytest.approx(100.0 * stall["excess_ms"] / span_ms,
                                  rel=1e-3)
    assert line["share"] == share and line["over_ms"] == 100.0
    # the trace holds it too: the chip ran the pass in flight for 8 ms of
    # that read and then had nothing
    (traced,) = line["traced"]
    assert traced["seq"] == 7 and 0.03 < traced["chip_busy_share"] < 0.1
    # one held in a launch names the call; 100 ms over is not yet a stall
    held = _rec(9, 0, 130_000, [(40, DECODE, 1_000, 121_400)],
                [(39, 122_000, 129_000)],
                phases=[["control", 0, 500], ["decode_prepare", 500, 900],
                        ["decode_dispatch", 900, 121_500],
                        ["decode_readback", 121_500, 129_000],
                        ["telemetry", 129_000, 130_000]])
    ((rec, excess),) = ln.stalls([held], {0: 8.0})
    assert rec is held and excess == pytest.approx(122.0)
    told, _ = ln.describe_stall(held, excess, {40: DECODE})
    assert told["phase"] == "decode_dispatch" and told["launch"] == DECODE
    assert told["call_ms"] == pytest.approx(120.4) and "read" not in told
    assert ln.stalls([held], {0: 30.0}) == []
    assert ln.stalls([held], {1: 8.0}) == []      # none of its like


def test_no_stall_reads_zero_and_not_nothing(monkeypatch):
    records, stamps, trace = Sim().run(PLAN).observed()
    obs, run = _obs(records, stamps, trace, monkeypatch), Log()
    assert _read("engine.stall_share", obs, run) == 0.0
    assert run.of("stalls")[0]["window"] == []


@pytest.mark.parametrize("fault", ["older_shape", "no_records", "no_trace",
                                   "lost_step"])
def test_what_is_not_there_is_not_reported_and_nothing_raises(
        fault, monkeypatch):
    records, stamps, trace = Sim().run(PLAN).observed()
    if fault == "older_shape":       # the parent's records
        for r in records:
            del r["launches"], r["reads"]
    obs = _obs(records, stamps, trace, monkeypatch)
    if fault == "no_records":
        monkeypatch.setattr(sp, "program_records", lambda: None)
    elif fault == "no_trace":
        del obs["trace"], obs["traced"]
    elif fault == "lost_step":       # the ring lost a traced step
        monkeypatch.setattr(sp, "program_records",
                            lambda: (records[1:], BASE))
    run = Log()
    got = {m: _read(m, obs, run) for m in TRACED + UNTRACED}
    if fault in ("older_shape", "no_records"):
        assert set(got.values()) == {None}
        assert not run.of("launch_join") and not run.of("starved")
    elif fault == "no_trace":
        assert [got[m] for m in TRACED] == [None] * 6
        assert all(got[m] is not None for m in UNTRACED)
    else:
        assert [got[m] for m in IDLE] == [None] * 5
        assert all(got[m] is not None for m in UNTRACED)


def test_the_eight_are_every_serving_cells_and_no_training_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = bench["per_layer"][-8:]
    assert [m["name"] for m in added] == TRACED + UNTRACED
    assert all("workloads" not in m for m in added)
    layers = {m["layer"] for m in added[:7]}
    assert len(layers) == 1 and layers.pop().startswith("launch path: ")
    assert added[7]["layer"] == next(
        m["layer"] for m in bench["per_layer"]
        if m["name"] == "engine.host_exposed_ms")
    assert [m["source"] for m in added] == \
        ["device_trace"] * 6 + ["program_span"] * 2
    for cell in bench["workloads"]:
        names = {m["name"] for m in registry_mod.metrics_of(
            bench, "per_layer", cell["name"])}
        assert (set(TRACED + UNTRACED) <= names) == ("serve" in cell["name"])
        assert "serve" in cell["name"] or not names & set(TRACED + UNTRACED)
    assert [REG.data("metrics", m)["args"] for m in IDLE] == \
        [{"part": p} for p in ln.PARTS]


@pytest.fixture(scope="module")
def serve_obs(tmp_path_factory):
    """The tiny serve cell's set-up and one untraced window, as
    ``run.execute`` drives them (the self-tests never trace)."""
    from benchmarks import run as run_mod
    roots = bench_tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    reg = registry_mod.Registry(roots)
    bench = reg.benchmark()
    out = io.StringIO()
    run = run_mod.Run(reg, bench, registry_mod.cell_of(bench,
                                                       "tiny-lm-serve"),
                      11, 0.4, 0, out)
    run_mod.find_devices(run, require_chip=False)
    gen = reg.module("generators", run.traffic["generator"]).Generator(run)
    gen.setup()
    obs = {"window": gen.window(0.4)}
    yield obs, run, out
    gen.prog.free()
    import horovod_tpu as hvd
    hvd.shutdown()


def test_the_tiny_serve_cell_gives_the_two_untraced_numbers(serve_obs):
    obs, run, out = serve_obs
    starved, stall = (_read(m, obs, run) for m in UNTRACED)
    # (a CPU's window may well hold a step 100 ms over its like)
    assert 0 < starved < 100 and 0 <= stall < 100
    assert [_read(m, obs, run) for m in TRACED] == [None] * 6
    got = ln.analysis(obs, run)
    records = got["window"]
    assert len(records) == len(obs["window"]["steps"])
    # the ledger of a real window: numbers run on, every step that
    # admitted folded, prefilled and wrote once an admission
    flat = ln.launches_of(records)
    assert [lc[0] for lc in flat] == list(
        range(flat[0][0], flat[0][0] + len(flat)))
    for r in records:
        names = [lc[1] for lc in r["launches"]]
        assert names.count(PREFILL) == names.count(FOLD) == \
            names.count(WRITE) == r["admitted"]
        assert names.count(DECODE) == (1 if r["active"] else 0)
    lines = [json.loads(x) for x in out.getvalue().splitlines()]
    (line,) = [x for x in lines if x["line"] == "starved"]
    assert line["window"]["share"] == starved and "traced" not in line
    assert line["window"]["admitted"] == sum(s[2] for s in
                                             obs["window"]["steps"])
    (line,) = [x for x in lines if x["line"] == "stalls"]
    assert line["share"] == stall and bool(line["window"]) == (stall > 0)
