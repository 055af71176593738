"""Request-path tracing (horovod_tpu/serving/tracing.py): span
lifecycle and exact phase decomposition on a fake clock, the queue and
engine integration (trace ids in results/events, goodput accounting,
KV-pressure requeues), flight-dump reconstruction of in-flight
requests, and the acceptance drill — inject a synthetic slow phase
(delayed prefill, forced KV-pressure requeue) and assert the hvd_slo
tail verdict names it."""

import json
import os
import sys
import time

import numpy as np  # noqa: F401 - keeps the jax import path warm
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from horovod_tpu.serving import tracing as serve_tracing
from horovod_tpu.serving.queue import AdmissionQueue, Request
from horovod_tpu.utils import metrics as hvd_metrics
from horovod_tpu.utils import tracing as hvd_tracing

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import hvd_postmortem  # noqa: E402
import hvd_slo  # noqa: E402


@pytest.fixture
def reg():
    """Live metrics registry + live tracer, torn down to env defaults."""
    r = hvd_metrics.reset(enabled=True)
    hvd_tracing.reset(enabled=True, rank=0)
    yield r
    hvd_tracing.reset()
    hvd_metrics.reset()


def _value(snap, name, **labels):
    fam = snap["metrics"].get(name)
    if fam is None:
        return None
    for v in fam["values"]:
        if all(v["labels"].get(k) == lv for k, lv in labels.items()):
            return v.get("value", v.get("count"))
    return None


def _events(snap, kind):
    return [e for e in snap["events"] if e["event"] == kind]


class FakeUsClock:
    """Deterministic microsecond clock with the tracer's interface."""

    def __init__(self):
        self.now_us = 0.0
        self.epoch_us_at_ts0 = 1_700_000_000_000_000

    def ts_us(self):
        return self.now_us

    def epoch_us(self, ts_us=None):
        return self.epoch_us_at_ts0 + (
            self.now_us if ts_us is None else ts_us)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# RequestTrace lifecycle on a fake clock: exact decomposition
# ---------------------------------------------------------------------------

class TestRequestTrace:
    def _tracer(self):
        return hvd_tracing.Tracer(rank=0, clock=FakeUsClock())

    def test_phase_decomposition_is_exact(self):
        tracer = self._tracer()
        clock = tracer.clock
        t = serve_tracing.RequestTrace(tracer, "r0").on_submit()
        clock.now_us += 5_000  # 5 ms queue_wait
        t.on_pop()
        for _ in range(2):  # 2 requeues, 3 ms each
            t.on_requeue()
            clock.now_us += 3_000
            t.on_pop()
        t.on_prefill_start(slot=1, prompt_len=4)
        clock.now_us += 7_000  # 7 ms prefill
        t.on_prefill_end(ttft_s=0.012)
        for _ in range(2):  # 2 decode ticks, 4 ms each
            clock.now_us += 4_000
            t.on_decode_tick(4_000)
        clock.now_us += 2_000  # 2 ms the ticks don't cover: the stall
        phases = t.on_retire("completed", tokens=8)
        assert phases == {"queue_wait": 5.0, "requeue": 6.0,
                          "prefill": 7.0, "decode": 8.0,
                          "scheduler_stall": 2.0}
        root = [s for s in tracer.spans()
                if s["stage"] == hvd_tracing.REQUEST]
        assert len(root) == 1
        attrs = root[0]["attrs"]
        assert attrs["outcome"] == "completed"
        assert attrs["slot"] == 1
        assert attrs["requeues"] == 2
        assert attrs["phase_ms"] == phases
        # every serve stage the lifecycle visited closed into the ring
        stages = {s["stage"] for s in tracer.spans()}
        assert {hvd_tracing.REQUEST, hvd_tracing.QUEUE_WAIT,
                hvd_tracing.PREFILL, hvd_tracing.DECODE} <= stages
        assert tracer.open_spans() == []

    def test_reject_closes_root_as_error(self):
        tracer = self._tracer()
        t = serve_tracing.RequestTrace(tracer, "r0").on_submit()
        tracer.clock.now_us += 2_000
        phases = t.on_reject("queue_full")
        assert phases["queue_wait"] == 2.0
        (root,) = [s for s in tracer.spans()
                   if s["stage"] == hvd_tracing.REQUEST]
        assert root["status"] == "error"
        assert root["attrs"]["outcome"] == "rejected"
        assert root["attrs"]["reason"] == "queue_full"
        assert tracer.open_spans() == []

    def test_close_is_idempotent(self):
        tracer = self._tracer()
        t = serve_tracing.RequestTrace(tracer, "r0").on_submit()
        t.on_pop()
        first = t.on_retire("completed")
        tracer.clock.now_us += 9_000
        assert t.on_retire("failed") == first  # no re-close, no drift
        roots = [s for s in tracer.spans()
                 if s["stage"] == hvd_tracing.REQUEST]
        assert len(roots) == 1

    def test_crash_mid_request_leaves_open_spans(self):
        # the failover-dump contract: an unretired request is visible
        # as open spans, never silently dropped
        tracer = self._tracer()
        t = serve_tracing.RequestTrace(tracer, "r0").on_submit()
        t.on_pop()
        t.on_prefill_start(slot=0, prompt_len=2)
        t.on_prefill_end()
        open_stages = {s.stage for s in tracer.open_spans()}
        assert {hvd_tracing.REQUEST, hvd_tracing.DECODE} <= open_stages


class TestBeginAttach:
    def test_begin_attaches_once_and_replaces_closed(self, reg):
        req = Request("a", (1, 2))
        t1 = serve_tracing.begin(req)
        assert serve_tracing.begin(req) is t1  # live: idempotent
        t1.on_pop()
        t1.on_retire("completed")
        t2 = serve_tracing.begin(req)  # resubmission: fresh lifecycle
        assert t2 is not t1 and not t2.closed

    def test_disabled_attaches_shared_null(self, reg, monkeypatch):
        monkeypatch.setenv("HVD_SERVE_TRACE", "0")
        req = Request("a", (1, 2))
        assert serve_tracing.begin(req) is serve_tracing._NULL_TRACE
        assert serve_tracing.trace_of(req).phase_ms() == {}
        # re-enabling replaces the null on the next submit
        monkeypatch.delenv("HVD_SERVE_TRACE")
        assert isinstance(serve_tracing.begin(req),
                          serve_tracing.RequestTrace)

    def test_trace_of_never_returns_none(self):
        assert serve_tracing.trace_of(Request("a", (1,))) is \
            serve_tracing._NULL_TRACE


# ---------------------------------------------------------------------------
# AdmissionQueue integration (no jax)
# ---------------------------------------------------------------------------

class TestQueueIntegration:
    def test_submit_pop_requeue_drive_wait_spans(self, reg):
        q = AdmissionQueue(max_depth=4, admission_timeout_s=10.0)
        req = Request("a", (1, 2))
        q.submit(req)
        trace = serve_tracing.trace_of(req)
        assert isinstance(trace, serve_tracing.RequestTrace)
        got = q.pop()
        assert got is req
        q.requeue(req)
        assert trace.requeues == 1
        q.pop()
        trace.on_retire("completed")
        tracer = hvd_tracing.get_tracer()
        waits = [s for s in tracer.spans()
                 if s["stage"] == hvd_tracing.QUEUE_WAIT]
        assert len(waits) == 2
        assert [bool((s.get("attrs") or {}).get("requeue"))
                for s in waits] == [False, True]

    def test_queue_full_reject_carries_trace_id(self, reg):
        q = AdmissionQueue(max_depth=1, admission_timeout_s=10.0)
        q.submit(Request("a", (1,)))
        rej = Request("b", (1,))
        assert not q.submit(rej)
        trace = serve_tracing.trace_of(rej)
        assert trace.closed
        (ev,) = _events(reg.snapshot(), "serve_reject")
        assert ev["trace_id"] == trace.trace_id
        assert ev["reason"] == "queue_full"

    def test_deadline_reject_closes_trace(self, reg):
        clock = FakeClock()
        q = AdmissionQueue(max_depth=8, admission_timeout_s=5.0,
                           clock=clock)
        stale = Request("stale", (1,), deadline_s=1.0)
        q.submit(stale)
        clock.t = 2.0
        assert q.pop() is None
        assert serve_tracing.trace_of(stale).closed
        (root,) = [s for s in hvd_tracing.get_tracer().spans()
                   if s["stage"] == hvd_tracing.REQUEST]
        assert root["attrs"]["reason"] == "deadline"


# ---------------------------------------------------------------------------
# ServeEngine integration (CPU, tiny fp32 config)
# ---------------------------------------------------------------------------

def _tiny():
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tr
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                    attention_impl="full")
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    from horovod_tpu.serving.engine import ServeEngine
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("kv_block", 8)
    kw.setdefault("queue", AdmissionQueue(max_depth=64,
                                          admission_timeout_s=1e9))
    return ServeEngine(cfg, params, **kw)


class TestEngineIntegration:
    def test_results_carry_trace_id_and_phases(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=5))
        (res,) = engine.run_to_completion()
        assert res.outcome == "completed"
        assert res.trace_id
        assert set(res.phase_ms) == set(serve_tracing.PHASES)
        assert res.phase_ms["prefill"] > 0
        assert res.phase_ms["decode"] > 0
        snap = reg.snapshot()
        # the decomposition reached the histogram, every phase labeled
        for phase in serve_tracing.PHASES:
            assert _value(snap, "hvd_serve_phase_seconds",
                          phase=phase) == 1, phase
        (admit,) = _events(snap, "serve_admit")
        (retire,) = _events(snap, "serve_retire")
        assert admit["trace_id"] == res.trace_id
        assert retire["trace_id"] == res.trace_id
        # all-met goodput: every prefill+decode token counts, none wasted
        assert _value(snap, "hvd_serve_goodput_tokens_total") == 8.0
        assert _value(snap, "hvd_serve_goodput_ratio") == 1.0
        assert "hvd_serve_wasted_tokens_total" not in snap["metrics"] or \
            not snap["metrics"]["hvd_serve_wasted_tokens_total"]["values"]

    def test_deadline_failure_counts_wasted_tokens(self, reg):
        cfg, params = _tiny()
        clock = FakeClock()
        queue = AdmissionQueue(max_depth=8, admission_timeout_s=1e9,
                               clock=clock)
        engine = _engine(cfg, params, queue=queue, clock=clock)
        engine.submit(Request("slow", (1, 2), max_new_tokens=20,
                              deadline_s=5.0))
        engine.step()
        clock.t = 6.0
        for _ in range(5):
            if engine.run_to_completion(max_steps=1):
                break
        snap = reg.snapshot()
        assert (_value(snap, "hvd_serve_wasted_tokens_total",
                       reason="deadline") or 0) > 0
        assert _value(snap, "hvd_serve_goodput_ratio") == 0.0
        assert _value(snap, "hvd_serve_goodput_tokens_total") in (None,
                                                                  0.0)

    def test_kv_pressure_requeues_are_traced(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=2, max_len=16,
                         total_blocks=2)
        engine.submit(Request("a", tuple(range(1, 9)), max_new_tokens=4))
        engine.submit(Request("b", tuple(range(1, 9)), max_new_tokens=4))
        results = {r.request_id: r
                   for r in engine.run_to_completion()}
        assert results["b"].phase_ms["requeue"] > 0
        roots = {s["tensor"]: s for s in hvd_tracing.get_tracer().spans()
                 if s["stage"] == hvd_tracing.REQUEST}
        assert roots["b"]["attrs"]["requeues"] >= 1
        assert roots["a"]["attrs"]["requeues"] == 0

    def test_flight_dump_names_inflight_requests(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("stuck", (1, 2, 3), max_new_tokens=40))
        engine.step()
        engine.step()  # mid-decode: the request is in flight
        dump = hvd_tracing.get_tracer().flight_snapshot("unit_test")
        open_by_stage = {}
        for s in dump["open_spans"]:
            open_by_stage.setdefault(s["stage"], []).append(s["tensor"])
        assert "stuck" in open_by_stage.get(hvd_tracing.REQUEST, [])
        assert "stuck" in open_by_stage.get(hvd_tracing.DECODE, [])
        # hvd_slo reconstructs it as in-flight work with real phases
        records = hvd_slo.requests_from_dumps([dump])
        (rec,) = [r for r in records if r["request_id"] == "stuck"]
        assert rec["inflight"] and rec["outcome"] == "inflight"
        assert rec["phase_ms"]["prefill"] > 0
        # and the postmortem names it in the blame reasons
        hvd_postmortem.rebase([dump])
        verdict = hvd_postmortem.analyze([dump])
        assert verdict["inflight_requests"] == ["stuck"]
        assert any("stuck" in r for r in verdict["reasons"])
        engine.run_to_completion()  # drain: no leaked slots after

    def test_tracing_off_engine_still_serves(self, reg, monkeypatch):
        monkeypatch.setenv("HVD_SERVE_TRACE", "0")
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=5))
        (res,) = engine.run_to_completion()
        assert res.outcome == "completed"
        assert res.trace_id is None and res.phase_ms is None
        tracer = hvd_tracing.get_tracer()
        assert not [s for s in tracer.spans()
                    if s["stage"] in hvd_tracing.SERVE_STAGES]


# ---------------------------------------------------------------------------
# the step record: one per engine step, written from inside the step
# ---------------------------------------------------------------------------

class SteppingUsClock(FakeUsClock):
    """Every read is one tick later than the last: intervals count the
    clock reads inside them, so the record's numbers are exact."""

    TICK = 1_000

    def ts_us(self):
        self.now_us += self.TICK
        return self.now_us


@pytest.fixture
def stepping(reg, monkeypatch):
    """The process tracer on a stepping clock (``reg`` restores it)."""
    tracer = hvd_tracing.Tracer(rank=0, clock=SteppingUsClock())
    monkeypatch.setattr(hvd_tracing, "_tracer", tracer)
    return tracer


def _tiles(rec):
    """The phases cover [start_us, end_us] with no overlap and no hole."""
    edges = [rec["start_us"]]
    for name, start, end in rec["phases"]:
        assert name in serve_tracing.STEP_PHASES
        assert start == edges[-1] and end > start, rec["phases"]
        edges.append(end)
    return edges[-1] == rec["end_us"]


class TestStepTrace:
    def test_a_step_that_admits_two_and_decodes_tiles_exactly(
            self, stepping):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        # compiled first: a dispatch that compiles reads what is unread
        engine.submit(Request("warm", (9, 9, 9), max_new_tokens=2))
        engine.run_to_completion()
        warm = len(stepping.steps())
        joins, retires = [], []
        sched = engine.scheduler
        join, retire = sched.join, sched.retire
        sched.join = lambda rid: joins.append(rid) or join(rid)
        sched.retire = lambda slot: retires.append(slot) or retire(slot)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=4))
        engine.submit(Request("b", (2, 7, 1, 8, 2), max_new_tokens=3))
        assert engine.step() == []
        (rec,) = stepping.steps()[warm:]
        # kv_bytes, passes: on a step that decoded, and on no other (below)
        assert set(rec) == {"seq", "start_us", "end_us", "phases",
                            "launches", "reads", "kv_bytes", "passes",
                            *serve_tracing.STEP_COUNTS}
        assert rec["passes"] == 1   # a stack that runs once
        assert rec["seq"] == warm + 1 and _tiles(rec)
        # everything is launched before anything is read: both prefills,
        # the pass over both rows, and only then each first token, in
        # admission order, with its bookkeeping. Both slots busy and
        # neither row on its last token: the step returns with its pass
        # in flight, and reads nothing of it
        launch, read = ["admit", "prefill"], ["prefill_readback",
                                              "bookkeeping"]
        assert [p[0] for p in rec["phases"]] == ["control"] + 2 * launch + [
            "decode_prepare", "decode_dispatch"] + 2 * read + ["telemetry"]
        # nothing but the phase's own closing read inside these, and in
        # a dispatch the two reads around its one launch besides
        tick = SteppingUsClock.TICK

        def bare(rec):
            for name, start, end in rec["phases"]:
                if name in ("control", "decode_readback",
                            "prefill_readback"):
                    assert end - start == tick, name
                elif name == "decode_dispatch":
                    assert end - start == 3 * tick
        bare(rec)
        # the counts are what the scheduler did in that step
        assert rec["admitted"] == len(joins) == 2
        assert rec["active"] == 2 and rec["cohorts"] == 1
        assert rec["prompt_tokens"] == 3 + 5
        assert rec["retired"] == len(retires) == 0
        assert rec["ahead"] == 1 and rec["admitted_ahead"] == 2
        # both rows greedy: the pass's sampler drew nothing
        assert "drew" in serve_tracing.STEP_COUNTS and rec["drew"] == 0
        # the next step launches its pass, then reads the one before
        # (the first readback) and, b being on its last token, its own
        (b,) = engine.step()
        assert b.request_id == "b" and len(b.tokens) == 3
        rec = stepping.steps()[-1]
        assert [p[0] for p in rec["phases"]] == [
            "control", "decode_prepare", "decode_dispatch",
            "decode_readback", "telemetry", "bookkeeping",
            "decode_readback", "bookkeeping", "telemetry"]
        bare(rec)
        assert _tiles(rec) and rec["ahead"] == 0 and rec["active"] == 2
        # a slot is free from here on: the synchronous order, as ever
        done = [b] + engine.run_to_completion()
        recs = stepping.steps()[warm:]
        assert [p[0] for p in recs[-1]["phases"]] == [
            "control", "admit", "decode_prepare", "decode_dispatch",
            "decode_readback", "telemetry", "bookkeeping", "telemetry"]
        bare(recs[-1])
        assert [r["seq"] for r in recs] == \
            list(range(warm + 1, warm + len(recs) + 1))
        assert all(_tiles(r) for r in recs)
        assert sum(r["retired"] for r in recs) == len(retires) == \
            len(done) == 2
        assert sum(r["admitted"] for r in recs) == 2
        # b retires in step 2, a in step 3: rows decoded 2, 2, 1
        assert [r["active"] for r in recs] == [2, 2, 1]
        assert [r["retired"] for r in recs] == [0, 1, 1]
        # steps follow one another on the one clock
        assert all(a["end_us"] <= b["start_us"]
                   for a, b in zip(recs, recs[1:]))

    def test_a_decode_tick_names_its_step_and_the_dump_holds_the_steps(
            self, stepping):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=4))
        engine.run_to_completion()
        recs = stepping.steps()
        ticks = [s for s in stepping.spans()
                 if s["stage"] == hvd_tracing.DECODE_TICK]
        assert [t["attrs"]["step"] for t in ticks] == \
            [r["seq"] for r in recs if r["active"]]
        by_seq = {r["seq"]: r for r in recs}
        for t in ticks:  # the tick lies inside the step it names
            r = by_seq[t["attrs"]["step"]]
            assert r["start_us"] < t["start_us"] < t["end_us"] < r["end_us"]
        dump = stepping.flight_snapshot("unit_test")
        assert dump["steps"] == recs
        json.dumps(dump)
        # an idle step is a record too: it looked at an empty queue
        engine.step()
        idle = stepping.steps()[-1]
        assert [p[0] for p in idle["phases"]] == ["control", "admit",
                                                  "telemetry"]
        assert not any(idle[c] for c in serve_tracing.STEP_COUNTS)
        assert "kv_bytes" not in idle

    def test_the_ring_drops_the_oldest_past_4096(self):
        tracer = hvd_tracing.Tracer(rank=0, clock=FakeUsClock())
        assert hvd_tracing.STEP_RING == 4096
        for i in range(hvd_tracing.STEP_RING + 5):
            tracer.record_step({"seq": tracer.next_step_seq()})
        seqs = [r["seq"] for r in tracer.steps()]
        assert seqs == list(range(6, hvd_tracing.STEP_RING + 6))
        # the step ring is its own: it pushes no span out
        assert tracer.flight_snapshot()["spans_dropped"] == 0

    def test_tracing_off_leaves_no_record_and_makes_no_annotation(
            self, stepping, monkeypatch):
        made = []

        class Spy(serve_tracing.TraceAnnotation):
            def __init__(self, name, **kw):
                made.append(name)
                super().__init__(name, **kw)
        monkeypatch.setattr(serve_tracing, "TraceAnnotation", Spy)
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        monkeypatch.setenv("HVD_SERVE_TRACE", "0")
        engine.submit(Request("off", (3, 1, 4), max_new_tokens=3))
        (res,) = engine.run_to_completion()
        assert res.outcome == "completed"
        assert stepping.steps() == [] and made == []
        assert serve_tracing.begin_step() is serve_tracing.NULL_STEP
        assert stepping.clock.now_us == 0  # not one clock read
        # the switch is read at each step: on again, the next step records
        monkeypatch.setenv("HVD_SERVE_TRACE", "1")
        engine.submit(Request("on", (3, 1, 4), max_new_tokens=3))
        engine.run_to_completion()
        recs = stepping.steps()
        assert len(recs) == 2
        assert made.count("hvd.serve.step") == 2
        assert set(made) == {"hvd.serve.step"} | {
            "hvd.serve." + p[0] for r in recs for p in r["phases"]}

    def test_tracing_off_carries_no_launch_number_and_the_same_tokens(
            self, stepping, monkeypatch):
        cfg, params = _tiny()

        def serve():
            engine = _engine(cfg, params, seed=3)
            engine.submit(Request("g", (3, 1, 4), max_new_tokens=5))
            engine.submit(Request("s", (2, 7, 1, 8), max_new_tokens=4,
                                  temperature=0.9))
            return engine, {r.request_id: r.tokens
                            for r in engine.run_to_completion()}
        _, on = serve()
        recorded = len(stepping.steps())
        assert recorded and all(r["launches"] for r in stepping.steps())
        monkeypatch.setenv("HVD_SERVE_TRACE", "0")
        engine, off = serve()
        assert off == on and len(stepping.steps()) == recorded
        # what the null step hands out is no number: a pass launched with
        # the switch off and read with it on books no read
        engine.submit(Request("x", (3, 1, 4), max_new_tokens=4))
        engine.submit(Request("y", (3, 1, 4), max_new_tokens=4))
        engine.step()
        assert engine._unread[2] is None
        monkeypatch.setenv("HVD_SERVE_TRACE", "1")
        engine.step()
        rec = stepping.steps()[-1]
        assert [lc[1] for lc in rec["launches"]] == ["_decode_jit"]
        assert "decode_readback" in [p[0] for p in rec["phases"]]
        assert rec["reads"] == []

    def test_a_step_that_raises_still_closes_its_record(
            self, stepping, monkeypatch):
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=3))

        def boom(*a, **kw):
            raise RuntimeError("device lost")
        monkeypatch.setattr(engine_mod, "_prefill_jit", boom)
        with pytest.raises(RuntimeError, match="device lost"):
            engine.step()
        (rec,) = stepping.steps()
        assert [p[0] for p in rec["phases"]] == ["control", "admit",
                                                 "prefill"]
        assert _tiles(rec) and engine._rec is serve_tracing.NULL_STEP
        # the call that raised is in the ledger, and it was left
        assert [lc[1] for lc in rec["launches"]] == [PREFILL]
        assert all(c0 < c1 for _, _, c0, c1 in rec["launches"])
        assert rec["reads"] == []

    def test_a_profile_shows_the_step_and_its_phases_on_the_host_line(
            self, reg, tmp_path):
        """Under ``jax.profiler.trace`` the annotations land in the
        ``.xplane.pb`` on the Python thread's line, each phase inside
        its ``hvd.serve.step``."""
        import glob
        import jax
        from jax.profiler import ProfileData
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        engine.submit(Request("warm", (3, 1, 4), max_new_tokens=2))
        engine.run_to_completion()  # compile outside the profile
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=4))
        with jax.profiler.trace(str(tmp_path)):
            engine.run_to_completion()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events
                  if e.name.startswith("hvd.serve.")]
        steps = [e for e in events if e[0] == "hvd.serve.step"]
        recs = hvd_tracing.get_tracer().steps()[-len(steps):]
        assert len(steps) == 3 == len(recs)
        names = {e[0] for e in events}
        assert names == {"hvd.serve.step"} | {
            "hvd.serve." + p[0] for r in recs for p in r["phases"]}
        for name, start, end in events:
            if name != "hvd.serve.step":
                assert any(s <= start and end <= e for _, s, e in steps)


# ---------------------------------------------------------------------------
# the launch ledger: every dispatch where it is made, every read-back naming
# the launch it waited for
# ---------------------------------------------------------------------------

PREFILL, WRITE, DECODE = "_prefill_jit", "_write_slot", "_decode_jit"
# an admission's launches: its key is folded on the host (serving/host_key.py)
# and is no launch
ADMISSION = [PREFILL, WRITE]


def _inside(rec, t0, t1):
    """The name of the phase entry that [t0, t1] lies in."""
    (name,) = [n for n, s, e in rec["phases"] if s <= t0 and t1 <= e]
    return name


def _warm(engine, n=1):
    for i in range(n):
        engine.submit(Request(f"warm{i}", (9, 9, 9), max_new_tokens=2))
    engine.run_to_completion()


class TestLaunchLedger:
    def test_every_jitted_call_of_a_step_is_one_entry_and_numbers_run_on(
            self, stepping, monkeypatch):
        """Counted against the calls themselves, over a run that admits,
        decodes ahead, retires and admits again."""
        import jax
        from horovod_tpu.serving import engine as engine_mod
        calls, eager_folds = [], []

        def counted(name, fn):
            def call(*a, **kw):
                calls.append(name)
                return fn(*a, **kw)
            return call

        def fold_in(key, count):
            # an eager fold has a Python count: the programs' is traced
            if isinstance(count, int):
                eager_folds.append(count)
            return real_fold_in(key, count)
        for name, attr in ((PREFILL, "_prefill_jit"), (WRITE, "_write_slot"),
                           (DECODE, "_decode_jit")):
            monkeypatch.setattr(engine_mod, attr,
                                counted(name, getattr(engine_mod, attr)))
        real_fold_in = jax.random.fold_in
        monkeypatch.setattr(jax.random, "fold_in", fold_in)
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        for i, new in enumerate((4, 3, 2, 5)):
            engine.submit(Request(f"r{i}", (3, 1, 4, 1 + i),
                                  max_new_tokens=new))
        done = engine.run_to_completion()
        assert len(done) == 4
        recs = stepping.steps()
        flat = [lc for r in recs for lc in r["launches"]]
        assert [lc[1] for lc in flat] == calls and DECODE in calls
        # no device call but the three programs: a prefill's key is folded
        # on the host, never by an eager ``jax.random.fold_in``
        assert eager_folds == [] and calls.count(PREFILL) == 4
        assert [lc[0] for lc in flat] == list(range(1, len(flat) + 1))
        for r in recs:
            for n, program, c0, c1 in r["launches"]:
                assert c0 < c1 and _inside(r, c0, c1) == (
                    "decode_dispatch" if program == DECODE else "prefill")
        # every read names a launch made before it, of the right kind: a
        # first token its prefill, a pass's ids its decode launch; every
        # prefill and every pass is read exactly once, no slot write ever
        program = {lc[0]: lc[1] for lc in flat}
        read = [rd for r in recs for rd in r["reads"]]
        assert sorted(rd[0] for rd in read) == sorted(
            n for n, p in program.items() if p in (PREFILL, DECODE))
        for r in recs:
            for n, start, end in r["reads"]:
                assert [n, start, end][1:] in [p[1:] for p in r["phases"]]
                assert _inside(r, start, end) == (
                    "prefill_readback" if program[n] == PREFILL
                    else "decode_readback")
                c1 = next(lc[3] for lc in flat if lc[0] == n)
                assert c1 <= start

    @pytest.mark.parametrize("temperature", [0.0, 1.0])
    def test_an_admission_is_two_launches_its_prefill_and_its_slot_write(
            self, stepping, temperature):
        """Greedy or sampled: the prefill's key launches nothing."""
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        _warm(engine)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=4,
                              temperature=temperature))
        engine.step()
        rec = stepping.steps()[-1]
        assert rec["admitted"] == 1
        assert [lc[1] for lc in rec["launches"]] == [PREFILL, WRITE, DECODE]
        # the first launch of the step is the prefill, and it is what the
        # first token's read waited for
        assert rec["reads"][0][0] == rec["launches"][0][0]

    def test_a_step_that_admits_two_launches_all_five_then_reads_the_two(
            self, stepping):
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        _warm(engine)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=4))
        engine.submit(Request("b", (2, 7, 1, 8, 2), max_new_tokens=3))
        engine.step()
        rec = stepping.steps()[-1]
        assert [lc[1] for lc in rec["launches"]] == 2 * ADMISSION + [DECODE]
        first = rec["launches"][0][0]
        assert [lc[0] for lc in rec["launches"]] == \
            list(range(first, first + 5))
        # the two prefills, in admission order; the pass is in flight
        assert [rd[0] for rd in rec["reads"]] == [first, first + 2]
        assert rec["ahead"] == 1
        readbacks = [p[1:] for p in rec["phases"]
                     if p[0] == "prefill_readback"]
        assert [rd[1:] for rd in rec["reads"]] == readbacks
        # ... and is the first thing the next step reads, after its own
        # launch; b is on its last token, so that pass is read as well
        engine.step()
        nxt = stepping.steps()[-1]
        assert [lc[:2] for lc in nxt["launches"]] == [[first + 5, DECODE]]
        assert [rd[0] for rd in nxt["reads"]] == [first + 4, first + 5]
        assert nxt["reads"][0][1] >= nxt["launches"][0][3]

    def test_a_third_admission_reads_the_oldest_unread_first(self, stepping):
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=4)
        _warm(engine)
        for rid in "abc":
            engine.submit(Request(rid, (3, 1, 4), max_new_tokens=4))
        engine.step()
        rec = stepping.steps()[-1]
        assert [lc[1] for lc in rec["launches"]] == 3 * ADMISSION + [DECODE]
        first = rec["launches"][0][0]
        assert [rd[0] for rd in rec["reads"]][:3] == \
            [first, first + 2, first + 4]
        # a's token is read with b's prefill and slot write queued behind
        # it and before c's prefill is launched; b's and c's after the
        # decode launch
        a, b, c = rec["reads"][:3]
        assert rec["launches"][3][3] <= a[1] and \
            a[2] <= rec["launches"][4][2]
        assert rec["launches"][6][3] <= b[1] <= c[1]
        # a free slot: the pass is read before the step returns
        assert rec["ahead"] == 0 and rec["reads"][3][0] == first + 6

    @pytest.mark.parametrize("slow,where,key", [
        ("device_get", "decode_readback", "read"),
        ("_decode_jit", "decode_dispatch", "launch")])
    def test_a_slow_tick_says_which_step_and_where(
            self, reg, monkeypatch, slow, where, key):
        import jax
        from horovod_tpu.serving import engine as engine_mod
        monkeypatch.setenv("HVD_SERVE_TRACE_SLOW_TICK_MS", "30")
        cfg, params = _tiny()
        engine = _engine(cfg, params)
        _warm(engine)
        engine.submit(Request("a", (3, 1, 4), max_new_tokens=3))
        engine.step()   # admits; its tick opens after the first token
        target = engine_mod if slow == "_decode_jit" else jax
        real = getattr(target, slow)

        def delayed(*a, **kw):
            time.sleep(0.05)
            return real(*a, **kw)
        monkeypatch.setattr(target, slow, delayed)
        engine.step()
        monkeypatch.setattr(target, slow, real)
        engine.run_to_completion()
        (event,) = _events(reg.snapshot(), "slow_decode_tick")
        rec = hvd_tracing.get_tracer().steps()[-2]
        assert event["step"] == rec["seq"] and event["where"] == where
        assert event[key] == DECODE and event["dur_ms"] >= 50
        assert event["where"] == max(
            rec["phases"], key=lambda p: p[2] - p[1])[0]


# ---------------------------------------------------------------------------
# the acceptance drill: inject a slow phase, the verdict must name it
# ---------------------------------------------------------------------------

class TestSlowPhaseAttribution:
    def test_delayed_prefill_dominates_tail(self, reg, monkeypatch):
        from horovod_tpu.serving import engine as engine_mod
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=2)
        # untimed warmup: compiles must not pollute the measured phases
        engine.submit(Request("warm-a", (1, 2, 3), max_new_tokens=4))
        engine.submit(Request("warm-b", (1, 2, 3, 4, 5),
                              max_new_tokens=4))
        engine.run_to_completion()
        hvd_tracing.reset(enabled=True, rank=0)

        real = engine_mod._prefill_jit

        def delayed(cfg_, params_, tokens, last, temp, rng):
            if int(last) >= 4:  # the 5-token prompts are the slow ones
                time.sleep(0.15)
            return real(cfg_, params_, tokens, last, temp, rng)

        monkeypatch.setattr(engine_mod, "_prefill_jit", delayed)
        # one request in flight at a time: the tail must be owned by
        # the injected prefill delay, not by slot contention
        results = []
        for rid, prompt in [("fast-0", (1, 2, 3)), ("fast-1", (1, 2, 3)),
                            ("slow-0", (1, 2, 3, 4, 5)),
                            ("fast-2", (1, 2, 3)),
                            ("slow-1", (1, 2, 3, 4, 5)),
                            ("fast-3", (1, 2, 3))]:
            engine.submit(Request(rid, prompt, max_new_tokens=4))
            results.extend(engine.run_to_completion())
        assert len(results) == 6

        dump = hvd_tracing.get_tracer().flight_snapshot("drill")
        verdict = hvd_slo.analyze_serve([dump], pct=70)
        assert verdict["requests"] == 6
        assert {r["request_id"] for r in verdict["tail"]} == \
            {"slow-0", "slow-1"}
        assert verdict["dominant_phase"] == "prefill"
        assert "dominated by prefill" in verdict["verdict"]
        assert not verdict["kv_pressure"]

    def test_kv_pressure_requeue_dominates_tail(self, reg):
        cfg, params = _tiny()
        engine = _engine(cfg, params, num_slots=2, max_len=16,
                         total_blocks=2)
        engine.submit(Request("warm", tuple(range(1, 9)),
                              max_new_tokens=4))
        engine.run_to_completion()
        hvd_tracing.reset(enabled=True, rank=0)

        # "a" holds the whole block budget for 8 decode steps; "b"
        # bounces off the ledger every step until "a" retires
        engine.submit(Request("a", tuple(range(1, 9)),
                              max_new_tokens=8))
        engine.submit(Request("b", tuple(range(1, 9)),
                              max_new_tokens=2))
        results = engine.run_to_completion()
        assert all(r.outcome == "completed" for r in results)

        dump = hvd_tracing.get_tracer().flight_snapshot("drill")
        verdict = hvd_slo.analyze_serve([dump], pct=50)
        (tail,) = verdict["tail"]
        assert tail["request_id"] == "b"
        assert tail["requeues"] >= 1
        assert verdict["dominant_phase"] in ("queue_wait", "requeue")
        assert verdict["kv_pressure"]
        assert "KV pressure" in verdict["verdict"]

    def test_selftest_passes(self, capsys):
        assert hvd_slo.selftest() == 0
        assert "ok" in capsys.readouterr().out
