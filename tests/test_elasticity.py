"""Elasticity plane (docs/elasticity.md): the controller's hysteresis
and canary-style grading, the graceful-drain lifecycle's zero-loss and
bounded-timeout edges, admission shedding with drain-rate retry-after,
per-replica circuit breakers, and the staleness exclusion that keeps a
silent replica from absorbing all traffic. All process-local on the
same four-method engine double tests/test_router.py uses; the
multi-process flap-storm and overload drills ride
tests/test_chaos_plane.py."""

import pytest

from horovod_tpu.router import (CircuitBreaker, ElasticityController,
                                Router)
from horovod_tpu.router import elastic as route_elastic
from horovod_tpu.serving.engine import ServeEngine
from horovod_tpu.serving.queue import Request, RequestResult
from horovod_tpu.utils import metrics as hvd_metrics


@pytest.fixture
def reg():
    r = hvd_metrics.reset(enabled=True)
    yield r
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


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class FakeEngine:
    """ServeEngine stand-in (same surface tests/test_router.py uses)."""

    def __init__(self, accept=True, generation=1):
        self.accept = accept
        self.generation = generation
        self.queue = []
        self.held = {}
        self.load = None
        self._done = []

    def submit(self, request):
        if not self.accept:
            return False
        self.held[request.request_id] = request
        return True

    @property
    def active_count(self):
        return len(self.held)

    def load_snapshot(self):
        if self.load is not None:
            return dict(self.load)
        return {"queue_depth": 0, "active_slots": len(self.held),
                "work_tokens": sum(r.max_new_tokens
                                   for r in self.held.values()),
                "free_slots": 8 - len(self.held), "free_blocks": 8,
                "generation": self.generation,
                "armed_generation": None}

    def finish(self, request_id, tokens=(5, 6, 7), ttft_s=0.01):
        req = self.held.pop(request_id)
        self._done.append(RequestResult(
            req.request_id, tuple(tokens), "completed", ttft_s=ttft_s,
            generation=self.generation))

    def step(self):
        out, self._done = self._done, []
        return out


class FakeRouter:
    """Just enough router surface for controller-only unit tests."""

    def __init__(self, live=(0,)):
        self.live = list(live)
        self.spawns_pending = 0
        self.drained = []

    def live_replicas(self):
        return sorted(self.live)

    def note_spawn_pending(self):
        self.spawns_pending += 1

    def begin_drain(self, rid):
        if rid not in self.live:
            return False
        self.live.remove(rid)
        self.drained.append(rid)
        return True


def _req(i, prompt=None, max_new_tokens=8):
    return Request(request_id=f"r{i}",
                   prompt=prompt if prompt is not None
                   else (100 + i, 200 + i, 300 + i),
                   max_new_tokens=max_new_tokens)


def _result(i, outcome="completed", ttft_s=0.01, tokens=(1, 2, 3)):
    return RequestResult(f"g{i}", tuple(tokens), outcome,
                         ttft_s=ttft_s)


def _ctrl(clock, spawn=None, **kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 0)
    kw.setdefault("dwell_s", 5.0)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("ttft_slo_s", 1.0)
    kw.setdefault("up_depth", 4.0)
    kw.setdefault("down_util", 0.25)
    kw.setdefault("window", 4)
    return ElasticityController(spawn=spawn, clock=clock, **kw)


PRESSURE = {"queue_depth": 10, "active_slots": 8, "free_slots": 0,
            "free_blocks": 4}
IDLE = {"queue_depth": 0, "active_slots": 0, "free_slots": 8,
        "free_blocks": 8}


# ---------------------------------------------------------------------------
# ElasticityController: hysteresis
# ---------------------------------------------------------------------------

class TestElasticHysteresis:
    def test_pressure_must_dwell_before_scale_up(self, reg):
        clock = FakeClock()
        spawned = []
        rt = FakeRouter([0])
        ctrl = _ctrl(clock, spawn=lambda r: spawned.append(1) or 7)
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert not spawned  # first sighting only starts the dwell
        clock.t = 4.9
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert not spawned
        clock.t = 5.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert spawned and rt.spawns_pending == 1
        snap = reg.snapshot()
        assert _value(snap, "hvd_elastic_changes_total",
                      action="scale_up") == 1
        (ev,) = _events(snap, "route_elastic_scale_up")
        assert ev["queue_depth"] == 10 and ev["replica"] == 7

    def test_pressure_blip_resets_the_dwell(self, reg):
        clock = FakeClock()
        spawned = []
        rt = FakeRouter([0])
        ctrl = _ctrl(clock, spawn=lambda r: spawned.append(1) or 7)
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        clock.t = 3.0
        ctrl.tick(rt, {0: dict(IDLE, queue_depth=1)}, clock.t)  # blip
        clock.t = 6.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert not spawned  # the dwell restarted at t=6
        clock.t = 11.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert spawned

    def test_cooldown_gates_the_next_change(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0])
        ctrl = _ctrl(clock, spawn=lambda r: 7, window=1)
        clock.t = 5.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, 0.0)
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)  # executes at t=5
        assert rt.spawns_pending == 1
        # grade it benignly so only the cooldown is in the way
        ctrl.observe(_result(1))
        ctrl._maybe_grade(rt, clock.t)
        assert ctrl.state == "steady"
        for t in (6.0, 10.0, 14.9):
            clock.t = t
            ctrl.tick(rt, {0: dict(PRESSURE)}, t)
        assert rt.spawns_pending == 1  # still inside the cooldown
        clock.t = 20.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert rt.spawns_pending == 2

    def test_max_replicas_caps_scale_up(self, reg):
        clock = FakeClock(10.0)
        rt = FakeRouter([0, 1])
        ctrl = _ctrl(clock, spawn=lambda r: 7, max_replicas=2)
        loads = {0: dict(PRESSURE), 1: dict(PRESSURE)}
        ctrl.tick(rt, loads, 0.0)
        ctrl.tick(rt, loads, 10.0)
        assert rt.spawns_pending == 0

    def test_idle_scale_down_drains_cheapest_and_respects_floor(
            self, reg):
        clock = FakeClock()
        rt = FakeRouter([0, 1])
        ctrl = _ctrl(clock, min_replicas=1)
        loads = {0: dict(IDLE, active_slots=1, free_slots=7),
                 1: dict(IDLE)}
        ctrl.tick(rt, loads, 0.0)
        ctrl.tick(rt, loads, 5.0)
        assert rt.drained == [1]  # the idler replica is the victim
        snap = reg.snapshot()
        assert _value(snap, "hvd_elastic_changes_total",
                      action="scale_down") == 1
        (ev,) = _events(snap, "route_elastic_scale_down")
        assert ev["replica"] == 1
        # at the floor, idle pressure never drains the last replica
        ctrl._grade = None
        ctrl.state = "steady"
        ctrl._last_change_ts = None
        ctrl.tick(rt, {0: dict(IDLE)}, 20.0)
        ctrl.tick(rt, {0: dict(IDLE)}, 30.0)
        assert rt.drained == [1]

    def test_kv_starvation_and_ttft_are_pressure(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0])
        ctrl = _ctrl(clock, spawn=lambda r: 7)
        starved = dict(IDLE, queue_depth=1, free_blocks=0)
        ctrl.tick(rt, starved and {0: starved}, 0.0)
        ctrl.tick(rt, {0: starved}, 5.0)
        assert rt.spawns_pending == 1
        (ev,) = _events(reg.snapshot(), "route_elastic_scale_up")
        assert ev["kv_starved"] is True
        # breached TTFT alone is pressure even with shallow queues
        ctrl2 = _ctrl(clock, spawn=lambda r: 8, ttft_slo_s=0.5)
        for i in range(3):
            ctrl2.observe(_result(i, ttft_s=2.0))
        busy = dict(IDLE, queue_depth=1, active_slots=4, free_slots=4)
        ctrl2.tick(rt, {0: dict(busy)}, 10.0)
        ctrl2.tick(rt, {0: dict(busy)}, 15.0)
        assert rt.spawns_pending == 2

    def test_pressure_gauge_tracks_the_band(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0])
        ctrl = _ctrl(clock)
        ctrl.tick(rt, {0: dict(PRESSURE)}, 0.0)
        assert _value(reg.snapshot(), "hvd_elastic_pressure") == 1
        ctrl.tick(rt, {0: dict(IDLE)}, 1.0)
        assert _value(reg.snapshot(), "hvd_elastic_pressure") == -1
        ctrl.tick(rt, {0: dict(IDLE, queue_depth=1, active_slots=4,
                               free_slots=4)}, 2.0)
        assert _value(reg.snapshot(), "hvd_elastic_pressure") == 0


# ---------------------------------------------------------------------------
# ElasticityController: canary-style grading
# ---------------------------------------------------------------------------

class TestElasticGrading:
    def _scale_down(self, clock, rt, ctrl):
        loads = {0: dict(IDLE), 1: dict(IDLE)}
        ctrl.tick(rt, loads, clock.t)
        clock.t += 5.0
        ctrl.tick(rt, loads, clock.t)
        assert rt.drained and ctrl.state == "grading"

    def test_benign_scale_down_promotes(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0, 1])
        ctrl = _ctrl(clock, spawn=lambda r: 9, window=4)
        for i in range(4):
            ctrl.observe(_result(i))  # the pre-change baseline
        self._scale_down(clock, rt, ctrl)
        for i in range(4):
            ctrl.observe(_result(10 + i))  # unchanged SLO after
        clock.t += 1.0
        ctrl.tick(rt, {0: dict(IDLE, queue_depth=1, active_slots=4,
                               free_slots=4)}, clock.t)
        assert ctrl.state == "steady"
        assert rt.spawns_pending == 0  # no rollback
        (verdict, evidence) = ctrl.decisions[-1]
        assert verdict == "promote" and evidence["breaches"] == []
        assert _events(reg.snapshot(), "route_elastic_promote")

    def test_breached_scale_down_rolls_back_by_respawning(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0, 1])
        respawned = []
        ctrl = _ctrl(clock, spawn=lambda r: respawned.append(9) or 9,
                     window=4, ttft_x=1.5, min_delta_s=0.025)
        for i in range(4):
            ctrl.observe(_result(i, ttft_s=0.01))
        self._scale_down(clock, rt, ctrl)
        for i in range(4):
            ctrl.observe(_result(10 + i, ttft_s=1.5))  # SLO got worse
        clock.t += 1.0
        ctrl.tick(rt, {0: dict(IDLE)}, clock.t)
        assert ctrl.state == "steady"
        assert respawned == [9] and rt.spawns_pending == 1
        (verdict, evidence) = ctrl.decisions[-1]
        assert verdict == "rollback"
        assert "ttft_p99" in evidence["breaches"]
        assert evidence["respawned"] == 9
        snap = reg.snapshot()
        assert _value(snap, "hvd_elastic_changes_total",
                      action="rollback") == 1
        (ev,) = _events(snap, "route_elastic_rollback")
        assert ev["action"] == "scale_down"
        assert [t["action"] for t in ctrl.transitions] == \
            ["scale_down", "rollback"]

    def test_one_change_at_a_time_while_grading(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0, 1])
        ctrl = _ctrl(clock, spawn=lambda r: 9, window=4)
        for i in range(4):
            ctrl.observe(_result(i))
        self._scale_down(clock, rt, ctrl)
        clock.t += 20.0  # well past dwell AND cooldown
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        clock.t += 5.0
        ctrl.tick(rt, {0: dict(PRESSURE)}, clock.t)
        assert rt.spawns_pending == 0  # the grade still holds the lock

    def test_baseline_freezes_before_the_change(self, reg):
        clock = FakeClock()
        rt = FakeRouter([0, 1])
        ctrl = _ctrl(clock, window=4)
        for i in range(4):
            ctrl.observe(_result(i, ttft_s=0.01))
        self._scale_down(clock, rt, ctrl)
        base = ctrl._grade["baseline"]
        n_before = base.n
        ctrl.observe(_result(99, ttft_s=9.0))  # post-change result
        assert base.n == n_before  # never contaminates the 'before'
        assert ctrl._grade["after"].n == 1


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def _breaker(self, clock, **kw):
        kw.setdefault("fails", 3)
        kw.setdefault("probe_s", 2.0)
        kw.setdefault("close_n", 2)
        kw.setdefault("timeout_s", 10.0)
        return CircuitBreaker(clock=clock, **kw)

    def test_consecutive_failures_trip_open(self, reg):
        clock = FakeClock()
        br = self._breaker(clock)
        br.record_failure(0)
        br.record_failure(0)
        assert br.state(0) == route_elastic.CLOSED
        br.record_failure(0)
        assert br.state(0) == route_elastic.OPEN
        allowed, probe = br.filter([0, 1])
        assert allowed == [1] and probe is None  # probe not due yet
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_breaker_state", replica="0") == 2
        assert _value(snap, "hvd_route_breaker_trips_total",
                      reason="dispatch_failed") == 1

    def test_success_resets_the_failure_streak(self, reg):
        clock = FakeClock()
        br = self._breaker(clock)
        br.record_failure(0)
        br.record_failure(0)
        br.record_success(0)
        br.record_failure(0)
        br.record_failure(0)
        assert br.state(0) == route_elastic.CLOSED

    def test_probe_halfopen_close_cycle(self, reg):
        clock = FakeClock()
        br = self._breaker(clock)
        for _ in range(3):
            br.record_failure(0)
        clock.t = 1.0
        allowed, probe = br.filter([0])
        assert probe is None  # first probe waits the full interval
        clock.t = 2.5
        allowed, probe = br.filter([0])
        assert allowed == [] and probe == 0
        br.mark_probe(0)
        _, again = br.filter([0])
        assert again is None  # one probe per interval, not a flood
        br.record_success(0)
        assert br.state(0) == route_elastic.HALF_OPEN
        br.record_success(0)
        assert br.state(0) == route_elastic.CLOSED
        snap = reg.snapshot()
        states = [e["state"] for e in _events(snap, "route_breaker")]
        assert states == ["open", "half_open", "closed"]
        assert _value(snap, "hvd_route_breaker_state", replica="0") == 0

    def test_halfopen_failure_retrips(self, reg):
        clock = FakeClock()
        br = self._breaker(clock)
        for _ in range(3):
            br.record_failure(0)
        clock.t = 2.5
        br.filter([0])
        br.mark_probe(0)
        br.record_success(0)
        assert br.state(0) == route_elastic.HALF_OPEN
        br.record_failure(0)
        assert br.state(0) == route_elastic.OPEN
        assert _value(reg.snapshot(), "hvd_route_breaker_trips_total",
                      reason="half_open_dispatch_failed") == 1

    def test_stale_and_wedged_trip_immediately(self, reg):
        clock = FakeClock()
        br = self._breaker(clock)
        br.note_stale(3)
        assert br.state(3) == route_elastic.OPEN
        br.note_wedged(4, age_s=12.5)
        assert br.state(4) == route_elastic.OPEN
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_breaker_trips_total",
                      reason="stale_snapshot") == 1
        assert _value(snap, "hvd_route_breaker_trips_total",
                      reason="wedged") == 1
        wedge = [e for e in _events(snap, "route_breaker")
                 if e["reason"] == "wedged"]
        assert wedge[0]["age_s"] == 12.5


# ---------------------------------------------------------------------------
# Router: staleness exclusion (the silent-replica regression)
# ---------------------------------------------------------------------------

class TestStaleExclusion:
    def test_silent_replica_no_longer_absorbs_all_traffic(self, reg):
        # the bug this pins: policy.score(None/stale-idle) == 0.0 is
        # the MOST attractive score, so a replica that stopped
        # reporting looked freshly idle forever and won every dispatch
        clock = FakeClock(10.0)
        busy, silent = FakeEngine(), FakeEngine()
        busy.load = {"queue_depth": 6, "active_slots": 8,
                     "free_slots": 0, "free_blocks": 8}
        silent.load = {"queue_depth": 0, "active_slots": 0,
                       "free_slots": 8, "free_blocks": 8, "ts": 0.0}
        router = Router({0: busy, 1: silent}, policy="least_loaded",
                        stale_s=5.0, shed_depth=0, clock=clock)
        assert router.submit(_req(1))
        # replica 1 scores far better but its snapshot is 10s old
        assert router.inflight["r1"] == 0

    def test_stale_exclusion_feeds_the_breaker(self, reg):
        clock = FakeClock(10.0)
        busy, silent = FakeEngine(), FakeEngine()
        silent.load = {"queue_depth": 0, "ts": 0.0}
        br = CircuitBreaker(fails=3, probe_s=60.0, clock=clock)
        router = Router({0: busy, 1: silent}, breaker=br,
                        stale_s=5.0, shed_depth=0, clock=clock)
        router.submit(_req(1))
        assert br.state(1) == route_elastic.OPEN

    def test_all_stale_falls_back_to_dispatching(self, reg):
        # availability beats discipline: when EVERY snapshot is stale
        # the router keeps dispatching rather than failing everything
        clock = FakeClock(10.0)
        a, b = FakeEngine(), FakeEngine()
        a.load = {"queue_depth": 0, "ts": 0.0}
        b.load = {"queue_depth": 0, "ts": 0.0}
        router = Router({0: a, 1: b}, stale_s=5.0, shed_depth=0,
                        clock=clock)
        assert router.submit(_req(1))

    def test_never_reported_grace_window(self, reg):
        clock = FakeClock(0.0)
        router = Router({0: FakeEngine()}, stale_s=5.0, clock=clock)
        # within the post-add grace window an unreported replica stays
        # routable (a brand-new spawn has not heartbeated yet)...
        fresh, probe = router._usable([0, 7], {0: {"ts": 0.0}}, 0.0)
        assert fresh == [0, 7]
        router._first_seen[7] = 0.0
        # ...and past it, forever-silent means excluded
        fresh, _ = router._usable([0, 7], {0: {"ts": 10.0}}, 10.0)
        assert fresh == [0]

    def test_stale_zero_disables(self, reg):
        clock = FakeClock(10.0)
        eng = FakeEngine()
        eng.load = {"queue_depth": 0, "ts": 0.0}
        router = Router({0: eng}, stale_s=0.0, shed_depth=0,
                        clock=clock)
        assert router.submit(_req(1))
        assert router.inflight["r1"] == 0


# ---------------------------------------------------------------------------
# Router: overload shedding
# ---------------------------------------------------------------------------

class TestShedding:
    def _saturated(self, depth=8):
        eng = FakeEngine()
        eng.load = {"queue_depth": depth, "active_slots": 8,
                    "free_slots": 0, "free_blocks": 4}
        return eng

    def test_sheds_when_every_replica_is_deep(self, reg):
        router = Router({0: self._saturated(), 1: self._saturated()},
                        shed_depth=4, stale_s=0, clock=FakeClock())
        assert router.submit(_req(1)) is False
        assert router.last_shed["reason"] == "queue_depth"
        assert router.last_shed["retry_after_s"] == 1.0  # no rate yet
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_shed_total",
                      reason="queue_depth") == 1
        (ev,) = _events(snap, "route_shed")
        assert ev["request_id"] == "r1" and ev["retry_after_s"] == 1.0
        assert not router.inflight  # rejected AT admission

    def test_kv_exhaustion_reason_when_all_out_of_blocks(self, reg):
        eng = FakeEngine()
        eng.load = {"queue_depth": 0, "free_blocks": 0}
        router = Router({0: eng}, shed_depth=4, stale_s=0,
                        clock=FakeClock())
        assert router.submit(_req(1)) is False
        assert router.last_shed["reason"] == "kv_exhausted"

    def test_headroom_anywhere_admits(self, reg):
        idle = FakeEngine()
        router = Router({0: self._saturated(), 1: idle}, shed_depth=4,
                        stale_s=0, clock=FakeClock())
        assert router.submit(_req(1))
        assert router.inflight["r1"] == 1

    def test_shed_depth_zero_disables(self, reg):
        router = Router({0: self._saturated()}, shed_depth=0,
                        stale_s=0, clock=FakeClock())
        assert router.submit(_req(1))

    def test_retry_after_prices_from_the_drain_rate(self, reg):
        clock = FakeClock()
        eng = FakeEngine()
        router = Router({0: eng}, shed_depth=4, stale_s=0, clock=clock)
        # two completions one second apart -> 1 req/s drain rate
        router.submit(_req(1))
        router.submit(_req(2))
        eng.finish("r1")
        clock.t = 1.0
        router.step()
        eng.finish("r2")
        clock.t = 2.0
        router.step()
        eng.load = {"queue_depth": 7, "active_slots": 8,
                    "free_slots": 0, "free_blocks": 4}
        assert router.submit(_req(3)) is False
        # 2 completions over the 1s since the first one -> 2 req/s;
        # depth 7 -> (7+1)/2 = 4s until the backlog clears
        assert router.last_shed["retry_after_s"] == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# Router: graceful drain
# ---------------------------------------------------------------------------

class TestGracefulDrain:
    def test_drain_excludes_dispatch_but_finishes_inflight(self, reg):
        clock = FakeClock()
        a, b = FakeEngine(), FakeEngine()
        router = Router({0: a, 1: b}, stale_s=0, shed_depth=0,
                        clock=clock)
        router.submit(_req(1, prompt=(1, 2, 3)))
        victim = router.inflight["r1"]
        assert router.begin_drain(victim)
        assert router.live_replicas() == [1 - victim]
        snap = reg.snapshot()
        (ev,) = _events(snap, "route_drain_begin")
        assert ev["replica"] == victim and ev["inflight"] == ["r1"]
        assert _value(snap, "hvd_route_replicas_draining") == 1
        # new work only lands on the survivor
        router.submit(_req(2, prompt=(9, 9, 9)))
        assert router.inflight["r2"] == 1 - victim
        # the draining engine keeps stepping: its request completes
        (a if victim == 0 else b).finish("r1")
        clock.t = 1.0
        results = router.step()
        assert [r.request_id for r in results] == ["r1"]
        assert results[0].outcome == "completed"
        assert not results[0].rerouted  # zero loss, no reroute
        handle = router._handles[victim]
        assert handle.state == handle.RETIRED
        snap = reg.snapshot()
        (done,) = _events(snap, "route_drain_done")
        assert done["replica"] == victim and done["drained_s"] == 1.0
        assert _value(snap, "hvd_route_replicas_draining") == 0

    def test_drain_timeout_reroutes_via_the_ledger(self, reg):
        clock = FakeClock()
        a, b = FakeEngine(), FakeEngine()
        router = Router({0: a, 1: b}, stale_s=0, shed_depth=0,
                        reroute_window_s=60.0, clock=clock)
        router.submit(_req(1, prompt=(1, 2, 3)))
        victim = router.inflight["r1"]
        wedged = a if victim == 0 else b
        survivor_eng = b if victim == 0 else a
        router.begin_drain(victim, timeout_s=5.0)
        clock.t = 6.0
        router.step()
        # force-retired: the remainder rerouted to the survivor
        assert router.inflight["r1"] == 1 - victim
        snap = reg.snapshot()
        (ev,) = _events(snap, "route_drain_timeout")
        assert ev["replica"] == victim and ev["rerouted"] == ["r1"]
        assert ev["drained_s"] == 6.0
        # a late completion from the retired engine can never
        # double-deliver: the engine is no longer stepped
        wedged.finish("r1")
        survivor_eng.finish("r1")
        results = router.step()
        assert [r.request_id for r in results] == ["r1"]
        assert results[0].replica == 1 - victim
        assert results[0].rerouted

    def test_reroute_window_expiry_racing_drain(self, reg):
        # the request is older than the reroute window by the time the
        # drain deadline fires: it must fail loudly, never resurrect
        clock = FakeClock()
        a, b = FakeEngine(), FakeEngine()
        router = Router({0: a, 1: b}, stale_s=0, shed_depth=0,
                        reroute_window_s=5.0, clock=clock)
        router.submit(_req(1, prompt=(1, 2, 3)))
        victim = router.inflight["r1"]
        router.begin_drain(victim, timeout_s=10.0)
        clock.t = 11.0  # past BOTH the drain bound and the window
        router.step()
        results = router.step()  # loss-path failures drain next step
        assert [r.request_id for r in results] == ["r1"]
        assert results[0].outcome == "failed"
        assert results[0].reason == "reroute_window"
        assert "r1" not in router.inflight

    def test_begin_drain_rejects_non_live(self, reg):
        router = Router({0: FakeEngine()}, clock=FakeClock())
        assert router.begin_drain(0)
        assert not router.begin_drain(0)  # already draining
        assert not router.begin_drain(42)  # unknown

    def test_drain_signals_the_engine(self, reg):
        eng = ServeEngine.__new__(ServeEngine)  # surface check only
        assert hasattr(eng, "begin_drain")
        a = FakeEngine()
        a.begin_drain = lambda: setattr(a, "drained", True)
        router = Router({0: a, 1: FakeEngine()}, clock=FakeClock())
        router.begin_drain(0)
        assert getattr(a, "drained", False)


# ---------------------------------------------------------------------------
# Router: scale-up + parked reroutes (no_survivors racing a spawn)
# ---------------------------------------------------------------------------

class TestScaleUpAndParked:
    def test_reroute_parks_against_pending_spawn(self, reg):
        clock = FakeClock()
        a = FakeEngine()
        router = Router({0: a}, stale_s=0, shed_depth=0,
                        reroute_window_s=30.0, clock=clock)
        router.submit(_req(1, prompt=(1, 2, 3)))
        router.note_spawn_pending()
        router.on_ranks_lost([0])
        # no survivors, but a spawn is mid-flight: parked, not failed
        assert not router.step()
        snap = reg.snapshot()
        (ev,) = _events(snap, "route_reroute_parked")
        assert ev["request_id"] == "r1" and ev["from_replica"] == 0
        # the landing spawn absorbs the parked reroute
        fresh = FakeEngine()
        clock.t = 1.0
        router.add_replica(1, fresh)
        assert router.inflight["r1"] == 1
        fresh.finish("r1")
        (res,) = router.step()
        assert res.outcome == "completed" and res.rerouted
        assert res.replica == 1
        assert _events(reg.snapshot(), "route_replica_added")

    def test_parked_reroute_expires_inside_the_window(self, reg):
        clock = FakeClock()
        router = Router({0: FakeEngine()}, stale_s=0, shed_depth=0,
                        reroute_window_s=5.0, clock=clock)
        router.submit(_req(1, prompt=(1, 2, 3)))
        router.note_spawn_pending()
        router.on_ranks_lost([0])
        clock.t = 6.0  # the spawn never lands; the window closes
        router.step()
        (res,) = router.step()
        assert res.outcome == "failed"
        assert res.reason == "reroute_window"
        assert not router._parked

    def test_without_pending_spawn_no_survivors_fails_loudly(self, reg):
        router = Router({0: FakeEngine()}, stale_s=0, shed_depth=0,
                        clock=FakeClock())
        router.submit(_req(1, prompt=(1, 2, 3)))
        router.on_ranks_lost([0])
        (res,) = router.step()
        assert res.outcome == "failed" and res.reason == "no_survivors"

    def test_add_replica_rejects_live_duplicate(self, reg):
        router = Router({0: FakeEngine()}, clock=FakeClock())
        with pytest.raises(ValueError):
            router.add_replica(0, FakeEngine())


# ---------------------------------------------------------------------------
# Router: breaker integration (probe dispatch, wedge detection)
# ---------------------------------------------------------------------------

class TestRouterBreaker:
    def test_rejected_dispatches_trip_and_probe_traffic_recovers(
            self, reg):
        clock = FakeClock()
        sick, ok = FakeEngine(accept=False), FakeEngine()
        sick.load = {"queue_depth": 0, "active_slots": 0,
                     "free_slots": 8, "free_blocks": 8}
        ok.load = {"queue_depth": 5, "active_slots": 8,
                   "free_slots": 0, "free_blocks": 8}
        br = CircuitBreaker(fails=2, probe_s=2.0, close_n=1,
                            clock=clock)
        router = Router({0: sick, 1: ok}, breaker=br, stale_s=0,
                        shed_depth=0, clock=clock)
        # the sick replica scores best, rejects twice, trips open
        assert router.submit(_req(1)) is False
        assert router.submit(_req(2)) is False
        assert br.state(0) == route_elastic.OPEN
        # while open, traffic flows to the scored-worse survivor
        assert router.submit(_req(3))
        assert router.inflight["r3"] == 1
        # probe window fires: the next request IS the probe
        sick.accept = True
        clock.t = 3.0
        assert router.submit(_req(4))
        assert router.inflight["r4"] == 0
        sick.finish("r4")
        router.step()
        assert br.state(0) == route_elastic.CLOSED  # close_n=1

    def test_wedged_inflight_trips_the_breaker(self, reg):
        clock = FakeClock()
        eng = FakeEngine()
        br = CircuitBreaker(fails=3, timeout_s=5.0, probe_s=60.0,
                            clock=clock)
        router = Router({0: eng, 1: FakeEngine()}, breaker=br,
                        stale_s=0, shed_depth=0, clock=clock)
        router.submit(_req(1))
        wedged_on = router.inflight["r1"]
        clock.t = 6.0  # held past the breaker timeout, never finished
        router.step()
        assert br.state(wedged_on) == route_elastic.OPEN
        trips = [e for e in _events(reg.snapshot(), "route_breaker")
                 if e["reason"] == "wedged"]
        assert trips and trips[0]["replica"] == wedged_on


# ---------------------------------------------------------------------------
# end-to-end: the controller drives a real Router
# ---------------------------------------------------------------------------

class TestElasticEndToEnd:
    def test_pressure_spawns_through_the_router(self, reg):
        clock = FakeClock()
        eng = FakeEngine()
        eng.load = {"queue_depth": 10, "active_slots": 8,
                    "free_slots": 0, "free_blocks": 8}

        def spawn(router):
            rid = max(router._handles) + 1
            return router.add_replica(rid, FakeEngine()).replica_id

        ctrl = ElasticityController(
            spawn=spawn, dwell_s=1.0, cooldown_s=100.0, window=4,
            up_depth=4.0, clock=clock)
        router = Router({0: eng}, elastic=ctrl, stale_s=0,
                        shed_depth=0, clock=clock)
        router.step()
        clock.t = 2.0
        router.step()
        assert router.live_replicas() == [0, 1]
        assert ctrl.state == "grading"
        (ev,) = _events(reg.snapshot(), "route_elastic_scale_up")
        assert ev["replica"] == 1

    def test_idle_drains_through_the_router(self, reg):
        clock = FakeClock()
        a, b = FakeEngine(), FakeEngine()
        ctrl = ElasticityController(
            spawn=None, dwell_s=1.0, cooldown_s=100.0, window=4,
            min_replicas=1, down_util=0.25, clock=clock)
        router = Router({0: a, 1: b}, elastic=ctrl, stale_s=0,
                        shed_depth=0, clock=clock)
        router.step()
        clock.t = 2.0
        router.step()
        assert len(router.live_replicas()) == 1
        assert router._draining or any(
            h.state == h.RETIRED for h in router._handles.values())


# ---------------------------------------------------------------------------
# overload on two real engines: nothing offered is lost
# ---------------------------------------------------------------------------

class TestOverloadConservation:
    """72 open-loop arrivals, one every other step (twice what two
    replicas of 2 slots sustain), output lengths 8 or 40
    (examples/serve_lm.py's own load). Counted in router steps; no
    clock decides anything."""

    N = 72

    @pytest.fixture(scope="class")
    def arms(self):
        import os
        import sys
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.serving import AdmissionQueue
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                        "examples"))
        from serve_lm import make_workload

        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
        workload = make_workload(seed=0, n_requests=self.N, rate=0.5)
        arrived = {req.request_id: t for t, req in workload}

        def engine():
            return ServeEngine(
                cfg, params, num_slots=2, max_len=64, kv_block=8, seed=0,
                queue=AdmissionQueue(max_depth=self.N + 8,
                                     admission_timeout_s=1e9))

        def run(shed_depth):
            router = Router({0: engine(), 1: engine()},
                            policy="least_loaded", shed_depth=shed_depth)
            done, sheds, i, steps = [], [], 0, 0
            while i < len(workload) or router.pending():
                while i < len(workload) and workload[i][0] <= steps:
                    if not router.submit(workload[i][1]):
                        sheds.append(dict(router.last_shed))
                    i += 1
                done.extend((r, steps) for r in router.step())
                steps += 1
                assert steps < 5000
            ttft = sorted(s - (len(r.tokens) - 1) - arrived[r.request_id]
                          for r, s in done)
            return {"outcomes": [r.outcome for r, _ in done],
                    "sheds": sheds,
                    "ttft_p99_steps": ttft[min(len(ttft) - 1,
                                               int(0.99 * len(ttft)))]}

        return {"control": run(0), "shed": run(2)}

    def test_control_arm_sheds_nothing_and_finishes_everything(self, arms):
        control = arms["control"]
        assert control["sheds"] == []
        assert control["outcomes"] == ["completed"] * self.N

    def test_completed_plus_shed_is_what_was_offered(self, arms):
        shed = arms["shed"]
        assert len(shed["sheds"]) >= 1
        assert set(shed["outcomes"]) == {"completed"}
        assert len(shed["outcomes"]) + len(shed["sheds"]) == self.N

    def test_every_shed_says_when_to_retry(self, arms):
        assert all(s["retry_after_s"] > 0 for s in arms["shed"]["sheds"])

    def test_shedding_holds_the_admitted_tail(self, arms):
        """The control arm's backlog grows for as long as arrivals
        outrun it, so its admitted TTFT tail (in steps) is at least
        twice that of the arm whose queues are bounded."""
        assert arms["control"]["ttft_p99_steps"] >= \
            2.0 * max(arms["shed"]["ttft_p99_steps"], 1.0), arms
