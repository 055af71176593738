"""Router plane (horovod_tpu/router/): dispatch scoring math, cache-
affinity stickiness, the exactly-once reroute ledger on replica loss,
and the SLO-gated canary state machine on synthetic histograms. All
process-local — the router sees engines through a four-method surface
(submit/step/load_snapshot/active_count + queue), so a test double
stands in and no jax is imported. The 2-process replica-loss and
poisoned-canary drills ride test_chaos_plane.py."""

import pytest

from horovod_tpu.router import CanaryController, Router
from horovod_tpu.router import canary as route_canary
from horovod_tpu.router import policy as route_policy
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
    """ServeEngine stand-in: holds admitted requests until the test
    says finish(), and lets the test pin the load snapshot exactly."""

    def __init__(self, accept=True, generation=1):
        self.accept = accept
        self.generation = generation
        self.queue = []   # router pending() len()s this
        self.held = {}    # request_id -> Request
        self.load = None  # pinned snapshot; None = derive from held
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

    def finish(self, request_id, tokens=(5, 6, 7)):
        req = self.held.pop(request_id)
        self._done.append(RequestResult(
            req.request_id, tuple(tokens), "completed", ttft_s=0.01,
            generation=self.generation))

    def step(self):
        out, self._done = self._done, []
        return out


def _req(i, prompt=None, max_new_tokens=8):
    return Request(request_id=f"r{i}",
                   prompt=prompt if prompt is not None
                   else (100 + i, 200 + i, 300 + i),
                   max_new_tokens=max_new_tokens)


# ---------------------------------------------------------------------------
# policy scoring math
# ---------------------------------------------------------------------------

class TestPolicyScore:
    def test_missing_snapshot_scores_idle(self):
        assert route_policy.score(None) == 0.0
        assert route_policy.score({}) == 0.0

    def test_weighted_sum(self):
        load = {"queue_depth": 2, "active_slots": 3, "work_tokens": 8,
                "free_blocks": 4}
        assert route_policy.score(load) == pytest.approx(
            2 * route_policy.QUEUE_WEIGHT + 3 * route_policy.SLOT_WEIGHT
            + 8 * route_policy.WORK_WEIGHT)

    def test_kv_exhaustion_penalty_dominates_queue_depth(self):
        exhausted = route_policy.score({"queue_depth": 0,
                                        "free_blocks": 0})
        assert exhausted == route_policy.KV_EXHAUSTED_PENALTY
        # a deep queue with blocks free still beats an exhausted replica
        assert route_policy.score({"queue_depth": 10,
                                   "free_blocks": 5}) < exhausted

    def test_work_term_separates_equal_queue_depths(self):
        # a queued 40-token request predicts more occupancy than a
        # queued 8-token one even though queue_depth says they're equal
        long = route_policy.score({"queue_depth": 1, "work_tokens": 40})
        short = route_policy.score({"queue_depth": 1, "work_tokens": 8})
        assert long > short

    def test_round_robin_cycles_id_order(self):
        p = route_policy.RoundRobin()
        picks = [p.choose([2, 0, 1], {}) for _ in range(4)]
        assert picks == [0, 1, 2, 0]

    def test_least_loaded_picks_min_with_id_tiebreak(self):
        p = route_policy.LeastLoaded()
        loads = {0: {"queue_depth": 2}, 1: {"queue_depth": 1},
                 2: {"queue_depth": 1}}
        assert p.choose([0, 1, 2], loads) == 1  # min score, lowest id
        assert p.choose([0, 2], loads) == 2

    def test_least_loaded_treats_unreported_as_idle(self):
        p = route_policy.LeastLoaded()
        # replica 3 has never heartbeated: routable, assumed idle
        assert p.choose([0, 3], {0: {"queue_depth": 1}}) == 3

    def test_prefix_key(self):
        assert route_policy.prefix_key((1, 2, 3, 4), 2) == (1, 2)
        assert route_policy.prefix_key((1, 2), 8) == (1, 2)
        assert route_policy.prefix_key((1, 2), 0) is None
        assert route_policy.prefix_key((), 8) is None

    def test_resolve_env_and_unknown(self, monkeypatch):
        assert isinstance(route_policy.resolve("round_robin"),
                          route_policy.RoundRobin)
        monkeypatch.setenv("HVD_ROUTE_POLICY", "round_robin")
        assert isinstance(route_policy.resolve(),
                          route_policy.RoundRobin)
        monkeypatch.delenv("HVD_ROUTE_POLICY")
        assert isinstance(route_policy.resolve(),
                          route_policy.LeastLoaded)
        with pytest.raises(ValueError, match="HVD_ROUTE_POLICY"):
            route_policy.resolve("fastest_ever")


# ---------------------------------------------------------------------------
# dispatch + affinity stickiness
# ---------------------------------------------------------------------------

class TestDispatch:
    def test_least_loaded_alternates_idle_replicas(self, reg):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0)
        for i in range(4):
            assert router.submit(_req(i))
        assert sorted(engines[0].held) == ["r0", "r2"]
        assert sorted(engines[1].held) == ["r1", "r3"]
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_requests_total", replica="0") == 2
        assert _value(snap, "hvd_route_requests_total", replica="1") == 2
        assert router.inflight == {"r0": 0, "r1": 1, "r2": 0, "r3": 1}

    def test_step_stamps_serving_replica(self, reg):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0)
        router.submit(_req(0))
        router.submit(_req(1))
        engines[1].finish("r1")
        (res,) = router.step()
        assert (res.request_id, res.replica, res.rerouted) == (
            "r1", 1, False)
        assert router.inflight == {"r0": 0}
        assert router.pending()
        engines[0].finish("r0")
        router.step()
        assert not router.pending()

    def test_affinity_sticks_within_slack_then_overflows(self, reg):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        engines[0].load = {"queue_depth": 0}
        engines[1].load = {"queue_depth": 0}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=4)
        prefix = (1, 2, 3, 4)
        # first sighting: miss, pins the prefix to the policy pick (0)
        router.submit(Request("a0", prefix + (9,)))
        assert "a0" in engines[0].held
        # sticky replica costs AFFINITY_SLACK more than the pick: the
        # warmth still wins (score gap 8 <= slack 8)
        engines[0].load = {"queue_depth": 2}
        router.submit(Request("a1", prefix + (8,)))
        assert "a1" in engines[0].held
        # past the slack, load wins and the prefix re-pins to 1
        engines[0].load = {"queue_depth": 3}
        router.submit(Request("a2", prefix + (7,)))
        assert "a2" in engines[1].held
        router.submit(Request("a3", prefix + (6,)))
        assert "a3" in engines[1].held  # re-pinned: hit on 1
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_affinity_total",
                      outcome="miss") == 1
        assert _value(snap, "hvd_route_affinity_total",
                      outcome="hit") == 2
        assert _value(snap, "hvd_route_affinity_total",
                      outcome="overflow") == 1

    def test_distinct_prefixes_do_not_share_stickiness(self, reg):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=4)
        router.submit(Request("p0", (1, 1, 1, 1, 5)))
        router.submit(Request("p1", (2, 2, 2, 2, 5)))
        assert "p0" in engines[0].held
        assert "p1" in engines[1].held  # its own miss, not p0's pin

    def test_rejecting_replica_surfaces_backpressure(self, reg):
        router = Router({0: FakeEngine(accept=False)},
                        affinity_prefix=0)
        assert not router.submit(_req(0))
        assert router.inflight == {}


# ---------------------------------------------------------------------------
# replica loss -> exactly-once reroute
# ---------------------------------------------------------------------------

class TestReroute:
    def _router(self, clock=None):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0, reroute_window_s=30.0,
                        clock=clock or FakeClock())
        for i in range(4):
            router.submit(_req(i))
        return engines, router

    def test_loss_requeues_to_survivor_exactly_once(self, reg):
        engines, router = self._router()
        router.on_ranks_lost([1])
        assert router.live_replicas() == [0]
        # r1/r3 moved off the dead replica; survivors hold each exactly
        # once and the ledger points every request at replica 0
        assert sorted(engines[0].held) == ["r0", "r1", "r2", "r3"]
        assert set(router.inflight.values()) == {0}
        # a second loss notification for the same replica is idempotent
        router.on_ranks_lost([1])
        assert sorted(engines[0].held) == ["r0", "r1", "r2", "r3"]
        snap = reg.snapshot()
        assert _value(snap, "hvd_route_rerouted_total") == 2
        assert _value(snap, "hvd_route_replicas_live") == 1
        lost = _events(snap, "route_replica_lost")
        assert [e["inflight"] for e in lost] == [["r1", "r3"], []]
        moves = _events(snap, "route_reroute")
        assert {(e["request_id"], e["from_replica"], e["to_replica"])
                for e in moves} == {("r1", 1, 0), ("r3", 1, 0)}

    def test_rerouted_results_are_stamped(self, reg):
        engines, router = self._router()
        router.on_ranks_lost([1])
        for rid in list(engines[0].held):
            engines[0].finish(rid)
        results = {r.request_id: r for r in router.step()}
        assert len(results) == 4  # each request finishes exactly once
        assert {k for k, r in results.items() if r.rerouted} == {
            "r1", "r3"}
        assert all(r.replica == 0 for r in results.values())
        assert not router.pending()

    def test_stale_request_fails_loud_instead_of_resurrecting(self, reg):
        clock = FakeClock()
        engines, router = self._router(clock)
        clock.t = 31.0  # past the 30s reroute window
        router.on_ranks_lost([1])
        assert sorted(engines[0].held) == ["r0", "r2"]  # no resurrection
        failed = {r.request_id: r for r in router.step()
                  if r.outcome == "failed"}
        assert sorted(failed) == ["r1", "r3"]
        assert all(r.reason == "reroute_window" and r.replica == 1
                   for r in failed.values())

    def test_no_survivors_fails_the_orphans(self, reg):
        router = Router({0: FakeEngine()}, affinity_prefix=0,
                        clock=FakeClock())
        router.submit(_req(0))
        router.on_ranks_lost([0])
        (res,) = router.step()
        assert (res.outcome, res.reason) == ("failed", "no_survivors")
        assert router.inflight == {}

    def test_survivor_rejection_fails_not_drops(self, reg):
        engines = {0: FakeEngine(), 1: FakeEngine()}
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0, clock=FakeClock())
        router.submit(_req(0))  # lands on replica 0
        engines[1].accept = False
        router.on_ranks_lost([0])
        (res,) = router.step()
        assert (res.outcome, res.reason) == ("failed",
                                             "reroute_rejected")


# ---------------------------------------------------------------------------
# canary rollout on synthetic histograms
# ---------------------------------------------------------------------------

def _canary(reg, **kw):
    kw.setdefault("pct", 50.0)
    kw.setdefault("window", 4)
    kw.setdefault("min_delta_s", 0.025)
    return CanaryController(clock=FakeClock(), **kw)


def _armed_loads(gen=2, replica=1):
    return {0: {"generation": 1, "armed_generation": None},
            replica: {"generation": 1, "armed_generation": gen}}


def _res(i, gen, ttft=0.008, tokens=8, outcome="completed",
         decode_ms=None):
    return RequestResult(
        f"c{i}", tuple(range(tokens)), outcome, ttft_s=ttft,
        generation=gen,
        phase_ms={"decode": decode_ms} if decode_ms is not None
        else None)


def _fill(ctrl, gen_baseline=1, gen_canary=2, canary_ttft=0.008,
          baseline_ttft=0.008, canary_outcomes=("completed",) * 4):
    for i in range(ctrl.window):
        ctrl.observe(_res(f"b{i}", gen_baseline, ttft=baseline_ttft), 0)
    for i, outcome in enumerate(canary_outcomes):
        ctrl.observe(_res(f"k{i}", gen_canary, ttft=canary_ttft,
                          outcome=outcome), 1)


class TestCanary:
    def test_tick_begins_on_armed_generation(self, reg):
        ctrl = _canary(reg)
        ctrl.tick({0: {"generation": 1, "armed_generation": None}})
        assert ctrl.state == "idle"
        ctrl.tick(_armed_loads(gen=2, replica=1))
        assert ctrl.state == "canary"
        assert ctrl.canary_generation == 2
        assert ctrl.canary_replicas == frozenset([1])
        (begin,) = _events(reg.snapshot(), "route_canary_begin")
        assert begin["generation"] == 2 and begin["replicas"] == [1]

    def test_cohort_bounded_when_everyone_arms(self, reg):
        ctrl = _canary(reg, max_canary_replicas=1)
        ctrl.tick({r: {"generation": 1, "armed_generation": 2}
                   for r in range(4)})
        assert ctrl.canary_replicas == frozenset([0])  # first id only
        assert not ctrl.allows_swap(3, 2)  # the rest hold as baseline
        assert ctrl.allows_swap(0, 2)

    def test_filter_splits_traffic_by_stable_hash(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        to_canary = next(f"q{i}" for i in range(200)
                         if route_canary._hash_pct(f"q{i}") < ctrl.pct)
        to_base = next(f"q{i}" for i in range(200)
                       if route_canary._hash_pct(f"q{i}") >= ctrl.pct)
        loads = {0: {"generation": 1}, 1: {"generation": 2}}
        assert ctrl.filter(to_canary, [0, 1], loads) == [1]
        assert ctrl.filter(to_base, [0, 1], loads) == [0]
        # same id, same cohort, every time — no flapping across retries
        assert ctrl.filter(to_canary, [0, 1], loads) == [1]

    def test_filter_availability_beats_cohort_discipline(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        to_canary = next(f"q{i}" for i in range(200)
                         if route_canary._hash_pct(f"q{i}") < ctrl.pct)
        # the canary replica is gone: its traffic still has a home
        assert ctrl.filter(to_canary, [0], {0: {"generation": 1}}) == [0]

    def test_promote_on_healthy_window(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        assert not ctrl.allows_swap(0, 2)  # holdback during canary
        _fill(ctrl, canary_ttft=0.008, baseline_ttft=0.008)
        assert ctrl.state == "promoted"
        assert ctrl.allows_swap(0, 2)  # gates open fleet-wide
        (verdict, evidence) = ctrl.decisions[-1]
        assert verdict == "promote"
        snap = reg.snapshot()
        (ev,) = _events(snap, "route_promote")
        assert ev["canary_n"] == ev["baseline_n"] == 4
        assert ev["ttft_p99_canary"] is not None
        assert _value(snap, "hvd_route_canary_fraction") == 100

    def test_rollback_on_ttft_breach_quarantines(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        _fill(ctrl, canary_ttft=0.4, baseline_ttft=0.008)
        assert ctrl.state == "rolled_back"
        assert 2 in ctrl.quarantined
        assert not ctrl.allows_swap(0, 2)  # quarantine outlives canary
        (verdict, evidence) = ctrl.decisions[-1]
        assert verdict == "rollback"
        assert "ttft_p99" in evidence["breaches"]
        snap = reg.snapshot()
        (ev,) = _events(snap, "route_rollback")
        assert ev["ttft_p99_canary"] > ev["ttft_p99_baseline"]
        assert _value(snap, "hvd_route_canary_fraction") == 0
        # replicas already serving the quarantined generation get no
        # traffic until a newer generation arms
        loads = {0: {"generation": 2}, 1: {"generation": 1}}
        assert ctrl.filter("any", [0, 1], loads) == [1]

    def test_min_delta_floor_absorbs_bucket_quantization(self, reg):
        # ratio 2x but the absolute gap (~2.5ms) is below min_delta_s:
        # fixed buckets can't resolve it, so the verdict is promote
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        _fill(ctrl, canary_ttft=0.004, baseline_ttft=0.002)
        assert ctrl.state == "promoted"

    def test_rollback_on_goodput_drop(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        _fill(ctrl, canary_outcomes=("completed", "completed",
                                     "failed", "failed"))
        assert ctrl.state == "rolled_back"
        (verdict, evidence) = ctrl.decisions[-1]
        assert evidence["breaches"] == ["goodput_ratio"]
        assert evidence["goodput_ratio_canary"] == pytest.approx(0.5)

    def test_cohort_is_the_generation_not_the_replica(self, reg):
        # pre-swap admissions decoded on a canary REPLICA under the old
        # generation count as baseline evidence, not canary evidence
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads())
        for i in range(ctrl.window):
            ctrl.observe(_res(f"o{i}", 1), 1)  # old gen, canary replica
        assert ctrl._stats["baseline"].n == ctrl.window
        assert ctrl._stats["canary"].n == 0
        assert ctrl.state == "canary"  # canary window still empty

    def test_quarantined_generation_never_recanaries(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads(gen=2))
        _fill(ctrl, canary_ttft=0.4)
        assert ctrl.state == "rolled_back"
        ctrl.tick(_armed_loads(gen=2))  # same build arms again: ignored
        assert ctrl.state == "rolled_back"
        ctrl.tick(_armed_loads(gen=3))  # the fixed build starts fresh
        assert ctrl.state == "canary"
        assert ctrl.canary_generation == 3

    def test_promoted_generation_not_reevaluated(self, reg):
        ctrl = _canary(reg)
        ctrl.tick(_armed_loads(gen=2))
        _fill(ctrl)
        assert ctrl.state == "promoted"
        ctrl.tick(_armed_loads(gen=2))  # stale arming gossip: no-op
        assert ctrl.state == "promoted"
        ctrl.tick(_armed_loads(gen=3))
        assert ctrl.state == "canary" and ctrl.canary_generation == 3


# ---------------------------------------------------------------------------
# router + canary integration (fake engines, real cohort steering)
# ---------------------------------------------------------------------------

class TestRouterWithCanary:
    def test_dispatch_respects_cohort_and_results_feed_verdict(self, reg):
        engines = {0: FakeEngine(generation=1),
                   1: FakeEngine(generation=1)}
        ctrl = _canary(reg)
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0, canary=ctrl)
        # replica 1 arms generation 2: the next router step's tick sees
        # it via load snapshots and opens the canary
        engines[1].load = {"generation": 1, "armed_generation": 2}
        router.step()
        assert ctrl.state == "canary"
        engines[1].load = None
        engines[1].generation = 2  # the cohort swaps; baseline holds
        ids = [f"q{i}" for i in range(200)]
        canary_ids = [i for i in ids
                      if route_canary._hash_pct(i) < ctrl.pct][:4]
        base_ids = [i for i in ids
                    if route_canary._hash_pct(i) >= ctrl.pct][:4]
        for rid in canary_ids + base_ids:
            assert router.submit(Request(rid, (1, 2, 3)))
        assert sorted(engines[1].held) == sorted(canary_ids)
        assert sorted(engines[0].held) == sorted(base_ids)
        for rid in canary_ids:
            engines[1].finish(rid)
        for rid in base_ids:
            engines[0].finish(rid)
        router.step()  # results flow through observe() -> verdict
        assert ctrl.state == "promoted"
        assert _events(reg.snapshot(), "route_promote")


# ---------------------------------------------------------------------------
# two real engines behind the router (the only tests here that import jax)
# ---------------------------------------------------------------------------

class TestTwoRealReplicas:
    """24 requests at step 0, alternately 40 and 8 output tokens, each
    with its own prompt, on replicas of 2 slots. Everything is counted
    in router steps (a step drives every live engine one scheduler
    iteration), so no verdict reads a clock."""

    N = 24

    @pytest.fixture(scope="class")
    def arms(self):
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.serving import AdmissionQueue, ServeEngine

        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        _, params = tr.init_params(cfg, jax.random.PRNGKey(0))

        def engine():
            return ServeEngine(
                cfg, params, num_slots=2, max_len=64, kv_block=8, seed=0,
                queue=AdmissionQueue(max_depth=self.N + 8,
                                     admission_timeout_s=1e9))

        def run(tag, submit, step, pending):
            for i in range(self.N):
                prompt = tuple((7 * i + j) % 250 + 1 for j in range(6))
                assert submit(Request(f"{tag}-{i}", prompt,
                                      max_new_tokens=8 if i % 2 else 40,
                                      temperature=0.0))
            done, steps = [], 0
            while pending():
                done.extend((r, steps) for r in step())
                steps += 1
                assert steps < 5000
            assert all(r.outcome == "completed" for r, _ in done)
            # a slot decodes one token a step, so the first token came
            # len(tokens) - 1 steps before the step that finished it
            ttft = sorted(s - (len(r.tokens) - 1) for r, s in done)
            return {
                "ids": sorted(int(r.request_id.split("-")[1])
                              for r, _ in done),
                "tokens_per_step": sum(len(r.tokens)
                                       for r, _ in done) / steps,
                "ttft_p99_steps": ttft[min(len(ttft) - 1,
                                           int(0.99 * len(ttft)))]}

        def routed(policy):
            # shedding off: the whole load arrives at once on purpose
            router = Router({0: engine(), 1: engine()}, policy=policy,
                            shed_depth=0)
            return run(policy, router.submit, router.step, router.pending)

        single = engine()
        return {
            "single": run("single", single.submit, single.step,
                          lambda: single.active_count or
                          len(single.queue)),
            "least_loaded": routed("least_loaded"),
            "round_robin": routed("round_robin")}

    def test_every_policy_completes_the_same_requests(self, arms):
        assert arms["single"]["ids"] == arms["least_loaded"]["ids"] == \
            arms["round_robin"]["ids"] == list(range(self.N))

    def test_two_replicas_nearly_double_tokens_a_router_step(self, arms):
        assert arms["least_loaded"]["tokens_per_step"] >= \
            1.8 * arms["single"]["tokens_per_step"], arms

    def test_least_loaded_ttft_tail_no_worse_than_round_robin(self, arms):
        """Round-robin's parity sends every 40-token request to one
        replica; least_loaded spreads them by the work queued."""
        assert arms["least_loaded"]["ttft_p99_steps"] <= \
            arms["round_robin"]["ttft_p99_steps"], arms
