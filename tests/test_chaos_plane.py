"""Chaos-plane drills: deterministic fault injection on the control-plane
transport (run/chaos.py + the hooks in run/network.py), failure detection
(the coordinator's liveness ledger, ops/negotiation.py), and bounded-time
recovery (BasicClient backoff/resend, RanksLostError fail-fast, elastic
auto-shrink).

Every test here is CPU-only, multi-PROCESS at most over the TCP control
plane (never the jax data plane — multiprocess XLA collectives do not
exist on the CPU backend), and bounded by explicit deadlines: the whole
point of the chaos plane is that no failure mode is allowed to hang, so
no drill is allowed to either.
"""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import horovod_tpu
from horovod_tpu.common.config import HorovodConfig
from horovod_tpu.common.exceptions import RanksLostError
from horovod_tpu.ops import negotiation as neg
from horovod_tpu.run import chaos, network
from horovod_tpu.run.elastic import (DrainReplicaRequest,
                                     ElasticSupervisor,
                                     ReplicaSupervisorClient,
                                     ReplicaSupervisorService,
                                     SpawnReplicaRequest)
from horovod_tpu.run.launch import run

KEY = b"k" * 32

_ENV = {"JAX_PLATFORMS": "cpu"}


def _config(**kw):
    kw.setdefault("fusion_threshold", 0)
    kw.setdefault("stall_warning_time_seconds", 0)
    return HorovodConfig(**kw)


def _addr_map(port):
    return {"local": [("127.0.0.1", port)]}


# module-level so they pickle by reference on the wire
class ApplyRequest:
    def __init__(self, req_id):
        self.req_id = req_id


class ApplyReply:
    def __init__(self, req_id):
        self.req_id = req_id


class CountingService(network.BasicService):
    """Minimal non-dedup'ing service: records every application so tests
    can distinguish applied-once from applied-twice under faults."""

    NAME = "chaos.counting"

    def __init__(self, key):
        self.applied = []
        super().__init__(self.NAME, key)

    def _handle(self, req, client_address):
        if isinstance(req, ApplyRequest):
            self.applied.append(req.req_id)
            return ApplyReply(req.req_id)
        return super()._handle(req, client_address)


@pytest.mark.chaos
class TestChaosSpec:
    def test_malformed_rules_raise(self):
        for bad in ("svc:Msg:drop_request",          # missing prob
                    "svc:Msg:no_such_fault:0.5",     # unknown fault
                    "svc:Msg:drop_request:1.5",      # prob out of range
                    "svc:drop_request:0.5"):         # missing field
            with pytest.raises(ValueError):
                chaos.parse_spec(bad, 0)

    def test_blank_spec_is_empty(self):
        assert chaos.parse_spec("", 0) == []
        assert chaos.parse_spec(" ; ;", 0) == []

    def test_same_seed_same_decisions(self):
        spec = "s:Resp:drop_response:0.3"

        def draws(seed):
            (rule,) = chaos.parse_spec(spec, seed)
            return [rule.fire() for _ in range(200)]

        assert draws(7) == draws(7)
        assert draws(7) != draws(8)

    def test_count_caps_total_injections(self):
        (rule,) = chaos.parse_spec("s:Req:drop_request:1.0:3", 0)
        assert sum(rule.fire() for _ in range(50)) == 3
        assert rule.injected == 3

    def test_injector_filters_by_service(self):
        rules = chaos.parse_spec("hvd.negotiation:*:drop_request:1.0", 0)
        assert not chaos.ChaosInjector("chaos.counting", rules, 50.0)
        inj = chaos.ChaosInjector("hvd.negotiation", rules, 50.0)
        assert inj and inj.decide("request", "CycleRequest") == \
            "drop_request"
        # response-side points never match a request-side fault
        assert inj.decide("response", "CycleResponse") is None

    def test_from_env_without_spec_is_none(self):
        assert "HVD_CHAOS_SPEC" not in os.environ
        assert "HOROVOD_CHAOS_SPEC" not in os.environ
        assert chaos.from_env("hvd.negotiation") is None


@pytest.mark.chaos
class TestClientBackoff:
    def test_full_jitter_bounded_by_cap(self):
        svc = network.BasicService("chaos.backoff", KEY)
        try:
            c = network.BasicClient("chaos.backoff", _addr_map(svc.port),
                                    KEY)
            for attempt in range(12):
                bound = min(0.05 * 2 ** attempt, 1.0)
                for _ in range(8):
                    d = c._backoff_delay(attempt)
                    assert 0.0 <= d <= bound + 1e-9
            # far past the cap crossover: still bounded, no overflow
            assert all(c._backoff_delay(60) <= 1.0 for _ in range(20))
            c.close()
        finally:
            svc.shutdown()


@pytest.mark.chaos
class TestInjectedTransportFaults:
    def test_retry_resends_same_request_verbatim(self, monkeypatch):
        """drop_response with transport retry: the client silently
        reconnects and resends the IDENTICAL request (same req_id on the
        wire) — the property that makes server-side req_id dedup
        sufficient for end-to-end exactly-once."""
        monkeypatch.setenv("HVD_CHAOS_SPEC",
                           "chaos.counting:ApplyReply:drop_response:1.0:1")
        svc = CountingService(KEY)
        try:
            c = network.BasicClient(CountingService.NAME,
                                    _addr_map(svc.port), KEY,
                                    retry_requests=True,
                                    backoff_base_s=0.01)
            resp = c.request(ApplyRequest(7))
            assert isinstance(resp, ApplyReply) and resp.req_id == 7
            # the handler ran twice (apply-then-lose, then the resend);
            # both applications carried the same id
            assert svc.applied == [7, 7]
            assert sum(svc._chaos.stats().values()) == 1
            c.close()
        finally:
            svc.shutdown()

    def test_no_retry_never_double_applies(self, monkeypatch):
        """retry_requests=False: a lost response surfaces as a transport
        error and the request is NOT resent — a non-idempotent service
        sees exactly one application."""
        monkeypatch.setenv("HVD_CHAOS_SPEC",
                           "chaos.counting:ApplyReply:drop_response:1.0:1")
        svc = CountingService(KEY)
        try:
            c = network.BasicClient(CountingService.NAME,
                                    _addr_map(svc.port), KEY)
            with pytest.raises((OSError, EOFError)):
                c.request(ApplyRequest(9))
            assert svc.applied == [9]
            # the rule's count is spent: the next request goes through
            assert c.request(ApplyRequest(10)).req_id == 10
            assert svc.applied == [9, 10]
            c.close()
        finally:
            svc.shutdown()

    def test_truncated_response_reads_as_eof_not_hmac_failure(
            self, monkeypatch):
        monkeypatch.setenv(
            "HVD_CHAOS_SPEC",
            "chaos.counting:ApplyReply:truncate_response:1.0:1")
        svc = CountingService(KEY)
        try:
            c = network.BasicClient(CountingService.NAME,
                                    _addr_map(svc.port), KEY)
            # a mid-frame cut must read as a disconnect (EOFError, which
            # retry logic handles), never as RuntimeError("Security
            # error...") — misdiagnosing faults as auth failures would
            # make every flaky link look like an attack
            with pytest.raises(EOFError):
                c.request(ApplyRequest(1))
            c.close()
        finally:
            svc.shutdown()

    def test_connection_reset_surfaces_as_oserror(self, monkeypatch):
        monkeypatch.setenv("HVD_CHAOS_SPEC",
                           "chaos.counting:ApplyReply:reset:1.0:1")
        svc = CountingService(KEY)
        try:
            c = network.BasicClient(CountingService.NAME,
                                    _addr_map(svc.port), KEY)
            with pytest.raises((OSError, EOFError)):
                c.request(ApplyRequest(1))
            c.close()
        finally:
            svc.shutdown()

    def test_delay_response_is_bounded_by_knob(self, monkeypatch):
        monkeypatch.setenv("HVD_CHAOS_SPEC",
                           "chaos.counting:ApplyReply:delay_response:1.0:1")
        monkeypatch.setenv("HVD_CHAOS_DELAY_MS", "200")
        svc = CountingService(KEY)
        try:
            c = network.BasicClient(CountingService.NAME,
                                    _addr_map(svc.port), KEY)
            t0 = time.monotonic()
            assert c.request(ApplyRequest(3)).req_id == 3
            assert time.monotonic() - t0 >= 0.15
            c.close()
        finally:
            svc.shutdown()

    def test_dup_request_deduped_by_coordinator_req_id(self, monkeypatch):
        """Network-level duplicate delivery of a CycleRequest: the
        handler runs twice, the req_id dedupe collapses it to one
        submission — total ordered work stays exactly one response."""
        monkeypatch.setenv("HVD_CHAOS_SPEC",
                           "hvd.negotiation:CycleRequest:dup_request:1.0:1")
        svc = neg.CoordinatorService(1, KEY, ports=[0], config=_config())
        try:
            c = network.BasicClient(neg.SERVICE_NAME, _addr_map(svc.port),
                                    KEY)
            m = neg.EntryMeta("a", "allreduce", "float32", (4,), 0, False)
            resp = c.request(neg.CycleRequest(0, [m], -1, req_id=1))
            assert sum(svc._chaos.stats().values()) == 1
            assert svc._base_seq + len(svc._responses) == 1
            (r,) = resp.responses
            assert r.kind == r.EXECUTE and r.names == ["a"]
            c.close()
        finally:
            svc.shutdown()


@pytest.mark.chaos
class TestLostResponseInjected:
    def test_dropped_unknown_ids_survive_transport_retry(self, monkeypatch):
        """The ADVICE.md lost-response bug, reproduced with a REAL
        injected fault end-to-end: the first CycleResponse (carrying
        unknown_ids) is dropped on the wire, the client's transport
        retry resends the same req_id, and the deduped retry must return
        the PERSISTED unknown-id verdict. On the pre-fix coordinator the
        retry answered unknown_ids=() and the hit tensors hung forever —
        this test fails on that code."""
        monkeypatch.setenv(
            "HVD_CHAOS_SPEC",
            "hvd.negotiation:CycleResponse:drop_response:1.0:1")
        svc = neg.CoordinatorService(2, KEY, ports=[0], config=_config())
        try:
            c = network.BasicClient(neg.SERVICE_NAME, _addr_map(svc.port),
                                    KEY, retry_requests=True,
                                    backoff_base_s=0.01)
            resp = c.request(neg.CycleRequest(
                0, [], -1, req_id=1, hits=neg.encode_hits([5])))
            assert sum(svc._chaos.stats().values()) == 1  # fault DID fire
            assert resp.unknown_ids == (5,)
            assert svc._seen_req[0] == (1, (5,))
            c.close()
        finally:
            svc.shutdown()


@pytest.mark.chaos
class TestDrillDropResponses:
    def test_negotiation_completes_under_20pct_response_loss(self):
        """Drill (a): 3 real processes negotiate 10 tensors over TCP
        while the coordinator drops 20% of CycleResponses. Required
        outcome: every rank applies the SAME execution order for all 10
        tensors within the deadline — loss slows the control plane, it
        never wedges or reorders it."""
        ports = set()
        while len(ports) < 3:
            ports.add(network.free_port())
        ports_env = ",".join(str(p) for p in sorted(ports))

        def fn():
            import os
            import time

            from horovod_tpu.common.config import HorovodConfig
            from horovod_tpu.ops import negotiation as neg

            rank = int(os.environ.get("HVD_PROCESS_ID", "0"))
            nproc = 3
            addresses = [("127.0.0.1", int(p)) for p in
                         os.environ["HVD_CHAOS_DRILL_PORTS"].split(",")]
            cfg = HorovodConfig(fusion_threshold=0,
                                stall_warning_time_seconds=0)
            worker = neg.NegotiationWorker(rank, nproc, cfg, addresses,
                                           neg.control_key(),
                                           start_timeout_s=60.0)
            names = [f"g{i}" for i in range(10)]
            entries = [neg.EntryMeta(n, "allreduce", "float32", (4,), 0,
                                     False) for n in names]
            applied, ack, req_id = [], -1, 1
            deadline = time.monotonic() + 60.0
            while len(applied) < len(names):
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"rank {rank}: drill deadline exceeded with only "
                        f"{applied} applied")
                try:
                    resp = worker.cycle(entries, ack, req_id=req_id)
                except (OSError, EOFError):
                    # transport retries exhausted: retry the SAME req_id
                    # (the dedupe token) so a half-applied cycle cannot
                    # double-submit
                    time.sleep(0.05)
                    continue
                entries = []  # recorded server-side under this req_id
                req_id += 1
                for i, r in enumerate(resp.responses):
                    seq = resp.base_seq + i
                    if seq <= ack:
                        continue
                    assert seq == ack + 1, "gap in the response log"
                    assert r.kind == r.EXECUTE, r.error
                    applied.extend(r.names)
                    ack = seq
                time.sleep(0.005)
            # final heartbeat delivers ack=9 (the request always lands;
            # only responses are being dropped)
            for _ in range(5):
                try:
                    worker.cycle([], ack, req_id=req_id)
                    break
                except (OSError, EOFError):
                    time.sleep(0.05)
            stats = None
            if rank == 0:
                svc = worker.service
                deadline = time.monotonic() + 60.0
                while not (len(svc._acks) == nproc and
                           min(svc._acks.values()) >= len(names) - 1):
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"acks never converged: {svc._acks}")
                    time.sleep(0.02)
                stats = svc._chaos.stats()
            worker.close(linger_s=0.5)
            return applied, stats

        env = dict(_ENV)
        env["HVD_CHAOS_DRILL_PORTS"] = ports_env
        env["HVD_CHAOS_SPEC"] = \
            "hvd.negotiation:CycleResponse:drop_response:0.2"
        env["HVD_CHAOS_SEED"] = "1234"
        t0 = time.monotonic()
        results = run(fn, num_proc=3, env=env, start_timeout_s=180.0)
        elapsed = time.monotonic() - t0
        assert elapsed < 120.0, f"drill took {elapsed:.1f}s"
        orders = [applied for applied, _ in results]
        assert sorted(orders[0]) == [f"g{i}" for i in range(10)]
        assert orders[1] == orders[0] and orders[2] == orders[0]
        stats = results[0][1]
        assert stats is not None and sum(stats.values()) > 0, \
            f"the drill injected nothing: {stats}"


_VICTIM_SCRIPT = r"""
import sys, time
from horovod_tpu.common.config import HorovodConfig
from horovod_tpu.ops import negotiation as neg

port = int(sys.argv[1])
cfg = HorovodConfig(fusion_threshold=0, stall_warning_time_seconds=0)
w = neg.NegotiationWorker(1, 3, cfg, [("127.0.0.1", port)], b"k" * 32,
                          start_timeout_s=30.0)
req_id = 1
while True:  # heartbeat forever, until SIGKILLed by the test
    try:
        w.cycle([], -1, req_id=req_id)
        req_id += 1
    except Exception:
        pass
    time.sleep(0.1)
"""


@pytest.mark.chaos
class TestDrillWorkerKilled:
    def test_killed_rank_fails_fast_with_ranks_lost(self):
        """Drill (b): SIGKILL one worker mid-negotiation. Survivors must
        receive RanksLostError NAMING the dead rank within a bounded
        interval — never the legacy stall-warning hang — and the
        coordinator must fail the pending work it can no longer
        complete."""
        cfg = _config(rank_lost_timeout_seconds=1.5)
        svc = neg.CoordinatorService(3, KEY, ports=[0], config=cfg)
        victim = worker2 = None
        try:
            venv = dict(os.environ)
            venv["JAX_PLATFORMS"] = "cpu"
            venv["PYTHONPATH"] = os.pathsep.join(
                [os.path.dirname(os.path.dirname(horovod_tpu.__file__))] +
                venv.get("PYTHONPATH", "").split(os.pathsep))
            victim = subprocess.Popen(
                [sys.executable, "-c", _VICTIM_SCRIPT, str(svc.port)],
                env=venv, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL)
            # rank 1 is "up" once its first heartbeat lands in the ledger
            deadline = time.monotonic() + 60.0
            while 1 not in svc._last_seen:
                assert time.monotonic() < deadline, \
                    "victim never heartbeated"
                assert victim.poll() is None, \
                    f"victim died early (rc={victim.poll()})"
                time.sleep(0.05)
            worker2 = neg.NegotiationWorker(2, 3, cfg,
                                            [("127.0.0.1", svc.port)],
                                            KEY, start_timeout_s=30.0)
            m = neg.EntryMeta("w", "allreduce", "float32", (4,), 0, False)
            # ranks 0 and 2 announce "w"; rank 1 never will
            svc._handle(neg.CycleRequest(0, [m], -1, req_id=1), ("", 0))
            resp = worker2.cycle([m], -1, req_id=1)
            assert resp.lost_ranks == ()
            victim.send_signal(signal.SIGKILL)
            victim.wait(timeout=10.0)
            t0 = time.monotonic()
            err = None
            req_id = 2
            while time.monotonic() - t0 < 15.0:
                # both survivors keep cycling (their heartbeats also
                # drive the coordinator's liveness scan)
                svc._handle(neg.CycleRequest(0, [], -1, req_id=req_id),
                            ("", 0))
                try:
                    neg.raise_if_ranks_lost(
                        worker2.cycle([], -1, req_id=req_id))
                except RanksLostError as e:
                    err = e
                    break
                req_id += 1
                time.sleep(0.1)
            elapsed = time.monotonic() - t0
            assert err is not None, \
                "survivors never saw RanksLostError (the legacy hang)"
            assert elapsed < 10.0, f"fail-fast took {elapsed:.1f}s"
            assert err.ranks == (1,)
            assert "1" in str(err)
            # the pending tensor was failed, not stranded
            errors = [r for r in svc._responses if r.kind == r.ERROR]
            assert any("RanksLostError" in r.error and r.names == ["w"]
                       for r in errors), errors
        finally:
            if victim is not None and victim.poll() is None:
                victim.kill()
                victim.wait(timeout=10.0)
            if worker2 is not None:
                worker2.close(linger_s=0.0)
            svc.shutdown()


@pytest.mark.chaos
class TestDrillPostmortem:
    def test_flight_dumps_and_postmortem_name_the_faulted_rank(
            self, tmp_path):
        """Drill (c), the tracing plane end to end: 3 real processes,
        every CycleResponse dropped on the wire. Each rank's coordinator
        escalates past the poison grace (RanksLostError naming rank 0),
        auto-dumping its flight recorder to the shared HVD_FLIGHT_DIR —
        then THIS process runs hvd_postmortem over the dumps and the
        verdict must name the faulted rank, the blocking tensor and the
        chaos injections as probable cause. No hand-built fixtures: the
        dumps are exactly what a real incident leaves behind."""

        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common.exceptions import RanksLostError
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            # enqueue immediately: the negotiate span must be open (and
            # announced) well before the ~2s escalation fires
            h = hvd.allreduce_async(np.ones((8,), np.float32),
                                    average=False, name="grad_drill")
            err = None
            try:
                hvd.synchronize(h)
            except RanksLostError as e:
                err = str(e)
            finally:
                try:
                    hvd.shutdown()
                except Exception:  # hvdlint: disable=HVD006(teardown of an already-failed job is best-effort)
                    pass
            return (r, err)

        env = dict(_ENV)
        env["HVD_FLIGHT_DIR"] = str(tmp_path)
        env["HVD_CHAOS_SPEC"] = \
            "hvd.negotiation:CycleResponse:drop_response:1.0"
        env["HVD_CHAOS_SEED"] = "7"
        env["HVD_COORDINATOR_LOST_TIMEOUT_SECONDS"] = "2.0"
        results = run(fn, num_proc=3, env=env, start_timeout_s=180.0)

        by_rank = dict(results)
        assert sorted(by_rank) == [0, 1, 2]
        for r, err in by_rank.items():
            assert err is not None, \
                f"rank {r} never saw RanksLostError under 100% loss"
            assert "0" in err  # the error names the lost rank
        # at least one rank had pending work whose trace id made it
        # into the error text end-to-end
        assert any("[trace " in err for err in by_rank.values()), by_rank

        dumps = sorted(p.name for p in tmp_path.glob("flight-rank*.json"))
        assert dumps == [f"flight-rank{r}.json" for r in range(3)], dumps

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        paths = hvd_postmortem.find_dumps(str(tmp_path))
        loaded, bad = hvd_postmortem.load_dumps(paths)
        assert not bad and len(loaded) == 3
        hvd_postmortem.rebase(loaded)
        verdict = hvd_postmortem.analyze(loaded)
        assert verdict["divergent_rank"] == 0, verdict
        assert verdict["tensor"] == "grad_drill", verdict
        assert verdict["trace_id"], verdict
        assert verdict["chaos_injections"], \
            "rank 0's rings carry no chaos breadcrumbs"
        assert "grad_drill" in verdict["waiting"]
        # and the CLI renders the same story without crashing
        report = hvd_postmortem.render_report(
            loaded, [], verdict, hvd_postmortem.last_cycles(loaded, 8), 0)
        assert "divergent rank : 0" in report
        assert "grad_drill" in report


@pytest.mark.chaos
class TestDrillNumericsDivergence:
    def test_poisoned_rank_yields_postmortem_verdict(self, tmp_path):
        """Drill (d), the numerics plane end to end: 3 real processes
        drive the negotiated control plane over TCP while each rank's
        REAL NumericsMonitor digests its own gradient stream. Rank 0's
        gradients are NaN-poisoned from cycle 2 on; the coordinator's
        divergence sentinel must name rank 0, the tensor, and the first
        bad cycle, solicit flight dumps from every rank — and
        hvd_postmortem over the resulting dumps must reach the same
        verdict. (The data plane never runs: multiprocess XLA
        collectives do not exist on the CPU backend — the digests are
        the product of the same observe path the eager flush feeds.)"""

        port = network.free_port()

        def fn():
            import os
            import time
            import numpy as np
            from horovod_tpu.common.config import HorovodConfig
            from horovod_tpu.ops import negotiation as neg
            from horovod_tpu.utils import metrics as hvd_metrics
            from horovod_tpu.utils import numerics as hvd_numerics
            from horovod_tpu.utils import tracing as hvd_tracing

            rank = int(os.environ["HVD_PROCESS_ID"])
            nproc = 3
            addresses = [("127.0.0.1",
                          int(os.environ["HVD_CHAOS_DRILL_PORTS"]))]
            hvd_metrics.get_registry().rank = rank
            hvd_tracing.reset(enabled=True, rank=rank)
            mon = hvd_numerics.reset(enabled=True)
            cfg = HorovodConfig(fusion_threshold=0,
                                stall_warning_time_seconds=0)
            worker = neg.NegotiationWorker(rank, nproc, cfg, addresses,
                                           neg.control_key(),
                                           start_timeout_s=60.0)
            healthy_red = np.full((16,), 3.0, np.float32)
            solicited = False
            req_id = 0
            try:
                for cyc in range(5):
                    loc = np.full((16,), 1.0 + rank, np.float32)
                    red = healthy_red
                    if rank == 0 and cyc >= 2:
                        loc = loc.copy()
                        loc[::4] = np.nan  # the injected perturbation
                        # a poisoned replica reduces its own corrupt
                        # copy; the healthy peers' post-state disagrees
                        red = loc
                    recs = mon.observe([("grad_poison", loc, red)],
                                       cycle=cyc)
                    digest = hvd_numerics.fold_digest(None, cyc, recs,
                                                      rank=rank)
                    req_id += 1
                    resp = worker.cycle([], -1, req_id=req_id,
                                        digest=digest)
                    solicited = solicited or resp.dump_requested
                # keep heartbeating until the coordinator's escalation
                # solicits a flight dump (it races the loop above)
                deadline = time.monotonic() + 30.0
                while not solicited:
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"rank {rank}: dump never solicited")
                    req_id += 1
                    solicited = worker.cycle(
                        [], -1, req_id=req_id).dump_requested
                    time.sleep(0.02)
                # attach this rank's flight snapshot for the coordinator
                # to persist (eager's loop does this automatically; the
                # drill drives the protocol by hand)
                req_id += 1
                worker.cycle([], -1, req_id=req_id,
                             flight=hvd_tracing.get_tracer()
                             .flight_snapshot("solicited"))
                flagged = first_bad = None
                if rank == 0:
                    svc = worker.service
                    deadline = time.monotonic() + 30.0
                    while len(svc.flight_dumps) < nproc:
                        if time.monotonic() > deadline:
                            raise RuntimeError(
                                f"dumps missing: "
                                f"{sorted(svc.flight_dumps)}")
                        time.sleep(0.02)
                    flagged = dict(svc._numerics_flagged)
                    first_bad = dict(svc._numerics_first_bad)
                return rank, flagged, first_bad
            finally:
                worker.close(linger_s=1.0)

        env = dict(_ENV)
        env["HVD_FLIGHT_DIR"] = str(tmp_path)
        env["HVD_CHAOS_DRILL_PORTS"] = str(port)
        results = run(fn, num_proc=3, env=env, start_timeout_s=180.0)

        by_rank = {r: (flagged, first_bad)
                   for r, flagged, first_bad in results}
        assert sorted(by_rank) == [0, 1, 2]
        flagged, first_bad = by_rank[0]
        # the live sentinel named the rank, the tensor, the first cycle
        assert flagged.get((2, "grad_poison", "nonfinite")) == 0, flagged
        assert any(kind == "divergence" and blamed == 0
                   for (_, _, kind), blamed in flagged.items()), flagged
        assert first_bad == {"grad_poison": 2}

        dumps = sorted(p.name for p in tmp_path.glob("flight-rank*.json"))
        assert dumps == [f"flight-rank{r}.json" for r in range(3)], dumps

        # ...and the offline postmortem reaches the same verdict from
        # nothing but the dumps
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        paths = hvd_postmortem.find_dumps(str(tmp_path))
        loaded, bad = hvd_postmortem.load_dumps(paths)
        assert not bad and len(loaded) == 3
        hvd_postmortem.rebase(loaded)
        verdict = hvd_postmortem.analyze(loaded)
        assert verdict["divergent_rank"] == 0, verdict
        assert verdict["tensor"] == "grad_poison", verdict
        assert verdict["first_bad_cycle"] == 2, verdict
        assert verdict["numerics_anomalies"], verdict
        assert any("numerics" in r for r in verdict["reasons"]), verdict
        report = hvd_postmortem.render_report(
            loaded, [], verdict, hvd_postmortem.last_cycles(loaded, 8), 0)
        assert "divergent rank : 0" in report
        assert "first bad cycle: 2" in report
        assert "grad_poison" in report


class _ExitedProc:
    """A job process that has already exited with a scripted code."""

    def __init__(self, rc):
        self._rc = rc
        self.pid = os.getpid()

    def wait(self, timeout=None):
        return self._rc

    def poll(self):
        return self._rc


@pytest.mark.chaos
class TestElasticAutoShrink:
    def _supervisor(self, rcs, calls, hosts="localhost:4", **kw):
        codes = list(rcs)

        def runner(argv):
            calls.append(list(argv))
            return _ExitedProc(codes.pop(0))

        kw.setdefault("auto_shrink_rc", RanksLostError.EXIT_CODE)
        return ElasticSupervisor(hosts, ["job", "{np}", "{bpa}",
                                         "{restart}"],
                                 ports=(0,), verbose=0, runner=runner, **kw)

    def test_ranks_lost_exit_shrinks_and_restarts(self):
        calls = []
        sup = self._supervisor([RanksLostError.EXIT_CODE, 0], calls)
        try:
            sup.start()
            assert sup.wait(poll_s=0.01) == 0
        finally:
            sup.shutdown()
        assert sup.restarts == 1
        # 4 slots, shrink 1 -> 3, then to 2 so 4 % total == 0 (exact
        # global-batch preservation via batches-per-allreduce)
        assert sup.current_total == 2
        assert calls == [["job", "4", "1", "0"], ["job", "2", "2", "1"]]

    def test_other_exit_codes_pass_through(self):
        calls = []
        sup = self._supervisor([3], calls)
        try:
            sup.start()
            assert sup.wait(poll_s=0.01) == 3
        finally:
            sup.shutdown()
        assert sup.restarts == 0 and len(calls) == 1

    def test_max_restarts_bounds_the_loop(self):
        calls = []
        rc = RanksLostError.EXIT_CODE
        sup = self._supervisor([rc, rc, rc], calls, max_restarts=2)
        try:
            sup.start()
            # shrinks twice (4 -> 2 -> 1), then surfaces the code
            assert sup.wait(poll_s=0.01) == rc
        finally:
            sup.shutdown()
        assert sup.restarts == 2 and len(calls) == 3

    def test_unshrinkable_allocation_surfaces_the_code(self):
        calls = []
        sup = self._supervisor([RanksLostError.EXIT_CODE], calls,
                               hosts="localhost:1")
        try:
            sup.start()
            # 1 slot cannot shrink: the failure surfaces instead of
            # looping
            assert sup.wait(poll_s=0.01) == RanksLostError.EXIT_CODE
        finally:
            sup.shutdown()
        assert sup.restarts == 0


@pytest.mark.chaos
class TestDrillServingReplicaLost:
    def test_replica_loss_is_bounded_and_postmortem_names_the_rank(
            self, tmp_path):
        """Drill (f), the serving plane: 2 replica processes on the
        negotiation control plane. Replica 1 wedges mid-stream (stops
        heartbeating but stays alive — the nasty case: no TCP reset, no
        exit code). Replica 0's engine must turn that silence into a
        bounded-time failover — RanksLostError via its per-step
        heartbeat, a serve_failover event, a flight dump — and KEEP
        SERVING: requests submitted after the failover still complete.
        Then THIS process runs hvd_postmortem over the dumps and the
        verdict must name the lost replica.

        The whole drill runs under HVD_LOCKDEP=1: every control-plane
        lock (coordinator, admission queue, tracer, metrics) is the
        instrumented kind, and the healthy path must produce ZERO
        lockdep findings — no inversions, no stalls — even while a
        peer wedges and the engine fails over."""

        def fn():
            import os
            import time
            import jax
            import jax.numpy as jnp
            from horovod_tpu.models import transformer as tr
            from horovod_tpu.serving.engine import ServeEngine
            from horovod_tpu.serving.queue import AdmissionQueue, Request
            from horovod_tpu.serving.replica import ReplicaGroup
            from horovod_tpu.utils import lockdep
            from horovod_tpu.utils import tracing as hvd_tracing

            r = int(os.environ["HVD_PROCESS_ID"])
            port = int(os.environ["DRILL_PORT"])
            done_file = os.environ["DRILL_DONE_FILE"]
            hvd_tracing.reset(enabled=True, rank=r)
            if r == 1:
                group = ReplicaGroup(r, 2, ("127.0.0.1", port),
                                     key=b"k" * 32,
                                     rank_lost_timeout_s=1.5,
                                     start_timeout_s=120.0)
                # the victim: a few healthy heartbeats, then silence
                for _ in range(3):
                    group.heartbeat()
                    time.sleep(0.05)
                deadline = time.monotonic() + 120.0
                while not os.path.exists(done_file) and \
                        time.monotonic() < deadline:
                    time.sleep(0.1)
                group.close(linger_s=0.0)
                return (r, None, None, None, lockdep.findings())

            # replica 0: a real serving engine riding the group. Warm
            # the jit caches BEFORE joining — multi-second compiles
            # inside the group would stall rank 0's own heartbeats past
            # the 1.5s window and the coordinator's ledger (triggered by
            # the victim's cycles) would declare the WRONG rank lost.
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            warm = ServeEngine(
                cfg, params, num_slots=2, max_len=32, kv_block=8,
                queue=AdmissionQueue(max_depth=8,
                                     admission_timeout_s=1e9))
            warm.submit(Request("warm", (3, 1, 4), max_new_tokens=4))
            warm.run_to_completion()
            group = ReplicaGroup(r, 2, ("127.0.0.1", port), key=b"k" * 32,
                                 rank_lost_timeout_s=1.5,
                                 start_timeout_s=120.0)
            lost_box = []
            queue = AdmissionQueue(max_depth=32, admission_timeout_s=1e9)
            engine = ServeEngine(
                cfg, params, num_slots=2, max_len=32, kv_block=8,
                queue=queue, replica=group,
                on_ranks_lost=lost_box.append)
            for i in range(2):
                engine.submit(Request(f"pre-{i}", (3, 1, 4),
                                      max_new_tokens=24))
            results = []
            t0 = time.monotonic()
            detect_s = None
            while time.monotonic() - t0 < 60.0:
                results.extend(engine.step())
                if lost_box:
                    detect_s = time.monotonic() - t0
                    break
                # pace the decode so pre-* are still mid-stream when
                # the loss lands: the flight dump must catch real
                # in-flight work, not an idle engine
                time.sleep(0.15)
            # release the victim before any assertion can exit early
            with open(done_file, "w") as f:
                f.write("done")
            # failover must not stop the music: post-loss requests serve
            for i in range(2):
                engine.submit(Request(f"post-{i}", (1, 2),
                                      max_new_tokens=4))
            results.extend(engine.run_to_completion())
            completed = sorted(x.request_id for x in results
                               if x.outcome == "completed")
            return (r, detect_s, lost_box, completed, lockdep.findings())

        env = dict(_ENV)
        env["HVD_FLIGHT_DIR"] = str(tmp_path)
        env["HVD_LOCKDEP"] = "1"
        env["DRILL_PORT"] = str(network.free_port())
        env["DRILL_DONE_FILE"] = str(tmp_path / "victim.done")
        results = run(fn, num_proc=2, env=env, start_timeout_s=180.0)

        by_rank = {x[0]: x for x in results}
        _, detect_s, lost_box, completed, _ = by_rank[0]
        # the lock-order sanitizer rode the whole drill on both
        # replicas: the healthy path must be finding-free
        for rank, result in sorted(by_rank.items()):
            assert result[4] == [], (
                f"lockdep findings on replica {rank}: {result[4]}")
        assert detect_s is not None, \
            "replica 0 never detected the wedged peer (the silent hang)"
        assert detect_s < 30.0, f"detection took {detect_s:.1f}s"
        assert lost_box == [(1,)], lost_box
        # serving continued through the failover: every request —
        # submitted before AND after the loss — completed
        assert completed == ["post-0", "post-1", "pre-0", "pre-1"]

        # the drill leaves real dumps behind; the postmortem must blame
        # the lost replica from them alone
        dumps = sorted(p.name for p in tmp_path.glob("flight-rank*.json"))
        assert "flight-rank0.json" in dumps, dumps
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        verdict = hvd_postmortem.analyze(loaded)
        assert verdict["divergent_rank"] == 1, verdict

        # the dump caught the in-flight requests: their request-path
        # spans are open, the failover event names them, and both
        # analyzers surface them by id
        (dump0,) = [d for d in loaded if d.get("rank") == 0]
        open_requests = sorted(
            s["tensor"] for s in dump0.get("open_spans", [])
            if s.get("stage") == "request")
        assert open_requests == ["pre-0", "pre-1"], dump0.get(
            "open_spans")
        (failover,) = [e for e in dump0.get("events", [])
                       if e.get("event") == "serve_failover"]
        assert failover["inflight"] == ["pre-0", "pre-1"], failover
        assert verdict["inflight_requests"] == ["pre-0", "pre-1"], \
            verdict
        assert any("pre-0" in r for r in verdict["reasons"]), \
            verdict["reasons"]
        import hvd_slo
        slo = hvd_slo.analyze_serve(loaded)
        assert slo["inflight"] == ["pre-0", "pre-1"], slo
        assert "pre-0" in slo["verdict"], slo["verdict"]


# ---------------------------------------------------------------------------
# checkpoint-plane drills: a real trainer subprocess under a real
# ElasticSupervisor, killed for real. Deterministic numpy "training"
# (per-step seeded data, loss depends on the whole weight history) so a
# wrong resume shows up as a diverged loss trajectory, not a vibe.
# ---------------------------------------------------------------------------

_DRILL_TRAINER = """\
import os, sys, time

import numpy as np

from horovod_tpu import trainer
from horovod_tpu.common.exceptions import PREEMPTED_EXIT_CODE

ck = trainer.Checkpointer(os.environ["DRILL_CKPT"],
                          every=int(os.environ["DRILL_EVERY"]),
                          async_save=False)
state, start, extra = ck.resume(like={"w": np.zeros(4)})
w = np.asarray(state["w"], dtype=np.float64)
steps = int(os.environ["DRILL_STEPS"])
f = open(os.environ["DRILL_PROG"], "a")
for i in range(start, steps):
    rng = np.random.default_rng(i)  # data position == step: resumable
    g = rng.standard_normal(4)
    w = w - 0.5 * (w - g)
    loss = float(np.sum((w - g) ** 2))
    f.write(f"{i + 1} {loss!r}\\n")
    f.flush()
    os.fsync(f.fileno())
    time.sleep(float(os.environ["DRILL_SLEEP"]))
    if ck.step_end(i + 1, {"w": w}, extra={"data_pos": i + 1}):
        sys.exit(PREEMPTED_EXIT_CODE)
ck.close()
"""


def _drill_trajectory(steps):
    """The uninterrupted run's exact (step, loss) sequence, computed
    in-process with the same arithmetic the drill trainer executes."""
    w = np.zeros(4, dtype=np.float64)
    out = []
    for i in range(steps):
        rng = np.random.default_rng(i)
        g = rng.standard_normal(4)
        w = w - 0.5 * (w - g)
        out.append((i + 1, float(np.sum((w - g) ** 2))))
    return out


def _progress_lines(path):
    if not os.path.exists(path):
        return []
    out = []
    for line in open(path).read().splitlines():
        parts = line.split()
        if len(parts) == 2:  # a kill can tear the final line mid-write
            try:
                out.append((int(parts[0]), float(parts[1])))
            except ValueError:
                pass
    return out


class _CapturingRunner:
    """ElasticSupervisor runner that launches the real subprocess and
    remembers it so the drill can deliver signals to the CURRENT job."""

    def __init__(self, env):
        self.env = env
        self.procs = []

    def __call__(self, argv):
        p = subprocess.Popen(argv, env=self.env,
                             stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
        self.procs.append(p)
        return p


def _run_drill(tmp_path, steps, every, sig, sup_kwargs,
               min_lines_before_kill, rto_bound_s=90.0):
    """Start the drill trainer under a supervisor, kill it once it has
    made progress, and return (exit_code, supervisor, runner, rto_s)."""
    import threading

    prog = str(tmp_path / "progress.log")
    env = dict(os.environ, **_ENV)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))] +
        env.get("PYTHONPATH", "").split(os.pathsep))
    env.update(DRILL_CKPT=str(tmp_path / "ckpt"), DRILL_PROG=prog,
               DRILL_STEPS=str(steps), DRILL_EVERY=str(every),
               DRILL_SLEEP="0.15")
    script = tmp_path / "drill_trainer.py"
    script.write_text(_DRILL_TRAINER)
    runner = _CapturingRunner(env)
    sup = ElasticSupervisor("localhost:2",
                            [sys.executable, str(script)],
                            ports=(0,), verbose=0, runner=runner,
                            **sup_kwargs)
    box = []
    sup.start()
    waiter = threading.Thread(target=lambda: box.append(
        sup.wait(poll_s=0.1)), daemon=True)
    waiter.start()
    try:
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline and \
                len(_progress_lines(prog)) < min_lines_before_kill:
            time.sleep(0.05)
        lines_at_kill = _progress_lines(prog)
        assert len(lines_at_kill) >= min_lines_before_kill, \
            "drill trainer made no progress before the kill"
        os.kill(runner.procs[-1].pid, sig)
        t_kill = time.monotonic()
        # RTO: wall clock from kill to the restarted job's first NEW step
        rto = None
        deadline = t_kill + rto_bound_s
        while time.monotonic() < deadline:
            lines = _progress_lines(prog)
            if lines and lines[-1][0] > lines_at_kill[-1][0]:
                rto = time.monotonic() - t_kill
                break
            time.sleep(0.05)
        assert rto is not None, (
            f"no recovery within the {rto_bound_s:.0f}s RTO bound after "
            f"{signal.Signals(sig).name}")
        waiter.join(timeout=120.0)
        assert box, "supervised job never reached a terminal exit"
        return box[0], sup, runner, rto
    finally:
        sup.shutdown()


@pytest.mark.chaos
class TestDrillCheckpointRestart:
    def test_sigkill_bounded_rto_and_exact_loss_trajectory(self,
                                                           tmp_path):
        """Drill (g), the checkpoint plane's reason to exist: SIGKILL a
        training process mid-run — no handler, no goodbye — under a
        supervisor consuming crashes. Recovery must be bounded in time,
        and the completed run's loss trajectory must match the
        uninterrupted run EXACTLY: same steps, same floats. Anything
        else means resume restored the wrong weights, step, or data
        position."""
        rc, sup, runner, rto = _run_drill(
            tmp_path, steps=10, every=1, sig=signal.SIGKILL,
            sup_kwargs=dict(auto_shrink_rc=-signal.SIGKILL),
            min_lines_before_kill=3)
        assert rc == 0
        assert sup.restarts == 1 and len(runner.procs) == 2
        assert rto < 90.0, f"RTO {rto:.1f}s"
        lines = _progress_lines(str(tmp_path / "progress.log"))
        # a SIGKILL between the progress write and the step_end() commit
        # legally re-runs that one step after resume; the LAST occurrence
        # of every step is the run's verdict
        final = dict(lines)
        expect = dict(_drill_trajectory(10))
        assert sorted(final) == sorted(expect), \
            f"missing/extra steps: got {sorted(final)}"
        for s in expect:
            assert abs(final[s] - expect[s]) < 1e-12, (
                f"loss diverged at step {s}: {final[s]!r} != "
                f"{expect[s]!r} — resume restored the wrong state")
        # each step ran at most twice (the in-flight one), never more
        seen = [s for s, _ in lines]
        assert all(seen.count(s) <= 2 for s in set(seen))

    def test_sigterm_preemption_exits_45_and_no_step_reruns(self,
                                                            tmp_path):
        """Drill (h), preemption-safe exit: SIGTERM must let the
        in-flight step finish, commit an EMERGENCY checkpoint (the
        periodic cadence is every=3 — without the emergency save, steps
        would re-run), exit PREEMPTED_EXIT_CODE, and restart with the
        SAME slots via graceful_restart_rc. The emergency save makes
        resume exact: every step appears EXACTLY once."""
        from horovod_tpu.common.exceptions import PREEMPTED_EXIT_CODE
        rc, sup, runner, rto = _run_drill(
            tmp_path, steps=9, every=3, sig=signal.SIGTERM,
            sup_kwargs=dict(graceful_restart_rc=PREEMPTED_EXIT_CODE),
            min_lines_before_kill=4)
        assert rc == 0
        assert sup.restarts == 1 and len(runner.procs) == 2
        assert runner.procs[0].wait() == PREEMPTED_EXIT_CODE
        assert sup.current_total == 2  # graceful restart never shrinks
        lines = _progress_lines(str(tmp_path / "progress.log"))
        seen = [s for s, _ in lines]
        assert seen == list(range(1, 10)), (
            f"steps must each run exactly once (emergency checkpoint "
            f"resumes at the exact boundary): {seen}")
        expect = dict(_drill_trajectory(9))
        for s, loss in lines:
            assert abs(loss - expect[s]) < 1e-12


# ---------------------------------------------------------------------------
# fleet drill: the whole train->serve weight path under fire. A real
# publishing trainer (subprocess, preempted mid-run) feeds a serving
# replica pair over the negotiation control plane; the replica hot-swaps
# generations mid-traffic, loses its peer, and every injected event must
# be named by the postmortem from the flight dumps alone.
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestDrillFleetHotSwap:
    def test_preemption_replica_loss_swaps_and_parity(self, tmp_path):
        """Drill (i), the fleet plane end to end: a publishing trainer
        runs as a subprocess under an ElasticSupervisor and is SIGTERMed
        mid-traffic (exit 45, emergency publish-commit, same-slot
        restart — the TPU preemption shape). Two replica processes serve
        open-loop Poisson traffic on the control plane; replica 0's
        engine must hot-swap through >=2 published generations WHILE
        decoding (zero drain), survive replica 1 wedging mid-stream, and
        complete every request. Temp-0 parity: each request's tokens
        must be bit-exact against a fresh engine running that
        generation's recomputed weights — a swap that armed the wrong
        bytes diverges here, not in a dashboard. Then hvd_postmortem
        must name every injected event from the dumps: the lost replica,
        the preemption's emergency commit, and each weight swap."""
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_fleet
        import hvd_postmortem

        ckpt_dir = str(tmp_path / "ckpt")
        traffic_started = str(tmp_path / "traffic.started")
        wedge_now = str(tmp_path / "wedge.now")
        done_file = str(tmp_path / "victim.done")

        # pre-publish generation 1 (the trainer's exact step-0 state) so
        # the replica can boot before the trainer exists; the trainer's
        # publisher resumes the generation counter from the pointer
        import jax
        import jax.numpy as jnp
        from horovod_tpu.fleet import WeightPublisher
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.utils import checkpoint as hvd_checkpoint
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        _, params0 = tr.init_params(cfg, jax.random.PRNGKey(0))
        mgr = hvd_checkpoint.CheckpointManager(ckpt_dir, rank=0,
                                               world_size=1,
                                               async_save=False)
        mgr.on_commit = WeightPublisher(ckpt_dir).publish
        mgr.save(params0, step=0, block=True)
        mgr.close()

        trainer_env = dict(os.environ, **_ENV)
        trainer_env["HVD_FLIGHT_DIR"] = str(tmp_path)

        def fn():
            import os
            import time
            import jax
            import jax.numpy as jnp
            from horovod_tpu.fleet import WeightSubscriber
            from horovod_tpu.models import transformer as tr
            from horovod_tpu.serving.engine import ServeEngine
            from horovod_tpu.serving.queue import AdmissionQueue, Request
            from horovod_tpu.serving.replica import ReplicaGroup
            from horovod_tpu.utils import checkpoint as hvd_checkpoint
            from horovod_tpu.utils import tracing as hvd_tracing

            r = int(os.environ["HVD_PROCESS_ID"])
            port = int(os.environ["DRILL_PORT"])
            ckpt = os.environ["DRILL_CKPT"]
            hvd_tracing.reset(enabled=True, rank=r)
            if r == 1:
                group = ReplicaGroup(r, 2, ("127.0.0.1", port),
                                     key=b"k" * 32,
                                     rank_lost_timeout_s=2.0,
                                     start_timeout_s=120.0)
                # healthy heartbeats until told to wedge, then silence
                deadline = time.monotonic() + 180.0
                while not os.path.exists(
                        os.environ["DRILL_WEDGE_FILE"]) and \
                        time.monotonic() < deadline:
                    group.heartbeat()
                    time.sleep(0.1)
                deadline = time.monotonic() + 180.0
                while not os.path.exists(os.environ["DRILL_DONE_FILE"]) \
                        and time.monotonic() < deadline:
                    time.sleep(0.1)
                group.close(linger_s=0.0)
                return (r, None, None, None, None, None, None)

            # replica 0: warm the jit caches BEFORE joining the group
            # (compiles inside would stall heartbeats past the window)
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params0 = tr.init_params(cfg, jax.random.PRNGKey(0))
            warm = ServeEngine(
                cfg, params0, num_slots=2, max_len=48, kv_block=8,
                queue=AdmissionQueue(max_depth=8,
                                     admission_timeout_s=1e9))
            warm.submit(Request("warm", (3, 1, 4), max_new_tokens=4))
            warm.run_to_completion()

            # subscribe to the trainer's publications (boot generation)
            deadline = time.monotonic() + 120.0
            while hvd_checkpoint.latest_manifest(ckpt) is None:
                if time.monotonic() > deadline:
                    raise RuntimeError("trainer never published")
                time.sleep(0.05)
            sub = WeightSubscriber(ckpt, like=params0,
                                   poll_interval_s=0.25)
            boot = sub.load_initial()
            gen_step = {boot.generation: boot.step}

            group = ReplicaGroup(r, 2, ("127.0.0.1", port),
                                 key=b"k" * 32, rank_lost_timeout_s=2.0,
                                 start_timeout_s=120.0)
            lost_box = []
            queue = AdmissionQueue(max_depth=64,
                                   admission_timeout_s=1e9)
            engine = ServeEngine(cfg, boot.params, num_slots=2,
                                 max_len=48, kv_block=8, queue=queue,
                                 replica=group, subscriber=sub,
                                 on_ranks_lost=lost_box.append)

            import hvd_fleet as hf
            workload = hf.make_workload(
                0, 12, 0.5,
                lambda rid, prompt, n: Request(rid, prompt,
                                               max_new_tokens=n))
            results = []
            i = steps = 0
            wedged = False
            deadline = time.monotonic() + 180.0
            while (i < len(workload) or engine.active_count or
                   len(engine.queue)) and time.monotonic() < deadline:
                while i < len(workload) and workload[i][0] <= steps:
                    engine.submit(workload[i][1])
                    i += 1
                results.extend(engine.step())
                steps += 1
                swap = engine.last_swap
                if swap and swap["generation"] not in gen_step:
                    gen_step[swap["generation"]] = swap["step"]
                if results and not os.path.exists(
                        os.environ["DRILL_START_FILE"]):
                    with open(os.environ["DRILL_START_FILE"], "w") as f:
                        f.write("started")  # main SIGTERMs the trainer
                if not wedged and len(gen_step) >= 2 and \
                        len(results) >= 3:
                    with open(os.environ["DRILL_WEDGE_FILE"], "w") as f:
                        f.write("wedge")  # inject the replica loss
                    wedged = True
                time.sleep(0.1)
            # keep polling until >=2 swaps landed and the loss was seen
            # (the wedge may still be pending if traffic drained fast)
            deadline = time.monotonic() + 90.0
            while (len(gen_step) < 3 or not wedged or not lost_box) and \
                    time.monotonic() < deadline:
                engine.step()
                swap = engine.last_swap
                if swap and swap["generation"] not in gen_step:
                    gen_step[swap["generation"]] = swap["step"]
                if not wedged and len(gen_step) >= 2:
                    with open(os.environ["DRILL_WEDGE_FILE"], "w") as f:
                        f.write("wedge")
                    wedged = True
                time.sleep(0.1)
            with open(os.environ["DRILL_DONE_FILE"], "w") as f:
                f.write("done")
            hvd_tracing.get_tracer().dump(reason="fleet_drill")

            probes = {}  # generation -> first completed request
            prompts = {req.request_id: (req.prompt, req.max_new_tokens)
                       for _, req in workload}
            for res in results:
                if res.outcome == "completed" and \
                        res.generation not in probes:
                    p, n = prompts[res.request_id]
                    probes[res.generation] = (list(p), n,
                                              list(res.tokens))
            ttfts = sorted(res.ttft_s for res in results
                           if res.ttft_s is not None)
            outcomes = sorted((res.request_id, res.outcome,
                               res.generation) for res in results)
            return (r, sorted(gen_step.items()), lost_box,
                    dict(sub.refusals), probes, ttfts, outcomes)

        env = dict(_ENV)
        env["HVD_FLIGHT_DIR"] = str(tmp_path)
        env["DRILL_PORT"] = str(network.free_port())
        env["DRILL_CKPT"] = ckpt_dir
        env["DRILL_START_FILE"] = traffic_started
        env["DRILL_WEDGE_FILE"] = wedge_now
        env["DRILL_DONE_FILE"] = done_file
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            [repo_root, os.path.join(repo_root, "tools")] +
            os.environ.get("PYTHONPATH", "").split(os.pathsep))

        import threading

        box = []  # (supervisor, runner) once the trainer is started

        def run_trainer_and_preempt():
            # start the trainer only when traffic is flowing (a slow
            # host's jit warmup must not let it finish unpreempted),
            # then SIGTERM it right after its first publish
            deadline = time.monotonic() + 150.0
            while not os.path.exists(traffic_started) and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            if not os.path.exists(traffic_started):
                return
            sup, runner = hvd_fleet.start_trainer(
                str(tmp_path), ckpt_dir, steps=40, every=3,
                sleep_s=0.3, env=trainer_env)
            box.append((sup, runner))
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                latest = hvd_checkpoint.latest_manifest(ckpt_dir)
                if latest is not None and \
                        int(latest[2].get("generation", 0)) >= 2:
                    break
                time.sleep(0.05)
            os.kill(runner.procs[-1].pid, signal.SIGTERM)

        killer = threading.Thread(target=run_trainer_and_preempt,
                                  daemon=True)
        killer.start()
        try:
            results = run(fn, num_proc=2, env=env, start_timeout_s=180.0)
            killer.join(timeout=180.0)
            assert box, "trainer never started: traffic never began"
            sup, runner = box[0]
            rc = sup.wait(poll_s=0.1)
        finally:
            if box:
                box[0][0].shutdown()

        # the trainer was preempted mid-run and restarted in-slot
        assert rc == 0
        from horovod_tpu.common.exceptions import PREEMPTED_EXIT_CODE
        assert sup.restarts == 1 and len(runner.procs) == 2
        assert runner.procs[0].wait() == PREEMPTED_EXIT_CODE

        by_rank = {x[0]: x for x in results}
        _, gen_step, lost_box, refusals, probes, ttfts, outcomes = \
            by_rank[0]
        gen_step = dict(gen_step)
        # >=2 mid-traffic swaps: three distinct generations served
        assert len(gen_step) >= 3, (
            f"expected >=2 swaps, served generations {gen_step}")
        assert lost_box == [(1,)], lost_box
        assert refusals == {}, refusals
        # zero-drain SLO: every request completed, and stamped with the
        # generation that decoded it; generous CPU-host latency bound
        assert outcomes and all(o == "completed" for _, o, _ in outcomes)
        assert all(g in gen_step for _, _, g in outcomes), outcomes
        assert ttfts and ttfts[-1] < 60.0, ttfts[-5:]

        # temp-0 parity: recompute each probed generation's weights from
        # the trainer's deterministic trajectory and decode solo — a
        # swap that armed the wrong bytes diverges token-for-token here
        from horovod_tpu.serving.engine import ServeEngine
        from horovod_tpu.serving.queue import AdmissionQueue, Request
        for gen, (prompt, n_new, tokens) in sorted(probes.items())[:3]:
            params = hvd_fleet.expected_params(
                params0, gen_step[gen], jax.tree_util.tree_map)
            solo = ServeEngine(
                cfg, params, num_slots=2, max_len=48, kv_block=8,
                queue=AdmissionQueue(max_depth=4,
                                     admission_timeout_s=1e9))
            solo.submit(Request("probe", tuple(prompt),
                                max_new_tokens=n_new))
            (ref,) = solo.run_to_completion()
            assert list(ref.tokens) == tokens, (
                f"generation {gen} (step {gen_step[gen]}) diverged: "
                f"swap armed the wrong weights")

        # the postmortem names every injected event from the dumps alone
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        verdict = hvd_postmortem.analyze(loaded)
        assert verdict["divergent_rank"] == 1, verdict
        swapped_gens = {e.get("generation")
                        for e in verdict["weight_swaps"]}
        assert len(swapped_gens) >= 2, verdict["weight_swaps"]
        assert any(e.get("event") == "ckpt_emergency_exit"
                   for e in verdict["preemptions"]), verdict
        assert any("preempted" in r for r in verdict["reasons"]), \
            verdict["reasons"]
        assert any("swapped to" in r for r in verdict["reasons"]), \
            verdict["reasons"]


# ---------------------------------------------------------------------------
# router-plane drills: the front door under replica loss (2-process,
# real control plane) and a poisoned canary generation (real fleet
# publish -> subscribe -> gate path, per-replica virtual clocks).
# ---------------------------------------------------------------------------

class TestDrillRouterReplicaLost:
    def test_reroute_is_exactly_once_and_postmortem_tells_it(
            self, tmp_path):
        """Drill (j), the router plane: 2 replica processes on the
        negotiation control plane. Rank 0 hosts the front door — a
        Router fronting two real engines, one riding the ReplicaGroup
        as rank 0 and one standing in (locally) for the remote
        replica's serving capacity under replica id 1. Rank 1 wedges
        mid-stream. The coordinator's ledger must turn that silence
        into RanksLostError on replica 0's heartbeat; the engine's
        failover hands the lost ranks to the router, which must requeue
        replica 1's in-flight requests to the survivor EXACTLY once —
        every request completes, the rerouted ones stamped — and the
        postmortem must name both the lost rank and each reroute from
        the dumps alone."""

        def fn():
            import os
            import time
            import jax
            import jax.numpy as jnp
            from horovod_tpu.models import transformer as tr
            from horovod_tpu.router import Router
            from horovod_tpu.serving.engine import ServeEngine
            from horovod_tpu.serving.queue import AdmissionQueue, Request
            from horovod_tpu.serving.replica import ReplicaGroup
            from horovod_tpu.utils import tracing as hvd_tracing

            r = int(os.environ["HVD_PROCESS_ID"])
            port = int(os.environ["DRILL_PORT"])
            done_file = os.environ["DRILL_DONE_FILE"]
            hvd_tracing.reset(enabled=True, rank=r)
            if r == 1:
                group = ReplicaGroup(r, 2, ("127.0.0.1", port),
                                     key=b"k" * 32,
                                     rank_lost_timeout_s=1.5,
                                     start_timeout_s=120.0)
                for _ in range(3):
                    group.heartbeat()
                    time.sleep(0.05)
                deadline = time.monotonic() + 120.0
                while not os.path.exists(done_file) and \
                        time.monotonic() < deadline:
                    time.sleep(0.1)
                group.close(linger_s=0.0)
                return (r, None, None, None, None)

            # rank 0: warm the jit caches BEFORE joining the group
            # (compiles inside would stall heartbeats past the window)
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            warm = ServeEngine(
                cfg, params, num_slots=2, max_len=48, kv_block=8,
                queue=AdmissionQueue(max_depth=8,
                                     admission_timeout_s=1e9))
            warm.submit(Request("warm", (3, 1, 4), max_new_tokens=4))
            warm.run_to_completion()

            group = ReplicaGroup(r, 2, ("127.0.0.1", port),
                                 key=b"k" * 32, rank_lost_timeout_s=1.5,
                                 start_timeout_s=120.0)
            lost_box, router_box = [], []

            def on_lost(lost):
                lost_box.append(lost)
                router_box[0].on_ranks_lost(lost)

            def build(replica=None, cb=None):
                return ServeEngine(
                    cfg, params, num_slots=2, max_len=48, kv_block=8,
                    queue=AdmissionQueue(max_depth=32,
                                         admission_timeout_s=1e9),
                    replica=replica, on_ranks_lost=cb)

            router = Router({0: build(group, on_lost), 1: build()},
                            policy="least_loaded", affinity_prefix=0,
                            reroute_window_s=60.0)
            router_box.append(router)
            for i in range(4):
                router.submit(Request(f"pre-{i}", (3, 1, 4),
                                      max_new_tokens=24))
            assigned = dict(router.inflight)
            results = []
            t0 = time.monotonic()
            detect_s = None
            while time.monotonic() - t0 < 60.0:
                results.extend(router.step())
                if lost_box:
                    detect_s = time.monotonic() - t0
                    break
                # pace the decode so pre-* are still mid-stream when
                # the loss lands — there must be work to reroute
                time.sleep(0.15)
            with open(done_file, "w") as f:
                f.write("done")
            # failover must not stop the music: post-loss requests
            # route to the survivor and serve
            for i in range(2):
                router.submit(Request(f"post-{i}", (1, 2),
                                      max_new_tokens=4))
            results.extend(router.run_to_completion())
            # the final dump supersedes the failover's and carries the
            # full event ring: replica_lost, each reroute, completions
            hvd_tracing.get_tracer().dump(reason="router_drill")
            outcomes = sorted((x.request_id, x.outcome, x.replica,
                               x.rerouted) for x in results)
            return (r, detect_s, lost_box, assigned, outcomes)

        env = dict(_ENV)
        env["HVD_FLIGHT_DIR"] = str(tmp_path)
        env["DRILL_PORT"] = str(network.free_port())
        env["DRILL_DONE_FILE"] = str(tmp_path / "victim.done")
        results = run(fn, num_proc=2, env=env, start_timeout_s=180.0)

        by_rank = {x[0]: x for x in results}
        _, detect_s, lost_box, assigned, outcomes = by_rank[0]
        assert detect_s is not None, \
            "replica 0 never detected the wedged peer"
        assert detect_s < 30.0, f"detection took {detect_s:.1f}s"
        assert lost_box == [(1,)], lost_box
        victims = sorted(rid for rid, rep in assigned.items()
                         if rep == 1)
        assert len(victims) == 2, assigned  # the split was 2/2
        # exactly-once: 6 submissions, 6 completions, no duplicates
        assert len(outcomes) == 6 and \
            len({rid for rid, _, _, _ in outcomes}) == 6, outcomes
        assert all(o == "completed" for _, o, _, _ in outcomes)
        # every result was served by the survivor or pre-loss replica 0,
        # and exactly the victims carry the rerouted stamp
        assert all(rep == 0 for _, _, rep, _ in outcomes), outcomes
        assert sorted(rid for rid, _, _, rr in outcomes if rr) == \
            victims, outcomes

        # the postmortem names the lost rank and each reroute
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        verdict = hvd_postmortem.analyze(loaded)
        assert verdict["divergent_rank"] == 1, verdict
        moves = {(e.get("request_id"), e.get("from_replica"),
                  e.get("to_replica")) for e in verdict["reroutes"]}
        assert moves == {(rid, 1, 0) for rid in victims}, verdict
        assert any("declared lost" in r for r in verdict["reasons"]), \
            verdict["reasons"]
        assert any("rerouted" in r for r in verdict["reasons"]), \
            verdict["reasons"]


class TestDrillCanaryRollback:
    def test_poisoned_generation_rolls_back_fixed_build_promotes(
            self, tmp_path, monkeypatch):
        """Drill (k), the canary state machine end to end on the REAL
        weight path: generation 2 publishes through the fleet plane
        (checkpoint commit -> publisher -> per-replica subscribers),
        the controller claims it, holds the baseline replica's gate,
        and steers the hashed cohort at it. Generation 2 is poisoned —
        its decode steps cost 30x on the serving clock — so the live
        TTFT histograms must breach and auto-roll-back: traffic to 0,
        generation quarantined, zero requests lost, and the quarantined
        replica drained of traffic until generation 3 (the fix) arms,
        canaries cleanly, and promotes fleet-wide.

        Replicas run on per-replica virtual clocks (the engines take a
        ``clock``): two replicas serve in parallel in production, so
        one replica's slow step must not bill the other's TTFT the way
        a serial test loop would. The weights, publish/arm/gate path,
        dispatch, and histogram math are all real."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.fleet import WeightPublisher, WeightSubscriber
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.router import CanaryController, Router
        from horovod_tpu.serving.engine import ServeEngine
        from horovod_tpu.serving.queue import AdmissionQueue, Request
        from horovod_tpu.utils import checkpoint as hvd_checkpoint
        from horovod_tpu.utils import metrics as hvd_metrics
        from horovod_tpu.utils import tracing as hvd_tracing

        monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hvd_metrics.reset(enabled=True)
        hvd_tracing.reset(enabled=True, rank=0)
        try:
            self._drill(tmp_path, jax, jnp, WeightPublisher,
                        WeightSubscriber, tr, CanaryController, Router,
                        ServeEngine, AdmissionQueue, Request,
                        hvd_checkpoint, hvd_metrics, hvd_tracing)
        finally:
            hvd_metrics.reset()
            hvd_tracing.reset()

    def _drill(self, tmp_path, jax, jnp, WeightPublisher,
               WeightSubscriber, tr, CanaryController, Router,
               ServeEngine, AdmissionQueue, Request, hvd_checkpoint,
               hvd_metrics, hvd_tracing):
        ckpt = str(tmp_path / "ckpt")
        cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                        attention_impl="full")
        _, params0 = tr.init_params(cfg, jax.random.PRNGKey(0))
        mgr = hvd_checkpoint.CheckpointManager(ckpt, rank=0,
                                               world_size=1,
                                               async_save=False)
        mgr.on_commit = WeightPublisher(ckpt).publish
        mgr.save(params0, step=0, block=True)  # generation 1

        class Clock:
            def __init__(self):
                self.t = 0.0

            def __call__(self):
                return self.t

        clocks = {0: Clock(), 1: Clock()}
        ctrl = CanaryController(pct=50.0, window=6, ttft_x=1.5,
                                goodput_drop=0.10, min_delta_s=0.025,
                                max_canary_replicas=1)
        subs, engines = {}, {}
        for rid in (0, 1):
            subs[rid] = WeightSubscriber(ckpt, like=params0,
                                         replica=rid,
                                         poll_interval_s=0.01)
            boot = subs[rid].load_initial()
            engines[rid] = ServeEngine(
                cfg, boot.params, num_slots=2, max_len=48, kv_block=8,
                queue=AdmissionQueue(max_depth=64,
                                     admission_timeout_s=1e9,
                                     clock=clocks[rid]),
                subscriber=subs[rid], swap_gate=ctrl.gate(rid),
                clock=clocks[rid])

        # per-replica serving time: a healthy step costs 10ms on that
        # replica's clock; a step serving the poisoned generation 2
        # costs 300ms — the regression the canary must catch
        for rid in (0, 1):
            def timed_step(engine=engines[rid], clk=clocks[rid]):
                clk.t += 0.300 if engine.generation == 2 else 0.010
                return type(engine).step(engine)
            engines[rid].step = timed_step
        router = Router(engines, policy="least_loaded",
                        affinity_prefix=0, canary=ctrl)

        submitted, results = [], []

        def pump(n_new, tag, deadline_s=60.0):
            """Feed ``n_new`` requests while stepping the router."""
            i, t0 = 0, time.monotonic()
            while (i < n_new or router.pending()) and \
                    time.monotonic() - t0 < deadline_s:
                if i < n_new:
                    rid = f"{tag}-{i}"
                    assert router.submit(Request(rid, (3, 1, 4),
                                                 max_new_tokens=4))
                    submitted.append(rid)
                    i += 1
                results.extend(router.step())

        # phase 1: steady state on generation 1, both replicas serving
        pump(6, "warm")
        assert ctrl.state == "idle"

        # phase 2: the poisoned build publishes; let the subscribers'
        # background loads ARM it before stepping again, so the tick at
        # the head of the next step claims it while every gate is still
        # closed — then drive traffic until the live histograms decide
        mgr.save(params0, step=1, block=True)  # generation 2
        for rid in (0, 1):
            subs[rid].poll(force=True)
        deadline = time.monotonic() + 60.0
        while any(subs[rid].armed_generation != 2 for rid in (0, 1)) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert all(subs[rid].armed_generation == 2 for rid in (0, 1))
        router.step()  # the tick at its head claims generation 2
        assert ctrl.state == "canary", ctrl.state
        assert ctrl.canary_generation == 2
        (canary_rid,) = ctrl.canary_replicas
        baseline_rid = 1 - canary_rid
        pump(40, "live")
        assert ctrl.state == "rolled_back", ctrl.state
        assert ctrl.quarantined == {2}
        verdict, evidence = ctrl.decisions[-1]
        assert verdict == "rollback"
        assert "ttft_p99" in evidence["breaches"], evidence
        assert evidence["ttft_p99_canary"] > \
            1.5 * evidence["ttft_p99_baseline"], evidence
        # the baseline replica's gate held: it never swapped to the
        # poisoned generation, before the verdict or after
        assert engines[baseline_rid].generation == 1
        assert engines[canary_rid].generation == 2

        # phase 3: post-rollback, the quarantined replica (still
        # serving generation 2 — swaps are monotonic) gets NO traffic
        before = len(results)
        pump(6, "post")
        drained = [x for x in results[before:]
                   if x.request_id.startswith("post-")]
        assert len(drained) == 6
        assert all(x.replica == baseline_rid for x in drained), drained

        # phase 4: the fixed build (generation 3) arms, canaries
        # cleanly, and promotes; the fleet converges on it
        mgr.save(params0, step=2, block=True)  # generation 3
        mgr.close()
        for rid in (0, 1):
            subs[rid].poll(force=True)
        deadline = time.monotonic() + 60.0
        while any(subs[rid].armed_generation != 3 for rid in (0, 1)) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        router.step()
        assert ctrl.state == "canary" and ctrl.canary_generation == 3
        pump(40, "fix")
        assert ctrl.state == "promoted", ctrl.state
        assert ctrl.quarantined == {2}  # the bad build stays banned
        deadline = time.monotonic() + 60.0
        while any(engines[rid].generation != 3 for rid in (0, 1)) \
                and time.monotonic() < deadline:
            router.step()
        assert all(engines[rid].generation == 3 for rid in (0, 1))

        # zero requests lost across the whole incident
        outcomes = {x.request_id: x.outcome for x in results}
        assert sorted(outcomes) == sorted(submitted)
        assert all(o == "completed" for o in outcomes.values())

        # the dumps alone replay both verdicts
        hvd_tracing.get_tracer().dump(reason="canary_drill")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        pm = hvd_postmortem.analyze(loaded)
        calls = [(e.get("event"), e.get("generation"))
                 for e in pm["canary_decisions"]]
        assert ("route_rollback", 2) in calls, calls
        assert ("route_promote", 3) in calls, calls
        assert any("ROLLED BACK" in r for r in pm["reasons"]), \
            pm["reasons"]


# ---------------------------------------------------------------------------
# elasticity plane: the supervisor's spawn/drain control door under
# injected transport faults (run/elastic.py ReplicaSupervisorService)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestReplicaSupervisorRPC:
    SPEC = ReplicaSupervisorService.NAME

    def _service(self):
        calls = {"spawn": 0, "drain": []}

        def on_spawn():
            calls["spawn"] += 1
            return 40 + calls["spawn"]

        def on_drain(rid):
            calls["drain"].append(rid)
            return True

        svc = ReplicaSupervisorService(KEY, on_spawn=on_spawn,
                                       on_drain=on_drain)
        return svc, calls

    def test_dropped_response_retries_without_double_spawn(
            self, monkeypatch):
        """drop_response on the spawn ack: the supervisor DID spawn,
        the ack died on the wire, the client's transport retry resends
        the same change_id — and the ledger replays the recorded
        response instead of starting a second replica."""
        monkeypatch.setenv(
            "HVD_CHAOS_SPEC",
            f"{self.SPEC}:ReplicaOpResponse:drop_response:1.0:1")
        monkeypatch.setenv("HVD_CHAOS_SEED", "3")
        svc, calls = self._service()
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            c.backoff_base_s = 0.01
            resp = c.spawn_replica("chg-1")
            assert sum(svc._chaos.stats().values()) == 1  # fault fired
            assert resp.ok and resp.replica_id == 41
            assert resp.duplicate  # the retry was served from the ledger
            assert calls["spawn"] == 1  # executed exactly once
            c.close()
        finally:
            svc.shutdown()

    def test_duplicated_drain_is_idempotent(self, monkeypatch):
        """Network-level duplicate delivery of a DrainReplicaRequest:
        the handler runs twice, the drain hook runs once."""
        monkeypatch.setenv(
            "HVD_CHAOS_SPEC",
            f"{self.SPEC}:DrainReplicaRequest:dup_request:1.0:1")
        svc, calls = self._service()
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            resp = c.drain_replica("chg-2", 1)
            assert sum(svc._chaos.stats().values()) == 1
            assert resp.ok and resp.replica_id == 1
            assert calls["drain"] == [1]  # once, not twice
            c.close()
        finally:
            svc.shutdown()

    def test_delayed_drain_completes_within_bound(self, monkeypatch):
        monkeypatch.setenv(
            "HVD_CHAOS_SPEC",
            f"{self.SPEC}:DrainReplicaRequest:delay_request:1.0:1")
        monkeypatch.setenv("HVD_CHAOS_DELAY_MS", "200")
        svc, calls = self._service()
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            t0 = time.monotonic()
            resp = c.drain_replica("chg-3", 2)
            elapsed = time.monotonic() - t0
            assert resp.ok and calls["drain"] == [2]
            assert 0.15 <= elapsed < 10.0  # delayed, not hung
            c.close()
        finally:
            svc.shutdown()

    def test_distinct_change_ids_execute_separately(self):
        svc, calls = self._service()
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            a = c.spawn_replica("chg-a")
            b = c.spawn_replica("chg-b")
            again = c.spawn_replica("chg-a")
            assert (a.replica_id, b.replica_id) == (41, 42)
            assert again.replica_id == 41 and again.duplicate
            assert calls["spawn"] == 2
            c.close()
        finally:
            svc.shutdown()

    def test_hook_exception_fails_loud_by_name(self):
        def bad_spawn():
            raise RuntimeError("no capacity on any host")

        svc = ReplicaSupervisorService(KEY, on_spawn=bad_spawn)
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            resp = c.spawn_replica("chg-x")
            assert not resp.ok
            assert "no capacity" in resp.detail  # the NAMED failure
            # the failure is ledgered too: a retry must not re-execute
            # a spawn that already failed loudly
            assert c.spawn_replica("chg-x").duplicate
            c.close()
        finally:
            svc.shutdown()

    def test_unconfigured_hooks_refuse(self):
        svc = ReplicaSupervisorService(KEY)
        try:
            c = ReplicaSupervisorClient(_addr_map(svc.port), KEY)
            assert not c.spawn_replica("c1").ok
            assert not c.drain_replica("c2", 0).ok
            c.close()
        finally:
            svc.shutdown()


# ---------------------------------------------------------------------------
# elasticity plane drills: planned scale-down with in-flight work,
# flap-storm convergence + graded rollback, and breaker isolation of a
# wedged-but-heartbeating replica (router/elastic.py, docs/elasticity.md)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestDrillElasticity:
    """Drills (l), the elasticity plane end to end on REAL serving
    engines: the ElasticityController rides ``Router.step()`` exactly
    as in production, engines run on a shared virtual clock (each
    engine step bills 10ms), and every verdict must be replayable from
    the flight dumps alone."""

    class _Clock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    def _engine(self, clock, cfg, params, num_slots):
        from horovod_tpu.serving.engine import ServeEngine
        from horovod_tpu.serving.queue import AdmissionQueue

        eng = ServeEngine(
            cfg, params, num_slots=num_slots, max_len=64, kv_block=8,
            queue=AdmissionQueue(max_depth=64, admission_timeout_s=1e9,
                                 clock=clock),
            clock=clock)

        def timed_step(engine=eng, clk=clock):
            clk.t += 0.010
            return type(engine).step(engine)

        eng.step = timed_step
        return eng

    def _postmortem(self, tmp_path, hvd_tracing, reason):
        hvd_tracing.get_tracer().dump(reason=reason)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        return hvd_postmortem.analyze(loaded)

    def test_planned_scale_down_drains_clean_with_exact_parity(
            self, tmp_path, monkeypatch):
        """The planned scale-down drill: two replicas each hold an
        in-flight decode when the operator lowers the floor; the
        controller drains the victim gracefully — its in-flight work
        finishes on it, nothing is killed, nothing is double-delivered
        — grades the shrunk fleet like a canary, promotes, and the
        postmortem names every transition from the dumps alone."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.router import Router
        from horovod_tpu.router.elastic import ElasticityController
        from horovod_tpu.serving.queue import Request
        from horovod_tpu.utils import metrics as hvd_metrics
        from horovod_tpu.utils import tracing as hvd_tracing

        monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hvd_metrics.reset(enabled=True)
        hvd_tracing.reset(enabled=True, rank=0)
        try:
            clock = self._Clock()
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            engines = {rid: self._engine(clock, cfg, params, 4)
                       for rid in (0, 1)}

            def spawn(router):
                rid = max(router._handles) + 1
                return router.add_replica(
                    rid, self._engine(clock, cfg, params, 4)).replica_id

            # min_replicas=2 holds the floor through warm-up (idle is
            # allowed to accumulate dwell, but the floor blocks action)
            ctrl = ElasticityController(
                spawn=spawn, min_replicas=2, dwell_s=0.2, cooldown_s=2.0,
                window=6, ttft_x=1.5, min_delta_s=0.5, up_depth=100.0,
                down_util=0.25, clock=clock)
            router = Router(engines, policy="least_loaded",
                            affinity_prefix=0, elastic=ctrl, shed_depth=0,
                            drain_timeout_s=60.0, clock=clock)
            submitted, results = [], []

            def pump(n_new, tag, max_tokens=2, steps_cap=2000):
                i, steps = 0, 0
                while (i < n_new or router.pending()) and \
                        steps < steps_cap:
                    if i < n_new:
                        rid = f"{tag}-{i}"
                        assert router.submit(
                            Request(rid, (3, 1, 4),
                                    max_new_tokens=max_tokens))
                        submitted.append(rid)
                        i += 1
                    results.extend(router.step())
                    steps += 1

            # phase 1: steady traffic fills the controller's baseline
            pump(6, "warm")
            assert ctrl.state == "steady"
            assert router.live_replicas() == [0, 1]

            # phase 2: one long decode IN FLIGHT on each replica — the
            # work a graceless scale-down would kill
            for i in range(2):
                rid = f"hold-{i}"
                assert router.submit(Request(rid, (3, 1, 4),
                                             max_new_tokens=16))
                submitted.append(rid)
                results.extend(router.step())
            assert sorted(set(router.inflight.values())) == [0, 1]

            # phase 3: the operator lowers the floor; idle has already
            # dwelled, so the next tick executes the planned scale-down
            ctrl.min_replicas = 1
            guard = 0
            while ctrl.state == "steady" and guard < 200:
                results.extend(router.step())
                guard += 1
            assert ctrl.state == "grading"
            assert ctrl.transitions[-1]["action"] == "scale_down"
            victim = ctrl.transitions[-1]["replica"]
            assert victim in router._draining
            # the victim was mid-decode when the drain began
            assert any(r == victim for r in router.inflight.values())

            # phase 4: the drain runs to completion — in-flight work
            # retires ON the victim, which then leaves the fleet
            guard = 0
            while router._draining and guard < 1000:
                results.extend(router.step())
                guard += 1
            assert not router._draining
            assert router.live_replicas() == [1 - victim]
            # the survivor's own long decode may still be running —
            # only the VICTIM's work had to finish before retirement
            guard = 0
            while router.pending() and guard < 1000:
                results.extend(router.step())
                guard += 1
            hold = {r.request_id: r for r in results
                    if r.request_id.startswith("hold-")}
            assert len(hold) == 2
            assert all(r.outcome == "completed" for r in hold.values())
            assert any(r.replica == victim for r in hold.values())

            # phase 5: the after-window fills on the shrunk fleet and
            # the change grades like a weight rollout: promote
            pump(6, "post")
            guard = 0
            while ctrl.state == "grading" and guard < 100:
                results.extend(router.step())
                guard += 1
            assert ctrl.state == "steady"
            verdict, evidence = ctrl.decisions[-1]
            assert verdict == "promote"
            assert evidence["action"] == "scale_down"
            assert evidence["breaches"] == []

            # zero lost requests, exact submission/completion parity
            assert len(results) == len(submitted)
            outcomes = {r.request_id: r.outcome for r in results}
            assert sorted(outcomes) == sorted(submitted)
            assert all(o == "completed" for o in outcomes.values())

            # the dumps alone name the transitions
            pm = self._postmortem(tmp_path, hvd_tracing,
                                  "elastic_scale_down_drill")
            acts = [(t["action"], t.get("replica"))
                    for t in pm["elastic_transitions"]]
            assert ("scale_down", victim) in acts, acts
            assert ("promote", victim) in acts, acts
            drains = [(e.get("event"), e.get("replica"))
                      for e in pm["drain_events"]]
            assert ("route_drain_begin", victim) in drains, drains
            assert ("route_drain_done", victim) in drains, drains
            assert not any(e == "route_drain_timeout"
                           for e, _ in drains), drains
            assert any("drained clean" in r for r in pm["reasons"]), \
                pm["reasons"]
            assert any("scale_down" in r for r in pm["reasons"]), \
                pm["reasons"]
        finally:
            hvd_metrics.reset()
            hvd_tracing.reset()

    def test_flap_storm_converges_and_bad_scale_down_rolls_back(
            self, tmp_path, monkeypatch):
        """The flap-storm drill: eight load oscillations faster than
        the dwell produce ZERO topology changes; a genuine lull then
        scales down — and when the next storm proves the shrunk fleet
        breaches the TTFT SLO, the grade rolls the scale-down back by
        re-spawning, after which the fleet converges and stays put."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.router import Router
        from horovod_tpu.router.elastic import ElasticityController
        from horovod_tpu.serving.queue import Request
        from horovod_tpu.utils import metrics as hvd_metrics
        from horovod_tpu.utils import tracing as hvd_tracing

        monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hvd_metrics.reset(enabled=True)
        hvd_tracing.reset(enabled=True, rank=0)
        try:
            clock = self._Clock()
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            engines = {rid: self._engine(clock, cfg, params, 2)
                       for rid in (0, 1)}
            spawned = []

            def spawn(router):
                rid = max(router._handles) + 1
                spawned.append(rid)
                return router.add_replica(
                    rid, self._engine(clock, cfg, params, 2)).replica_id

            ctrl = ElasticityController(
                spawn=spawn, min_replicas=1, dwell_s=0.3, cooldown_s=0.5,
                window=6, ttft_x=1.5, min_delta_s=0.025, up_depth=100.0,
                down_util=0.2, clock=clock)
            router = Router(engines, policy="least_loaded",
                            affinity_prefix=0, elastic=ctrl, shed_depth=0,
                            drain_timeout_s=60.0, clock=clock)
            submitted, results = [], []

            def pump(n_new, tag, max_tokens=4, steps_cap=2000):
                i, steps = 0, 0
                while (i < n_new or router.pending()) and \
                        steps < steps_cap:
                    if i < n_new:
                        rid = f"{tag}-{i}"
                        assert router.submit(
                            Request(rid, (3, 1, 4),
                                    max_new_tokens=max_tokens))
                        submitted.append(rid)
                        i += 1
                    results.extend(router.step())
                    steps += 1

            # phase 1, the flap storm: 8 oscillations, each lull far
            # shorter than the dwell — hysteresis must absorb ALL of it
            for cycle in range(8):
                pump(4, f"flap{cycle}")
                for _ in range(3):  # ~60ms lull << 300ms dwell
                    results.extend(router.step())
            assert ctrl.state == "steady"
            assert ctrl.transitions == []  # not one flap leaked through
            assert router.live_replicas() == [0, 1]

            # phase 2, a real lull: idle holds past the dwell and the
            # controller drains one replica
            guard = 0
            while ctrl.state == "steady" and guard < 200:
                results.extend(router.step())
                guard += 1
            assert ctrl.state == "grading"
            assert ctrl.transitions[-1]["action"] == "scale_down"
            victim = ctrl.transitions[-1]["replica"]
            guard = 0
            while router._draining and guard < 200:
                results.extend(router.step())
                guard += 1
            assert router.live_replicas() == [1 - victim]

            # phase 3, the storm returns on the shrunk fleet: a 16-deep
            # burst queues behind the survivor's two slots, the
            # after-window breaches TTFT vs the flap-era baseline and
            # the scale-down ROLLS BACK by re-spawning
            for i in range(16):
                rid = f"storm-{i}"
                assert router.submit(Request(rid, (3, 1, 4),
                                             max_new_tokens=8))
                submitted.append(rid)
            guard = 0
            while ctrl.state == "grading" and guard < 500:
                results.extend(router.step())
                guard += 1
            verdict, evidence = ctrl.decisions[-1]
            assert verdict == "rollback", ctrl.decisions
            assert "ttft_p99" in evidence["breaches"], evidence
            assert evidence["ttft_p99_after"] > \
                1.5 * evidence["ttft_p99_baseline"], evidence
            assert spawned, "rollback must re-spawn what was drained"
            assert len(router.live_replicas()) == 2

            # phase 4, convergence: steady trickle, no further changes
            changes = len(ctrl.transitions)
            pump(12, "settle", max_tokens=2)
            assert len(ctrl.transitions) == changes
            assert ctrl.state == "steady"
            assert len(router.live_replicas()) == 2

            # zero lost requests across every phase of the storm
            assert len(results) == len(submitted)
            outcomes = {r.request_id: r.outcome for r in results}
            assert sorted(outcomes) == sorted(submitted)
            assert all(o == "completed" for o in outcomes.values())

            # the dumps replay the whole storm
            pm = self._postmortem(tmp_path, hvd_tracing,
                                  "elastic_flap_drill")
            acts = [t["action"] for t in pm["elastic_transitions"]]
            assert acts.count("scale_down") == 1, acts
            assert acts.count("rollback") == 1, acts
            assert any("ROLLED BACK" in r for r in pm["reasons"]), \
                pm["reasons"]
        finally:
            hvd_metrics.reset()
            hvd_tracing.reset()

    def test_breaker_isolates_wedged_but_heartbeating_replica(
            self, tmp_path, monkeypatch):
        """The sick-but-alive drill: a replica keeps serving fresh load
        snapshots (its heartbeat is fine) but stops finishing work
        mid-decode. The circuit breaker must trip on the wedged
        in-flight age within its timeout bound, steer ALL new traffic
        to the healthy replica while open, and close again once the
        replica recovers — with every request eventually completing."""
        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.router import Router
        from horovod_tpu.router.elastic import CircuitBreaker
        from horovod_tpu.serving.queue import Request
        from horovod_tpu.utils import metrics as hvd_metrics
        from horovod_tpu.utils import tracing as hvd_tracing

        monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hvd_metrics.reset(enabled=True)
        hvd_tracing.reset(enabled=True, rank=0)
        try:
            clock = self._Clock()
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            engines = {rid: self._engine(clock, cfg, params, 2)
                       for rid in (0, 1)}
            breaker = CircuitBreaker(fails=3, probe_s=0.5, close_n=1,
                                     timeout_s=1.0, clock=clock)
            router = Router(engines, policy="least_loaded",
                            affinity_prefix=0, breaker=breaker,
                            shed_depth=0, clock=clock)
            submitted, results = [], []

            def feed(tag, n, max_tokens=2):
                ids = set()
                for i in range(n):
                    rid = f"{tag}-{i}"
                    assert router.submit(Request(rid, (3, 1, 4),
                                                 max_new_tokens=max_tokens))
                    submitted.append(rid)
                    ids.add(rid)
                    results.extend(router.step())
                return ids

            def drive(want, max_steps=600):
                done = {r.request_id for r in results}
                for _ in range(max_steps):
                    if want <= done:
                        return
                    for r in router.step():
                        results.append(r)
                        done.add(r.request_id)
                assert want <= done, f"never finished: {want - done}"

            drive(feed("warm", 4))

            # one long decode on each replica, then wedge the one
            # serving hold-1: step() stops making progress while
            # load_snapshot stays perfectly fresh (the router stamps
            # fronted engines' snapshots 'now' — heartbeat looks fine)
            feed("hold", 2, max_tokens=32)
            wedged = router.inflight["hold-1"]
            healthy = 1 - wedged
            real_step = engines[wedged].step
            engines[wedged].step = lambda: []
            t_wedge = clock.t

            guard = 0
            while breaker.state(wedged) != "open" and guard < 500:
                results.extend(router.step())
                guard += 1
            assert breaker.state(wedged) == "open"
            # bounded isolation: the trip lands within the wedge
            # timeout plus scheduler granularity
            assert clock.t - t_wedge <= breaker.timeout_s + 0.25, \
                (clock.t, t_wedge)
            # ...while its heartbeat never went stale
            assert router.loads()[wedged]["ts"] == clock.t

            # while open, every new request lands on the healthy
            # replica (probe timer hasn't fired yet)
            before = len(results)
            iso = feed("iso", 4)
            drive(iso)
            served = [r for r in results[before:]
                      if r.request_id in iso]
            assert len(served) == 4
            assert all(r.replica == healthy for r in served), served

            # recovery: the replica unwedges, its stuck decode retires,
            # and that success closes the breaker (close_n=1)
            engines[wedged].step = real_step
            drive({"hold-0", "hold-1"})
            assert breaker.state(wedged) == "closed"
            # submit the batch before stepping: queue-depth feedback
            # must spread it across BOTH replicas again
            back = set()
            for i in range(4):
                rid = f"back-{i}"
                assert router.submit(Request(rid, (3, 1, 4),
                                             max_new_tokens=2))
                submitted.append(rid)
                back.add(rid)
            drive(back)
            assert any(r.replica == wedged for r in results
                       if r.request_id in back)

            # exact parity: the wedge delayed work, it lost none
            assert len(results) == len(submitted)
            outcomes = {r.request_id: r.outcome for r in results}
            assert sorted(outcomes) == sorted(submitted)
            assert all(o == "completed" for o in outcomes.values())

            pm = self._postmortem(tmp_path, hvd_tracing,
                                  "elastic_breaker_drill")
            moves = [(e.get("replica"), e.get("state"), e.get("reason"))
                     for e in pm["breaker_transitions"]]
            assert (wedged, "open", "wedged") in moves, moves
            assert (wedged, "closed", "recovered") in moves, moves
            assert any("tripped open (wedged)" in r
                       for r in pm["reasons"]), pm["reasons"]
        finally:
            hvd_metrics.reset()
            hvd_tracing.reset()


# ---------------------------------------------------------------------------
# alerting & run-history plane drill: KV-pressure overload burns the
# goodput budget, the alert fires inside its for-duration bound with a
# durable incident, resolves once load drops, and the postmortem names
# the whole episode from dumps alone (utils/alerts.py, docs/alerts.md)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
class TestDrillAlertPlane:
    """Drills (m), the alerting plane end to end on a REAL serving
    engine: the AlertManager rides ``ServeEngine.step()`` exactly as in
    production (no drill-only control loop), the engine runs on a
    virtual clock (each step bills 250ms so the 60s/15s burn windows
    cost hundreds of steps, not wall-minutes), and the episode must be
    replayable from the flight dumps and the incident file alone."""

    class _Clock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            return self.t

    def _engine(self, clock, cfg, params, num_slots):
        from horovod_tpu.serving.engine import ServeEngine
        from horovod_tpu.serving.queue import AdmissionQueue

        eng = ServeEngine(
            cfg, params, num_slots=num_slots, max_len=64, kv_block=8,
            queue=AdmissionQueue(max_depth=64, admission_timeout_s=1e9,
                                 clock=clock),
            clock=clock)

        def timed_step(engine=eng, clk=clock):
            clk.t += 0.250
            return type(engine).step(engine)

        eng.step = timed_step
        return eng

    def _postmortem(self, tmp_path, hvd_tracing, reason):
        hvd_tracing.get_tracer().dump(reason=reason)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import hvd_postmortem
        loaded, bad = hvd_postmortem.load_dumps(
            hvd_postmortem.find_dumps(str(tmp_path)))
        assert not bad
        hvd_postmortem.rebase(loaded)
        return hvd_postmortem.analyze(loaded)

    def test_kv_pressure_fires_goodput_burn_and_resolves(
            self, tmp_path, monkeypatch):
        """The KV-pressure drill: a healthy baseline, then an overload
        whose requests blow their deadlines mid-decode — every one of
        their tokens becomes wasted work, both burn windows go hot, and
        ``serve_goodput_burn`` walks pending -> firing inside its
        for-duration bound. The incident file names the dominant serve
        phase and the requests stranded in slots at capture time; once
        the overload stops the alert resolves through the clear-hold;
        and the postmortem names the incident from dumps alone."""
        import json as _json

        import jax
        import jax.numpy as jnp
        from horovod_tpu.models import transformer as tr
        from horovod_tpu.serving.queue import Request
        from horovod_tpu.utils import alerts as hvd_alerts
        from horovod_tpu.utils import history as hvd_history
        from horovod_tpu.utils import metrics as hvd_metrics
        from horovod_tpu.utils import tracing as hvd_tracing

        flight_dir = tmp_path / "flight"
        hist_dir = tmp_path / "hist"
        monkeypatch.setenv("HVD_FLIGHT_DIR", str(flight_dir))
        monkeypatch.setenv("HVD_HISTORY_DIR", str(hist_dir))
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        hvd_metrics.reset(enabled=True)
        hvd_tracing.reset(enabled=True, rank=0)
        hvd_history.reset(enabled=True, dirpath=str(hist_dir), rank=0,
                          interval_s=2.0)
        mgr = hvd_alerts.reset(enabled=True)
        rule = next(r for r in mgr.rules
                    if r.name == "serve_goodput_burn")
        try:
            clock = self._Clock()
            cfg = tr.TransformerConfig.tiny(dtype=jnp.float32,
                                            attention_impl="full")
            _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
            eng = self._engine(clock, cfg, params, 4)
            results = []

            def state():
                return mgr.states()["serve_goodput_burn"]["state"]

            # phase 1: ~70 virtual seconds of healthy traffic — every
            # request completes, the burn windows fill with goodput.
            i = 0
            while clock.t < 70.0:
                if len(eng.queue) < 2:
                    eng.submit(Request(f"warm-{i}", (3, 1, 4),
                                       max_new_tokens=2))
                    i += 1
                results.extend(eng.step())
            assert state() == "inactive"

            # phase 2: KV-pressure overload — slots saturate with
            # decodes that blow staggered deadlines of one to five
            # steps, so every admitted token is wasted work, by reason,
            # and at any instant some requests sit admitted-but-
            # unretired. (A full engine sees a blown deadline when it
            # reads the pass, a step after launching it: with deadlines
            # only a step apart all four rows could leave in one step.)
            t_pending = t_firing = None
            j = 0
            guard = 0
            while t_firing is None and guard < 400:
                while len(eng.queue) < 4:
                    eng.submit(Request(f"kv-{j}", (3, 1, 4),
                                       max_new_tokens=16,
                                       deadline_s=0.3 + 0.45 * (j % 3)))
                    j += 1
                results.extend(eng.step())
                s = state()
                if t_pending is None and s in ("pending", "firing"):
                    t_pending = clock.t
                if s == "firing":
                    t_firing = clock.t
                guard += 1
            assert t_firing is not None, "goodput burn never fired"
            # the for-duration hysteresis held: not a same-tick page,
            # and firing landed within the bound (for_s plus one alert
            # interval plus one step of tick granularity).
            assert t_firing - t_pending >= rule.for_s
            assert t_firing - t_pending <= rule.for_s + \
                mgr.interval_s + 0.250 + 1e-6
            ev = mgr.states()["serve_goodput_burn"]["evidence"]
            assert ev["burn_60s"] >= ev["threshold"]
            assert ev["burn_15s"] >= ev["threshold"]

            # the incident file: dominant phase + stranded requests
            incidents = [p for p in mgr.incidents
                         if "serve_goodput_burn" in p]
            assert len(incidents) == 1
            with open(incidents[0]) as f:
                inc = _json.load(f)
            assert inc["alert"] == "serve_goodput_burn"
            assert inc["severity"] == "page"
            assert inc["dominant_phase"] is not None
            assert inc["stranded_request_ids"], \
                "overload left no admitted-but-unretired requests?"
            assert all(r.startswith("kv-")
                       for r in inc["stranded_request_ids"])
            assert inc["history"], "incident carries no WAL slice"
            assert inc["manifest"] is not None

            # phase 3: the overload stops; the engine drains, the short
            # window cools, and the alert resolves through clear_s.
            guard = 0
            while state() == "firing" and guard < 400:
                if len(eng.queue) < 2:
                    eng.submit(Request(f"cool-{j}", (3, 1, 4),
                                       max_new_tokens=2))
                    j += 1
                results.extend(eng.step())
                guard += 1
            assert state() == "inactive"
            assert "serve_goodput_burn" not in mgr.firing()

            # the dumps alone name the episode: the firing escalation
            # already dumped once (reason=alert:serve_goodput_burn);
            # the postmortem reads those plus a final dump.
            pm = self._postmortem(flight_dir, hvd_tracing,
                                  "alert_plane_drill")
            trans = [(t["alert"], t["transition"])
                     for t in pm["alert_transitions"]]
            assert ("serve_goodput_burn", "pending") in trans, trans
            assert ("serve_goodput_burn", "firing") in trans, trans
            assert ("serve_goodput_burn", "resolved") in trans, trans
            assert any(i["alert"] == "serve_goodput_burn"
                       for i in pm["incidents"])
            assert any("incident for 'serve_goodput_burn'" in r
                       for r in pm["reasons"]), pm["reasons"]
        finally:
            hvd_alerts.reset(enabled=False)
            hvd_history.reset(enabled=False)
            hvd_metrics.reset()
            hvd_tracing.reset()
