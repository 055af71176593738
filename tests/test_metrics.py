"""Telemetry plane (utils/metrics.py): registry semantics, rank-0
aggregation (= sum of per-rank registries), Prometheus round-trip,
the HTTP exposition server, and the negotiation-cycle piggyback that
makes the control plane the metrics transport."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu.run.launch import run
from horovod_tpu.utils import metrics as hvd_metrics

_ENV = {"JAX_PLATFORMS": "cpu"}


@pytest.fixture
def reg():
    """Fresh enabled process registry; restores the env default after."""
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()


class TestInstruments:
    def test_counter_sums(self, reg):
        c = reg.counter("t_c", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_gauge_sets_and_incs(self, reg):
        g = reg.gauge("t_g")
        g.set(7)
        g.inc(-2)
        assert g.value == 5.0

    def test_histogram_bucket_placement(self, reg):
        h = reg.histogram("t_h", buckets=(1.0, 2.0, 4.0)).labels()
        for v in (0.5, 1.5, 1.5, 3.0, 99.0):
            h.observe(v)
        # per-bucket (non-cumulative) counts incl. the +Inf bucket
        assert h.counts == [1, 2, 1, 1]
        assert h.count == 5
        assert h.sum == pytest.approx(105.5)

    def test_histogram_rejects_unsorted_bounds(self):
        with pytest.raises(ValueError, match="not sorted"):
            hvd_metrics.Histogram((2.0, 1.0))

    def test_labeled_children_are_distinct(self, reg):
        fam = reg.counter("t_ops", labels=("op",))
        fam.labels(op="allreduce").inc(3)
        fam.labels(op="allgather").inc(1)
        assert fam.labels(op="allreduce").value == 3
        assert fam.labels(op="allgather").value == 1

    def test_reregistration_is_idempotent(self, reg):
        assert reg.counter("t_same") is reg.counter("t_same")

    def test_kind_mismatch_raises(self, reg):
        reg.counter("t_kind")
        with pytest.raises(ValueError, match="re-registered"):
            reg.gauge("t_kind")

    def test_bucket_mismatch_raises(self, reg):
        reg.histogram("t_b", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="different buckets"):
            reg.histogram("t_b", buckets=(1.0, 3.0))

    def test_event_ring_and_clock(self, reg):
        ev = reg.event("stall", tensor="grad0", missing_ranks=[1])
        assert ev["event"] == "stall" and ev["missing_ranks"] == [1]
        # shared timeline clock: ts_us on the monotonic base, epoch_us
        # the cross-rank-comparable anchor
        clock = hvd_metrics.shared_clock()
        assert ev["epoch_us"] == clock.epoch_us_at_ts0 + ev["ts_us"]
        assert reg.events()[-1] is ev


class TestAggregation:
    """The acceptance contract: rank-0 aggregation equals the sum of the
    per-rank registries."""

    def _rank_registry(self, rank):
        r = hvd_metrics.MetricsRegistry(rank=rank)
        r.counter("hvd_negotiation_cycles_total").inc(10 * (rank + 1))
        r.gauge("hvd_stalled_tensors").set(rank)
        h = r.histogram("hvd_negotiation_cycle_seconds",
                        buckets=(0.001, 0.01, 0.1))
        h.observe(0.005 * (rank + 1))
        r.counter("hvd_collective_bytes_total", labels=("op",)) \
            .labels(op="allreduce").inc(1024 * (rank + 1))
        r.event("marker", rank=rank)
        return r

    def test_merge_is_sum_of_per_rank_registries(self):
        regs = [self._rank_registry(r) for r in range(3)]
        agg = hvd_metrics.merge_snapshots([r.snapshot() for r in regs])
        assert agg["ranks"] == [0, 1, 2]
        m = agg["metrics"]
        assert m["hvd_negotiation_cycles_total"]["values"][0]["value"] \
            == 10 + 20 + 30
        assert m["hvd_stalled_tensors"]["values"][0]["value"] == 0 + 1 + 2
        hist = m["hvd_negotiation_cycle_seconds"]["values"][0]
        assert hist["count"] == 3
        assert hist["sum"] == pytest.approx(0.005 + 0.010 + 0.015)
        assert sum(hist["counts"]) == 3
        (ar,) = m["hvd_collective_bytes_total"]["values"]
        assert ar["labels"] == {"op": "allreduce"}
        assert ar["value"] == 1024 + 2048 + 3072
        # events concatenate ordered by the epoch anchor
        assert [e["rank"] for e in agg["events"]
                if e["event"] == "marker"] == [0, 1, 2]

    def test_bucket_bounds_mismatch_across_ranks_raises(self):
        a = hvd_metrics.MetricsRegistry(rank=0)
        b = hvd_metrics.MetricsRegistry(rank=1)
        a.histogram("h", buckets=(1.0, 2.0)).observe(1)
        b.histogram("h", buckets=(1.0, 3.0)).observe(1)
        with pytest.raises(ValueError, match="bucket bounds differ"):
            hvd_metrics.merge_snapshots([a.snapshot(), b.snapshot()])


class TestPrometheus:
    def _populated(self):
        r = hvd_metrics.MetricsRegistry(rank=0)
        r.counter("hvd_coordinator_cycles_total", "cycles").inc(42)
        r.gauge("hvd_stalled_ranks").set(2)
        r.counter("hvd_collective_bytes_total", labels=("op",)) \
            .labels(op="allreduce").inc(4096)
        h = r.histogram("hvd_flush_seconds", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 5.0):
            h.observe(v)
        return r

    def test_round_trip_names_types_values(self):
        snap = self._populated().snapshot()
        text = hvd_metrics.render_prometheus(snap)
        parsed = hvd_metrics.parse_prometheus(text)
        assert parsed["hvd_coordinator_cycles_total"]["type"] == "counter"
        assert parsed["hvd_stalled_ranks"]["type"] == "gauge"
        assert parsed["hvd_flush_seconds"]["type"] == "histogram"
        (labels, v), = parsed["hvd_coordinator_cycles_total"]["samples"]
        assert v == 42
        samples = parsed["hvd_collective_bytes_total"]["samples"]
        assert samples == [({"op": "allreduce"}, 4096.0)]

    def test_histogram_buckets_cumulative_and_monotonic(self):
        snap = self._populated().snapshot()
        parsed = hvd_metrics.parse_prometheus(
            hvd_metrics.render_prometheus(snap))
        samples = parsed["hvd_flush_seconds"]["samples"]
        buckets = [(l["le"], v) for l, v in samples
                   if l.get("__series__") == "bucket"]
        counts = [v for _, v in buckets]
        assert counts == sorted(counts), "bucket counts must be cumulative"
        assert buckets[-1][0] == "+Inf"
        total = [v for l, v in samples
                 if l.get("__series__") == "count"][0]
        assert counts[-1] == total == 4
        ssum = [v for l, v in samples if l.get("__series__") == "sum"][0]
        assert ssum == pytest.approx(5.105)

    def test_label_values_with_commas_and_quotes_survive(self):
        r = hvd_metrics.MetricsRegistry()
        r.counter("t_esc", labels=("k",)).labels(k='a,"b",c').inc()
        parsed = hvd_metrics.parse_prometheus(r.to_prometheus())
        (labels, v), = parsed["t_esc"]["samples"]
        assert labels["k"] == 'a,"b",c' and v == 1

    def test_histogram_quantile_interpolates(self):
        bounds = (1.0, 2.0, 4.0)
        counts = [0, 100, 0, 0]  # everything in (1, 2]
        q50 = hvd_metrics.histogram_quantile(bounds, counts, 0.5)
        assert 1.0 < q50 <= 2.0
        assert hvd_metrics.histogram_quantile(bounds, [0, 0, 0, 0],
                                              0.5) is None


class TestDisabled:
    def test_null_registry_is_inert(self):
        r = hvd_metrics.reset(enabled=False)
        try:
            assert not r.enabled
            r.counter("x").inc()
            r.gauge("y").labels(op="z").set(5)
            r.histogram("h").observe(1.0)
            assert r.event("stall") is None
            snap = r.snapshot()
            assert snap["metrics"] == {} and snap.get("disabled")
            assert r.to_prometheus() == ""
        finally:
            hvd_metrics.reset()

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("HVD_METRICS", "0")
        r = hvd_metrics.reset()
        try:
            assert isinstance(r, hvd_metrics.NullRegistry)
        finally:
            monkeypatch.delenv("HVD_METRICS")
            hvd_metrics.reset()


class TestHTTPServer:
    def _get(self, port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
            return resp.read().decode()

    def test_scrape_round_trip_with_remote_aggregate(self):
        local = hvd_metrics.MetricsRegistry(rank=0)
        local.counter("hvd_negotiation_cycles_total").inc(5)
        remote = hvd_metrics.MetricsRegistry(rank=1)
        remote.counter("hvd_negotiation_cycles_total").inc(7)
        srv = hvd_metrics.MetricsServer(
            0, local.snapshot,
            remote_snapshots_fn=lambda: {1: remote.snapshot()})
        try:
            text = self._get(srv.port, "/metrics")
            parsed = hvd_metrics.parse_prometheus(text)
            (_, v), = parsed["hvd_negotiation_cycles_total"]["samples"]
            assert v == 12  # aggregate = local + remote
            data = json.loads(self._get(srv.port, "/metrics.json"))
            assert set(data["ranks"]) == {"0", "1"}
            agg = data["aggregate"]
            assert agg["ranks"] == [0, 1]
            assert agg["metrics"]["hvd_negotiation_cycles_total"][
                "values"][0]["value"] == 12
        finally:
            srv.close()

    def test_live_local_registry_wins_over_stale_self_snapshot(self):
        local = hvd_metrics.MetricsRegistry(rank=0)
        c = local.counter("hvd_coordinator_cycles_total")
        c.inc(3)
        stale = local.snapshot()
        c.inc(97)  # live value moves past the snapshot
        srv = hvd_metrics.MetricsServer(
            0, local.snapshot,
            remote_snapshots_fn=lambda: {0: stale})
        try:
            parsed = hvd_metrics.parse_prometheus(
                self._get(srv.port, "/metrics"))
            (_, v), = parsed["hvd_coordinator_cycles_total"]["samples"]
            assert v == 100  # not 103: the stale rank-0 snapshot dropped
        finally:
            srv.close()


class TestCoordinatorTelemetry:
    """Coordinator-side instruments and the snapshot piggyback, using
    the in-process CycleRequest harness (no processes involved)."""

    def _service(self, nproc=2, **cfg_kw):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        cfg_kw.setdefault("stall_warning_time_seconds", 0)
        cfg = HorovodConfig(**cfg_kw)
        svc = neg.CoordinatorService(nproc, b"k" * 32,
                                     ports=[0], config=cfg)
        return svc, neg

    def _meta(self, neg, name, dtype="float32"):
        return neg.EntryMeta(name, "allreduce", dtype, (4,), 0, False)

    def test_cycle_counters_and_cache_hit_miss(self, reg):
        svc, neg = self._service()
        try:
            meta = self._meta(neg, "g")
            svc._handle(neg.CycleRequest(0, [meta], ack=-1, req_id=1),
                        ("127.0.0.1", 0))
            svc._handle(neg.CycleRequest(1, [meta], ack=-1, req_id=1),
                        ("127.0.0.1", 0))
            assert reg.counter("hvd_coordinator_cycles_total").value == 2
            assert reg.counter("hvd_response_cache_misses_total").value \
                == 2
            # steady state: the name EXECUTEd, so both ranks resubmit as
            # a cache hit
            cid = svc._cache_id_of["g"]
            hits = neg.encode_hits([cid])
            for r in (0, 1):
                svc._handle(neg.CycleRequest(r, [], ack=0, req_id=2,
                                             hits=hits),
                            ("127.0.0.1", 0))
            assert reg.counter("hvd_response_cache_hits_total").value == 2
            # an id the coordinator never issued scans as unknown
            resp = svc._handle(
                neg.CycleRequest(0, [], ack=0, req_id=3,
                                 hits=neg.encode_hits([cid + 999])),
                ("127.0.0.1", 0))
            assert resp.unknown_ids == (cid + 999,)
            assert reg.counter(
                "hvd_response_cache_unknown_ids_total").value == 1
            # tensors/cycle histogram saw every announcement
            h = reg.histogram("hvd_coordinator_tensors_per_cycle",
                              buckets=hvd_metrics.COUNT_BUCKETS).labels()
            assert h.count == 5
        finally:
            svc.shutdown()

    def test_wire_bytes_counter_tracks_encode_decode(self, reg):
        from horovod_tpu.ops import negotiation as neg
        resp = neg.CycleResponse(0, [], (64 << 20, 5.0), False)
        payload = neg.encode_response(resp)
        neg.decode_response(payload)
        fam = reg.counter("hvd_response_wire_bytes_total",
                          labels=("direction",))
        assert fam.labels(direction="out").value == len(payload)
        assert fam.labels(direction="in").value == len(payload)

    def test_piggybacked_snapshot_stored_and_aggregated(self, reg):
        svc, neg = self._service()
        try:
            reg.rank = 0
            worker = hvd_metrics.MetricsRegistry(rank=1)
            worker.counter("hvd_negotiation_cycles_total").inc(7)
            snap = worker.snapshot()
            svc._handle(neg.CycleRequest(1, [], ack=-1, req_id=1,
                                         metrics=snap),
                        ("127.0.0.1", 0))
            assert svc.metrics_snapshots[1] is snap
            # rank 0's exposition server serves the merged view
            srv = hvd_metrics.MetricsServer(
                0, reg.snapshot,
                remote_snapshots_fn=lambda: dict(svc.metrics_snapshots))
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics.json",
                        timeout=5) as r:
                    data = json.loads(r.read().decode())
            finally:
                srv.close()
            agg = data["aggregate"]
            assert agg["ranks"] == [0, 1]
            assert agg["metrics"]["hvd_negotiation_cycles_total"][
                "values"][0]["value"] == 7
            # rank 0's own coordinator counter rides the same aggregate
            assert agg["metrics"]["hvd_coordinator_cycles_total"][
                "values"][0]["value"] == 1
        finally:
            svc.shutdown()

    def test_stall_scan_sets_gauge_and_event_then_clears(self, reg):
        svc, neg = self._service(stall_warning_time_seconds=0.05)
        try:
            svc._submit(0, [self._meta(neg, "slow")])  # rank 1 missing
            time.sleep(0.08)
            svc._stall_scan()
            assert reg.gauge("hvd_stalled_ranks").value == 1
            assert reg.gauge("hvd_coordinator_stalled_tensors").value == 1
            (ev,) = [e for e in reg.events() if e["event"] == "stall"]
            assert ev["tensor"] == "slow"
            assert ev["missing_ranks"] == [1]
            assert ev["waited_s"] >= 0.05
            # one structured event per tensor, like the log line
            svc._stall_scan()
            assert len([e for e in reg.events()
                        if e["event"] == "stall"]) == 1
            # the laggard arrives: the row negotiates away and the
            # gauges CLEAR — stall state is current, not sticky
            svc._submit(1, [self._meta(neg, "slow")])
            svc._negotiate()
            svc._stall_scan()
            assert reg.gauge("hvd_stalled_ranks").value == 0
            assert reg.gauge("hvd_coordinator_stalled_tensors").value == 0
        finally:
            svc.shutdown()


class TestSatelliteInstrumentation:
    def test_fusion_plan_records_fill_fraction(self, reg):
        from horovod_tpu.ops import fusion
        leaves = [np.zeros((10,), np.float32) for _ in range(4)]  # 40 B
        fusion.plan_buckets(leaves, fusion_threshold=100)
        assert reg.counter("hvd_fusion_tensors_total").value == 4
        assert reg.counter("hvd_fusion_bytes_total").value == 160
        assert reg.counter("hvd_fusion_buckets_total").value == 2
        h = reg.histogram("hvd_fusion_fill_ratio",
                          buckets=hvd_metrics.RATIO_BUCKETS).labels()
        assert h.count == 2
        assert h.sum == pytest.approx(1.6)  # 80/100 + 80/100

    def test_fusion_plan_flags_oversized(self, reg):
        """A tensor at/over the threshold bypasses fusion — that must be
        loud (event + counter), not a mystery extra collective."""
        from horovod_tpu.ops import fusion
        leaves = [np.zeros((50,), np.float32),   # 200 B >= 100
                  np.zeros((10,), np.float32),
                  np.zeros((10,), np.float32)]
        buckets = fusion.plan_buckets(leaves, fusion_threshold=100)
        assert [b.indices for b in buckets] == [[0], [1, 2]]
        assert reg.counter("hvd_fusion_oversized_total").value == 1
        (ev,) = [e for e in reg.events()
                 if e["event"] == "oversized_tensor"]
        assert ev["index"] == 0
        assert ev["nbytes"] == 200
        assert ev["threshold"] == 100
        # threshold 0 = fusion disabled BY REQUEST: every tensor rides
        # alone, and none of that is "oversized"
        fusion.plan_buckets(leaves, fusion_threshold=0)
        assert reg.counter("hvd_fusion_oversized_total").value == 1
        # a bucket exactly filled by several members is not oversized
        fusion.plan_buckets([np.zeros((20,), np.float32),
                             np.zeros((5,), np.float32)],
                            fusion_threshold=100)
        assert reg.counter("hvd_fusion_oversized_total").value == 1

    def test_fusion_plan_never_mixes_dtypes(self, reg):
        from horovod_tpu.ops import fusion
        leaves = [np.zeros((4,), np.float32), np.zeros((4,), np.float16),
                  np.zeros((4,), np.float32), np.zeros((4,), np.float16)]
        buckets = fusion.plan_buckets(leaves, fusion_threshold=1 << 20)
        assert [b.indices for b in buckets] == [[0, 2], [1, 3]]
        for b in buckets:
            assert len({str(leaves[i].dtype) for i in b.indices}) == 1

    def test_chaos_injection_counts(self, reg):
        from horovod_tpu.run import chaos
        rules = chaos.parse_spec("negotiation:*:drop_request:1.0", seed=7)
        inj = chaos.ChaosInjector("negotiation", rules, delay_ms=0)
        assert inj.decide("request", "CycleRequest") == "drop_request"
        fam = reg.counter("hvd_chaos_injections_total",
                          labels=("fault",))
        assert fam.labels(fault="drop_request").value == 1
        (ev,) = [e for e in reg.events()
                 if e["event"] == "chaos_injection"]
        assert ev["fault"] == "drop_request"
        assert ev["service"] == "negotiation"

    def test_instrument_step_counts_and_throughput(self, reg):
        from horovod_tpu import trainer
        stepped = []

        def step(x):
            stepped.append(x)
            time.sleep(0.01)
            return x * 2

        wrapped = trainer.instrument_step(step, tokens_per_step=1024,
                                          name="unit")
        assert wrapped(3) == 6 and stepped == [3]
        m = reg.snapshot()["metrics"]
        (steps,) = m["hvd_steps_total"]["values"]
        assert steps["labels"] == {"loop": "unit"} and steps["value"] == 1
        (sec,) = m["hvd_step_seconds"]["values"]
        assert sec["count"] == 1 and sec["sum"] >= 0.01
        (tps,) = m["hvd_tokens_per_second"]["values"]
        assert 0 < tps["value"] <= 1024 / 0.01

    def test_instrument_step_mfu_gauge(self, reg):
        from horovod_tpu import trainer
        from horovod_tpu.utils import costmodel
        spec = costmodel.ChipSpec("test", 1e9, 1e9, 1e9)

        def step(x):
            time.sleep(0.01)
            return x

        wrapped = trainer.instrument_step(
            step, tokens_per_step=1000, name="unit",
            flops_per_token=1e6, spec=spec)
        wrapped(1)
        m = reg.snapshot()["metrics"]
        (mfu,) = m["hvd_mfu"]["values"]
        assert mfu["labels"] == {"loop": "unit"}
        # flops_per_step=1e9 at peak 1e9 → mfu = 1/dt seconds⁻¹·s;
        # dt ≥ 10ms → mfu ≤ 100, > 0
        assert 0 < mfu["value"] <= 100

    def test_instrument_step_no_mfu_without_spec_on_cpu(self, reg):
        from horovod_tpu import trainer
        wrapped = trainer.instrument_step(
            lambda x: x, tokens_per_step=10, name="unit",
            flops_per_token=100)  # spec auto-detect → cpu → no gauge
        wrapped(1)
        assert "hvd_mfu" not in reg.snapshot()["metrics"]

    def test_instrument_step_peak_hbm_gauge(self, reg, monkeypatch):
        # memory plane (docs/memory.md): allocator-backed peak bytes
        # next to the MFU gauge; CPU has no allocator stats, so the
        # probe is faked the way a TPU backend would answer
        from horovod_tpu import trainer
        from horovod_tpu.utils import memory as hvd_memory
        monkeypatch.setattr(hvd_memory, "step_peak_bytes",
                            lambda device=None: 12345)
        wrapped = trainer.instrument_step(lambda x: x, name="unit")
        wrapped(1)
        m = reg.snapshot()["metrics"]
        (peak,) = m["hvd_step_peak_hbm_bytes"]["values"]
        assert peak["labels"] == {"loop": "unit"}
        assert peak["value"] == 12345

    def test_step_peak_sums_in_use_and_reserved(self, reg, monkeypatch):
        # on the v5e runtime peak_bytes_in_use leaves out the scratch
        # of loaded programs, which peak_bytes_reserved counts (PERF.md
        # §6): the gauge publishes their sum, as benchmarks/run.py does
        from horovod_tpu import trainer
        from horovod_tpu.utils import memory as hvd_memory
        monkeypatch.setattr(
            hvd_memory, "device_memory_stats",
            lambda device=None: {"bytes_in_use": 5,
                                 "peak_bytes_in_use": 7_290,
                                 "peak_bytes_reserved": 3_580})
        assert hvd_memory.step_peak_bytes() == 10_870
        wrapped = trainer.instrument_step(lambda x: x, name="unit")
        wrapped(1)
        (peak,) = reg.snapshot()["metrics"][
            "hvd_step_peak_hbm_bytes"]["values"]
        assert peak["value"] == 10_870
        # a runtime without the reserved field reads the first alone
        monkeypatch.setattr(hvd_memory, "device_memory_stats",
                            lambda device=None: {"peak_bytes_in_use": 9})
        assert hvd_memory.step_peak_bytes() == 9

    def test_instrument_step_no_peak_gauge_on_cpu(self, reg):
        # the CPU-null arm, mirroring the MFU gauge: no allocator
        # stats → the gauge is never created, not created-as-zero
        from horovod_tpu import trainer
        wrapped = trainer.instrument_step(lambda x: x, name="unit")
        wrapped(1)
        assert "hvd_step_peak_hbm_bytes" not in \
            reg.snapshot()["metrics"]

    def test_instrument_step_periodic_attribution(self, reg):
        import jax
        import jax.numpy as jnp

        from horovod_tpu import trainer
        f = jax.jit(lambda x: jnp.dot(x, x).sum())
        x = jnp.ones((64, 64))
        f(x).block_until_ready()  # compile outside the wrapper

        def step(x):
            out = f(x)
            out.block_until_ready()
            return out

        wrapped = trainer.instrument_step(step, name="unit",
                                          attrib_every=2)
        for _ in range(5):  # captures at steps 2 and 4
            wrapped(x)
        assert not [e for e in reg.events()
                    if e["event"] == "perf_attrib_error"]
        m = reg.snapshot()["metrics"]
        (busy,) = m["hvd_step_device_busy_frac"]["values"]
        assert busy["labels"] == {"loop": "unit"}
        assert busy["value"] >= 0
        classes = {v["labels"]["op_class"]
                   for v in m["hvd_step_breakdown_ms"]["values"]}
        assert "matmul" in classes
        # second capture has an EMA to drift against
        assert m["hvd_step_breakdown_drift"]["values"]
        assert m["hvd_step_exposed_comm_ms"]["values"]
        assert m["hvd_step_hidden_comm_ms"]["values"]

    def test_instrument_step_attrib_off_by_default(self, reg):
        from horovod_tpu import trainer
        wrapped = trainer.instrument_step(lambda x: x, name="unit")
        for _ in range(3):
            wrapped(1)
        m = reg.snapshot()["metrics"]
        assert "hvd_step_breakdown_ms" not in m
        assert "hvd_step_device_busy_frac" not in m

    def test_instrument_step_disabled_is_passthrough(self):
        hvd_metrics.reset(enabled=False)
        try:
            from horovod_tpu import trainer

            def step():
                return 1

            assert trainer.instrument_step(step) is step
        finally:
            hvd_metrics.reset()


class TestTwoRankEndpoints:
    """Acceptance: a 2-rank run with HVD_METRICS_PORT serves Prometheus
    and JSON endpoints, and rank 0's aggregate covers both ranks."""

    def test_two_rank_scrape_covers_both_ranks(self):
        def fn():
            import json as _json
            import os
            import time
            import urllib.request
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.utils import metrics as hm
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])

            # The negotiation control plane (and therefore the metrics
            # piggyback) is pure TCP and works everywhere; the XLA data
            # plane may not support multiprocess CPU — telemetry must
            # still flow, so execution failures are tolerated and the
            # data-plane assertions become conditional.
            data_plane_ok = True

            def reduce(name):
                nonlocal data_plane_ok
                h = hvd.allreduce_async(np.ones((64,), np.float32),
                                        average=False, name=name)
                try:
                    hvd.synchronize(h)
                except Exception:
                    data_plane_ok = False

            for i in range(3):
                reduce(f"m{i}")
            # outlive HVD_METRICS_INTERVAL so the next flush piggybacks
            # a fresh worker snapshot onto the negotiation cycle
            time.sleep(0.3)
            reduce("late")
            port = int(os.environ["HVD_METRICS_PORT"]) + r
            deadline = time.monotonic() + 10
            data = text = None
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=5) as resp:
                    text = resp.read().decode()
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics.json",
                        timeout=5) as resp:
                    data = _json.loads(resp.read().decode())
                if r != 0 or len(data["aggregate"].get("ranks", [])) == 2:
                    break
                time.sleep(0.2)
            parsed = hm.parse_prometheus(text)
            agg = data["aggregate"]["metrics"]
            cyc = parsed.get("hvd_negotiation_cycle_seconds",
                             {"samples": []})["samples"]
            bucket_counts = [v for l, v in cyc
                             if l.get("__series__") == "bucket"]
            out = {
                "rank": r,
                "data_plane_ok": data_plane_ok,
                "prom_names": sorted(parsed.keys()),
                "agg_ranks": data["aggregate"].get("ranks", []),
                "agg_cycles": agg.get(
                    "hvd_negotiation_cycles_total",
                    {"values": [{"value": 0}]})["values"][0]["value"],
                "coord_cycles": agg.get(
                    "hvd_coordinator_cycles_total",
                    {"values": [{"value": 0}]})["values"][0]["value"],
                "buckets_monotonic":
                    bucket_counts == sorted(bucket_counts),
            }
            hvd.shutdown()
            return out

        base = 19100 + (os.getpid() % 1000)
        env = dict(_ENV)
        env["HVD_METRICS_PORT"] = str(base)
        env["HVD_METRICS_INTERVAL"] = "0.1"
        results = run(fn, num_proc=2, env=env)
        by_rank = {res["rank"]: res for res in results}
        for res in results:
            assert "hvd_negotiation_cycles_total" in res["prom_names"]
            if res["data_plane_ok"]:
                assert "hvd_collective_bytes_total" in res["prom_names"]
            assert res["buckets_monotonic"]
        r0 = by_rank[0]
        assert r0["agg_ranks"] == [0, 1], r0
        assert "hvd_coordinator_cycles_total" in r0["prom_names"]
        assert r0["coord_cycles"] >= 4  # >= one cycle per rank per tensor
        # aggregate cycles = both ranks' worth: strictly more than any
        # single rank could have contributed alone
        assert r0["agg_cycles"] > by_rank[1]["agg_cycles"] / 2, results
