"""Numerics plane (utils/numerics.py): one-pass stats math in all three
segment layouts, the fixed-arity batched kernels and their async
park/drain lifecycle, the EMA anomaly policy, digest wire stability,
rank blame, the coordinator's cross-rank divergence sentinel, and the
CycleRequest piggyback end to end over real TCP.

Everything here is single-host CPU; the cross-PROCESS story (a real
divergence drill with flight dumps and a postmortem verdict) lives in
tests/test_chaos_plane.py.
"""

import logging
import math
import os

import numpy as np
import pytest

from horovod_tpu.common.config import HorovodConfig
from horovod_tpu.ops import negotiation as neg
from horovod_tpu.run import network
from horovod_tpu.utils import metrics as hvd_metrics
from horovod_tpu.utils import numerics as hvd_numerics
from horovod_tpu.utils import tracing as hvd_tracing

KEY = b"k" * 32


def _val(reg, name, **labels):
    """Read one instrument's value by family name (families register
    once, at monitor/coordinator construction)."""
    fam = reg._families[name]
    return fam.labels(**labels).value if labels else fam.value


def _anomaly_events(reg):
    return [e for e in reg.events() if e.get("event") == "numerics_anomaly"]


@pytest.fixture
def reg():
    """Fresh enabled metrics registry (the monitor binds its instruments
    at construction, so this must precede the monitor fixture)."""
    r = hvd_metrics.reset(enabled=True)
    yield r
    hvd_metrics.reset()


@pytest.fixture
def monitor(reg, tmp_path, monkeypatch):
    """Fresh enabled monitor with deterministic policy knobs and flight
    dumps routed into tmp_path."""
    monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
    hvd_tracing.reset(enabled=True)
    m = hvd_numerics.reset(enabled=True, ema_beta=0.5, ema_k=4.0,
                           warmup=2)
    yield m
    hvd_numerics.reset()
    hvd_tracing.reset()


def _expect_stats(x):
    """Reference stats computed with plain numpy (float64 accumulation
    is fine: the assertions use rel tolerances far above f32 noise)."""
    f = np.asarray(x, np.float64).reshape(-1)
    finite = np.isfinite(f)
    safe = np.where(finite, f, 0.0)
    n = f.size
    return {
        "l2": math.sqrt(float(np.sum(safe * safe))),
        "max_abs": float(np.max(np.abs(safe))) if n else 0.0,
        "nonfinite": float(n - np.count_nonzero(finite)),
        "zero_frac": float(np.count_nonzero(f == 0.0) / n) if n else 0.0,
        "checksum": float(np.sum(safe)),
    }


def _assert_row(row, x, rel=1e-4, abs_tol=1e-4):
    want = _expect_stats(x)
    S = hvd_numerics
    assert float(row[S.S_L2]) == pytest.approx(want["l2"], rel=rel,
                                               abs=abs_tol)
    assert float(row[S.S_MAX_ABS]) == pytest.approx(want["max_abs"],
                                                    rel=1e-5)
    assert float(row[S.S_NONFINITE]) == want["nonfinite"]
    assert float(row[S.S_ZERO_FRAC]) == pytest.approx(want["zero_frac"],
                                                      abs=1e-6)
    assert float(row[S.S_CHECKSUM]) == pytest.approx(want["checksum"],
                                                     rel=rel,
                                                     abs=max(abs_tol, 1e-3))


class TestTensorStats:
    def test_known_values(self):
        s = hvd_numerics.tensor_stats(np.array([3.0, -4.0, 0.0],
                                               np.float32))
        assert float(s["l2"]) == pytest.approx(5.0)
        assert float(s["max_abs"]) == pytest.approx(4.0)
        assert float(s["nonfinite"]) == 0.0
        assert float(s["zero_frac"]) == pytest.approx(1.0 / 3.0)
        assert float(s["checksum"]) == pytest.approx(-1.0)

    def test_nonfinite_counted_but_excluded_from_norms(self):
        x = np.array([np.nan, np.inf, -np.inf, 2.0], np.float32)
        s = hvd_numerics.tensor_stats(x)
        # a NaN burst must not wipe out the norm gauges describing it
        assert float(s["nonfinite"]) == 3.0
        assert float(s["l2"]) == pytest.approx(2.0)
        assert float(s["max_abs"]) == pytest.approx(2.0)

    def test_empty_input_is_all_zero(self):
        s = hvd_numerics.tensor_stats(np.zeros((0,), np.float32))
        assert all(float(v) == 0.0 for v in s.values())

    def test_integer_input_has_no_nonfinites(self):
        s = hvd_numerics.tensor_stats(np.array([[1, -2], [0, 4]],
                                               np.int32))
        assert float(s["nonfinite"]) == 0.0
        assert float(s["max_abs"]) == pytest.approx(4.0)
        assert float(s["zero_frac"]) == pytest.approx(0.25)

    def test_stats_vector_matches_dict_layout(self):
        x = np.array([1.0, np.nan, 0.0, -7.0], np.float32)
        v = np.asarray(hvd_numerics.stats_vector(x))
        assert v.shape == (5,)
        _assert_row(v, x)


class TestSegmentStats:
    def _check_layout(self, sizes, seed=0, rel=1e-4, abs_tol=1e-4):
        rng = np.random.default_rng(seed)
        parts = [rng.standard_normal(s).astype(np.float32) for s in sizes]
        if parts and parts[0].size:
            parts[0][0] = np.nan  # nonfinite lands in slice 0 only
        flat = (np.concatenate(parts) if parts
                else np.zeros((0,), np.float32))
        mat = np.asarray(hvd_numerics.segment_stats(flat, sizes))
        assert mat.shape == (len(sizes), 5)
        for row, part in zip(mat, parts):
            _assert_row(row, part, rel=rel, abs_tol=abs_tol)

    def test_uniform_layout(self):
        # all sizes equal: the no-gather reshape path
        self._check_layout([16] * 8)

    def test_padded_gather_layout(self):
        self._check_layout([3, 17, 1, 30, 9])

    def test_cumsum_fallback_layout(self):
        # one huge slice beside tiny ones: n * max_s blows the padding
        # budget, forcing the cumsum-difference + segment_max path
        sizes = [8192] + [2] * 40
        assert len(sizes) * max(sizes) > max(4 * sum(sizes), 4096)
        # loose ABSOLUTE tolerance: a tiny segment's sum-of-squares
        # comes out as the difference of two large f32 cumulative sums,
        # so the error scales with the whole buffer, not the segment
        # (cancellation is the price of the memory-bounded fallback)
        self._check_layout(sizes, rel=5e-3, abs_tol=2e-2)

    def test_empty_segment_among_real_ones(self):
        rng = np.random.default_rng(1)
        flat = rng.standard_normal(8).astype(np.float32)
        mat = np.asarray(hvd_numerics.segment_stats(flat, [5, 0, 3]))
        _assert_row(mat[0], flat[:5])
        # the empty slice reads as all-zero, never -inf/NaN
        assert np.all(np.isfinite(mat[1])) and np.all(mat[1] == 0.0)
        _assert_row(mat[2], flat[5:])

    def test_layouts_agree_with_each_other(self):
        # the uniform and padded-gather impls are interchangeable: same
        # logical slices, same rows
        rng = np.random.default_rng(2)
        flat = rng.standard_normal(64).astype(np.float32)
        uniform = np.asarray(hvd_numerics.segment_stats(flat, [16] * 4))
        padded = np.asarray(hvd_numerics.segment_stats(
            np.concatenate([flat, np.zeros(2, np.float32)]),
            [16, 16, 16, 16, 2]))[:4]
        np.testing.assert_allclose(uniform, padded, rtol=1e-5, atol=1e-6)


class TestBatchedKernels:
    def test_batch_stats_matches_per_tensor(self):
        rng = np.random.default_rng(3)
        arrays = [rng.standard_normal((4, 8)).astype(np.float32)
                  for _ in range(3)]
        arrays.append(np.full((7,), np.inf, np.float32))  # second shape
        arrays.append(rng.standard_normal(5).astype(np.float64))
        mat = hvd_numerics._batch_stats(arrays)
        assert mat.shape == (5, 5)
        for row, a in zip(mat, arrays):
            _assert_row(row, a)

    def test_pow2_padding_rows_never_leak(self):
        # 3 same-shape arrays ride a 4-ary kernel; the zero padding row
        # must be sliced off before the caller sees anything
        arrays = [np.full((6,), float(i + 1), np.float32)
                  for i in range(3)]
        groups = list(hvd_numerics._batch_stats_groups(arrays))
        assert len(groups) == 1
        idxs, k, dev = groups[0]
        assert idxs == [0, 1, 2] and k == 3
        assert np.asarray(dev).shape == (4, 5)  # padded on device...
        mat = hvd_numerics._batch_stats(arrays)
        assert mat.shape == (3, 5)              # ...sliced at the host
        for row, a in zip(mat, arrays):
            _assert_row(row, a)

    def test_kernel_cache_keys_are_pow2_not_batch_layout(self):
        # racy flush splits must not compile fresh kernels: any group of
        # 5..8 same-shape tensors lands on the same 8-ary kernel
        fn = hvd_numerics._group_stats_fn
        assert fn(8, (6,)) is fn(8, (6,))
        for k in (5, 6, 7, 8):
            arrays = [np.ones((6,), np.float32)] * k
            ((_, got_k, dev),) = hvd_numerics._batch_stats_groups(arrays)
            assert got_k == k and np.asarray(dev).shape == (8, 5)

    def test_mixed_shapes_group_independently(self):
        arrays = [np.ones((4,), np.float32), np.ones((2, 2), np.float32),
                  np.ones((4,), np.float32)]
        groups = {tuple(idxs) for idxs, _, _ in
                  hvd_numerics._batch_stats_groups(arrays)}
        assert groups == {(0, 2), (1,)}


class TestMonitorObserve:
    def test_local_path_is_async_and_drain_forces(self, monitor, reg):
        g = np.array([3.0, 4.0], np.float32)
        out = monitor.observe([("w", g, None)])
        assert out == {}  # local path never builds wire records
        monitor.drain()   # force the parked kernel result in
        assert _val(reg, "hvd_grad_norm", tensor="w") == pytest.approx(5.0)
        assert _val(reg, "hvd_numerics_tensors_observed_total") == 1

    def test_gauges_lag_by_at_most_one_drain(self, monitor, reg):
        # the async contract: after N observes plus one drain, all N
        # tensors' gauges are live (nothing is lost, only deferred)
        for i in range(4):
            monitor.observe([(f"t{i}", np.full((3,), float(i + 1),
                                               np.float32), None)])
        monitor.drain()
        for i in range(4):
            assert _val(reg, "hvd_grad_norm", tensor=f"t{i}") > 0.0

    def test_digest_path_returns_mirrored_records(self, monitor):
        g = np.array([1.0, -1.0, 0.0, np.nan], np.float32)
        recs = monitor.observe([("w", g, None)], cycle=7)
        R = hvd_numerics
        rec = recs["w"]
        assert len(rec) == 7
        # single-process: the reduced copy IS the local contribution
        assert rec[R.R_RED_L2] == rec[R.R_LOC_L2]
        assert rec[R.R_RED_NONFINITE] == rec[R.R_LOC_NONFINITE] == 1
        assert rec[R.R_RED_L2] == pytest.approx(math.sqrt(2.0), rel=1e-4)

    def test_digest_path_with_distinct_reduced_side(self, monitor):
        loc = np.array([2.0, 0.0], np.float32)
        red = np.array([8.0, 6.0], np.float32)
        rec = monitor.observe([("w", loc, red)], cycle=1)["w"]
        R = hvd_numerics
        assert rec[R.R_RED_L2] == pytest.approx(10.0, rel=1e-4)
        assert rec[R.R_LOC_L2] == pytest.approx(2.0, rel=1e-4)

    def test_ingest_builds_records_only_with_cycle(self, monitor):
        mat = np.asarray([[1.0, 1.0, 0.0, 0.0, 1.0]], np.float32)
        assert monitor.ingest(["w"], mat) == {}
        assert "w" in monitor.ingest(["w"], mat, cycle=3)

    def test_empty_observe_is_a_noop(self, monitor):
        assert monitor.observe([]) == {}
        assert monitor.observe([], cycle=1) == {}


class TestAnomalyPolicy:
    def test_nonfinite_flags_event_and_counter(self, monitor, reg,
                                               tmp_path):
        g = np.array([np.nan, 1.0, np.inf], np.float32)
        monitor.observe([("w", g, None)], cycle=2)
        evs = _anomaly_events(reg)
        assert len(evs) == 1
        ev = evs[0]
        assert ev["anomaly"] == hvd_numerics.ANOMALY_NONFINITE
        assert ev["tensor"] == "w" and ev["cycle"] == 2
        assert ev["nonfinite_local"] == 2
        assert _val(reg, "hvd_nonfinite_total", tensor="w",
                    where="local") == 2
        # the escalation wrote exactly one flight dump
        assert list(tmp_path.glob("flight-rank*.json"))

    def test_norm_spike_trips_after_warmup(self, monitor, reg):
        # warmup=2, ema_k=4: two calm steps arm the policy, then a 100x
        # spike trips it
        calm = np.ones((4,), np.float32)
        for c in range(3):
            monitor.observe([("w", calm, None)], cycle=c)
        monitor.observe([("w", calm * 100.0, None)], cycle=3)
        evs = _anomaly_events(reg)
        assert len(evs) == 1
        assert evs[0]["anomaly"] == hvd_numerics.ANOMALY_NORM_SPIKE
        assert evs[0]["l2"] == pytest.approx(200.0)
        assert evs[0]["ema"] == pytest.approx(2.0)
        # the drift gauge reads post-update: the spike is already folded
        # into the EMA (beta=0.5 -> ema 101), so drift = 200/101
        assert _val(reg, "hvd_grad_norm_drift",
                    tensor="w") == pytest.approx(200.0 / 101.0, rel=1e-5)

    def test_spike_policy_disarmed_during_warmup(self, monitor, reg):
        monitor.observe([("w", np.ones((4,), np.float32), None)], cycle=0)
        monitor.observe([("w", np.full((4,), 1e4, np.float32), None)],
                        cycle=1)
        assert not _anomaly_events(reg)

    def test_all_zero_warmup_never_flags_first_real_gradient(
            self, monitor, reg):
        z = np.zeros((4,), np.float32)
        for c in range(5):
            monitor.observe([("w", z, None)], cycle=c)
        monitor.observe([("w", np.ones((4,), np.float32) * 50.0, None)],
                        cycle=5)
        assert not _anomaly_events(reg)

    def test_anomaly_deduped_per_tensor_and_kind(self, monitor, reg):
        bad = np.array([np.nan], np.float32)
        for c in range(4):
            monitor.observe([("w", bad, None)], cycle=c)
        assert len(_anomaly_events(reg)) == 1  # a persistent NaN must
        # not flood the event ring — but the raw counter keeps counting
        assert _val(reg, "hvd_nonfinite_total", tensor="w",
                    where="local") == 4


class TestDigestWire:
    def test_round_is_stable_at_six_digits(self):
        assert hvd_numerics._round(1.23456789) == 1.23457
        assert hvd_numerics._round(0.1 + 0.2) == 0.3
        # two ranks arriving at the same value through different float
        # histories encode the same wire number
        assert hvd_numerics._round(sum([0.1] * 10)) == \
            hvd_numerics._round(1.0)

    def test_fold_digest_accumulates_cycles(self):
        d = hvd_numerics.fold_digest(None, 3, {"a": (1,) * 7}, rank=2)
        d = hvd_numerics.fold_digest(d, 3, {"b": (2,) * 7}, rank=2)
        d = hvd_numerics.fold_digest(d, 4, {"a": (3,) * 7}, rank=2)
        assert d["v"] == hvd_numerics.DIGEST_VERSION and d["rank"] == 2
        assert sorted(d["cycles"]) == [3, 4]
        assert sorted(d["cycles"][3]) == ["a", "b"]

    def test_fold_digest_empty_records_change_nothing(self):
        assert hvd_numerics.fold_digest(None, 1, {}, rank=0) is None

    def test_records_disagree_tolerance(self):
        a = (10.0, 2.0, 0, 5.0, 10.0, 2.0, 0)
        within = (10.0 * (1 + 5e-5), 2.0, 0, 5.0, 99.0, 2.0, 0)
        beyond = (10.0 * 1.01, 2.0, 0, 5.0, 10.0, 2.0, 0)
        assert not hvd_numerics.records_disagree(a, within, tol=1e-4)
        assert hvd_numerics.records_disagree(a, beyond, tol=1e-4)
        # local columns are evidence for blame, not for disagreement
        assert not hvd_numerics.records_disagree(
            a, (10.0, 2.0, 0, 5.0, 77.0, 9.0, 0), tol=1e-4)

    def test_records_disagree_on_any_nonfinite_mismatch(self):
        a = (10.0, 2.0, 0, 5.0, 10.0, 2.0, 0)
        b = (10.0, 2.0, 1, 5.0, 10.0, 2.0, 1)
        assert hvd_numerics.records_disagree(a, b, tol=1e9)

    def test_blame_prefers_local_nonfinite_carrier(self):
        recs = {0: (1.0, 1.0, 1, 1.0, 1.0, 1.0, 0),
                2: (1.0, 1.0, 1, 1.0, 1.0, 1.0, 3),
                1: (1.0, 1.0, 1, 1.0, 1.0, 1.0, 0)}
        assert hvd_numerics.blame_rank(recs) == 2

    def test_blame_picks_local_l2_outlier(self):
        def rec(loc_l2):
            return (5.0, 1.0, 0, 2.0, loc_l2, 1.0, 0)
        assert hvd_numerics.blame_rank(
            {0: rec(1.0), 1: rec(1.1), 2: rec(40.0), 3: rec(0.9)}) == 2

    def test_blame_is_deterministic_and_total(self):
        assert hvd_numerics.blame_rank({}) is None
        one = {5: (1.0, 1.0, 0, 1.0, 1.0, 1.0, 0)}
        assert hvd_numerics.blame_rank(one) == 5


def _digest(rank, cycle, name, loc_l2, nonfinite=0):
    rec = (hvd_numerics._round(loc_l2), 1.0, int(nonfinite),
           hvd_numerics._round(loc_l2), hvd_numerics._round(loc_l2),
           1.0, int(nonfinite))
    return hvd_numerics.fold_digest(None, cycle, {name: rec}, rank=rank)


class TestCoordinatorSentinel:
    """The sentinel itself, driven through the real request handler
    (no sockets: _handle is what the TCP layer calls)."""

    def _service(self, nproc=2):
        cfg = HorovodConfig(fusion_threshold=0,
                            stall_warning_time_seconds=0)
        return neg.CoordinatorService(nproc, KEY, ports=[0], config=cfg)

    def test_agreeing_digests_stay_quiet(self, reg):
        svc = self._service()
        try:
            for r in range(2):
                svc._handle(neg.CycleRequest(
                    r, [], -1, req_id=1,
                    digest=_digest(r, 0, "g", 3.0)), ("", 0))
            assert not svc._numerics_flagged
            assert _val(reg, "hvd_numerics_divergent_rank") == -1
        finally:
            svc.shutdown()

    def test_divergent_digest_names_rank_tensor_cycle(self, reg,
                                                      monkeypatch,
                                                      tmp_path):
        monkeypatch.setenv("HVD_FLIGHT_DIR", str(tmp_path))
        hvd_tracing.reset(enabled=True)
        svc = self._service(nproc=3)
        try:
            # cycles 0-1 healthy everywhere; rank 1 diverges at cycle 2.
            # The divergent rank reports LAST each cycle: blame needs a
            # 3-holder median (a 2-holder split is symmetric — neither
            # side is the outlier yet)
            for cyc in range(3):
                for r in (0, 2, 1):
                    l2 = 9.0 if (r == 1 and cyc >= 2) else 3.0
                    svc._handle(neg.CycleRequest(
                        r, [], -1, req_id=cyc + 1,
                        digest=_digest(r, cyc, "g", l2)), ("", 0))
            key = (2, "g", hvd_numerics.ANOMALY_DIVERGENCE)
            assert key in svc._numerics_flagged
            assert svc._numerics_flagged[key] == 1
            assert svc._numerics_first_bad["g"] == 2
            assert _val(reg, "hvd_numerics_divergent_rank") == 1
            evs = _anomaly_events(reg)
            assert evs and evs[0]["divergent_rank"] == 1
            assert evs[0]["tensor"] == "g"
            assert evs[0]["first_bad_cycle"] == 2
        finally:
            svc.shutdown()
            hvd_tracing.reset()

    def test_nonfinite_digest_blames_the_carrier(self, reg):
        svc = self._service()
        try:
            svc._handle(neg.CycleRequest(
                0, [], -1, req_id=1,
                digest=_digest(0, 5, "g", 3.0)), ("", 0))
            svc._handle(neg.CycleRequest(
                1, [], -1, req_id=1,
                digest=_digest(1, 5, "g", 3.0, nonfinite=2)), ("", 0))
            key = (5, "g", hvd_numerics.ANOMALY_NONFINITE)
            assert svc._numerics_flagged.get(key) == 1
            assert _val(reg, "hvd_coordinator_numerics_anomalies_total",
                        kind=hvd_numerics.ANOMALY_NONFINITE) >= 1
        finally:
            svc.shutdown()

    def test_digest_store_bounded_by_window(self, reg, monkeypatch):
        monkeypatch.setenv("HVD_NUMERICS_DIGEST_CYCLES", "4")
        svc = self._service(nproc=1)
        try:
            for cyc in range(10):
                svc._handle(neg.CycleRequest(
                    0, [], -1, req_id=cyc + 1,
                    digest=_digest(0, cyc, "g", 1.0)), ("", 0))
            assert len(svc._digests) == 4
            assert min(svc._digests) == 6
        finally:
            svc.shutdown()

    def test_unversioned_digest_is_ignored(self, reg):
        svc = self._service(nproc=1)
        try:
            svc._handle(neg.CycleRequest(
                0, [], -1, req_id=1, digest={"v": 999, "cycles": {
                    0: {"g": (1.0,) * 7}}}), ("", 0))
            svc._handle(neg.CycleRequest(
                0, [], -1, req_id=2, digest="not a digest"), ("", 0))
            assert not svc._digests
        finally:
            svc.shutdown()


class TestPiggybackTransport:
    def test_digest_rides_a_real_tcp_cycle(self, reg):
        """CycleRequest.digest over a live socket: the worker attaches
        the digest the monitor built, the coordinator's sentinel sees it
        (same transport pattern as the metrics snapshot)."""
        cfg = HorovodConfig(fusion_threshold=0,
                            stall_warning_time_seconds=0)
        svc = neg.CoordinatorService(1, KEY, ports=[0], config=cfg)
        try:
            c = network.BasicClient(neg.SERVICE_NAME,
                                    {"local": [("127.0.0.1", svc.port)]},
                                    KEY)
            m = neg.EntryMeta("g", "allreduce", "float32", (4,), 0, False)
            c.request(neg.CycleRequest(
                0, [m], -1, req_id=1,
                digest=_digest(0, 0, "g", 2.0, nonfinite=1)))
            assert 0 in svc._digests and "g" in svc._digests[0][0]
            key = (0, "g", hvd_numerics.ANOMALY_NONFINITE)
            assert svc._numerics_flagged.get(key) == 0
            c.close()
        finally:
            svc.shutdown()


class TestNullMonitor:
    def test_disabled_monitor_is_inert(self, reg):
        m = hvd_numerics.reset(enabled=False)
        try:
            assert not m.enabled
            assert m.observe([("w", np.array([np.nan], np.float32),
                               None)], cycle=1) == {}
            assert m.ingest(["w"], np.ones((1, 5), np.float32)) == {}
            assert m.drain() is None
            m.observe_compression("w", np.ones(2), np.ones(2), "fp16")
            assert not _anomaly_events(reg)
        finally:
            hvd_numerics.reset()

    def test_env_gate_selects_null(self, monkeypatch):
        monkeypatch.setenv("HVD_NUMERICS", "0")
        try:
            m = hvd_numerics.reset()
            assert isinstance(m, hvd_numerics.NullMonitor)
        finally:
            monkeypatch.delenv("HVD_NUMERICS")
            hvd_numerics.reset()

    def test_default_is_enabled(self):
        assert "HVD_NUMERICS" not in os.environ
        assert "HOROVOD_NUMERICS" not in os.environ
        assert hvd_numerics.numerics_enabled()


class TestCompressionDelta:
    def test_relative_norm_delta_gauge(self, monitor, reg):
        before = np.array([3.0, 4.0], np.float32)  # l2 = 5
        after = np.array([3.0, 0.0], np.float32)   # l2 = 3
        monitor.observe_compression("w", before, after, "topk")
        assert _val(reg, "hvd_compression_norm_delta", tensor="w",
                    compressor="topk") == pytest.approx(0.4, rel=1e-5)
        assert _val(reg, "hvd_compressed_tensors_total",
                    compressor="topk") == 1

    def test_a_stacked_buffers_statistics_are_one_program(
            self, monitor, reg, caplog):
        """Over a buffer with a row a device every reduction is a
        collective, and op by op ten of them were in flight at once: under
        load XLA's CPU client then never returned, and a worker of
        tests/test_quantization.py sat out its launcher's ten minutes
        (ROADMAP D13 (i)). Counted where it cannot be missed: a first call
        at a shape of its own compiles ONE program."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        rows = NamedSharding(Mesh(np.asarray(jax.devices()), ("dp",)),
                             P("dp"))
        n = len(jax.devices())
        before = jax.device_put(
            np.full((n, 1237), 2.0, np.float32), rows)
        after = jax.device_put(np.full((n, 1237), 1.0, np.float32), rows)
        with jax.log_compiles(), caplog.at_level(logging.WARNING, "jax"):
            monitor.observe_compression("g", before, after, "int8")
        compiled = [r.getMessage().split(" with ")[0]
                    for r in caplog.records
                    if r.getMessage().startswith("Compiling ")]
        assert compiled == ["Compiling jit(impl)"]    # _group_stats_fn's
        assert _val(reg, "hvd_compression_norm_delta", tensor="g",
                    compressor="int8") == pytest.approx(0.5, rel=1e-5)

    def test_zero_norm_input_reports_zero_delta(self, monitor, reg):
        z = np.zeros((3,), np.float32)
        monitor.observe_compression("z", z, z, "fp16")
        assert _val(reg, "hvd_compression_norm_delta", tensor="z",
                    compressor="fp16") == 0.0
