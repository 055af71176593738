"""Response cache for the negotiated control plane (reference
response_cache.h:43-92 / response_cache.cc:317-354 + the RunBypass fast
path, operations.cc:1168-1215): steady-state resubmissions ride the wire
as cache-id bits instead of full EntryMetas, with invalidation on
signature change and recovery via unknown-id re-announcement."""

import numpy as np
import pytest

from horovod_tpu.run.launch import run

_ENV = {"JAX_PLATFORMS": "cpu"}


class TestHitCodec:
    def test_roundtrip(self):
        from horovod_tpu.ops import negotiation as neg
        for ids in ([], [0], [7], [0, 1, 2, 3], [5, 1000, 30000],
                    list(range(1000)), [999999], list(range(0, 4096, 3))):
            assert neg.decode_hits(neg.encode_hits(ids)) == sorted(ids)

    def test_dense_encoding_is_compact(self):
        from horovod_tpu.ops import negotiation as neg
        # 1000 steady-state tensors: ~1 bit each on the wire
        assert len(neg.encode_hits(list(range(1000)))) <= 130

    def test_sparse_encoding_is_bounded(self):
        from horovod_tpu.ops import negotiation as neg
        # one surviving stable name with a huge id must not cost
        # id/8 bytes (the varint arm wins over the bitset)
        assert len(neg.encode_hits([10_000_000])) < 8


class TestCoordinatorCache:
    def _service(self, nproc=2, capacity=1024, threshold=0):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        cfg = HorovodConfig(fusion_threshold=threshold,
                            stall_warning_time_seconds=0,
                            cache_capacity=capacity)
        svc = neg.CoordinatorService(nproc, b"k" * 32, ports=[0],
                                     config=cfg)
        return svc, neg

    def _meta(self, neg, name, shape=(4,), dtype="float32",
              op="allreduce"):
        return neg.EntryMeta(name, op, dtype, shape, 0, False)

    def test_execute_assigns_cache_ids(self):
        svc, neg = self._service()
        try:
            m = self._meta(neg, "a")
            svc._submit(0, [m])
            svc._submit(1, [m])
            svc._negotiate()
            (r,) = svc._responses
            assert r.kind == r.EXECUTE and r.cache_ids == [0]
            assert svc._cache_id_of == {"a": 0}
        finally:
            svc.shutdown()

    def test_hit_resolves_to_cached_meta(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            m = self._meta(neg, "a")
            # round 1: full metas both ranks
            for rank in (0, 1):
                svc._handle(CycleRequest(rank, [m], -1, req_id=1), ("", 0))
            assert len(svc._responses) == 1
            # round 2: both ranks announce via hit bits only (ack=-1 so
            # the log is not pruned under the assertions)
            hits = neg.encode_hits([0])
            for rank in (0, 1):
                resp = svc._handle(
                    CycleRequest(rank, [], -1, req_id=2, hits=hits),
                    ("", 0))
                assert resp.unknown_ids == ()
            assert len(svc._responses) == 2
            assert svc._responses[1].names == ["a"]
            assert svc._responses[1].cache_ids == [0]  # id is stable
        finally:
            svc.shutdown()

    def test_unknown_id_reported(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            resp = svc._handle(
                CycleRequest(0, [], -1, req_id=1,
                             hits=neg.encode_hits([5])), ("", 0))
            assert resp.unknown_ids == (5,)
            assert svc._responses == []  # nothing planted
        finally:
            svc.shutdown()

    def test_changed_signature_invalidates_id(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            m = self._meta(neg, "a", shape=(4,))
            for rank in (0, 1):
                svc._handle(CycleRequest(rank, [m], -1, req_id=1), ("", 0))
            assert svc._cache_id_of == {"a": 0}
            # shape changes on both ranks (ragged last batch)
            m2 = self._meta(neg, "a", shape=(2,))
            for rank in (0, 1):
                svc._handle(CycleRequest(rank, [m2], -1, req_id=2),
                            ("", 0))
            # old id is gone; the new EXECUTE assigned a fresh one
            assert 0 not in svc._cache
            assert svc._cache_id_of == {"a": 1}
            assert svc._responses[1].cache_ids == [1]
            # a straggler hit on the dead id is unknown, not aliased
            resp = svc._handle(
                CycleRequest(0, [], -1, req_id=3,
                             hits=neg.encode_hits([0])), ("", 0))
            assert resp.unknown_ids == (0,)
        finally:
            svc.shutdown()

    def test_capacity_evicts_lru_and_never_reuses_ids(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service(capacity=2)
        try:
            for i, name in enumerate(["a", "b", "c"]):
                m = self._meta(neg, name)
                for rank in (0, 1):
                    svc._handle(
                        CycleRequest(rank, [m], i - 1, req_id=i + 1),
                        ("", 0))
            assert sorted(svc._cache) == [1, 2]       # "a" (id 0) evicted
            assert sorted(svc._cache_id_of) == ["b", "c"]
            assert svc._next_cache_id == 3
            resp = svc._handle(
                CycleRequest(0, [], 2, req_id=9,
                             hits=neg.encode_hits([0])), ("", 0))
            assert resp.unknown_ids == (0,)
        finally:
            svc.shutdown()

    def test_capacity_zero_disables_caching(self):
        svc, neg = self._service(capacity=0)
        try:
            m = self._meta(neg, "a")
            svc._submit(0, [m])
            svc._submit(1, [m])
            svc._negotiate()
            (r,) = svc._responses
            assert r.cache_ids is None
            assert svc._cache == {}
        finally:
            svc.shutdown()

    def test_deduped_retry_returns_persisted_unknown_ids(self):
        """Lost-response regression (ADVICE.md, medium): the unknown-id
        verdict is resolved on the FIRST processing of a req_id and must
        be returned VERBATIM on a deduped retry. Before the fix the
        retry hit the dedupe arm and answered unknown_ids=() — the
        worker never learned its hits were stale, and the hit tensors
        waited in _negotiated_pending forever."""
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            hits = neg.encode_hits([5])  # id never assigned: unknown
            r1 = svc._handle(CycleRequest(0, [], -1, req_id=1, hits=hits),
                             ("", 0))
            assert r1.unknown_ids == (5,)
            # the response above is "lost on the wire"; the transport
            # retry resends the identical request (same req_id)
            r2 = svc._handle(CycleRequest(0, [], -1, req_id=1, hits=hits),
                             ("", 0))
            assert r2.unknown_ids == (5,), \
                "deduped retry dropped the unknown-id re-announce signal"
            # and a NEW req_id re-resolves fresh rather than replaying
            r3 = svc._handle(CycleRequest(0, [], -1, req_id=2), ("", 0))
            assert r3.unknown_ids == ()
        finally:
            svc.shutdown()

    def test_retry_with_hits_is_idempotent(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            m = self._meta(neg, "a")
            for rank in (0, 1):
                svc._handle(CycleRequest(rank, [m], -1, req_id=1), ("", 0))
            hits = neg.encode_hits([0])
            # rank 0's response was lost: the retry reuses req_id and
            # must not plant a second row
            for _ in range(2):
                svc._handle(CycleRequest(0, [], -1, req_id=2, hits=hits),
                            ("", 0))
            assert len(svc._table) == 1  # one pending row for "a", rank 0
            svc._handle(CycleRequest(1, [], -1, req_id=2, hits=hits),
                        ("", 0))
            # total ordered work = exactly two responses for "a"
            assert svc._base_seq + len(svc._responses) == 2
        finally:
            svc.shutdown()


class TestNegotiatedCacheEndToEnd:
    def test_steady_state_uses_hits_and_stays_correct(self):
        """Same gradient names over repeated steps: after step 1 every
        announcement is a cache bit, and results stay exact."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            outs = []
            for step in range(4):
                hs = [hvd.allreduce_async(
                    np.full((8,), float(step * 10 + i), np.float32),
                    average=False, name=f"grad{i}") for i in range(5)]
                outs.append([float(np.asarray(hvd.synchronize(h))[0])
                             for h in hs])
            coord = state.global_state().coordinator
            hits = coord._neg_hit_count
            cached = len(coord._neg_cache)
            hvd.shutdown()
            return outs, hits, cached

        results = run(fn, num_proc=2, env=_ENV)
        for outs, hits, cached in results:
            for step in range(4):
                assert outs[step] == \
                    [2.0 * (step * 10 + i) for i in range(5)]
            # steps 2-4 announce all 5 names via bits (step 1 may
            # partially hit if fused responses landed mid-step)
            assert hits >= 15, (hits, cached)
            assert cached == 5

    def test_shape_change_mid_run_invalidates_and_recovers(self):
        """The ragged-last-batch pattern: a cached name resubmitted with
        a new shape must invalidate cleanly and still reduce exactly."""
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            outs = []
            for shape in [(4,), (4,), (2,), (4,)]:
                h = hvd.allreduce_async(
                    np.full(shape, 3.0, np.float32), average=False,
                    name="g")
                out = np.asarray(hvd.synchronize(h))
                outs.append((out.shape, float(out[0])))
            hvd.shutdown()
            return outs

        results = run(fn, num_proc=2, env=_ENV)
        for outs in results:
            assert outs == [((4,), 6.0), ((4,), 6.0), ((2,), 6.0),
                            ((4,), 6.0)]
