"""Rank-0 coordinator negotiation (ops/negotiation.py — the reference's
Request/Response control plane, operations.cc:1217-1245): any-order
submission across processes, coordinator-side fusion and meta checking,
subset-stall reporting, shutdown propagation."""

import numpy as np
import pytest

from horovod_tpu.run.launch import run

_ENV = {"JAX_PLATFORMS": "cpu"}


class TestCoordinatorUnit:
    """CoordinatorService negotiation logic, no processes involved."""

    def _service(self, nproc=2, threshold=64 << 20):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        cfg = HorovodConfig(fusion_threshold=threshold,
                            stall_warning_time_seconds=0)
        svc = neg.CoordinatorService(nproc, b"k" * 32,
                                     ports=[0], config=cfg)
        return svc, neg

    def _meta(self, neg, name, op="allreduce", dtype="float32",
              shape=(4,), root=0, average=False):
        return neg.EntryMeta(name, op, dtype, shape, root, average)

    def test_holds_until_all_ranks_submit(self):
        svc, neg = self._service()
        try:
            svc._submit(0, [self._meta(neg, "a")])
            svc._negotiate()
            assert svc._responses == []
            svc._submit(1, [self._meta(neg, "a")])
            svc._negotiate()
            assert len(svc._responses) == 1
            assert svc._responses[0].names == ["a"]
        finally:
            svc.shutdown()

    def test_fuses_ready_same_dtype_allreduces(self):
        svc, neg = self._service()
        try:
            metas = [self._meta(neg, f"g{i}") for i in range(4)] + \
                [self._meta(neg, "d", dtype="float64")] + \
                [self._meta(neg, "b", op="broadcast")]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            kinds = [(r.op, tuple(r.names)) for r in svc._responses]
            assert ("allreduce", ("g0", "g1", "g2", "g3")) in kinds
            assert ("allreduce", ("d",)) in kinds
            assert ("broadcast", ("b",)) in kinds
        finally:
            svc.shutdown()

    def test_fusion_respects_threshold(self):
        # 4-float tensors = 16 bytes each; threshold 32 → pairs
        svc, neg = self._service(threshold=32)
        try:
            metas = [self._meta(neg, f"g{i}") for i in range(4)]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            groups = [r.names for r in svc._responses]
            assert groups == [["g0", "g1"], ["g2", "g3"]]
        finally:
            svc.shutdown()

    def test_zero_threshold_disables_fusion(self):
        svc, neg = self._service(threshold=0)
        try:
            metas = [self._meta(neg, f"g{i}") for i in range(3)]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            assert [r.names for r in svc._responses] == \
                [["g0"], ["g1"], ["g2"]]
        finally:
            svc.shutdown()

    def test_meta_mismatch_becomes_error_response(self):
        svc, neg = self._service()
        try:
            svc._submit(0, [self._meta(neg, "x", shape=(2, 3))])
            svc._submit(1, [self._meta(neg, "x", shape=(2, 4))])
            svc._negotiate()
            (r,) = svc._responses
            assert r.kind == r.ERROR
            assert "x" in r.error and "ConstructResponse" in r.error
        finally:
            svc.shutdown()

    def test_response_log_pruned_after_all_ranks_ack(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._service()
        try:
            dtypes = ["float32", "float64", "int32", "int64"]  # no fusion
            for i in range(4):
                svc._submit(0, [self._meta(neg, f"t{i}", dtype=dtypes[i])])
                svc._submit(1, [self._meta(neg, f"t{i}", dtype=dtypes[i])])
            svc._negotiate()
            assert len(svc._responses) == 4
            # both ranks acknowledge seq 2 → seqs 0..2 pruned
            svc._handle(CycleRequest(0, [], ack=2), ("127.0.0.1", 0))
            svc._handle(CycleRequest(1, [], ack=2), ("127.0.0.1", 0))
            assert svc._base_seq == 3 and len(svc._responses) == 1
            # a straggler request for older seqs still gets the tail
            resp = svc._handle(CycleRequest(0, [], ack=2),
                               ("127.0.0.1", 0))
            assert resp.base_seq == 3 and len(resp.responses) == 1
        finally:
            svc.shutdown()

    def test_allgather_first_dim_may_differ(self):
        svc, neg = self._service()
        try:
            svc._submit(0, [self._meta(neg, "g", op="allgather",
                                       shape=(2, 3))])
            svc._submit(1, [self._meta(neg, "g", op="allgather",
                                       shape=(5, 3))])
            svc._negotiate()
            (r,) = svc._responses
            assert r.kind == r.EXECUTE
        finally:
            svc.shutdown()

    def _quant_service(self):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        cfg = HorovodConfig(fusion_threshold=64 << 20,
                            stall_warning_time_seconds=0,
                            compression="int8", quant_min_bytes=1024)
        svc = neg.CoordinatorService(2, b"k" * 32, ports=[0], config=cfg)
        return svc, neg

    def test_negotiated_plan_carries_per_tensor_codec(self):
        svc, neg = self._quant_service()
        try:
            metas = [self._meta(neg, "big", shape=(1024,)),
                     self._meta(neg, "small", shape=(4,)),
                     self._meta(neg, "ints", dtype="int32",
                                shape=(1024,))]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            by_names = {tuple(r.names): r for r in svc._responses}
            # big float tensor rides the quantized wire
            assert by_names[("big",)].codec == "int8"
            # under quant_min_bytes: the encode overhead isn't worth it
            assert by_names[("small",)].codec is None
            # integer reductions are exact already; never quantized
            assert by_names[("ints",)].codec is None
        finally:
            svc.shutdown()

    def test_codec_splits_fusion_buckets(self):
        # same dtype, same average — but only one clears the size gate,
        # so they must NOT share a fused bucket (one wire format per
        # fusion buffer)
        svc, neg = self._quant_service()
        try:
            metas = [self._meta(neg, "a", shape=(1024,)),
                     self._meta(neg, "b", shape=(4,)),
                     self._meta(neg, "c", shape=(2048,))]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            plans = {tuple(r.names): getattr(r, "codec", None)
                     for r in svc._responses}
            assert plans[("a", "c")] == "int8"
            assert plans[("b",)] is None
        finally:
            svc.shutdown()

    def test_codec_fingerprint_mismatch_fails_ready_tensors(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._quant_service()
        try:
            fp0 = svc._codec_fp
            assert fp0.startswith("int8/")
            svc._handle(CycleRequest(0, [self._meta(neg, "g",
                                                    shape=(1024,))],
                                     ack=-1, codec_fp=fp0),
                        ("127.0.0.1", 0))
            svc._handle(CycleRequest(1, [self._meta(neg, "g",
                                                    shape=(1024,))],
                                     ack=-1,
                                     codec_fp="none/b256/min1024/ef1"),
                        ("127.0.0.1", 0))
            svc._negotiate()
            (r,) = svc._responses
            assert r.kind == r.ERROR
            assert "Mismatched wire-codec config" in r.error
            assert "int8" in r.error and "none" in r.error
            # the mismatch is sticky: later tensors fail too, nothing
            # ever executes under asymmetric codecs
            svc._submit(0, [self._meta(neg, "h")])
            svc._submit(1, [self._meta(neg, "h")])
            svc._negotiate()
            assert all(x.kind == x.ERROR for x in svc._responses[1:])
        finally:
            svc.shutdown()

    def test_matching_fingerprints_do_not_trip(self):
        from horovod_tpu.ops.negotiation import CycleRequest
        svc, neg = self._quant_service()
        try:
            for rank in (0, 1):
                svc._handle(CycleRequest(rank,
                                         [self._meta(neg, "g",
                                                     shape=(1024,))],
                                         ack=-1, codec_fp=svc._codec_fp),
                            ("127.0.0.1", 0))
            svc._negotiate()
            assert not svc._codec_mismatch
            (r,) = svc._responses
            assert r.kind == r.EXECUTE and r.codec == "int8"
        finally:
            svc.shutdown()


class TestResponseWire:
    """Compact CycleResponse encoding (the per-cycle hot message pickles
    via __reduce__ into versioned struct/varint bytes instead of a
    class-layout pickle; the request path's encode_hits went compact
    first)."""

    def _full_response(self, neg):
        responses = [
            neg.NegotiatedResponse(
                neg.NegotiatedResponse.EXECUTE, "allreduce",
                ["g0", "g1", "g2"], cache_ids=[0, 1, 7]),
            neg.NegotiatedResponse(
                neg.NegotiatedResponse.EXECUTE, "allreduce",
                ["q0", "q1"], codec="int8"),
            neg.NegotiatedResponse(
                neg.NegotiatedResponse.ERROR, "broadcast", ["bad"],
                error="Mismatched broadcast 'bad' across processes"),
            neg.NegotiatedResponse(
                neg.NegotiatedResponse.EXECUTE, "allgather", ["ag"]),
        ]
        return neg.CycleResponse(
            base_seq=42, responses=responses, params=(64 << 20, 5.0),
            shutdown=False, stale_ack=True, unknown_ids=(5, 9),
            lost_ranks=(3,))

    def _assert_equal(self, a, b):
        assert b.base_seq == a.base_seq
        assert b.params == a.params
        assert b.shutdown == a.shutdown
        assert b.stale_ack == a.stale_ack
        assert b.unknown_ids == a.unknown_ids
        assert b.lost_ranks == a.lost_ranks
        assert len(b.responses) == len(a.responses)
        for ra, rb in zip(a.responses, b.responses):
            assert (rb.kind, rb.op, rb.names, rb.error, rb.cache_ids,
                    rb.codec) == \
                (ra.kind, ra.op, ra.names, ra.error, ra.cache_ids,
                 ra.codec)

    def test_roundtrip_through_pickle(self):
        import cloudpickle
        from horovod_tpu.ops import negotiation as neg
        resp = self._full_response(neg)
        out = cloudpickle.loads(cloudpickle.dumps(resp))
        self._assert_equal(resp, out)

    def test_roundtrip_empty_response(self):
        import cloudpickle
        from horovod_tpu.ops import negotiation as neg
        resp = neg.CycleResponse(0, [], (0, 99.22), True)
        out = cloudpickle.loads(cloudpickle.dumps(resp))
        self._assert_equal(resp, out)

    def test_unknown_op_rides_as_string(self):
        from horovod_tpu.ops import negotiation as neg
        resp = neg.CycleResponse(1, [neg.NegotiatedResponse(
            neg.NegotiatedResponse.EXECUTE, "future_op", ["x"])],
            (1, 2.0), False)
        out = neg.decode_response(neg.encode_response(resp))
        assert out.responses[0].op == "future_op"

    def test_version_mismatch_fails_loudly(self):
        from horovod_tpu.ops import negotiation as neg
        payload = bytearray(neg.encode_response(
            self._full_response(neg)))
        payload[0] = neg.RESPONSE_WIRE_VERSION + 1
        with pytest.raises(ValueError, match="wire version"):
            neg.decode_response(bytes(payload))
        with pytest.raises(ValueError):
            neg.decode_response(b"")

    def test_compact_beats_legacy_pickle(self):
        """The point of the encoding: the steady-state message must be
        much smaller than a class-layout pickle of the same content."""
        import pickle
        from horovod_tpu.ops import negotiation as neg
        resp = self._full_response(neg)
        legacy = pickle.dumps(  # what the old wire effectively carried
            {"base_seq": resp.base_seq, "params": resp.params,
             "shutdown": resp.shutdown, "stale_ack": resp.stale_ack,
             "unknown_ids": resp.unknown_ids,
             "lost_ranks": resp.lost_ranks,
             "responses": [(r.kind, r.op, r.names, r.error, r.cache_ids)
                           for r in resp.responses]})
        assert len(neg.encode_response(resp)) < len(legacy) / 2


class TestAnyOrderSubmission:
    def test_ranks_submit_in_opposite_order(self):
        """The capability negotiation exists for (reference
        operations.cc:852-855): eager frameworks cannot guarantee
        cross-rank submission order. Without the coordinator this
        deadlocks or mismatches; with it, both complete."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            names = ["A", "B"] if r == 0 else ["B", "A"]
            handles = {n: hvd.allreduce_async(
                np.full((3,), 1.0 + (n == "B"), np.float32),
                average=False, name=n) for n in names}
            out = {n: float(np.asarray(hvd.synchronize(h))[0])
                   for n, h in handles.items()}
            hvd.shutdown()
            return out

        results = run(fn, num_proc=2, env=_ENV)
        for res in results:
            assert res == {"A": 2.0, "B": 4.0}, results

    def test_three_ranks_rotated_orders(self):
        """Three processes submit the same three tensors, each in a
        different rotation — the coordinator serializes them all."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            names = ["X", "Y", "Z"]
            order = names[r:] + names[:r]  # rotate by rank
            handles = {n: hvd.allreduce_async(
                np.full((2,), float(ord(n)), np.float32),
                average=True, name=n) for n in order}
            out = {n: float(np.asarray(hvd.synchronize(h))[0])
                   for n, h in handles.items()}
            hvd.shutdown()
            return out

        results = run(fn, num_proc=3, env=_ENV)
        want = {n: float(ord(n)) for n in "XYZ"}
        for res in results:
            assert res == want, results

    def test_burst_is_fused_by_coordinator(self):
        def fn():
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            handles = [hvd.allreduce_async(
                np.full((8,), float(i), np.float32), average=False,
                name=f"burst{i}") for i in range(6)]
            outs = [float(np.asarray(hvd.synchronize(h))[0])
                    for h in handles]
            coord = state.global_state().coordinator
            # 6 tensors completed in fewer responses than tensors →
            # the coordinator fused them
            n_responses = coord._applied_seq + 1
            hvd.shutdown()
            return outs, n_responses

        results = run(fn, num_proc=2, env=_ENV)
        for outs, n_responses in results:
            assert outs == [2.0 * i for i in range(6)]
            assert n_responses < 6, n_responses

    def test_broadcast_object_rides_the_core(self):
        def fn():
            import os
            import horovod_tpu.torch as thvd
            thvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            obj = {"epoch": 7, "blob": list(range(50))} if r == 0 else None
            out = thvd.broadcast_object(obj, root_rank=0)
            thvd.shutdown()
            return out

        results = run(fn, num_proc=2, env=_ENV)
        want = {"epoch": 7, "blob": list(range(50))}
        assert results == [want, want]


class TestNegotiatedFailure:
    def test_subset_submission_stalls_not_hangs(self):
        """A tensor only rank 0 submits must fail its synchronize with
        StalledError at the shutdown deadline (reference stall shutdown,
        operations.cc:688-786) — and the coordinator logs the missing
        ranks meanwhile."""
        def fn():
            import logging
            import os
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import hvd_logging
            records = []

            class Capture(logging.Handler):
                def emit(self, record):
                    records.append(record.getMessage())

            hvd_logging.get_logger().addHandler(Capture())
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            # both ranks run one common collective first
            hvd.allreduce(np.ones((2,), np.float32), name="common")
            result = "none"
            if r == 0:
                try:
                    hvd.allreduce(np.ones((2,), np.float32), name="only0")
                except hvd.StalledError:
                    result = "stalled"
            else:
                import time
                time.sleep(2.5)
            warned = any("only0" in m and "missing ranks" in m
                         for m in records)
            hvd.shutdown()
            return result, (warned if r == 0 else None)

        env = dict(_ENV)
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "0.5"
        env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = "1.5"
        results = run(fn, num_proc=2, env=env)
        assert results[0][0] == "stalled", results
        assert results[0][1] is True, results

    def test_peer_shutdown_fails_pending(self):
        """Rank 1 shuts down while rank 0 waits on a collective rank 1
        never submitted: rank 0 gets ShutdownError, not a hang
        (RequestList.shutdown → ResponseList.shutdown,
        operations.cc:1442-1478)."""
        def fn():
            import os
            import time
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            if r == 1:
                time.sleep(0.5)
                hvd.shutdown()
                return "exited"
            try:
                hvd.allreduce(np.ones((2,), np.float32), name="waiting")
                return "completed"
            except hvd.ShutdownError:
                return "shutdown"
            finally:
                hvd.shutdown()

        results = run(fn, num_proc=2, env=_ENV)
        assert results[0] == "shutdown" and results[1] == "exited", results


class TestShutdownDrain:
    """Teardown must not strand peers inside the data plane (reference
    drains outstanding responses before finalize, operations.cc:1101-1122):
    already-ordered EXECUTE work is applied by the departing rank's final
    drain cycle; work becoming ready after shutdown turns into ERROR."""

    def test_coordinator_errors_newly_ready_after_shutdown(self):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        from horovod_tpu.ops.negotiation import CycleRequest
        cfg = HorovodConfig(stall_warning_time_seconds=0)
        svc = neg.CoordinatorService(2, b"k" * 32, ports=[0], config=cfg)
        try:
            m = neg.EntryMeta("pre", "allreduce", "float32", (4,), 0, False)
            # both ranks submit "pre"; rank 1's final request also asks
            # for shutdown — "pre" became ready IN that request, so it is
            # still EXECUTE (the drain applies it)
            svc._handle(CycleRequest(0, [m], ack=-1, req_id=1),
                        ("127.0.0.1", 0))
            resp = svc._handle(CycleRequest(1, [m], ack=-1, shutdown=True,
                                            req_id=1), ("127.0.0.1", 0))
            assert resp.shutdown
            assert [r.kind for r in resp.responses] == ["execute"]
            # work completing AFTER the shutdown flag becomes an ERROR —
            # an EXECUTE would strand the remaining rank
            m2 = neg.EntryMeta("post", "allreduce", "float32", (4,), 0,
                               False)
            svc._handle(CycleRequest(0, [m2], ack=0, req_id=2),
                        ("127.0.0.1", 0))
            resp = svc._handle(CycleRequest(1, [m2], ack=0, req_id=2),
                               ("127.0.0.1", 0))
            (err,) = resp.responses
            assert err.kind == err.ERROR and "shut down" in err.error
        finally:
            svc.shutdown()

    def test_response_log_hard_cap_marks_laggards_stale(self):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        from horovod_tpu.ops.negotiation import CycleRequest
        cfg = HorovodConfig(fusion_threshold=0,
                            stall_warning_time_seconds=0)
        svc = neg.CoordinatorService(2, b"k" * 32, ports=[0], config=cfg)
        svc.MAX_RESPONSE_LOG = 4  # shrink the cap for the test
        try:
            # rank 1 acks nothing (crashed); rank 0 keeps submitting is
            # not enough — entries need BOTH ranks, so submit from both
            # but only advance rank 0's ack
            for i in range(8):
                m = neg.EntryMeta(f"t{i}", "allreduce", "float32", (4,),
                                  0, False)
                svc._handle(CycleRequest(0, [m], ack=i - 1, req_id=10 + i),
                            ("127.0.0.1", 0))
                svc._handle(CycleRequest(1, [m], ack=-1, req_id=10 + i),
                            ("127.0.0.1", 0))
            assert len(svc._responses) <= 4  # bounded despite no min-ack
            # the laggard's next request predates the retained window
            resp = svc._handle(CycleRequest(1, [], ack=-1, req_id=99),
                               ("127.0.0.1", 0))
            assert resp.stale_ack
            # the up-to-date rank is unaffected
            resp = svc._handle(CycleRequest(0, [], ack=7, req_id=100),
                               ("127.0.0.1", 0))
            assert not resp.stale_ack
        finally:
            svc.shutdown()

    def test_departing_rank_drains_ordered_collective(self):
        """Rank 1 pauses its background loop after announcing a tensor,
        so the EXECUTE response can only be applied by shutdown()'s final
        drain — rank 0, already blocked inside the device collective,
        must complete instead of hanging (the pre-fix behavior)."""
        def fn():
            import os
            import time
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            coord = state.global_state().coordinator
            if r == 1:
                h = hvd.allreduce_async(np.full((2,), 2.0, np.float32),
                                        average=False, name="drained")
                time.sleep(0.5)          # announcement cycle runs
                coord._paused = True     # loop can no longer apply it
                time.sleep(1.0)          # rank 0 blocks in the collective
                hvd.shutdown()           # drain applies the EXECUTE
                return "shutdown-drained"
            time.sleep(0.8)
            h = hvd.allreduce_async(np.full((2,), 1.0, np.float32),
                                    average=False, name="drained")
            out = float(np.asarray(hvd.synchronize(h))[0])
            hvd.shutdown()
            return out

        results = run(fn, num_proc=2, env=_ENV, start_timeout_s=120.0)
        assert results[1] == "shutdown-drained"
        assert results[0] == 3.0, results


class TestPoisonGrace:
    """Control-plane loss declaration (ops/eager.py): >=3 failed cycles
    alone must NOT poison the plane — only >=3 failures sustained for
    POISON_GRACE_S (transient coordinator pauses and TCP resets at the
    5 ms cycle cadence must not tear the job down in ~15 ms)."""

    def _coordinator_with_failing_negotiator(self):
        import time as _time

        import horovod_tpu as hvd
        from horovod_tpu.common import state

        hvd.init()
        coord = state.global_state().coordinator
        coord._paused = True  # keep the background loop out of the way

        class FailingNegotiator:
            calls = 0

            def cycle(self, *a, **kw):
                FailingNegotiator.calls += 1
                raise ConnectionRefusedError("synthetic control-plane loss")

            def close(self):
                pass

        coord._negotiator = FailingNegotiator()
        return hvd, coord

    def test_three_fast_failures_do_not_poison(self):
        hvd, coord = self._coordinator_with_failing_negotiator()
        try:
            for _ in range(5):
                coord._cycle_backoff_until = 0.0  # bypass waiting
                coord._negotiated_flush_locked()
            assert coord._cycle_failures >= 3
            assert not coord._negotiation_dead, (
                "fast consecutive failures must not poison the plane "
                "before POISON_GRACE_S elapses")
            assert coord._cycle_backoff_until > 0  # backoff engaged
        finally:
            coord._negotiator = None
            hvd.shutdown()

    def test_sustained_unreachability_poisons(self):
        import time

        hvd, coord = self._coordinator_with_failing_negotiator()
        try:
            coord._cycle_backoff_until = 0.0
            coord._negotiated_flush_locked()  # first failure stamps since
            # simulate the grace window having elapsed
            coord._cycle_fail_since = (time.monotonic() -
                                       coord.POISON_GRACE_S - 1.0)
            for _ in range(3):
                coord._cycle_backoff_until = 0.0
                coord._negotiated_flush_locked()
            assert coord._negotiation_dead
        finally:
            coord._negotiator = None
            hvd.shutdown()

    def test_backoff_defers_cycles(self):
        import time

        hvd, coord = self._coordinator_with_failing_negotiator()
        try:
            coord._cycle_backoff_until = 0.0
            coord._negotiated_flush_locked()
            calls_after_first = type(coord._negotiator).calls
            # backoff window is active: the next flush must not hit the
            # negotiator at all
            coord._negotiated_flush_locked()
            assert type(coord._negotiator).calls == calls_after_first
            assert coord._cycle_backoff_until > time.monotonic() - 2.0
        finally:
            coord._negotiator = None
            hvd.shutdown()
