"""Fused allgather: the coordinator buckets ready same-dtype allgathers
into one response, executed as a single allgatherv with per-rank
displacement math (reference Response::add_allgather_response,
message.h:172; output offsets collective_operations.cc:68-134;
MPI_Allgatherv mpi_operations.cc:86-173)."""

import numpy as np
import pytest

from horovod_tpu.run.launch import run

_ENV = {"JAX_PLATFORMS": "cpu"}


class TestCoordinatorGatherFusion:
    def _service(self, nproc=2, threshold=64 << 20):
        from horovod_tpu.common.config import HorovodConfig
        from horovod_tpu.ops import negotiation as neg
        cfg = HorovodConfig(fusion_threshold=threshold,
                            stall_warning_time_seconds=0)
        svc = neg.CoordinatorService(nproc, b"k" * 32, ports=[0],
                                     config=cfg)
        return svc, neg

    def _meta(self, neg, name, op="allgather", dtype="float32",
              shape=(4, 2)):
        return neg.EntryMeta(name, op, dtype, shape, 0, False)

    def test_same_dtype_allgathers_fuse(self):
        svc, neg = self._service()
        try:
            metas = [self._meta(neg, f"g{i}") for i in range(3)] + \
                [self._meta(neg, "idx", dtype="int32")] + \
                [self._meta(neg, "r", op="allreduce", shape=(4,))]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            kinds = [(r.op, tuple(r.names)) for r in svc._responses]
            assert ("allgather", ("g0", "g1", "g2")) in kinds
            assert ("allgather", ("idx",)) in kinds
            assert ("allreduce", ("r",)) in kinds
        finally:
            svc.shutdown()

    def test_gather_fusion_respects_threshold(self):
        # (4,2) float32 = 32 bytes; threshold 64 → pairs
        svc, neg = self._service(threshold=64)
        try:
            metas = [self._meta(neg, f"g{i}") for i in range(4)]
            svc._submit(0, metas)
            svc._submit(1, metas)
            svc._negotiate()
            assert [r.names for r in svc._responses] == \
                [["g0", "g1"], ["g2", "g3"]]
        finally:
            svc.shutdown()

    def test_ragged_first_dims_still_fuse(self):
        # allgatherv: dim0 may differ per rank, fusion must still group
        svc, neg = self._service()
        try:
            svc._submit(0, [self._meta(neg, "a", shape=(1, 2)),
                            self._meta(neg, "b", shape=(5, 2))])
            svc._submit(1, [self._meta(neg, "a", shape=(3, 2)),
                            self._meta(neg, "b", shape=(2, 2))])
            svc._negotiate()
            (r,) = svc._responses
            assert r.op == "allgather" and r.names == ["a", "b"]
        finally:
            svc.shutdown()


class TestFusedAllgatherEndToEnd:
    def test_burst_fuses_and_stays_exact(self):
        """Six float32 allgathers with per-rank ragged first dims and
        mixed inner shapes complete in fewer responses than tensors,
        with exact allgatherv results."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            tensors = {}
            handles = {}
            for i in range(6):
                d0 = 1 + ((r + i) % 3)  # ragged across ranks
                inner = (2,) if i % 2 == 0 else (3, 2)
                t = np.full((d0,) + inner, 10.0 * r + i, np.float32)
                tensors[f"t{i}"] = t
                handles[f"t{i}"] = hvd.allgather_async(
                    t, name=f"t{i}", kind="replicated")
            outs = {n: np.asarray(hvd.synchronize(h))
                    for n, h in handles.items()}
            coord = state.global_state().coordinator
            n_responses = coord._applied_seq + 1
            hvd.shutdown()
            return tensors, outs, n_responses

        results = run(fn, num_proc=2, env=_ENV)
        locals_by_rank = [res[0] for res in results]
        for tensors, outs, n_responses in results:
            for i in range(6):
                want = np.concatenate(
                    [locals_by_rank[p][f"t{i}"] for p in range(2)], axis=0)
                np.testing.assert_array_equal(outs[f"t{i}"], want)
            assert n_responses < 6, n_responses  # gathers were fused

    def test_mixed_dtypes_split_buckets_exactly(self):
        """float32 values + int32 indices (the sparse pattern): two
        buckets, both exact, including a scalar member."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            hv = [hvd.allgather_async(
                np.full((r + 1, 2), float(10 * r + i), np.float32),
                name=f"v{i}", kind="replicated") for i in range(2)]
            hs = hvd.allgather_async(np.float32(r + 7.0), name="scalar",
                                     kind="replicated")
            hi = hvd.allgather_async(
                np.arange(r + 2, dtype=np.int32) + 100 * r,
                name="idx", kind="replicated")
            outv = [np.asarray(hvd.synchronize(h)) for h in hv]
            outs = np.asarray(hvd.synchronize(hs))
            outi = np.asarray(hvd.synchronize(hi))
            hvd.shutdown()
            return outv, outs, outi

        results = run(fn, num_proc=2, env=_ENV)
        for outv, outs, outi in results:
            for i in range(2):
                want = np.concatenate([
                    np.full((1, 2), float(i), np.float32),
                    np.full((2, 2), float(10 + i), np.float32)], axis=0)
                np.testing.assert_array_equal(outv[i], want)
            np.testing.assert_array_equal(
                outs, np.asarray([7.0, 8.0], np.float32))
            np.testing.assert_array_equal(
                outi, np.concatenate([np.arange(2, dtype=np.int32),
                                      np.arange(3, dtype=np.int32) + 100]))

    def test_grouped_sparse_allreduce_rides_fused_gathers(self):
        """The word2vec pattern: several IndexedSlices reduced with all
        gathers in flight — union semantics preserved, fewer responses
        than collectives."""
        def fn():
            import os
            import numpy as np
            import horovod_tpu as hvd
            from horovod_tpu.common import state
            from horovod_tpu.ops.sparse import (IndexedSlices,
                                                grouped_sparse_allreduce)
            hvd.init()
            r = int(os.environ["HVD_PROCESS_ID"])
            slices = [IndexedSlices(
                np.full((2, 3), float(r + i), np.float32),
                np.asarray([2 * r, 2 * r + 1], np.int32),
                (8, 3)) for i in range(3)]
            outs = grouped_sparse_allreduce(slices, average=True)
            coord = state.global_state().coordinator
            n_responses = coord._applied_seq + 1
            got = [(np.asarray(o.values), np.asarray(o.indices))
                   for o in outs]
            hvd.shutdown()
            return got, n_responses

        results = run(fn, num_proc=2, env=_ENV)
        for got, n_responses in results:
            for i, (vals, idx) in enumerate(got):
                want_vals = np.concatenate([
                    np.full((2, 3), float(i), np.float32),
                    np.full((2, 3), float(1 + i), np.float32)]) / 2.0
                np.testing.assert_allclose(vals, want_vals)
                np.testing.assert_array_equal(
                    idx, np.asarray([0, 1, 2, 3], np.int32))
            # 6 gathers (3 values + 3 indices) → 2 fused responses
            assert n_responses <= 3, n_responses
