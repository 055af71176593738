"""Control-plane cost of the negotiated cycle, with and without the
response cache (reference response_cache.cc:317-354 / RunBypass,
operations.cc:1168-1215).

Drives the real CoordinatorService over real TCP with N worker clients
(threads — the control plane is pure TCP + pickle, no data plane), each
announcing T tensors per step. Step 1 is the cold path (full EntryMetas
everywhere); steady-state steps are all cache hits. Reports request
bytes/cycle per worker and cycle round-trip latency, cache on vs off.

Usage: python tools/control_plane_bench.py [--workers 8] [--tensors 1000]
       [--steps 5] [--json]
"""

import argparse
import json
import statistics
import threading
import time

from horovod_tpu.common.config import HorovodConfig
from horovod_tpu.ops import negotiation as neg
from horovod_tpu.run import network


class _Worker:
    """Minimal stand-in for eager's negotiated flush loop: local
    (name -> id, signature) cache, hit announcement, assignment learning
    via the seq-ordered response log — the same protocol steps as
    ops/eager.py _negotiated_flush_locked."""

    def __init__(self, rank, nproc, config, addresses, key,
                 digest_fn=None):
        self.rank = rank
        self.neg = neg.NegotiationWorker(rank, nproc, config, addresses,
                                         key)
        self.applied = -1
        self.req_id = 0
        self.cache = {}      # name -> (cache_id, signature)
        self.pending = set()
        self.req_bytes = []  # per-cycle request payload bytes
        self.cycles = 0
        # optional numerics piggyback: digest_fn(rank, step) -> digest
        # attached to the step's first cycle, mirroring eager's
        # _negotiated_flush_locked (one digest per flush, not per cycle)
        self.digest_fn = digest_fn
        self.steps_done = 0

    def step(self, metas_by_name):
        """Announce every tensor (full meta or hit bit), then cycle until
        all of them have been ordered."""
        self.pending = set(metas_by_name)
        metas, hit_ids = [], []
        for name, meta in metas_by_name.items():
            sig = (meta.op, meta.dtype, meta.shape, meta.root_rank,
                   meta.average)
            cached = self.cache.get(name)
            if cached is not None and cached[1] == sig:
                hit_ids.append(cached[0])
            else:
                metas.append(meta)
        self.req_id += 1
        digest = (self.digest_fn(self.rank, self.steps_done)
                  if self.digest_fn is not None else None)
        self.steps_done += 1
        wire = self.neg._client._wire
        before = wire.bytes_out
        resp = self.neg.cycle(metas, self.applied, req_id=self.req_id,
                              hits=neg.encode_hits(hit_ids),
                              digest=digest)
        self.req_bytes.append(wire.bytes_out - before)
        self.cycles = 1
        self._apply(resp, metas_by_name)
        while self.pending:
            self.req_id += 1
            before = wire.bytes_out
            resp = self.neg.cycle([], self.applied, req_id=self.req_id)
            self.req_bytes[-1] += wire.bytes_out - before
            self.cycles += 1
            self._apply(resp, metas_by_name)
            if not resp.responses:
                time.sleep(0.001)

    def _apply(self, resp, metas_by_name):
        for off, r in enumerate(resp.responses):
            seq = resp.base_seq + off
            if seq <= self.applied:
                continue
            if r.kind == r.EXECUTE and r.cache_ids:
                for name, cid in zip(r.names, r.cache_ids):
                    meta = metas_by_name.get(name)
                    if meta is not None:
                        sig = (meta.op, meta.dtype, meta.shape,
                               meta.root_rank, meta.average)
                        self.cache[name] = (cid, sig)
            self.pending.difference_update(r.names)
            self.applied = seq


def run_case(nproc, ntensors, steps, cache_capacity, digest_fn=None):
    key = b"b" * 32
    cfg = HorovodConfig(fusion_threshold=64 << 20,
                        stall_warning_time_seconds=0,
                        cache_capacity=cache_capacity)
    # per-run free ports (not a fixed base): concurrent CI shards and
    # back-to-back cases must not collide on TIME_WAIT sockets
    addrs = [("127.0.0.1", network.free_port())]
    workers = [None] * nproc

    def make(rank):
        workers[rank] = _Worker(rank, nproc, cfg, addrs, key,
                                digest_fn=digest_fn)

    t0 = threading.Thread(target=make, args=(0,))
    t0.start()
    t0.join()  # rank 0 hosts the service; peers probe after it binds
    threads = [threading.Thread(target=make, args=(r,))
               for r in range(1, nproc)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    metas = {f"grad_{i}": neg.EntryMeta(f"grad_{i}", "allreduce",
                                        "float32", (256,), 0, False)
             for i in range(ntensors)}
    lat = []
    for _ in range(steps):
        start = time.perf_counter()
        ts = [threading.Thread(target=w.step, args=(metas,))
              for w in workers]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        lat.append((time.perf_counter() - start) * 1e3)
    workers[0].neg.close(linger_s=0.0)
    cold = statistics.mean(w.req_bytes[0] for w in workers)
    steady = statistics.mean(b for w in workers for b in w.req_bytes[1:])
    return {
        "cold_req_bytes_per_worker": round(cold),
        "steady_req_bytes_per_worker": round(steady),
        "cold_cycle_ms": round(lat[0], 2),
        "steady_cycle_ms": round(statistics.mean(lat[1:]), 2),
        # min is robust to scheduler noise: best-case latencies are
        # compared, not means
        "best_cycle_ms": round(min(lat[1:]), 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--tensors", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=5,
                    help="per case; >= 2 (one cold + steady-state)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.steps < 2:
        ap.error("--steps must be >= 2 (one cold step + steady state)")

    off = run_case(args.workers, args.tensors, args.steps,
                   cache_capacity=0)
    on = run_case(args.workers, args.tensors, args.steps,
                  cache_capacity=4096)
    out = {
        "workers": args.workers, "tensors": args.tensors,
        "cache_off": off, "cache_on": on,
        "steady_bytes_reduction_x": round(
            off["steady_req_bytes_per_worker"] /
            max(1, on["steady_req_bytes_per_worker"]), 1),
        "steady_latency_speedup_x": round(
            off["steady_cycle_ms"] / max(1e-9, on["steady_cycle_ms"]), 2),
    }
    if args.json:
        print(json.dumps(out))
    else:
        print(f"control plane @ {args.workers} workers x "
              f"{args.tensors} tensors/step")
        for label, case in (("cache off", off), ("cache on", on)):
            print(f"  {label:9s} cold {case['cold_req_bytes_per_worker']:>10,} B "
                  f"/ {case['cold_cycle_ms']:>8.1f} ms   "
                  f"steady {case['steady_req_bytes_per_worker']:>10,} B "
                  f"/ {case['steady_cycle_ms']:>8.1f} ms")
        print(f"  steady-state: {out['steady_bytes_reduction_x']}x fewer "
              f"request bytes, {out['steady_latency_speedup_x']}x faster "
              f"cycles")


if __name__ == "__main__":
    main()
