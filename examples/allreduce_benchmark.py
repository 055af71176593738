"""Eager allreduce throughput microbenchmark: measures the tensor-fusion
win directly (bytes/µs with fusion on vs HOROVOD_FUSION_THRESHOLD=0), the
same score the autotuner optimizes (reference ParameterManager,
parameter_manager.cc:155-210) and the measurable knob SURVEY's design
translation calls for.

Enqueues N same-sized tensors async (the gradient-burst pattern a backward
pass produces), flushes once, joins — fused: few bucketed collectives;
unfused: one collective per tensor.

    python examples/allreduce_benchmark.py
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/allreduce_benchmark.py --sizes-kb 4,64,1024
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import horovod_tpu as hvd


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--num-tensors", type=int, default=32,
                   help="tensors per burst (one backward pass's gradients)")
    p.add_argument("--sizes-kb", default="4,64,1024",
                   help="per-tensor payload sizes to sweep, KB")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--num-proc", type=int, default=1,
                   help=">1: spawn processes and measure the negotiated "
                        "multi-process path (rank-0 coordinator fusion) "
                        "instead of the single-controller stacked path")
    return p.parse_args()


def measure(n_tensors, elems, iters):
    """Mean bytes/µs for a burst of n_tensors stacked [world, elems]
    float32 allreduces (timed after one untimed warmup burst)."""
    import horovod_tpu.common.state as state
    world = hvd.size()
    coord = state.global_state().coordinator
    tensors = [np.full((world, elems), float(i), np.float32)
               for i in range(n_tensors)]
    nbytes = sum(t.nbytes for t in tensors)
    rates = []
    for it in range(iters + 1):
        with coord.hold_cycle():  # the burst lands in one fused cycle
            handles = [hvd.allreduce_async(t, average=False,
                                           name=f"ar.{it}.{i}")
                       for i, t in enumerate(tensors)]
        t0 = time.perf_counter()
        coord.flush()
        outs = [hvd.synchronize(h) for h in handles]
        for o in outs:
            np.asarray(o)  # device-to-host read: the completion barrier
        dt = time.perf_counter() - t0
        if it > 0:  # first burst warms compilation caches
            rates.append(nbytes / dt / 1e6)  # bytes/µs
    return float(np.mean(rates))


def _measure_multiproc(num_proc, n_tensors, sizes_kb, iters, threshold):
    """Per-size bytes/µs for bursts of replicated allreduces across
    num_proc real processes: with the default threshold the rank-0
    negotiation coordinator fuses each burst into few cross-process
    collectives; with HOROVOD_FUSION_THRESHOLD=0 every tensor pays its
    own round. One launch sweeps every size — process spawn + rendezvous
    + backend import are paid once per threshold, not per point."""
    from horovod_tpu.run.launch import run

    def fn(n_tensors, sizes_kb, iters):
        import time as _time
        import numpy as _np
        import horovod_tpu as _hvd
        _hvd.init()
        out = {}
        for kb in sizes_kb:
            elems = max(1, kb * 1024 // 4)
            tensors = [_np.full((elems,), float(i), _np.float32)
                       for i in range(n_tensors)]
            nbytes = sum(t.nbytes for t in tensors)
            rates = []
            for it in range(iters + 1):
                t0 = _time.perf_counter()
                handles = [_hvd.allreduce_async(
                    t, average=False, name=f"ar.{kb}.{it}.{i}")
                    for i, t in enumerate(tensors)]
                for h in handles:
                    _np.asarray(_hvd.synchronize(h))
                dt = _time.perf_counter() - t0
                if it > 0:
                    rates.append(nbytes / dt / 1e6)
            out[kb] = sum(rates) / len(rates)
        _hvd.shutdown()
        return out

    env = {"JAX_PLATFORMS": "cpu",
           "HOROVOD_FUSION_THRESHOLD": str(threshold)}
    per_rank = run(fn, args=(n_tensors, sizes_kb, iters),
                   num_proc=num_proc, env=env)
    return {kb: float(np.mean([r[kb] for r in per_rank]))
            for kb in sizes_kb}


def main():
    args = parse_args()
    if args.iters < 1:
        raise SystemExit("--iters must be >= 1")
    if args.num_proc > 1:
        sizes_kb = [int(s) for s in args.sizes_kb.split(",")]
        fused = _measure_multiproc(args.num_proc, args.num_tensors,
                                   sizes_kb, args.iters, 64 << 20)
        unfused = _measure_multiproc(args.num_proc, args.num_tensors,
                                     sizes_kb, args.iters, 0)
        results = {}
        for kb in sizes_kb:
            results[f"{kb}KB"] = {
                "fused_bytes_per_us": round(fused[kb], 3),
                "unfused_bytes_per_us": round(unfused[kb], 3),
                "speedup": round(fused[kb] / unfused[kb], 2)}
            print(f"{args.num_proc} proc, {args.num_tensors} x {kb} KB: "
                  f"negotiated-fused {fused[kb]:.2f} B/us, unfused "
                  f"{unfused[kb]:.2f} B/us, {fused[kb] / unfused[kb]:.2f}x")
        print(json.dumps({
            "metric": "negotiated_allreduce_fusion_speedup",
            "num_proc": args.num_proc,
            "num_tensors": args.num_tensors, "results": results}))
        return
    hvd.init()
    from horovod_tpu.common import state
    sizes_kb = [int(s) for s in args.sizes_kb.split(",")]
    results = {}
    for kb in sizes_kb:
        elems = max(1, kb * 1024 // 4 // hvd.size())
        fused = measure(args.num_tensors, elems, args.iters)
        cfg = state.global_state().config
        saved = cfg.fusion_threshold
        cfg.fusion_threshold = 0  # one collective per tensor
        try:
            unfused = measure(args.num_tensors, elems, args.iters)
        finally:
            cfg.fusion_threshold = saved
        results[f"{kb}KB"] = {"fused_bytes_per_us": round(fused, 3),
                              "unfused_bytes_per_us": round(unfused, 3),
                              "speedup": round(fused / unfused, 2)}
        print(f"{args.num_tensors} x {kb} KB: fused {fused:.2f} B/us, "
              f"unfused {unfused:.2f} B/us, "
              f"{fused / unfused:.2f}x")
    print(json.dumps({"metric": "eager_allreduce_fusion_speedup",
                      "num_tensors": args.num_tensors,
                      "results": results}))


if __name__ == "__main__":
    main()
