"""Fused vs unfused allgather burst at 2+ processes.

Measures the eager negotiated path end-to-end: K same-dtype allgathers
submitted async then synchronized (one burst). Fusion on (default
threshold: the coordinator buckets the burst into one allgatherv) vs
off (HOROVOD_FUSION_THRESHOLD=0 semantics: one collective per tensor).
The two configs are toggled LIVE on the coordinator and interleaved
round-by-round so host drift is common-mode.

Usage: python tools/gather_burst_bench.py [--procs 2] [--tensors 16]
       [--rows 4096] [--rounds 5] [--json]
"""

import argparse
import json
import statistics
import sys


def worker(args_tuple):
    tensors, rows, rounds = args_tuple
    import os
    import time
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu.common import state

    hvd.init()
    r = int(os.environ["HVD_PROCESS_ID"])
    cfg = state.global_state().config

    def burst(tag):
        hs = [hvd.allgather_async(
            np.full((rows + r, 4), float(i), np.float32),
            name=f"{tag}.g{i}", kind="replicated")
            for i in range(tensors)]
        outs = [hvd.synchronize(h) for h in hs]
        np.asarray(outs[-1])  # materialize
        return outs

    burst("warm")  # compile/negotiate warmup
    fused_ms, unfused_ms = [], []
    for rnd in range(rounds):
        for fused in (True, False) if rnd % 2 == 0 else (False, True):
            # live coordinator knob: rank 0's config object is the one
            # the coordinator reads when planning buckets
            cfg.fusion_threshold = (64 << 20) if fused else 0
            time.sleep(0.05)  # let the knob settle across cycles
            t0 = time.perf_counter()
            burst(f"r{rnd}f{int(fused)}")
            dt = (time.perf_counter() - t0) * 1e3
            (fused_ms if fused else unfused_ms).append(dt)
    coord = state.global_state().coordinator
    n_responses = coord._applied_seq + 1
    hvd.shutdown()
    return fused_ms, unfused_ms, n_responses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--tensors", type=int, default=16)
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    from horovod_tpu.run.launch import run
    results = run(worker, num_proc=args.procs,
                  args=((args.tensors, args.rows, args.rounds),),
                  env={"JAX_PLATFORMS": "cpu"})
    fused_ms, unfused_ms, _ = results[0]
    fused = statistics.median(fused_ms)
    unfused = statistics.median(unfused_ms)
    out = {
        "procs": args.procs, "tensors": args.tensors,
        "bytes_per_tensor": args.rows * 4 * 4,
        "fused_burst_ms": round(fused, 2),
        "unfused_burst_ms": round(unfused, 2),
        "speedup_x": round(unfused / max(1e-9, fused), 2),
        "rounds": args.rounds,
    }
    if args.json:
        print(json.dumps(out))
    else:
        print(f"allgather burst @ {args.procs} procs x {args.tensors} "
              f"tensors ({out['bytes_per_tensor']} B each), "
              f"{args.rounds} interleaved rounds:")
        print(f"  fused   {fused:8.1f} ms/burst")
        print(f"  unfused {unfused:8.1f} ms/burst")
        print(f"  speedup {out['speedup_x']}x")
    return 0


if __name__ == "__main__":
    sys.exit(main())
