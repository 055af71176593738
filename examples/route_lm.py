"""Serve the transformer LM behind the router plane (docs/routing.md).

Fronts N serving replicas with one Router and drives the same bimodal
open-loop workload serve_lm.py uses — but through the front door:
every request is dispatched by the routing policy over live load
snapshots, with cache-affinity stickiness on prompt prefixes. With
``--compare`` the SAME workload also runs under round_robin on fresh
replicas, so the load-aware policy's tail-latency win under imbalance
is measured, not asserted. This is the sanctioned client shape hvdlint
HVD017 enforces: examples submit through a Router, never a bare
``ServeEngine.submit``.

Usage:
    # CPU, tiny config, 2 replicas, least_loaded vs round_robin
    JAX_PLATFORMS=cpu python examples/route_lm.py --compare

    # more replicas, heavier traffic
    python examples/route_lm.py --replicas 4 --requests 96 --rate 0.8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from horovod_tpu.router import Router
from horovod_tpu.serving.engine import ServeEngine
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils import metrics as hvd_metrics

from serve_lm import make_workload, serving_config

from horovod_tpu.models import transformer as tr


def run_routed(router, workload, max_steps=100000):
    """Drive the router under the arrival schedule: submit every
    request whose arrival step has passed, then step every replica.
    Returns (results, steps, wall_s)."""
    i = 0
    steps = 0
    results = []
    t0 = time.monotonic()
    while i < len(workload) or router.pending():
        while i < len(workload) and workload[i][0] <= steps:
            router.submit(workload[i][1])
            i += 1
        results.extend(router.step())
        steps += 1
        if steps >= max_steps:
            raise RuntimeError(
                f"load never drained in {max_steps} steps "
                f"({len(results)} done)")
    return results, steps, time.monotonic() - t0


def route_workload(cfg, params, workload, policy, replicas, num_slots,
                   max_len, kv_block=8, seed=0):
    """One arm of the comparison: ``replicas`` fresh engines behind a
    fresh Router under ``policy``. Each engine builds its own admission
    queue (HVD_SERVE_QUEUE_DEPTH / HVD_SERVE_ADMISSION_TIMEOUT_S);
    the arms share nothing but params."""
    engines = {
        rid: ServeEngine(cfg, params, num_slots=num_slots,
                         max_len=max_len, kv_block=kv_block, seed=seed)
        for rid in range(replicas)}
    router = Router(engines, policy=policy)
    results, steps, wall_s = run_routed(router, workload)
    completed = [r for r in results if r.outcome == "completed"]
    decode_tokens = sum(len(r.tokens) for r in completed)
    ttfts = sorted(r.ttft_s for r in completed if r.ttft_s is not None)
    by_replica = {}
    for r in completed:
        by_replica[r.replica] = by_replica.get(r.replica, 0) + 1

    def pct(q):
        if not ttfts:
            return None
        return ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))]

    for rid, eng in engines.items():
        assert eng.kv.ledger.blocks_in_use == 0, \
            f"KV blocks leaked on replica {rid}"
    return {
        "policy": policy,
        "replicas": replicas,
        "completed": len(completed),
        "failed": len(results) - len(completed),
        "by_replica": {str(k): v for k, v in sorted(by_replica.items())},
        "decode_tokens": decode_tokens,
        "steps": steps,
        "tokens_per_step": decode_tokens / max(steps, 1),
        "wall_s": round(wall_s, 3),
        "ttft_p50_s": pct(0.50),
        "ttft_p99_s": pct(0.99),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=2,
                    help="decode slots per replica")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step (open loop)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--kv-block", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="least_loaded",
                    help="dispatch policy (HVD_ROUTE_POLICY)")
    ap.add_argument("--compare", action="store_true",
                    help="also run the round_robin arm and report the "
                         "p99 TTFT ratio")
    args = ap.parse_args(argv)

    compile_cache.configure()
    on_tpu = jax.default_backend() == "tpu"
    cfg = serving_config(on_tpu)
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    workload = make_workload(args.seed, args.requests, args.rate)

    out = {"backend": jax.default_backend(),
           "replicas": args.replicas, "slots": args.slots,
           "requests": args.requests, "rate": args.rate}
    out[args.policy] = route_workload(
        cfg, params, workload, args.policy, args.replicas, args.slots,
        args.max_len, kv_block=args.kv_block, seed=args.seed)
    if args.compare and args.policy != "round_robin":
        out["round_robin"] = route_workload(
            cfg, params, workload, "round_robin", args.replicas,
            args.slots, args.max_len, kv_block=args.kv_block,
            seed=args.seed)
        a, b = out[args.policy], out["round_robin"]
        if a["ttft_p99_s"] and b["ttft_p99_s"]:
            out["p99_ttft_ratio"] = round(
                a["ttft_p99_s"] / b["ttft_p99_s"], 3)
    out["metrics"] = hvd_metrics.get_registry().snapshot(max_events=8)
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
