"""Serve the transformer LM with continuous batching (docs/serving.md).

Generates synthetic open-loop Poisson traffic against the serving
engine (horovod_tpu/serving/) and reports decode throughput plus
per-request SLO latencies — and, with ``--baseline``, runs the SAME
engine in drain (static-batch) mode so the two scheduling policies are
compared at an equal slot budget. Prints one JSON result line.

Usage:
    # CPU, tiny config, continuous vs static side by side
    JAX_PLATFORMS=cpu python examples/serve_lm.py --baseline

    # heavier load, more slots
    python examples/serve_lm.py --slots 8 --requests 96 --rate 0.8
"""

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as tr
from horovod_tpu.serving.engine import ServeEngine
from horovod_tpu.serving.queue import AdmissionQueue, Request
from horovod_tpu.utils import compile_cache
from horovod_tpu.utils import metrics as hvd_metrics


def serving_config(on_tpu):
    """The LM this example serves: the flagship config on TPU, the tiny
    fp32 config on CPU (fp32 because CPU bf16 emulation is slow and the
    example's point is scheduling, not dtype)."""
    if on_tpu:
        return tr.TransformerConfig.gpt2_small_tpu(
            attention_impl="flash")
    return tr.TransformerConfig.tiny(dtype=jnp.float32,
                                     attention_impl="full")


def make_workload(seed, n_requests, rate, short_tokens=8, long_tokens=40,
                  long_frac=0.25, prompt_lens=(4, 8), temperature=0.0):
    """Open-loop Poisson arrival schedule: [(arrival_step, Request)].

    Arrival times are exponential inter-arrival gaps at ``rate``
    requests per decode step — open-loop, so the schedule never adapts
    to how the engine is doing (the honest way to measure overload).
    Decode lengths are bimodal (mostly short, a heavy tail of long)
    because that is the regime where continuous batching pays: under
    drain scheduling every short request in a wave waits for the wave's
    longest.
    """
    r = np.random.RandomState(seed)
    t = 0.0
    workload = []
    for i in range(n_requests):
        t += r.exponential(1.0 / rate)
        n_new = long_tokens if r.rand() < long_frac else short_tokens
        plen = int(r.randint(prompt_lens[0], prompt_lens[1] + 1))
        prompt = tuple(int(x) for x in r.randint(1, 250, plen))
        workload.append((t, Request(f"req-{i}", prompt,
                                    max_new_tokens=n_new,
                                    temperature=temperature)))
    return workload


def run_load(engine, workload, max_steps=100000):
    """Drive the engine under the arrival schedule: submit every request
    whose arrival step has passed, then step. Returns (results,
    decode_steps, wall_s)."""
    i = 0
    steps = 0
    results = []
    t0 = time.monotonic()
    while i < len(workload) or engine.active_count or len(engine.queue):
        while i < len(workload) and workload[i][0] <= steps:
            engine.submit(workload[i][1])
            i += 1
        results.extend(engine.step())
        steps += 1
        if steps >= max_steps:
            raise RuntimeError(
                f"load never drained in {max_steps} steps "
                f"({len(results)} done, {engine.active_count} active)")
    return results, steps, time.monotonic() - t0


def serve_workload(cfg, params, workload, policy, num_slots, max_len,
                   kv_block=8, seed=0):
    """One arm of the comparison: serve ``workload`` under ``policy``
    and summarize throughput + latency. Fresh engine per arm so the
    arms share nothing but params."""
    queue = AdmissionQueue(max_depth=len(workload) + 1,
                           admission_timeout_s=1e9)
    engine = ServeEngine(cfg, params, num_slots=num_slots,
                         max_len=max_len, kv_block=kv_block,
                         policy=policy, queue=queue, seed=seed)
    results, steps, wall_s = run_load(engine, workload)
    completed = [r for r in results if r.outcome == "completed"]
    decode_tokens = sum(len(r.tokens) for r in completed)
    ttfts = sorted(r.ttft_s for r in completed if r.ttft_s is not None)

    def pct(q):
        if not ttfts:
            return None
        return ttfts[min(len(ttfts) - 1, int(q * len(ttfts)))]
    assert engine.kv.ledger.blocks_in_use == 0, "KV blocks leaked"
    return {
        "policy": policy,
        "completed": len(completed),
        "failed": len(results) - len(completed),
        "decode_tokens": decode_tokens,
        "steps": steps,
        "tokens_per_step": decode_tokens / max(steps, 1),
        "wall_s": round(wall_s, 3),
        "tokens_per_s": round(decode_tokens / wall_s, 1) if wall_s else 0,
        "ttft_p50_s": pct(0.50),
        "ttft_p99_s": pct(0.99),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=0.5,
                    help="mean arrivals per decode step (open loop)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--kv-block", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--baseline", action="store_true",
                    help="also run the drain (static-batch) arm and "
                         "report the speedup")
    args = ap.parse_args(argv)

    compile_cache.configure()
    on_tpu = jax.default_backend() == "tpu"
    cfg = serving_config(on_tpu)
    _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
    workload = make_workload(args.seed, args.requests, args.rate,
                             temperature=args.temperature)

    out = {"backend": jax.default_backend(), "slots": args.slots,
           "requests": args.requests, "rate": args.rate}
    out["continuous"] = serve_workload(
        cfg, params, workload, "continuous", args.slots, args.max_len,
        kv_block=args.kv_block, seed=args.seed)
    if args.baseline:
        out["static"] = serve_workload(
            cfg, params, workload, "drain", args.slots, args.max_len,
            kv_block=args.kv_block, seed=args.seed)
        out["speedup_tokens_per_step"] = round(
            out["continuous"]["tokens_per_step"] /
            max(out["static"]["tokens_per_step"], 1e-9), 3)
    out["metrics"] = hvd_metrics.get_registry().snapshot(max_events=8)
    print(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
