"""Scaling-efficiency sweep — the reference's headline metric
(docs/benchmarks.md:6-7: total_imgs_per_sec(N) / (N * imgs_per_sec(1)),
90% for Inception V3 / ResNet-101 at 512 GPUs) measured in one process
over growing device counts.

Weak scaling: per-worker batch is fixed, so perfect scaling is a flat
img/sec/worker line; efficiency(N) = rate_per_worker(N) /
rate_per_worker(baseline), where baseline is the smallest count in the
sweep (1 unless --device-counts says otherwise — the output labels it).
Runs on all local TPU chips or the virtual CPU mesh:

    python examples/scaling_benchmark.py                   # all local chips
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/scaling_benchmark.py --model resnet18 --batch-size 4
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.utils import compile_cache

from bench_common import build_step, positive_int, timed_rates


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=sorted(models.names()) + ["transformer"])
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-worker batch (fixed across the sweep)")
    p.add_argument("--device-counts", default=None,
                   help="comma-separated, e.g. 1,2,4,8 "
                        "(default: powers of two up to all devices)")
    p.add_argument("--num-warmup-batches", type=int, default=5)
    p.add_argument("--num-iters", type=positive_int, default=3)
    p.add_argument("--num-batches-per-iter", type=positive_int, default=10)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--fp16-allreduce", action="store_true")
    return p.parse_args()


def measure(args, n_devices):
    """samples/sec per worker (images, or sequences for the flagship
    transformer) on the first n_devices local devices."""
    from bench_common import build_transformer_step

    hvd.init(devices=jax.devices()[:n_devices])
    batch = args.batch_size * n_devices
    if args.model == "transformer":
        from horovod_tpu.parallel import mesh as mesh_mod
        if args.fp16_allreduce or args.image_size is not None:
            raise SystemExit(
                "--fp16-allreduce/--image-size apply to the image zoo "
                "only; the transformer step has its own recipe "
                "(bench_common.build_transformer_step)")
        on_tpu = jax.devices()[0].platform == "tpu"
        seq = 1024 if on_tpu else 64
        # the transformer's param specs name dp/tp/sp/ep axes, so it
        # needs the named mesh, not init()'s default 1-D 'hvd' mesh
        dp_mesh = mesh_mod.build_mesh(
            dp=n_devices, devices=jax.devices()[:n_devices])
        step, params, opt_state, batch_data, _ = build_transformer_step(
            dp_mesh, batch, seq, on_tpu=on_tpu)
    else:
        step, params, opt_state, batch_data = build_step(
            args.model, hvd.mesh(), batch, args.image_size,
            fp16_allreduce=args.fp16_allreduce)
    rates = timed_rates(step, params, opt_state, batch_data, batch,
                        args.num_warmup_batches, args.num_iters,
                        args.num_batches_per_iter)
    hvd.shutdown()
    return float(np.mean(rates)) / n_devices


def main():
    args = parse_args()
    compile_cache.configure()
    n_avail = len(jax.devices())
    if args.device_counts:
        try:
            counts = sorted({positive_int(c)
                             for c in args.device_counts.split(",")})
        except ValueError as e:
            raise SystemExit(f"--device-counts: {e}")
        bad = [c for c in counts if c > n_avail]
        if bad:
            raise SystemExit(f"asked for {bad} devices, have {n_avail}")
    else:
        counts, c = [], 1
        while c <= n_avail:
            counts.append(c)
            c *= 2
    if args.image_size is None and args.model != "transformer":
        on_tpu = jax.devices()[0].platform == "tpu"
        args.image_size = models.image_size(args.model) if on_tpu else 64

    base = counts[0]
    shape_note = ("seq 1024 (64 on cpu)" if args.model == "transformer"
                  else f"image {args.image_size}")
    print(f"Model: {args.model}, batch {args.batch_size}/worker, "
          f"{shape_note}, devices {counts} "
          f"(efficiency baseline: {base} worker(s))")
    rate_unit = "seq/sec" if args.model == "transformer" else "img/sec"
    results = []
    for n in counts:
        rate = measure(args, n)
        eff = rate / results[0][1] if results else 1.0
        results.append((n, rate, eff))
        print(f"  {n} worker(s): {rate:.1f} {rate_unit}/worker, "
              f"total {rate * n:.1f}, "
              f"efficiency vs {base}-worker: {eff:.1%}")

    print(json.dumps({
        "metric": f"{args.model}_scaling_efficiency_{base}to"
                  f"{counts[-1]}_workers",
        "value": round(results[-1][2], 4),
        "unit": "fraction",
        "baseline_workers": base,
        "rate_unit": rate_unit,
        "per_worker_rate": {str(n): round(r, 1) for n, r, _ in results},
    }))


if __name__ == "__main__":
    main()
