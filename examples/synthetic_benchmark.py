"""Synthetic ResNet benchmark — parity with the reference harness
(examples/pytorch_synthetic_benchmark.py: --model, --batch-size,
--num-warmup-batches 10, --num-iters 10, --num-batches-per-iter 10; prints
img/sec per worker and total with stddev).

TPU-native: bf16 compute, NHWC, one fused gradient psum per bucket inside a
single compiled train step.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.utils import compile_cache

from bench_common import (build_eager_image_step, build_step, positive_int,
                          timed_rates)


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=models.names())
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-worker batch size (reference default 32)")
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--num-iters", type=positive_int, default=10)
    p.add_argument("--num-batches-per-iter", type=positive_int, default=10)
    p.add_argument("--image-size", type=int, default=None,
                   help="default: the model's canonical size (224; "
                        "inception3 299)")
    p.add_argument("--fp16-allreduce", action="store_true",
                   help="bf16 compression on gradient allreduce")
    p.add_argument("--eager-allreduce", action="store_true",
                   help="average gradients through the EAGER collective "
                        "core per step (reference Horovod's regime, and "
                        "the one HOROVOD_AUTOTUNE scores) instead of the "
                        "in-graph psum")
    args = p.parse_args()
    if args.image_size is None:
        args.image_size = models.image_size(args.model)
    return args


def main():
    args = parse_args()
    compile_cache.configure()
    hvd.init()
    world = hvd.size()
    batch = args.batch_size * world

    if args.eager_allreduce:
        step, params, opt_state, batch_data = build_eager_image_step(
            args.model, world, args.batch_size, args.image_size,
            compression=hvd.Compression.bf16 if args.fp16_allreduce
            else None)
    else:
        step, params, opt_state, batch_data = build_step(
            args.model, hvd.mesh(), batch, args.image_size,
            fp16_allreduce=args.fp16_allreduce)

    if hvd.process_rank() == 0:
        print(f"Model: {args.model}")
        print(f"Batch size: {args.batch_size} per worker x {world} workers")
        if args.eager_allreduce:
            print("Gradient averaging: eager fused allreduce "
                  "(autotune-scorable)")

    def on_iter(i, rate):
        if hvd.process_rank() == 0:
            print(f"Iter #{i}: {rate / world:.1f} img/sec per worker")

    rates = timed_rates(step, params, opt_state, batch_data, batch,
                        args.num_warmup_batches, args.num_iters,
                        args.num_batches_per_iter, on_iter=on_iter)

    if hvd.process_rank() == 0:
        img_secs = [r / world for r in rates]
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec per worker: {mean:.1f} +-{conf:.1f}")
        print(f"Total img/sec on {world} worker(s): "
              f"{mean * world:.1f} +-{conf * world:.1f}")


if __name__ == "__main__":
    main()
