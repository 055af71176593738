"""Per-kernel microbenchmark for the Pallas flash-attention kernels.

Times each kernel (fwd, fwd+bwd, dq, dkv) on the real chip at the
flagship shape (b8 s1024 h12 d64, bf16, causal) and reports achieved MXU
utilization against the causal-attention matmul FLOPs. This is the
harness behind the kernel table in docs/benchmarks.md.

Measurement scheme: per-call host overhead must not be read as kernel
time, so each measurement runs N chained iterations INSIDE one jitted call
(lax.fori_loop with a data dependency between iterations) and two loop
counts (N1 < N2) are timed — the slope (t2-t1)/(N2-N1) is pure device
time per iteration, with call overhead cancelled.

Usage: python tools/flash_microbench.py [--seq 1024] [--batch 8] ...
"""

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _peak_flops():
    kind = getattr(jax.devices()[0], "device_kind", "")
    if kind.startswith("TPU v5 lite"):
        return 197e12
    if kind.startswith("TPU v6"):
        return 918e12
    if kind.startswith("TPU v4"):
        return 275e12
    return 197e12


def _time_call(fn, args, trials):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
    times = []
    for _ in range(trials + 1):
        t0 = time.perf_counter()
        out = fn(*args)
        float(jnp.sum(jax.tree_util.tree_leaves(out)[0]))
        times.append(time.perf_counter() - t0)
    return float(np.min(times[1:]))  # drop first (cache warm); min = device floor


def bench_chained(make_loop, args, n1, n2, trials, name, flops=None):
    """make_loop(n) -> jitted fn running n chained iterations."""
    t1 = _time_call(make_loop(n1), args, trials)
    t2 = _time_call(make_loop(n2), args, trials)
    dt = (t2 - t1) / (n2 - n1)
    util = f"  mxu={flops / dt / _peak_flops() * 100:5.1f}%" if flops else ""
    print(f"{name:<26} {dt * 1e3:8.3f} ms{util}")
    return dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--n1", type=int, default=8)
    ap.add_argument("--n2", type=int, default=48)
    ap.add_argument("--trials", type=int, default=4)
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--block-k", type=int, default=512)
    ap.add_argument("--variant", default="auto",
                    help="forward variant: auto/online/lazy/twopass, or "
                         "'all' to time every variant back to back "
                         "in-process (one process, one session: the "
                         "comparison that drift cannot fake)")
    ap.add_argument("--skip-xla", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="repeat measurements in-process (to see the "
                         "spread before trusting a difference)")
    ap.add_argument("--sweep-dkv", action="store_true",
                    help="sweep dkv kernel block sizes in-process")
    args = ap.parse_args()

    from horovod_tpu.ops import flash_attention as fa

    b, s, h, d = args.batch, args.seq, args.heads, args.dim
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    print(f"shape b{b} s{s} h{h} d{d} bf16 causal "
          f"blocks q{args.block_q}/k{args.block_k}")

    # causal attention matmul FLOPs (two matmuls fwd, five bwd; the
    # causal mask halves the logits footprint)
    fwd_flops = b * h * 2 * 2 * s * s * d * 0.5
    bwd_flops = fwd_flops / 2 * 5
    interp = jax.default_backend() != "tpu"
    scale = d ** -0.5

    def make_loops(variant):
        flash = functools.partial(fa.flash_attention, causal=True,
                                  block_q=args.block_q,
                                  block_k=args.block_k, variant=variant)

        # fwd: chain q <- flash(q, k, v) (same shape, true dependency)
        def fwd_loop(n):
            @jax.jit
            def run(q, k, v):
                return jax.lax.fori_loop(
                    0, n, lambda i, qq: flash(qq, k, v), q)
            return run

        # fwd+bwd: chain q <- q - 1e-3 * (dq + dk + dv)
        gradfn = jax.grad(
            lambda *a: jnp.sum(flash(*a).astype(jnp.float32)),
            argnums=(0, 1, 2))

        def grad_loop(n):
            @jax.jit
            def run(q, k, v):
                def body(i, qq):
                    # consume ALL grads or XLA DCEs the dkv kernel
                    dq, dk, dv = gradfn(qq, k, v)
                    return qq - (1e-3 * (dq + dk + dv)).astype(qq.dtype)
                return jax.lax.fori_loop(0, n, body, q)
            return run

        return fwd_loop, grad_loop

    if args.variant == "all":
        # interleaved variant sweep: every forward variant timed back to
        # back per round, so any drift is common-mode
        for rep in range(2):
            for var in fa.VARIANTS:
                vf, vg = make_loops(var)
                bench_chained(vf, (q, k, v), args.n1, args.n2,
                              args.trials, f"fwd {var} r{rep}", fwd_flops)
                bench_chained(vg, (q, k, v), args.n1, args.n2,
                              args.trials, f"f+b {var} r{rep}",
                              fwd_flops * 2 + bwd_flops)
        return

    fwd_loop, grad_loop = make_loops(args.variant)

    if args.sweep:
        # repeated in-process measurements: the spread to hold any
        # difference against
        for rep in range(3):
            bench_chained(fwd_loop, (q, k, v), args.n1, args.n2,
                          args.trials, f"fwd  r{rep}", fwd_flops)
            bench_chained(grad_loop, (q, k, v), args.n1, args.n2,
                          args.trials, f"f+b  r{rep}",
                          fwd_flops * 2 + bwd_flops)
        return

    if args.sweep_dkv:
        def dkv_grad_loop(bq2, bk2):
            fl = functools.partial(
                fa.flash_attention, causal=True, block_q=args.block_q,
                block_k=args.block_k, block_q_dkv=bq2, block_k_dkv=bk2)
            gf = jax.grad(
                lambda *a: jnp.sum(fl(*a).astype(jnp.float32)),
                argnums=(0, 1, 2))

            def make(n):
                @jax.jit
                def run(q, k, v):
                    def body(i, qq):
                        dq, dk, dv = gf(qq, k, v)
                        return qq - (1e-3 * (dq + dk + dv)).astype(qq.dtype)
                    return jax.lax.fori_loop(0, n, body, q)
                return run
            return make

        for bq2 in (128, 256, 512, 1024):
            for bk2 in (256, 512, 1024):
                if bq2 > s or bk2 > s:
                    continue
                bench_chained(dkv_grad_loop(bq2, bk2), (q, k, v),
                              args.n1, args.n2, args.trials,
                              f"f+b dkv q{bq2} k{bk2}",
                              fwd_flops * 2 + bwd_flops)
        return

    bench_chained(fwd_loop, (q, k, v), args.n1, args.n2, args.trials,
                  "flash fwd", fwd_flops)
    bench_chained(grad_loop, (q, k, v), args.n1, args.n2, args.trials,
                  "flash fwd+bwd", fwd_flops * 2 + bwd_flops)

    # ---- individual bwd kernels at the padded-lane shape the VJP runs
    dpad = -d % 128 if not interp else 0
    pads = ((0, 0), (0, 0), (0, 0), (0, dpad))
    qp, kp, vp = (jnp.pad(t, pads) for t in (q, k, v))
    out, lse = jax.jit(functools.partial(
        fa._flash_fwd, causal=True, block_q=args.block_q,
        block_k=args.block_k, interpret=interp, scale=scale))(qp, kp, vp)
    g = jnp.ones_like(out)

    bwdfn = functools.partial(
        fa._flash_bwd, causal=True, block_q=args.block_q,
        block_k=args.block_k, interpret=interp, scale=scale)

    def bwd_loop(n):
        @jax.jit
        def run(qp, kp, vp, out, lse, g):
            def body(i, gg):
                dq, dk, dv = bwdfn(qp, kp, vp, out, lse, gg)
                # consume all three or XLA DCEs the unused kernel
                return gg + ((dq + dk + dv) * 1e-6).astype(gg.dtype)
            return jax.lax.fori_loop(0, n, body, g)
        return run

    bench_chained(bwd_loop, (qp, kp, vp, out, lse, g), args.n1, args.n2,
                  args.trials, "flash bwd (dq+dkv)", bwd_flops)

    if args.skip_xla:
        return

    # ---- XLA full attention reference
    def full(q, k, v):
        s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s_ = jnp.where(mask, s_, -1e30)
        p = jax.nn.softmax(s_, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def xla_fwd_loop(n):
        @jax.jit
        def run(q, k, v):
            return jax.lax.fori_loop(0, n, lambda i, qq: full(qq, k, v), q)
        return run

    bench_chained(xla_fwd_loop, (q, k, v), args.n1, args.n2, args.trials,
                  "xla full fwd", fwd_flops)

    gfull = jax.grad(lambda *a: jnp.sum(full(*a).astype(jnp.float32)),
                     argnums=(0, 1, 2))

    def xla_grad_loop(n):
        @jax.jit
        def run(q, k, v):
            def body(i, qq):
                dq, _, _ = gfull(qq, k, v)
                return qq - (1e-3 * dq).astype(qq.dtype)
            return jax.lax.fori_loop(0, n, body, q)
        return run

    bench_chained(xla_grad_loop, (q, k, v), args.n1, args.n2, args.trials,
                  "xla full fwd+bwd", fwd_flops * 2 + bwd_flops)


if __name__ == "__main__":
    main()
