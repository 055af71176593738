#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process drives the main paths once, through the entry points a user
calls, at the full width of the flagship LM and of ResNet-50:

  kernels     the flash-attention forward and backward, compiled by
              Mosaic, against parallel.ring.full_attention at the training
              shape and at the serving prefill lengths; the forward and
              the backward on both sides of its rule timed under the
              profiler at the training cell's shape; the decode program's
              sampler alone at the two largest (rows, vocabulary) served,
              a greedy batch beside one with a sampling row
  lm_train    hvd.init -> build_mesh -> trainer.make_gspmd_step on
              gpt2_small_tpu (12 layers, batch 16 x seq 1024, flash):
              loss finite, falling, first step equal to full attention;
              then the same step under trainer.instrument_step
  resnet      bench_common.build_step("resnet50", mesh, 256, 224), the
              shard_map data-parallel path
  serve       ServeEngine (8 slots, max_len 1024, kv_block 16), twelve
              seeded requests: all complete, temperature-0 tokens equal
              to TransformerLM.apply's greedy choice
  four_chips  (when jax.device_count() >= 4) the LM step on dp=4 and on
              dp=2,tp=2 against the one-chip leg and against each other

Every leg prints one JSON line naming the device. A leg that fails raises,
and the run exits non-zero. There is no CPU mode: without a TPU the run
fails before the first leg. The last line of standard output is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--hvdrun`` is a second, separate invocation for a host with several
chips: a parent that never touches JAX launches ``hvdrun -np <chips>`` and
checks a one-chip-per-process eager allreduce (docs/tpus.md).

tests/test_chip_smoke.py runs the same leg functions on the CPU at
TransformerConfig.tiny with interpreted kernels.
"""

import argparse
import dataclasses
import functools
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_ROOT, "examples"))

LEGS = ("kernels", "lm_train", "resnet", "serve", "four_chips")

#: first-step loss, flash kernels against XLA full attention, same
#: weights and tokens (bf16 activations; measured 7e-6 on the chip)
FLASH_VS_FULL_RTOL = 1e-3
#: sharded against single-device first-step loss — the tolerance of
#: tests/test_mesh_plane.py (RTOL there)
MESH_RTOL = 5e-4
#: kernel outputs and gradients against full_attention, bf16 operands
KERNEL_ATOL = 3e-2
#: a served token may differ from the reference argmax only where the
#: reference itself is this close to a tie, in logit units (unit-variance
#: logits at random init). The engine's cached bf16 decode and a full bf16
#: forward round differently: on the chip 17 of 499 tokens differed, by at
#: most 0.032; a wrong cache row or position is off by order 1.
SERVE_TIE_TOL = 0.1
#: a v5e's HBM peak (benchmarks/peaks.json): what the new kernels' lines
#: state their time against; a statement, never a check
HBM_BYTES_PER_S = 819e9
#: and its bfloat16 peak, which the flash kernels' times stand beside
BF16_FLOPS = 197e12
# (share of served tokens that may miss the plain forward's choice, widest
# miss) of the ``laguna_small`` leg: set from its reading on the v5e
LAGUNA_SMALL_ROUTED = (0.08, 1.8)


def device_fields():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": len(jax.devices())}


def emit(leg, **fields):
    print(json.dumps({"leg": leg, **device_fields(), **fields}), flush=True)


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def leg_kernels(shapes, atol=KERNEL_ATOL, dtype=None):
    """Forward and backward of the flash kernels against
    parallel.ring.full_attention, one compile per shape."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring import full_attention

    dtype = dtype or jnp.bfloat16
    t0 = time.perf_counter()
    worst = {}
    for shape in shapes:
        rng = np.random.RandomState(sum(shape))
        qkvw = [jnp.asarray(rng.randn(*shape) * 0.5, dtype)
                for _ in range(4)]

        def out_and_grads(attend):
            """jitted (q, k, v, w) -> (out, dq, dk, dv) for sum(out * w)."""
            def run(q, k, v, w):
                def loss(q, k, v):
                    out = attend(q, k, v)
                    return jnp.sum(out.astype(jnp.float32) *
                                   w.astype(jnp.float32)), out
                (_, out), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
                return (out,) + grads
            return jax.jit(run)

        ref = out_and_grads(full_attention)(*qkvw)
        got = out_and_grads(functools.partial(
            flash_attention, causal=True))(*qkvw)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            _check(np.isfinite(a).all(), f"{name} at {shape}: not finite")
            err = float(np.max(np.abs(a - b)))
            _check(err <= atol, f"{name} at {shape}: max abs err "
                   f"{err:.4g} > {atol} against full_attention")
            worst[name] = max(worst.get(name, 0.0), err)
    emit("kernels", shapes=[list(s) for s in shapes], max_abs_err=worst,
         atol=atol, seconds=round(time.perf_counter() - t0, 2))


def profiled(fn, args, calls=4):
    """The profiler's trace (benchmarks/lib/xplane.py ``Trace``) of
    ``calls`` calls of the jitted ``fn(*args)``, after one that is not
    traced; no TPU plane, no events."""
    import tempfile

    import jax

    from benchmarks.lib import xplane

    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as tracedir:
        with jax.profiler.trace(tracedir):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        return xplane.load(xplane.find(tracedir))


def mosaic_ms(fn, args, calls=4):
    """{kernel: (ms, events) a call} of the Mosaic events one call of
    the jitted ``fn(*args)`` leaves on chip 0's ``XLA Ops`` line: the
    median of ``calls`` calls under the profiler. An event goes by its
    instruction's name: the kernel's own where the call has one
    (``flash_backward``), else the jitted function's, so the dQ and
    dK/dV kernels of one call are ONE key of two events. A kernel alone
    under the profiler and the same kernel inside a cell's traced step
    agree to 0.3% (docs/benchmarks.md, lesson 8). Empty where the trace
    has no TPU plane. Read with the benchmark's own reader of the
    profiler's file (benchmarks/lib/xplane.py)."""
    from benchmarks.lib import xplane

    took = {}
    for e in profiled(fn, args, calls).ops.get(0, []):
        if xplane.op_class(e.name) == "mosaic":
            name = re.match(r"%?([\w\-]+?)(\.\d+)? ", e.name + " ").group(1)
            took.setdefault(name, []).append(1e3 * (e.end - e.start))
    out = {}
    for name, ms in took.items():
        n = len(ms) // calls    # events a call
        out[name] = (float(np.median(
            [sum(ms[c * n:(c + 1) * n]) for c in range(calls)])), n)
    return out


def leg_flash_timing(bh=64, s=4096, d=128, block=512, on_chip=True):
    """The flash forward and the backward on BOTH sides of its rule
    (``flash_attention.bwd_one_pass``: one pass over a head's tiles, and
    the dQ and dK/dV kernels, reached here as the tests reach them, by
    taking the budget away) at the training cell's shape, causal bfloat16
    ``[bh, s, d]``: the gradients of the two sides against each other,
    and each kernel's ms a call under the profiler beside the MXU's own
    time for it (benchmarks/counts/baichuan.py: 2 and 5 products of
    ``bh * s^2 * d`` over the causal half, at 197 TFLOP/s)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(bh + s + d)
    q, k, v, g = (jnp.asarray(rng.randn(1, bh, s, d) * 0.5, jnp.bfloat16)
                  for _ in range(4))
    forward = jax.jit(lambda q, k, v: fa._flash_fwd(
        q, k, v, True, block, block, None, layout="bhsd"))
    out, lse = forward(q, k, v)

    def backward():
        # a fresh jit a side: the rule is read when the call is traced
        return jax.jit(lambda *a: fa._flash_bwd(
            *a, True, block, block, None, layout="bhsd"))

    _check(fa.bwd_one_pass(s, s, d, q.dtype),
           f"[{bh}, {s}, {d}] is past the one-pass backward's budget")
    sides = {"one_pass": backward()}
    grads = {"one_pass": sides["one_pass"](q, k, v, out, lse, g)}
    budget, fa._BWD_ONE_PASS_BYTES = fa._BWD_ONE_PASS_BYTES, 0
    try:
        sides["two_kernel"] = backward()
        grads["two_kernel"] = sides["two_kernel"](q, k, v, out, lse, g)
    finally:
        fa._BWD_ONE_PASS_BYTES = budget
    apart = {}
    for name, a, b in zip(("dq", "dk", "dv"), *grads.values()):
        _check(bool(jnp.isfinite(a.astype(jnp.float32)).all()),
               f"{name}: not finite")
        apart[name] = float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))
        _check(apart[name] <= KERNEL_ATOL,
               f"{name}: one pass and two kernels {apart[name]:.4g} apart")
    fields = dict(shape=[bh, s, d], block=block, max_abs_apart=apart)
    if on_chip:
        took = {"forward": mosaic_ms(forward, (q, k, v))}
        took.update((name, mosaic_ms(fn, (q, k, v, out, lse, g)))
                    for name, fn in sides.items())
        events = {side: {k: n for k, (_, n) in by.items()}
                  for side, by in took.items()}
        _check(events["one_pass"] == {"flash_backward": 1}
               and "flash_backward" not in events["two_kernel"]
               and sum(events["two_kernel"].values()) == 2,
               f"the sides' Mosaic events a call are {events}")
        # products of bh * s^2 * d a side has to make, over the peak
        least = {name: 1e3 * n * bh * s * s * d / BF16_FLOPS
                 for name, n in (("forward", 2), ("one_pass", 5),
                                 ("two_kernel", 5))}
        ms = {side: sum(t for t, _ in by.values())
              for side, by in took.items()}
        fields.update(kernel_ms=ms, least_ms=least, roofline_share={
            side: round(least[side] / ms[side], 4) for side in ms})
    emit("flash_timing", **fields)


def sampler_ms(fn, args, rows, vocab, calls=4):
    """What one call of the jitted sampler ``fn(*args)`` takes on chip 0,
    ms: ``call`` (the median of its ``XLA Modules`` events: everything the
    chip does for it), and of its ``XLA Ops`` events those over the
    ``f32[rows, vocab]`` logits, a call: ``draw`` (the one that also reads
    the threefry counters, ``u32[rows]``: bits, Gumbel noise and an
    argmax) and ``argmax`` (the logits alone). Names and operands as the
    decode program's own trace has them
    (benchmarks/fixtures/phi4flash_events_v5e.json)."""
    from benchmarks.lib import xplane

    trace = profiled(fn, args, calls)
    took = {"draw": 0.0, "argmax": 0.0}
    for e in trace.ops.get(0, []):
        shapes = xplane.shapes(e.name)
        # a reduction over the logits: it reads them and makes [rows]
        if xplane.opcode(e.name) == "fusion" and \
                ("f32", (rows, vocab)) in shapes[1:] and \
                shapes[0][1] == (rows,):
            took["draw" if ("u32", (rows,)) in shapes else "argmax"] += \
                1e3 * (e.end - e.start) / calls
    took["call"] = float(np.median(
        [1e3 * (m.end - m.start) for m in trace.modules.get(0, [])]))
    return took


def leg_sampler_timing(shapes=((96, 200064), (64, 154880)), temperature=0.7,
                       on_chip=True):
    """``serving.sampling.sample_tokens`` alone at the serving cells'
    (rows, vocabulary): every row greedy beside one row at ``temperature``.
    A greedy batch is served its argmax and runs NO event of the draw;
    with one sampling row the draw runs whole and the greedy rows are
    still served their argmax. ms a call of each under the profiler.
    (``call`` holds a copy the decode program does not make: this lone
    program is handed its logits in HBM and moves them into VMEM for the
    conditional's operand, 97 us at 96 x 200,064, whichever branch runs;
    the decode program's head writes them there. PERF.md §6, PR 51.)"""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.serving.sampling import sample_tokens

    sampler = jax.jit(sample_tokens)
    cases = []
    for rows, vocab in shapes:
        logits = jax.random.normal(jax.random.PRNGKey(vocab), (rows, vocab),
                                   jnp.float32)
        rng = jax.random.PRNGKey(rows)
        batches = {"greedy": jnp.zeros(rows, jnp.float32),
                   "one_row": jnp.zeros(rows, jnp.float32).at[0].set(
                       temperature)}
        top = np.asarray(jnp.argmax(logits, axis=-1))
        case = {"shape": [rows, vocab]}
        for name, temps in batches.items():
            ids = np.asarray(sampler(rng, logits, temps))
            keep = np.asarray(temps) <= 0.0
            _check((ids[keep] == top[keep]).all(),
                   f"{name} at {rows} x {vocab}: a greedy row was not "
                   f"served its argmax")
            if on_chip:
                case[name] = {k: round(v, 4) for k, v in sampler_ms(
                    sampler, (rng, logits, temps), rows, vocab).items()}
        if on_chip:
            _check(case["greedy"]["draw"] == 0.0 < case["one_row"]["draw"]
                   and case["greedy"]["argmax"] > 0.0,
                   f"the sampler's events at {rows} x {vocab}: {case}")
            _check(case["greedy"]["call"] < case["one_row"]["call"],
                   f"a greedy batch took no less than one that draws: "
                   f"{case}")
        cases.append(case)
    emit("sampler_timing", temperature=temperature, cases=cases)


def leg_window_kernel(cases, atol=KERNEL_ATOL, dtype=None):
    """The banded forward (``window_attention``: a window layer's prefill)
    against dense attention under the band mask, computed in float32 from
    the same operands; ``cases`` are (batch, seq, heads, kv_heads,
    head_dim, window)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    dtype = dtype or jnp.bfloat16
    t0 = time.perf_counter()
    worst = 0.0
    for b, s, h, hk, d, window in cases:
        rng = np.random.RandomState(s + h + window)
        q = jnp.asarray(rng.randn(b, s, h, d) * 0.5, dtype)
        k, v = (jnp.asarray(rng.randn(b, s, hk, d) * 0.5, dtype)
                for _ in range(2))

        def dense(q, k, v):
            kf, vf = (jnp.repeat(t.astype(jnp.float32), h // hk, axis=2)
                      for t in (k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                                kf) * d ** -0.5
            gap = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
            scores = jnp.where((gap >= 0) & (gap < window), scores, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, axis=-1), vf)
        got = np.asarray(jax.jit(functools.partial(
            fa.window_attention, window=window))(q, k, v).astype(jnp.float32))
        _check(np.isfinite(got).all(), f"window_attention at "
               f"{(b, s, h, hk, d, window)}: not finite")
        err = float(np.max(np.abs(got - np.asarray(jax.jit(dense)(q, k, v)))))
        _check(err <= atol, f"window_attention at {(b, s, h, hk, d, window)}"
               f": max abs err {err:.4g} > {atol} against masked attention")
        worst = max(worst, err)
    emit("window_kernel", cases=[list(c) for c in cases], max_abs_err=worst,
         atol=atol, seconds=round(time.perf_counter() - t0, 2))


def grouped_product(assignments, stack_shape, dtype):
    """What computes a grouped SwiGLU of ``assignments`` rows over stacks
    of ``stack_shape`` ``[E, d, f]``: this repo's Mosaic kernel with the
    rows ``resident`` in VMEM or ``streamed`` through it from HBM
    (ops/grouped_matmul.py decides from the call: never on the CPU, over
    a mesh, for float32 or widths that are no whole lane tiles), or
    ``ragged_dot``."""
    import jax.numpy as jnp

    from horovod_tpu.ops import grouped_matmul
    if not grouped_matmul.selected(assignments, stack_shape, dtype):
        return "ragged_dot"
    return "resident" if grouped_matmul.resident(
        assignments, stack_shape[1], jnp.dtype(dtype).itemsize) \
        else "streamed"


def leg_grouped_kernel(cases, atol=KERNEL_ATOL, dtype=None, products=None):
    """The routed experts' grouped SwiGLU (``models/moe.experts``, which
    picks the product from the call) against a plain loop over the experts
    in float32 from the same operands; ``cases`` are (tokens, experts per
    token, experts, hidden width, expert width, real tokens: the rest are
    padding under the mask). ``products``: what each case has to run
    (``grouped_product``), for a case chosen to drive one path."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import moe

    dtype = dtype or jnp.bfloat16
    t0 = time.perf_counter()
    worst = 0.0
    ran = [grouped_product(t * k, (num, d, f), dtype)
           for t, k, num, d, f, _ in cases]
    _check(products is None or ran == list(products),
           f"grouped products {ran}, wanted {products}")
    for t, k, num, d, f, real in cases:
        keys = jax.random.split(jax.random.PRNGKey(t + num), 5)
        y = jax.random.normal(keys[0], (t, d), jnp.float32).astype(dtype)
        gate, up, down = (
            (jax.random.normal(key, shape, jnp.float32)
             / shape[1] ** 0.5).astype(dtype)
            for key, shape in zip(keys[1:4], ((num, d, f), (num, d, f),
                                              (num, f, d))))
        idx, weights = moe.route(
            y, jax.random.normal(keys[4], (d, num), jnp.float32), None, k)
        mask = jnp.arange(t) < real

        def loop(y, idx, weights, gate, up, down, mask):
            y = y.astype(jnp.float32)
            share = jnp.sum(jax.nn.one_hot(idx, num) * weights[..., None],
                            axis=1) * mask[:, None]           # [t, E]

            def one(e, out):
                g, u, w = (a[e].astype(jnp.float32) for a in (gate, up, down))
                return out + share[:, e, None] * (
                    (jax.nn.silu(y @ g) * (y @ u)) @ w)
            return jax.lax.fori_loop(0, num, one, jnp.zeros_like(y))
        args = (y, idx, weights, gate, up, down, mask)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(loop)(*args))
        got, load = jax.jit(lambda *a: moe.experts(*a))(*args)
        got = np.asarray(got.astype(jnp.float32))
        case = (t, k, num, d, f, real)
        _check(int(np.asarray(load).sum()) == real * k,
               f"experts at {case}: {int(np.asarray(load).sum())} "
               f"assignments, wanted {real * k}")
        _check(np.isfinite(got).all() and not got[real:].any(),
               f"experts at {case}: not finite, or a padding token got "
               f"an expert")
        err = float(np.max(np.abs(got - want)))
        _check(err <= atol, f"experts at {case}: max abs err {err:.4g} > "
               f"{atol} against the loop over the experts")
        worst = max(worst, err)
    emit("grouped_kernel", cases=[list(c) for c in cases], products=ran,
         max_abs_err=worst, atol=atol,
         seconds=round(time.perf_counter() - t0, 2))


# ---------------------------------------------------------------------------
# LM train
# ---------------------------------------------------------------------------

def mosaic_kernel_counts(lowered):
    """{kernel name: count} of the Mosaic custom calls in a lowered step —
    empty when the kernels were interpreted (plain HLO, no custom call)."""
    names = re.findall(r'kernel_name\s*=\s*"([^"]+)"', lowered.as_text())
    return {n: names.count(n) for n in sorted(set(names))}


def _lm_setup(mesh, batch, seq, cfg):
    import bench_common
    return bench_common.build_transformer_step(mesh, batch, seq, cfg=cfg)


def _run_steps(step, params, opt_state, toks, n):
    import jax
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, toks)
        jax.block_until_ready(loss)
        secs.append(time.perf_counter() - t0)
        losses.append(float(loss))
    return params, opt_state, losses, secs


def leg_lm_train(cfg, batch, seq, steps=4, on_chip=True):
    """The flagship recipe (examples/bench_common.build_transformer_step)
    on one chip; returns the first-step loss for the four-chip leg."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import trainer
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.ops import flash_attention as flash_mod
    from horovod_tpu.parallel import mesh as mesh_mod
    from horovod_tpu.utils import costmodel
    from horovod_tpu.utils import history as hvd_history
    from horovod_tpu.utils import memory as hvd_memory
    from horovod_tpu.utils import metrics as hvd_metrics
    from horovod_tpu.utils import tracing as hvd_tracing

    hvd.init()
    mesh = mesh_mod.build_mesh(dp=1, devices=jax.devices()[:1])
    step, params, opt_state, toks, cfg = _lm_setup(mesh, batch, seq, cfg)

    # the same first step with XLA's full attention on the same weights:
    # forward only (the step's first loss IS the loss at these weights)
    full = tr.TransformerLM(dataclasses.replace(cfg, attention_impl="full"))
    ref_loss = float(jax.jit(tr.lm_loss_fn(full))(params, toks))

    kernels = mosaic_kernel_counts(step.lower(params, opt_state, toks))
    if on_chip:
        # a layer is one forward and the backward its shapes choose: one
        # pass over the head's tiles, or the dQ and dK/dV kernels
        head_dim = cfg.d_model // cfg.num_heads
        backward = ["flash_backward"] if flash_mod.bwd_one_pass(
            seq, seq, -(-head_dim // 128) * 128, cfg.dtype) \
            else ["_dq_kernel", "_dkv_kernel"]
        want = {k: cfg.num_layers for k in ["_fwd_kernel"] + backward}
        _check(kernels == want,
               f"lowered step's Mosaic calls are {kernels}, not {want} — "
               f"the kernels were interpreted")

    t0 = time.perf_counter()
    params, opt_state, first, _ = _run_steps(step, params, opt_state, toks, 1)
    setup_s = time.perf_counter() - t0
    params, opt_state, rest, secs = _run_steps(step, params, opt_state, toks,
                                               steps - 1)
    losses = first + rest
    _check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"loss not falling: {losses}")
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    _check(rel <= FLASH_VS_FULL_RTOL,
           f"first-step loss {losses[0]} differs from full attention's "
           f"{ref_loss} by {rel:.3g} > {FLASH_VS_FULL_RTOL}")

    # the step as users run it: every observability plane on the device
    name = "chip_smoke"
    inst = trainer.instrument_step(
        step, tokens_per_step=batch * seq, name=name,
        flops_per_token=tr.matmul_flops_per_token(cfg, seq))
    n_inst = 3
    params, opt_state, inst_losses, _ = _run_steps(inst, params, opt_state,
                                                   toks, n_inst)
    _check(all(np.isfinite(inst_losses)), f"loss not finite: {inst_losses}")
    _check(step._cache_size() == 1,
           f"{step._cache_size()} compiles of the step, expected exactly 1")
    site = hvd_memory.get_tracker().site_summary()[f"train:{name}"]
    _check(site["misses"] == 1 and site["hits"] == n_inst - 1,
           f"compile tracker saw {site}")
    metrics = hvd_metrics.get_registry().snapshot()["metrics"]

    def gauge(metric):
        for val in metrics.get(metric, {"values": []})["values"]:
            if val["labels"].get("loop") == name:
                return val["value"]
        return None

    planes = {"steps_total": gauge("hvd_steps_total"),
              "tokens_per_second": gauge("hvd_tokens_per_second")}
    _check(planes["steps_total"] == n_inst, f"hvd_steps_total: {planes}")
    _check(any(s["stage"] == hvd_tracing.STEP
               for s in hvd_tracing.get_tracer().spans()),
           "tracer recorded no step span")
    hvd_history.flush()
    manifest = hvd_history.load_manifest(hvd_history.history_dir()) or {}
    recorded = (manifest.get("provenance") or manifest).get("device_kind")
    _check(recorded == jax.devices()[0].device_kind,
           f"history manifest names device_kind {recorded!r}")
    spec = costmodel.chip_spec(jax.devices()[0])
    if on_chip:  # a real chip: its kind is known and the gauges live
        _check(spec is not None and spec.kind != "cpu",
               f"costmodel knows no chip {jax.devices()[0].device_kind!r}")
        planes["mfu"] = gauge("hvd_mfu")
        planes["peak_hbm_bytes"] = gauge("hvd_step_peak_hbm_bytes")
        _check(planes["mfu"] is not None and 0.0 < planes["mfu"] < 1.0,
               f"hvd_mfu gauge: {planes}")
        _check(planes["peak_hbm_bytes"] and planes["peak_hbm_bytes"] > 0,
               f"hvd_step_peak_hbm_bytes gauge: {planes}")
    emit("lm_train", model="gpt2_small_tpu", layers=cfg.num_layers,
         d_model=cfg.d_model, heads=cfg.num_heads, vocab=cfg.vocab_size,
         batch=batch, seq=seq, attention=cfg.attention_impl,
         mosaic_kernels=kernels, losses=[round(x, 5) for x in losses],
         full_attention_first_loss=round(ref_loss, 5),
         flash_vs_full_rel=float(f"{rel:.3g}"),
         setup_seconds=round(setup_s, 2),
         step_seconds=round(float(np.median(secs)), 4),
         compiles=step._cache_size(), instrumented=planes,
         chip_spec=spec.kind if spec else None)
    return losses[0]


# ---------------------------------------------------------------------------
# ResNet-50 train
# ---------------------------------------------------------------------------

def leg_resnet(model="resnet50", batch=256, image_size=224, steps=3):
    import jax

    import bench_common
    import horovod_tpu as hvd

    hvd.init()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]),
                             hvd.mesh().axis_names[:1])
    step, params, opt_state, data = bench_common.build_step(
        model, mesh, batch, image_size)
    t0 = time.perf_counter()
    params, opt_state, first, _ = _run_steps(step, params, opt_state, data, 1)
    setup_s = time.perf_counter() - t0
    params, opt_state, rest, secs = _run_steps(step, params, opt_state, data,
                                               steps - 1)
    losses = first + rest
    _check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    _check(step._cache_size() == 1,
           f"{step._cache_size()} compiles of the step, expected exactly 1")
    emit("resnet", model=model, batch=batch, image_size=image_size,
         losses=[round(x, 5) for x in losses],
         setup_seconds=round(setup_s, 2),
         step_seconds=round(float(np.median(secs)), 4))


# ---------------------------------------------------------------------------
# LM serve
# ---------------------------------------------------------------------------

def _serve_requests(vocab, lengths, max_len, seed=0):
    from horovod_tpu.serving.queue import Request
    rng = np.random.RandomState(seed)
    reqs = []
    for i, plen in enumerate(lengths):
        new = int(min(rng.randint(16, 65), max_len - plen + 1))
        prompt = tuple(int(t) for t in rng.randint(1, vocab, plen))
        reqs.append(Request(f"smoke-{i}", prompt, max_new_tokens=new))
    return reqs


def _served_model(cfg):
    """(params, rows) of a served configuration of either family: seeded
    parameters, and the plain reference ``rows(params, seq [max_len], at
    [k]) -> float32 logits [k, vocab]`` of a full-attention forward over
    ``seq`` at the positions ``at`` — TransformerLM.apply, for a
    model with a recurrent mixer its prefill (the chunked scan, no cache),
    once for each position, and for a looped stack its plain forward over
    every pass, for latent attention with experts its plain (expanded)
    forward, for window and full layers with experts its plain forward
    (whole sequences under the band mask, no ring), for the
    decoder-hybrid-decoder its plain forward (every layer at every
    position, no cache, no short-cut)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import (hybrid, latent_moe, looped, sambay,
                                    window_moe)
    from horovod_tpu.models import transformer as tr

    ref_cfg = dataclasses.replace(cfg, attention_impl="full")
    if isinstance(cfg, hybrid.HybridConfig):
        params = hybrid.init_params(cfg, jax.random.PRNGKey(0))
        rows = jax.jit(lambda p, seq, at: jax.vmap(
            lambda i: hybrid.prefill(ref_cfg, p, seq[None], i)[0][0])(at))
    elif isinstance(cfg, looped.LoopedConfig):
        params = looped.init_params(cfg, jax.random.PRNGKey(0))
        rows = jax.jit(lambda p, seq, at: looped.forward(
            ref_cfg, p, seq[None])[0][0, at].astype(jnp.float32))
    elif isinstance(cfg, latent_moe.LatentMoEConfig):
        params = latent_moe.init_params(cfg, jax.random.PRNGKey(0))
        rows = jax.jit(lambda p, seq, at: latent_moe.forward(
            ref_cfg, p, seq[None])[0][0, at].astype(jnp.float32))
    elif isinstance(cfg, window_moe.WindowMoEConfig):
        params = window_moe.init_params(cfg, jax.random.PRNGKey(0))
        rows = jax.jit(lambda p, seq, at: window_moe.forward(
            ref_cfg, p, seq[None])[0][0, at].astype(jnp.float32))
    elif isinstance(cfg, sambay.SambaYConfig):
        # served in bfloat16 as every other leg's model
        params = jax.tree_util.tree_map(
            lambda a: a.astype(cfg.dtype),
            sambay.init_params(cfg, jax.random.PRNGKey(0)))
        rows = jax.jit(lambda p, seq, at: sambay.forward(
            ref_cfg, p, seq[None])[0, at].astype(jnp.float32))
    else:
        _, params = tr.init_params(cfg, jax.random.PRNGKey(0))
        ref_model = tr.TransformerLM(ref_cfg)
        rows = jax.jit(lambda p, seq, at: ref_model.apply(
            {"params": p}, seq[None])[0, at].astype(jnp.float32))
    return jax.device_put(params, jax.devices()[0]), rows


def decode_attention_selected(cfg, slots, max_len):
    """Which decode attention this backend runs over a cache of ``slots``
    x ``max_len``: the positional kinds the model declares, and whether
    the Mosaic kernel that reads them by length and in place was selected
    (ops/flash_attention.py decides from the call; False on the CPU, over
    a mesh, and for a cache whose tiles it cannot read as they lie)."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.serving import decode as serve_decode
    shapes = serve_decode.state_shapes(cfg, slots, max_len)
    kinds = [k for k in serve_decode.positional_kinds(cfg) if k in shapes]
    if kinds == ["latent"]:
        kernel = fa._latent_kernel_selected(shapes["latent"].shape,
                                            cfg.kv_rank)
    elif hasattr(cfg, "lanes"):  # rows of packed heads (models/sambay.py)
        kernel = all(fa._packed_kernel_selected(shapes[k].shape, cfg.lanes)
                     for k in kinds)
    else:  # every class of K/V the model keeps: full rows, and rings
        kernel = all(fa._decode_kernel_selected(shapes[k].shape, None)
                     for k in kinds)
    return {"kinds": kinds, "kernel": bool(kernel)}


def grouped_experts_selected(cfg, slots, prefill_lengths=()):
    """Which grouped product a model with routed experts runs
    (``grouped_product``): ``kernel`` for a decode pass over ``slots``
    rows, and under ``prefill`` what each padded prompt length of
    ``prefill_lengths`` runs, so that the line says whether ANY program of
    the model still takes ``jax.lax.ragged_dot``. None for a model without
    experts."""
    from horovod_tpu.models import latent_moe, window_moe
    if not isinstance(cfg, (latent_moe.LatentMoEConfig,
                            window_moe.WindowMoEConfig)) or \
            not cfg.expert_layers:
        return None

    def product(tokens):
        return grouped_product(
            tokens * cfg.experts_per_tok,
            (cfg.num_experts, cfg.d_model, cfg.d_expert), cfg.dtype)
    return {"kernel": product(slots) != "ragged_dot",
            "prefill": {str(s): product(s)
                        for s in sorted(set(prefill_lengths))}}


def laguna_small_config():
    """The ``laguna_small`` leg's model: window (128) and full attention
    layers with 6 and 8 query heads over 2 key/value heads in TWO classes
    of cache (rows of 1,024, rings of 128 + 128), a per-head gate, partial
    YaRN rotary on the full layers, 16 experts of which 4, one shared,
    behind a dense first layer: at widths the decode kernel (both classes,
    both groups), the banded and the flash forward and the grouped product
    take."""
    from horovod_tpu.models import window_moe
    return window_moe.WindowMoEConfig(
        vocab_size=4096, d_model=512, head_dim=128, num_kv_heads=2,
        layer_types=("full", "window", "window", "window", "full"),
        heads_per_layer=(6, 8, 8, 8, 6), window=128,
        rope_full=window_moe.Rotary(
            theta=500000.0, fraction=0.5, factor=8.0, original_len=256,
            beta_fast=32.0, beta_slow=1.0,
            attention_factor=0.1 * math.log(8.0) + 1.0),
        rope_window=window_moe.Rotary(theta=10000.0), d_ff=1024,
        first_dense=1, num_experts=16, experts_per_tok=4, d_expert=256,
        d_shared=256, route_scale=2.5, max_seq_len=1024,
        attention_impl="flash")


def phi4flash_small_config():
    """The ``phi4flash_small`` leg's model: Phi-4-mini-flash's published
    WIDTHS (hidden 2,560, 40 query and 20 key/value heads of 64, SwiGLU
    10,240, window 512, a state of 16 over 5,120 channels) at 8 layers, one
    period of each kind (mamba, window, mamba, window, mamba, full, gmu,
    cross), and a vocabulary of 32,768: the packed decode kernel over the
    one plane (two readers) and two rings of 512 + 128, the banded and the
    flash forward at scale 1/8, the scan and the state's update."""
    from horovod_tpu.models import sambay
    return sambay.SambaYConfig(
        vocab_size=32768, num_layers=8, d_model=2560, d_ff=10240,
        num_heads=40, num_kv_heads=20, window=512, d_state=16, d_conv=4,
        expand=2, dt_rank=160, max_seq_len=1024, attention_impl="flash")


def leg_packed_kernel(cases, atol=KERNEL_ATOL):
    """``packed_decode_attention`` (the Mosaic kernel: selected here or the
    leg fails) against the two-softmax definition at the cell's shapes:
    ``cases`` of (planes, rows, s_max, pairs, lengths): every plain head's
    softmax over its own 64 lanes of the pair's key, the pair's whole
    value, in float32 over the same bfloat16 cache. Says what the kernel
    took and what that is of the HBM peak for the live tokens."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa
    out = []
    for planes, b, s_max, pairs, lengths in cases:
        shape = (planes, b, s_max, 1, pairs * 128)
        _check(fa._packed_kernel_selected(shape),
               f"the packed decode kernel is not selected at {shape}")
        rng = np.random.RandomState(s_max + b)
        k = jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)
        v = jnp.asarray(rng.randn(*shape) * 0.5, jnp.bfloat16)
        # [pair, differential head, (q1, q2), half of the lanes, 64]
        q = rng.randn(b, pairs, 2, 2, 2, 64)
        q[:, :, :, 0, 1] = 0.0                  # [q1|0]
        q[:, :, :, 1, 0] = 0.0                  # [0|q2]
        q = jnp.asarray(q.reshape(b, pairs * 4, 128), jnp.bfloat16)
        n = jnp.asarray(lengths, jnp.int32)
        plane = planes - 1

        def definition(q, k, v, n):
            f32 = jnp.float32
            kp = k[plane].reshape(b, s_max, pairs, 2, 64).astype(f32)
            vp = v[plane].reshape(b, s_max, pairs, 128).astype(f32)
            qh = q.reshape(b, pairs, 2, 2, 2, 64).astype(f32)
            valid = (jnp.arange(s_max)[None, :] < n[:, None])
            outs = []
            for j in (0, 1):  # the head's own half of the pair's key
                logits = jnp.einsum("bphd,bspd->bphs", qh[:, :, :, j, j],
                                    kp[:, :, :, j], precision="highest") / 8
                p = jax.nn.softmax(jnp.where(valid[:, None, None], logits,
                                             -jnp.inf), axis=-1)
                outs.append(jnp.einsum("bphs,bspd->bphd", p, vp,
                                       precision="highest"))
            return jnp.stack(outs, axis=3).reshape(b, pairs * 4, 128)
        kernel = jax.jit(lambda q, k, v, n: fa.packed_decode_attention(
            q, k, v, n, plane, 0.125))
        got = np.asarray(kernel(q, k, v, n))
        want = np.asarray(jax.jit(definition)(q, k, v, n))
        live = np.asarray(lengths) > 0
        err = float(np.abs(got[live] - want[live]).max())
        _check(np.isfinite(got).all() and err <= atol,
               f"packed decode kernel at {shape}: max abs err {err:.4g} > "
               f"{atol} against the two-softmax definition")
        _check(not got[~live].any(), "a row of length 0 read something")
        t0 = time.perf_counter()
        for _ in range(10):
            last = kernel(q, k, v, n)
        last.block_until_ready()
        took = (time.perf_counter() - t0) / 10
        need = 2 * pairs * 128 * 2 * float(np.sum(lengths))
        out.append({"cache": list(shape), "max_abs_err": round(err, 5),
                    "kernel_ms": round(took * 1e3, 3),
                    "hbm_roofline_pct": round(
                        100 * need / HBM_BYTES_PER_S / took, 1)})
    emit("packed_decode_kernel", cases=out, atol=atol)


def leg_mamba1(channels=5120, states=16, rows=96, planes=9, lengths=(256,)):
    """The Mamba-1 recurrence at the published state (16 x 5,120 a row and
    layer): the prefill scan against the literal position-by-position
    definition over a right-padded prompt, and the decode step's update in
    place in the stacked state against ``state_step``, a masked row bit
    for bit. float32 on both sides: 1e-5. Says what each took."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import mamba1
    rng = np.random.RandomState(0)
    a = -jnp.broadcast_to(jnp.arange(1, states + 1, dtype=jnp.float32)
                          [:, None], (states, channels))
    scans = []
    for s in lengths:
        x = jnp.asarray(rng.randn(1, s, channels), jnp.bfloat16)
        dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                            (1, s, channels))), jnp.float32)
        dt = dt.at[:, s - 9:].set(0.0)       # a right-padded prompt
        b, c = (jnp.asarray(rng.randn(1, s, states), jnp.bfloat16)
                for _ in range(2))
        scan = jax.jit(mamba1.selective_scan)
        y, last = scan(x, dt, a, b, c)
        head = 48                            # the literal loop, unrolled
        want_y, _ = jax.jit(mamba1.literal_scan)(
            x[:, :head], dt[:, :head], a, b[:, :head], c[:, :head])
        err = float(jnp.abs(y[:, :head] - want_y).max())
        _, before = scan(x[:, :s - 9], dt[:, :s - 9], a, b[:, :s - 9],
                         c[:, :s - 9])
        held = float(jnp.abs(last - before).max())
        _check(err <= 1e-5 and held <= 1e-5,
               f"the scan at {s}: {err:.3g} from the literal definition, "
               f"{held:.3g} moved over the pad")
        t0 = time.perf_counter()
        for _ in range(5):
            y, last = scan(x, dt, a, b, c)
        last.block_until_ready()
        scans.append({"positions": s, "max_abs_err": err,
                      "ms": round((time.perf_counter() - t0) / 5 * 1e3, 3)})
    ssm = jnp.asarray(rng.randn(planes, rows, states, channels), jnp.float32)
    x = jnp.asarray(rng.randn(rows, channels), jnp.bfloat16)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                        (rows, channels))), jnp.float32)
    b, c = (jnp.asarray(rng.randn(rows, states), jnp.bfloat16)
            for _ in range(2))
    mask = jnp.arange(rows) != 3
    want, want_y = mamba1.state_step(ssm[2], x, dt, a, b, c)
    kept = np.asarray(ssm[2, 3])
    update = jax.jit(lambda ssm, *t: mamba1.decode_update(ssm, 2, *t),
                     donate_argnums=0)
    ssm, y = update(ssm, x, dt, a, b, c, mask)
    err = float(jnp.abs(jnp.where(mask[:, None, None], ssm[2] - want,
                                  0.0)).max())
    err = max(err, float(jnp.abs(jnp.where(mask[:, None], y - want_y,
                                           0.0)).max()))
    _check(err <= 1e-5, f"the state's update: {err:.3g} from state_step")
    _check((np.asarray(ssm[2, 3]) == kept).all(),
           "a row outside the mask did not keep its state bit for bit")
    t0 = time.perf_counter()
    for _ in range(10):
        ssm, y = update(ssm, x, dt, a, b, c, mask)
    ssm.block_until_ready()
    took = (time.perf_counter() - t0) / 10
    emit("mamba1", scans=scans, update={
        "state": [planes, rows, states, channels], "max_abs_err": err,
        "ms": round(took * 1e3, 3), "hbm_roofline_pct": round(
            100 * 2 * (rows - 1) * states * channels * 4
            / HBM_BYTES_PER_S / took, 1)})


def leg_serve(cfg, slots=8, max_len=1024, kv_block=16,
              lengths=(5, 16, 100, 513, 1000, 7, 33, 250, 640, 90, 12, 400),
              tie_tol=SERVE_TIE_TOL, name="gpt2_small_tpu",
              routed_elsewhere=(0.0, 0.0)):
    """Two passes of the same seeded requests through ServeEngine — the
    first pays every compile under a patient queue, the second runs warm
    behind the default admission queue — then a teacher-forced check:
    each served token must be the plain forward's argmax given the tokens
    before it (so a plain greedy decode yields the same sequence) or tie
    with it within ``tie_tol``. ``cfg`` is a TransformerConfig, a
    HybridConfig, a LoopedConfig, a LatentMoEConfig or a WindowMoEConfig
    (``_served_model``).
    The line says which decode attention ran (``decode_attention``) and,
    for a model with experts, which grouped product in the decode pass and
    in the prefill of each padded length (``experts``).
    ``routed_elsewhere`` (share, deficit): routing is discrete, and two
    bfloat16 paths of one model with experts (the expanded plain forward,
    the served prefill and absorbed decode) round a token's router scores
    differently, so at a near tie they give it another expert; at most
    ``share`` of the served tokens may miss the plain forward's choice by
    more than ``tie_tol``, none by more than ``deficit``. (0, 0) for a
    model without experts."""
    import jax.numpy as jnp

    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    params, reference_rows = _served_model(cfg)

    def one_pass(queue):
        eng = engine_mod.ServeEngine(cfg, params, num_slots=slots,
                                     max_len=max_len, kv_block=kv_block,
                                     queue=queue, seed=0)
        reqs = _serve_requests(cfg.vocab_size, lengths, max_len)
        t0 = time.perf_counter()
        for r in reqs:
            _check(eng.submit(r), f"{r.request_id} refused at submit")
        results = {r.request_id: r for r in eng.run_to_completion()}
        dt = time.perf_counter() - t0
        _check(len(results) == len(reqs),
               f"{len(reqs) - len(results)} of {len(reqs)} requests never "
               f"came back (rejected in the queue)")
        bad = {k: (r.outcome, r.reason) for k, r in results.items()
               if r.outcome != "completed"}
        _check(not bad, f"requests not completed: {bad}")
        for r in reqs:
            _check(len(results[r.request_id].tokens) == r.max_new_tokens,
                   f"{r.request_id}: {len(results[r.request_id].tokens)} "
                   f"tokens, wanted {r.max_new_tokens}")
        return reqs, results, dt

    reqs, cold, cold_s = one_pass(AdmissionQueue(admission_timeout_s=900.0))
    compiled = (engine_mod._prefill_jit._cache_size(),
                engine_mod._decode_jit._cache_size())
    _, warm, warm_s = one_pass(None)
    _check((engine_mod._prefill_jit._cache_size(),
            engine_mod._decode_jit._cache_size()) == compiled,
           "the warm pass compiled")
    _check(all(warm[k].tokens == cold[k].tokens for k in cold),
           "the two passes served different tokens")
    # no silent copying path either: both engines' cache-writing
    # programs consumed the arrays they were given (docs/serving.md)
    from horovod_tpu.utils import metrics as hvd_metrics
    reg = hvd_metrics.get_registry()
    kv_in_place = (reg.gauge("hvd_serve_kv_in_place").value
                   if reg.enabled else None)  # None: HVD_METRICS=0
    _check(kv_in_place != 0,
           "the KV cache is copied every step, not updated in place "
           "(a donation was dropped)")
    # ...and no step that waits for the host where nothing waits for the
    # step: with more requests than slots every slot stays busy for most
    # of a pass, and those steps return with their decode pass in flight
    steps_ahead = (reg.counter("hvd_serve_steps_ahead_total").value
                   if reg.enabled else None)
    _check(steps_ahead != 0 or len(lengths) <= slots,
           "every slot was busy and no step ran ahead: each decode pass "
           "waited for the host to read the one before")
    # ...nor an admission that keeps the chip waiting: a request that joins
    # a batch that decodes has its first token read after the step's pass
    # is launched, and with more requests than slots some have to
    admissions_ahead = (reg.counter("hvd_serve_admissions_ahead_total").value
                        if reg.enabled else None)
    _check(admissions_ahead != 0 or len(lengths) <= slots,
           "requests joined a decoding batch and no first token was read "
           "behind the step's decode launch: each admission held the chip "
           "until the host had read and booked it")

    # reference: a full-attention forward over prompt + served tokens
    most = max(r.max_new_tokens for r in reqs)
    exact = ties = total = 0
    worst = 0.0
    flip_share, flip_tol = routed_elsewhere
    for r in reqs:
        served = cold[r.request_id].tokens
        seq = np.zeros(max_len, np.int32)
        seq[:len(r.prompt) + len(served) - 1] = \
            (list(r.prompt) + list(served))[:-1]
        at = np.minimum(len(r.prompt) - 1 + np.arange(most), max_len - 1)
        logits = np.asarray(reference_rows(params, jnp.asarray(seq),
                                           jnp.asarray(at)))
        for j, tok in enumerate(served):
            row = logits[j]
            deficit = float(row.max() - row[tok])
            total += 1
            exact += int(tok == int(row.argmax()))
            ties += int(tok != int(row.argmax()) and deficit <= tie_tol)
            worst = max(worst, deficit)
    missed = total - exact - ties
    _check(missed <= flip_share * total and
           (not missed or worst <= flip_tol),
           f"{missed} of {total} served tokens are not the reference's "
           f"greedy choice (worst logit deficit {worst:.4g} > {tie_tol}; "
           f"allowed: {flip_share:.1%} of them, each within {flip_tol})")
    emit("serve", model=name, layers=cfg.num_layers,
         slots=slots, max_len=max_len, kv_block=kv_block,
         decode_attention=decode_attention_selected(cfg, slots, max_len),
         experts=grouped_experts_selected(
             cfg, slots, [min(-(-n // kv_block) * kv_block, max_len)
                          for n in lengths]),
         requests=len(reqs), prompt_lengths=list(lengths),
         new_tokens=[r.max_new_tokens for r in reqs], tokens=total,
         greedy_exact=exact, greedy_ties=ties, greedy_missed=missed,
         worst_logit_deficit=round(worst, 5), tie_tol=tie_tol,
         prefill_compiles=compiled[0], decode_compiles=compiled[1],
         kv_in_place=kv_in_place, steps_ahead=steps_ahead,
         admissions_ahead=admissions_ahead,
         setup_seconds=round(cold_s - warm_s, 2),
         request_seconds=round(warm_s, 3),
         ttft_seconds_warm=round(float(np.median(
             [r.ttft_s for r in warm.values()])), 4))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _tp_leaf_bytes_per_device(params):
    """{device id: bytes it holds of the leaves param_specs shards over a
    mesh axis} — whole copies on a dp-only mesh, 1/tp of them under tp."""
    import jax
    from jax.sharding import PartitionSpec

    from horovod_tpu.models import transformer as tr
    specs = jax.tree_util.tree_leaves(
        tr.param_specs(params),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    out = {}
    for leaf, spec in zip(jax.tree_util.tree_leaves(params), specs):
        if all(axis is None for axis in spec):
            continue
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return out


def attention_operand_shapes(compiled):
    """Per-chip operand shapes of the Mosaic attention calls in a
    compiled step: {(b*h, s, d) as text: count}."""
    shapes = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r"operand_layout_constraints=\{(.*?)\}, ", line)
        first = re.search(r"\[([0-9,]*)\]", m.group(1) if m else "")
        key = first.group(1) if first else "?"
        shapes[key] = shapes.get(key, 0) + 1
    return shapes


def leg_four_chips(cfg, batch, seq, one_chip_loss, on_chip=True,
                   steps=3):
    """dp=4 and dp=2,tp=2 through the same make_gspmd_step: first on the
    one-chip leg's tokens and weights (same seeds, same batch), then at
    ``batch`` per chip, where the two layouts must agree."""
    import jax

    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import mesh as mesh_mod

    devices = jax.devices()[:4]
    head_dim = cfg.d_model // cfg.num_heads
    report = {}
    wide = {}
    for name, kw in (("dp4", dict(dp=4)), ("dp2_tp2", dict(dp=2, tp=2))):
        mesh = mesh_mod.build_mesh(devices=devices, **kw)
        if on_chip:
            # tp neighbours (the fastest-varying mesh axis that is >1)
            # must be ICI neighbours on the host's 2x2 torus
            for row in mesh.devices.reshape(-1, kw.get("tp", 1)):
                hops = [sum(abs(a - b) for a, b in zip(x.coords, y.coords))
                        for x, y in zip(row, row[1:])]
                _check(all(h == 1 for h in hops),
                       f"{name}: tp neighbours {[d.coords for d in row]} "
                       f"are not ICI neighbours")
        entry = {"mesh": {a: int(s) for a, s in mesh.shape.items()
                          if s > 1},
                 "coords": [list(getattr(d, "coords", ()))
                            for d in mesh.devices.flat]}
        for tag, b in (("same_batch", batch), ("full_width", 4 * batch)):
            step, params, opt_state, toks, _ = _lm_setup(mesh, b, seq, cfg)
            if tag == "full_width":
                entry["tp_leaf_bytes_per_device"] = \
                    _tp_leaf_bytes_per_device(params)
                if on_chip:
                    compiled = step.lower(params, opt_state, toks).compile()
                    shapes = attention_operand_shapes(compiled)
                    dp, tp = kw.get("dp", 1), kw.get("tp", 1)
                    want = f"{(b // dp) * (cfg.num_heads // tp)},{seq}," \
                           f"{-(-head_dim // 128) * 128}"
                    _check(set(shapes) == {want} and
                           sum(shapes.values()) == 3 * cfg.num_layers,
                           f"{name}: attention operands {shapes}, wanted "
                           f"{3 * cfg.num_layers} calls on [{want}]")
                    entry["attention_operands"] = shapes
            t0 = time.perf_counter()
            params, opt_state, losses, secs = _run_steps(
                step, params, opt_state, toks, steps)
            _check(all(np.isfinite(losses)), f"{name}: loss {losses}")
            _check(step._cache_size() == 1, f"{name}: recompiled")
            entry[tag] = {"batch": b, "losses": [round(x, 5) for x in losses],
                          "seconds": round(time.perf_counter() - t0, 2),
                          "step_seconds": round(float(np.median(secs[1:])),
                                                4)}
            if tag == "same_batch":
                rel = abs(losses[0] - one_chip_loss) / abs(one_chip_loss)
                _check(rel <= MESH_RTOL,
                       f"{name}: first loss {losses[0]} against the "
                       f"one-chip leg's {one_chip_loss}: {rel:.3g} > "
                       f"{MESH_RTOL}")
                entry[tag]["vs_one_chip_rel"] = float(f"{rel:.3g}")
            else:
                wide[name] = losses
                live = {}
                for d in devices:
                    stats = d.memory_stats() if on_chip else None
                    live[d.id] = (stats or {}).get("bytes_in_use")
                if on_chip:
                    _check(all(v and v > 0 for v in live.values()),
                           f"{name}: a device holds no live buffers: {live}")
                entry["bytes_in_use"] = live
            del step, params, opt_state, toks
        report[name] = entry
    a, b = wide["dp4"], wide["dp2_tp2"]
    rels = [abs(x - y) / abs(x) for x, y in zip(a, b)]
    _check(max(rels) <= MESH_RTOL * 4,
           f"layouts disagree at full width: dp4 {a}, dp2_tp2 {b}")
    dp_bytes = report["dp4"]["tp_leaf_bytes_per_device"]
    tp_bytes = report["dp2_tp2"]["tp_leaf_bytes_per_device"]
    _check(len(tp_bytes) == 4 and
           all(2 * tp_bytes[d] == dp_bytes[d] for d in dp_bytes),
           f"tp=2 does not halve the sharded leaves on every device: "
           f"dp=4 {dp_bytes}, tp=2 {tp_bytes}")
    emit("four_chips", seq=seq, layouts=report,
         layouts_max_rel=float(f"{max(rels):.3g}"))


# ---------------------------------------------------------------------------
# --hvdrun: one process per chip (a separate invocation; no JAX here)
# ---------------------------------------------------------------------------

_HVDRUN_CHILD = """
import json, os, jax, numpy as np
import horovod_tpu as hvd
hvd.init()
local = jax.local_devices()
assert len(local) == 1 and local[0].platform == "tpu", local
me = int(os.environ["HVD_PROCESS_ID"])
out = np.asarray(hvd.allreduce(np.full((8,), float(me + 1), np.float32),
                               average=False, name="smoke"))
want = sum(range(1, hvd.process_count() + 1))
assert np.allclose(out, want), (out, want)
print(json.dumps({"rank": me, "processes": hvd.process_count(),
                  "devices": jax.device_count(), "chip": local[0].id,
                  "device_kind": local[0].device_kind, "sum": float(out[0])}))
"""


def run_hvdrun(num_proc, timeout=600):
    """``hvdrun -np N``: N children, one chip each, one eager allreduce
    over the negotiated plane. This parent stays off JAX."""
    import tempfile
    with tempfile.TemporaryDirectory() as outdir:
        cmd = [sys.executable, "-m", "horovod_tpu.run.cli",
               "-np", str(num_proc), "--output-dir", outdir,
               sys.executable, "-c", _HVDRUN_CHILD]
        proc = subprocess.run(cmd, cwd=_ROOT, capture_output=True, text=True,
                              timeout=timeout)
        ranks, errors = [], []
        for r in range(num_proc):
            with open(os.path.join(outdir, f"rank.{r}.out")) as f:
                lines = f.read().strip().splitlines()
            with open(os.path.join(outdir, f"rank.{r}.err")) as f:
                errors.append(f.read()[-3000:])
            if lines and lines[-1].startswith("{"):
                ranks.append(json.loads(lines[-1]))
    ok = (proc.returncode == 0 and len(ranks) == num_proc and
          len({r["chip"] for r in ranks}) == num_proc and
          all(r["devices"] == num_proc for r in ranks))
    print(json.dumps({"leg": "hvdrun", "ok": ok, "num_proc": num_proc,
                      "exit_code": proc.returncode, "ranks": ranks}),
          flush=True)
    if not ok:
        sys.stderr.write(proc.stderr[-4000:] + "\n".join(errors))
    return 0 if ok else 1


# ---------------------------------------------------------------------------

def _native_core():
    """Which core serves the eager plane; a failed build with a compiler
    present is an error, not a fallback."""
    from horovod_tpu import _native
    lib = _native.load()
    if lib is None and shutil.which("g++"):
        raise RuntimeError(
            f"g++ is present but libhvd_core did not build or load: "
            f"{_native.LOAD_ERROR}")
    return ("native " + lib.hvd_core_version().decode()) if lib \
        else f"python fallback ({_native.LOAD_ERROR})"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma-separated subset of: " + ", ".join(LEGS))
    ap.add_argument("--hvdrun", type=int, metavar="N", default=0,
                    help="instead of the legs: launch hvdrun -np N, one "
                         "chip per process (this parent stays off JAX)")
    args = ap.parse_args(argv)
    if args.hvdrun:
        return run_hvdrun(args.hvdrun)
    legs = [s for s in args.legs.split(",") if s]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        ap.error(f"unknown legs {unknown}")

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{device.platform!r} ({device.device_kind}); there is no CPU "
              f"mode", file=sys.stderr)
        return 1

    from horovod_tpu.models import hybrid, latent_moe, looped
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    t0 = time.perf_counter()
    emit("start", legs=legs, native_core=_native_core(),
         compile_cache={"dir": cache_dir, "entries_at_start": cached,
                        "warm": cached > 0},
         jax=jax.__version__)

    train_cfg = tr.TransformerConfig.gpt2_small_tpu(
        attention_impl="flash", tie_embeddings=True, logits_fp32=False)
    serve_cfg = tr.TransformerConfig.gpt2_small_tpu(attention_impl="flash")
    heads, head_dim = train_cfg.num_heads, 128
    first_loss = None
    if "kernels" in legs:
        # the training shape, then prefill at the padded lengths the serve
        # leg's prompts produce (16, 112, 528 -> 640, 1008 -> 1024)
        leg_kernels([(16, 1024, heads, head_dim)] +
                    [(1, s, heads, head_dim) for s in (16, 112, 528, 1008)])
        # the training cell's attention, timed: [64, 4096, 128]
        leg_flash_timing()
        # the sampler alone at the two largest (rows, vocabulary) served:
        # a greedy batch draws nothing
        leg_sampler_timing()
        # the banded forward: a window under, at and over a 512-tile, both
        # group sizes of the laguna_small leg, a length no block divides
        leg_window_kernel([(1, 1024, 8, 2, 128, 128),
                           (1, 2048, 16, 2, 128, 512),
                           (2, 640, 6, 2, 128, 128),
                           (1, 1008, 8, 8, 128, 700)])
        # the grouped product at Laguna-XS.2's hidden width: rows resident
        # (4,096 assignments, 64 a group) and streamed (8,192 of which
        # 6,000 real, 94 a group, padding behind the last; 16,384 over 256
        # experts, 64 a group)
        leg_grouped_kernel([(512, 8, 64, 2048, 512, 512),
                            (1024, 8, 64, 2048, 512, 750),
                            (2048, 8, 256, 2048, 512, 2048)],
                           products=["resident", "streamed", "streamed"])
        # the packed decode kernel at the reasoning cell's cache: the one
        # plane of 96 x 3,072 at the lengths a window holds, and a ring of
        # 512 + 128, ten pairs a row; rows of length 0 and 1 among them
        lens = np.random.RandomState(1)
        leg_packed_kernel([
            (1, 96, 3072, 10, [0, 1] + list(lens.randint(400, 1500, 94))),
            (8, 96, 640, 10, [0, 129] + [512] * 94)])
        leg_mamba1(lengths=(256, 1024))
    if "lm_train" in legs or "four_chips" in legs:
        first_loss = leg_lm_train(train_cfg, 16, 1024)
    if "resnet" in legs:
        leg_resnet()
    if "serve" in legs:
        leg_serve(serve_cfg)
        # a recurrent mixer beside grouped-query attention, at widths both
        # of its decode kernels take (ops/ssm.py, ops/flash_attention.py)
        leg_serve(hybrid.HybridConfig(
            vocab_size=4096, num_layers=2, d_model=512, d_ff=1024,
            num_heads=4, num_kv_heads=2, head_dim=128, ssm_heads=8,
            ssm_head_dim=64, ssm_state=128, ssm_groups=2,
            attention_impl="flash"), kv_block=128, name="hybrid_small")
        # a layer stack run four times over its weights, K/V per (pass,
        # layer) plane, at widths the decode kernel takes
        leg_serve(looped.LoopedConfig(
            vocab_size=4096, num_layers=2, num_heads=4, d_model=512,
            d_ff=1024, passes=4, rope_theta=1e6, max_seq_len=1024,
            attention_impl="flash"), kv_block=128, name="looped_small")
        # latent attention (ONE latent kind in the cache, 128 + 64 numbers
        # in 256 lanes) and dropless experts (8 of which 2, one shared,
        # behind a dense first layer), at widths its decode kernel, the
        # flash kernel and the grouped product take
        leg_serve(latent_moe.LatentMoEConfig(
            vocab_size=4096, num_layers=3, d_model=512, num_heads=4,
            q_rank=128, kv_rank=128, nope_dim=64, rope_dim=64, v_dim=128,
            rope_theta=1e6, d_ff=1024, first_dense=1, num_experts=8,
            experts_per_tok=2, shared_experts=1, d_expert=256,
            route_scale=1.8, max_seq_len=1024, attention_impl="flash"),
            kv_block=128, name="latent_moe_small",
            # 8 experts of which 2: one token routed elsewhere is half its
            # routed output. WHICH tokens sit at a near tie moves with any
            # change of rounding: the v5e misses 4 of 500 tokens, the worst
            # by 0.675, with ragged_dot's grouped products (PR 42) and 7,
            # the worst by 0.693, with the grouped kernel, which is the
            # nearer of the two to a float32 loop at these widths (PR 43,
            # both in one process; the same on every run: the requests are
            # seeded). Held to 2% and 0.8: an eleventh token or a wider
            # miss fails
            routed_elsewhere=(0.02, 0.8))
        # window and full layers in two classes of cache, with experts;
        # prompts of 513, 640 and 1,000 leave their last 128 tokens in a
        # ring, and every row wraps it. Top 4 of 16: a token routed
        # elsewhere is a quarter of its routed output
        leg_serve(laguna_small_config(), kv_block=128, name="laguna_small",
                  routed_elsewhere=LAGUNA_SMALL_ROUTED)
        # the decoder-hybrid-decoder at published widths: prompts of 513,
        # 640 and 1,000 lie above the window of 512 and leave a wrapped
        # ring, the others below it; ONE plane, read by two layers
        leg_serve(phi4flash_small_config(), kv_block=128,
                  name="phi4flash_small")
    if "four_chips" in legs:
        if jax.device_count() >= 4:
            leg_four_chips(train_cfg, 16, 1024, first_loss)
        else:
            emit("four_chips", absent=True,
                 reason=f"{jax.device_count()} device(s)")
    emit("done", legs=legs, seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
