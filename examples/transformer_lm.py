"""Flagship transformer-LM training across the full mesh (dp x tp x sp).

The workload the reference never had but its successors need: a GPT-style
decoder trained with every parallelism axis this framework provides —
data parallel (gradient psum, the reference's core capability), tensor
parallel (Megatron-style sharded heads/MLP), and sequence parallel
(ring/Ulysses attention for long context). One script, one mesh, `pjit`
does the rest.

Usage:
    # single chip / all local chips, GPT-2-small-ish, synthetic tokens
    python examples/transformer_lm.py --steps 20

    # 8-way CPU mesh: 2-way dp x 2-way tp x 2-way sp with ring attention
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/transformer_lm.py --dp 2 --tp 2 --sp 2 \
        --attention ring --size tiny --steps 5

    # throughput benchmark mode (tokens/sec, docs/benchmarks.md)
    python examples/transformer_lm.py --bench --steps 30
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import trainer
from horovod_tpu.common.exceptions import PREEMPTED_EXIT_CODE
from horovod_tpu.models import transformer as tr
from horovod_tpu.parallel import mesh as mesh_mod
from horovod_tpu.utils import compile_cache


SIZES = {"tiny": tr.TransformerConfig.tiny,
         "gpt2-small": tr.TransformerConfig.gpt2_small,
         "gpt2-small-tpu": tr.TransformerConfig.gpt2_small_tpu,
         "llama-1b": tr.TransformerConfig.llama_1b}


def parse_args():
    p = argparse.ArgumentParser(description="horovod_tpu transformer LM")
    p.add_argument("--size", default="tiny", choices=sorted(SIZES))
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel ways (default: all devices / tp / sp)")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel ways (ring/ulysses attention)")
    p.add_argument("--attention", default="full",
                   choices=["full", "ring", "ring_flash", "ulysses",
                            "flash"])
    p.add_argument("--batch-size", type=int, default=4,
                   help="per-dp-way batch size")
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup-steps", type=int, default=10)
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel ways (MoE experts shard over 'ep')")
    p.add_argument("--num-experts", type=int, default=0,
                   help="experts per MoE layer; 0 = dense MLP")
    p.add_argument("--remat-policy", default=None,
                   choices=["dots", "dots_no_batch"],
                   help="jax.checkpoint policy under --remat (default: "
                        "save nothing)")
    p.add_argument("--remat", action="store_true",
                   help="jax.checkpoint each block (HBM for FLOPs)")
    p.add_argument("--vocab-chunk", type=int, default=0,
                   help="compute the loss blockwise over this many vocab "
                        "entries instead of materializing [B,S,V] logits "
                        "(memory-bound large-batch/long-seq configs)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=100,
                   help="save an async checkpoint every N steps "
                        "(trainer.Checkpointer contract: auto-resume on "
                        "start, SIGTERM/SIGINT exits preemption-safe "
                        "with an emergency save and code 45)")
    p.add_argument("--eager-allreduce", action="store_true",
                   help="average gradients through the EAGER collective "
                        "core (fused stacked allreduce per step) instead "
                        "of the in-graph GSPMD psum — the regime "
                        "HOROVOD_AUTOTUNE's passive scorer observes, so "
                        "autotuning tunes against these exact steps. "
                        "Pure data-parallel only (tp/sp/ep must be 1).")
    p.add_argument("--bench", action="store_true",
                   help="skip checkpointing/logging; print tokens/sec")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main():
    args = parse_args()
    compile_cache.configure()
    hvd.init()
    n = hvd.size()
    # The named-mesh data plane (docs/mesh.md): CLI flags win when given;
    # otherwise the HOROVOD_MESH / HOROVOD_MESH_TP / HOROVOD_MESH_SP env
    # knobs configure the layout, and with nothing set this is the same
    # pure-dp mesh as always. The result is committed as THE process
    # mesh — trainer/checkpoint/serving helpers all place through it.
    cli = (args.dp is not None or args.tp != 1 or args.sp != 1 or
           args.ep != 1)
    if cli:
        dp = args.dp or n // (args.tp * args.sp * args.ep)
        if dp * args.tp * args.sp * args.ep != n:
            raise SystemExit(
                f"dp*tp*sp*ep = {dp}*{args.tp}*{args.sp}*{args.ep} "
                f"!= {n} devices")
        mesh = mesh_mod.build_mesh(dp=dp, tp=args.tp, sp=args.sp,
                                   ep=args.ep)
    else:
        mesh = mesh_mod.mesh_from_env()
    mesh_mod.set_global_mesh(mesh)
    dp = mesh_mod.mesh_axis_size(mesh, "dp")
    tp = mesh_mod.mesh_axis_size(mesh, "tp")
    sp = mesh_mod.mesh_axis_size(mesh, "sp")
    ep = mesh_mod.mesh_axis_size(mesh, "ep")
    verbose = hvd.process_rank() == 0

    cfg = SIZES[args.size](attention_impl=args.attention, remat=args.remat,
                           remat_policy=args.remat_policy,
                           num_experts=args.num_experts)
    seq = args.seq_len or min(cfg.max_seq_len, 256)
    batch = args.batch_size * dp
    if verbose:
        print(f"mesh dp={dp} tp={tp} sp={sp} "
              f"model={args.size} seq={seq} attention={args.attention}")

    model = tr.TransformerLM(cfg)
    rng = np.random.RandomState(args.seed)
    sample = jnp.zeros((2, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), sample)["params"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    if verbose:
        print(f"{n_params / 1e6:.1f}M params")

    # LR: linear warmup then cosine — the jit-friendly schedule form of the
    # reference's LearningRateWarmupCallback (callbacks.warmup_schedule is
    # the epoch-keyed equivalent).
    sched = optax.warmup_cosine_decay_schedule(
        0.0, args.lr, args.warmup_steps, max(args.steps, 2 * args.warmup_steps))
    tx = optax.adamw(sched, weight_decay=0.01)

    specs = None
    if args.eager_allreduce:
        if tp * sp * ep != 1:
            raise SystemExit("--eager-allreduce is pure data-parallel: "
                             "tp/sp/ep must all be 1")
        from bench_common import build_eager_lm_step
        step, params, opt_state, _ = build_eager_lm_step(
            cfg, n, args.batch_size, seq, tx=tx, params=params)
        if verbose:
            print("eager allreduce: gradients ride the coordination core "
                  "(autotune-scorable; HOROVOD_AUTOTUNE=1 to tune)")
    else:
        loss_fn = tr.lm_loss_fn(model, vocab_chunk=args.vocab_chunk)
        specs = tr.param_specs(params)
        step, param_shardings, batch_sharding = trainer.make_gspmd_step(
            loss_fn, tx, mesh, specs, tr.batch_spec(sp=sp > 1),
            params=params)
        # tree-wide placement through the sanctioned helper (HVD019):
        # one batched transfer, every leaf pinned by its spec
        params = trainer.place(params, mesh, specs)
        opt_state = trainer.init_opt_state(tx, params, mesh, specs)

    # Checkpoint plane (docs/checkpoint.md): async saves every
    # --checkpoint-every steps, auto-resume, preemption-safe SIGTERM
    # exit. Only when every leaf is host-addressable — multi-host
    # sharded params need a gather or per-process checkpointing.
    addressable = all(getattr(x, "is_fully_addressable", True)
                      for x in jax.tree_util.tree_leaves(
                          (params, opt_state)))
    ckptr = None
    start_step = 0
    if args.checkpoint_dir and not args.eager_allreduce and not args.bench:
        if addressable:
            ckptr = trainer.Checkpointer(
                args.checkpoint_dir, every=args.checkpoint_every,
                preemption=jax.process_index() == 0,
                rank=jax.process_index(), verbose=verbose,
                layout=mesh_mod.mesh_layout(mesh))
            # cross-layout resume (docs/mesh.md): the checkpoint may have
            # been saved under a different dp×tp×sp factorization — the
            # spec tree re-places every leaf on THIS run's mesh
            resume_specs = (specs,
                            trainer.opt_state_specs(tx, params, specs))
            (params, opt_state), start_step, _extra = ckptr.resume(
                like=(params, opt_state), mesh=mesh,
                spec_tree=resume_specs)
        elif verbose:
            print("checkpointing disabled: params span non-addressable "
                  "devices (multi-host sharded); gather or use "
                  "per-process checkpointing")

    def batch_tokens():
        # [batch, seq]; the loss shifts inputs/targets internally. seq (not
        # seq+1) keeps the sequence dim divisible by sp for device_put.
        if args.eager_allreduce:
            # stacked eager layout: [world, per_shard, seq]
            toks = rng.randint(0, cfg.vocab_size,
                               (n, args.batch_size, seq),
                               dtype=np.int64).astype(np.int32)
            return jnp.asarray(toks)
        toks = rng.randint(0, cfg.vocab_size, (batch, seq),
                           dtype=np.int64).astype(np.int32)
        return jax.device_put(jnp.asarray(toks), batch_sharding)

    # compile + warmup (scalar read = true barrier, see timing note below)
    params, opt_state, loss = step(params, opt_state, batch_tokens())
    float(loss)

    # Per-axis wire attribution (docs/metrics.md): analytic payload bytes
    # of the step's collectives, split by mesh axis — the dp leg is the
    # gradient allreduce (every param), the tp leg the Megatron
    # activation allreduces (2 fwd + 2 bwd per layer of one dp-shard's
    # [B/dp, S, D] residual). GSPMD hides the executed collectives inside
    # the compiled step, so the counters carry the model, not a probe.
    itemsize = jnp.dtype(cfg.dtype).itemsize
    dp_step_bytes = sum(x.size * np.dtype(x.dtype).itemsize
                        for x in jax.tree_util.tree_leaves(params)) \
        if dp > 1 else 0
    tp_step_bytes = (4 * cfg.num_layers * (batch // dp) * seq *
                     cfg.d_model * itemsize) if tp > 1 else 0

    t0 = time.perf_counter()
    tokens_done = 0
    for i in range(start_step, args.steps):
        params, opt_state, loss = step(params, opt_state, batch_tokens())
        tokens_done += batch * seq
        if dp_step_bytes:
            mesh_mod.account_axis_bytes("dp", dp_step_bytes)
        if tp_step_bytes:
            mesh_mod.account_axis_bytes("tp", tp_step_bytes)
        if not args.bench and verbose and (i + 1) % 10 == 0:
            print(f"step {i + 1}: loss={float(loss):.4f}")
        if ckptr is not None and ckptr.step_end(
                i + 1, (params, opt_state), extra={"data_pos": i + 1}):
            # preemption: the in-flight step finished, an emergency
            # checkpoint committed; the elastic supervisor's
            # --graceful-restart-on-preempt resumes from exactly here
            sys.exit(PREEMPTED_EXIT_CODE)
    float(loss)  # device→host read: the barrier that ends the timed loop
    dt = time.perf_counter() - t0
    if ckptr is not None:
        ckptr.close()  # drain the async writer before reporting

    if verbose:
        tps = tokens_done / dt
        ms = dt * 1e3 / max(1, args.steps - start_step)
        print(f"final loss {float(loss):.4f}")
        print(f"{tps:,.0f} tokens/sec total ({tps / n:,.0f}/chip, "
              f"{ms:.1f} ms/step)")
        if args.bench and sp > 1:
            # ring/Ulysses sequence parallelism: per-chip residency and
            # wire volume scale with seq/sp, so the measured single-chip
            # envelope (docs/benchmarks.md) projects to sp x that length
            # on a ring of sp chips
            h = cfg.num_heads
            hd = cfg.d_model // h
            blk = (batch // dp) * (seq // sp) * h * hd * 2  # bf16
            print(f"sp={sp}: seq/chip {seq // sp} of {seq} "
                  f"global; ring hop payload {2 * blk / 2 ** 20:.1f} MiB "
                  f"(K+V); projected envelope ≈ sp x single-chip "
                  f"(same per-chip residency)")


if __name__ == "__main__":
    main()
