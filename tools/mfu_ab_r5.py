"""Round-5 MFU experiments on the flagship step, paired against baseline.

Every variant is measured INTERLEAVED with the baseline (B,V,B,V
window order, median of per-window s/step, ratio per pair) so that
drift between windows is common-mode — an un-paired A/B can compare
drift, not the knob.

Variants:
  block:BQxBK[:BQ2xBK2]  flash kernel block sizes (fwd [,dkv])
  batch:N                per-chip batch operating point
  base                   (implicit)

Usage:
  python tools/mfu_ab_r5.py --variants block:1024x512,block:512x1024
  python tools/mfu_ab_r5.py --variants batch:24 --steps 20 --rounds 2
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import numpy as np


def make_cfg(size, remat_policy=None):
    import dataclasses
    from horovod_tpu.models import transformer as tr
    if size == "flagship":
        return None  # bench_common default (gpt2-small-tpu)
    cfg = {"llama-1b": tr.TransformerConfig.llama_1b}[size]()
    return dataclasses.replace(cfg, remat=True,
                               remat_policy=remat_policy)


def build(batch, seq=1024, inner=10, cfg=None, vocab_chunk=0):
    import horovod_tpu as hvd  # noqa: F401 — initializes the runtime
    from horovod_tpu.parallel import mesh as mesh_mod
    from bench_common import build_transformer_step

    mesh = mesh_mod.build_mesh(dp=1)
    step, params, opt_state, toks, cfg = build_transformer_step(
        mesh, batch, seq, cfg=cfg, on_tpu=True, n_steps=inner,
        vocab_chunk=vocab_chunk)
    live = {"p": params, "o": opt_state, "t": toks}

    def window():
        t0 = time.perf_counter()
        live["p"], live["o"], loss = step(live["p"], live["o"], live["t"])
        float(loss)
        return (time.perf_counter() - t0) / inner

    def release():
        live.clear()

    window()  # compile + warmup
    return window, cfg, release


class BlockPatch:
    """Re-defaults flash_attention's block sizes for the variant build."""

    def __init__(self, bq, bk, bq2=None, bk2=None):
        self.args = (bq, bk, bq2, bk2)
        self.orig = None

    def __enter__(self):
        from horovod_tpu.ops import flash_attention as fa
        self.fa = fa
        self.orig = fa.flash_attention
        bq, bk, bq2, bk2 = self.args
        self.fa.flash_attention = functools.partial(
            self.orig, block_q=bq, block_k=bk,
            block_q_dkv=bq2, block_k_dkv=bk2)
        return self

    def __exit__(self, *exc):
        self.fa.flash_attention = self.orig


def parse_variant(spec, args):
    """Returns (label, build_kwargs, block_patch_or_None)."""
    base = {"batch": args.batch, "seq": args.seq, "inner": args.inner,
            "cfg": make_cfg(args.size), "vocab_chunk": args.vocab_chunk}
    if spec.startswith("block:"):
        parts = spec[6:].split(":")
        bq, bk = (int(x) for x in parts[0].split("x"))
        bq2 = bk2 = None
        if len(parts) > 1:
            bq2, bk2 = (int(x) for x in parts[1].split("x"))
        return spec, base, BlockPatch(bq, bk, bq2, bk2)
    if spec.startswith("batch:"):
        return spec, dict(base, batch=int(spec[6:])), None
    if spec.startswith("chunk:"):
        return spec, dict(base, vocab_chunk=int(spec[6:])), None
    if spec.startswith("policy:"):
        name = spec[7:] or None
        return spec, dict(base, cfg=make_cfg(args.size, name)), None
    raise ValueError(spec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", required=True,
                    help="comma list, e.g. "
                         "block:1024x512,batch:24,chunk:16384,"
                         "policy:dots_no_batch")
    ap.add_argument("--size", default="flagship",
                    choices=["flagship", "llama-1b"])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--inner", type=int, default=10)
    ap.add_argument("--vocab-chunk", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=3,
                    help="paired (base, variant) window rounds")
    ap.add_argument("--sequential", action="store_true",
                    help="bracketed sequential mode (teardown between "
                         "builds; required at llama-1b scale)")
    args = ap.parse_args()

    if args.sequential:
        return run_sequential(args)

    base_window, cfg, _ = build(args.batch, args.seq, args.inner,
                                cfg=make_cfg(args.size),
                                vocab_chunk=args.vocab_chunk)
    from bench_common import transformer_matmul_flops_per_token
    flops_tok = transformer_matmul_flops_per_token(cfg, args.seq)

    results = {}
    for spec in args.variants.split(","):
        label, kw, patch = parse_variant(spec.strip(), args)
        if patch is not None:
            with patch:
                v_window, _, v_release = build(**kw)
        else:
            v_window, _, v_release = build(**kw)
        vbatch = kw["batch"]
        base_s, var_s = [], []
        for rd in range(args.rounds):
            order = ((base_window, base_s), (v_window, var_s))
            if rd % 2:
                order = order[::-1]
            for win, sink in order:
                sink.append(win())
        v_release()
        b = float(np.median(base_s))
        v = float(np.median(var_s))
        base_tok = args.batch * args.seq / b
        var_tok = vbatch * args.seq / v
        results[label] = {
            "base_ms": round(b * 1e3, 2),
            "variant_ms": round(v * 1e3, 2),
            "base_tok_s": round(base_tok),
            "variant_tok_s": round(var_tok),
            "tok_s_ratio": round(var_tok / base_tok, 4),
            "variant_mfu": round(var_tok * flops_tok / 197e12, 4),
            "base_mfu": round(base_tok * flops_tok / 197e12, 4),
        }
        print(json.dumps({label: results[label]}), flush=True)
    print(json.dumps({"summary": results}))


def run_sequential(args):
    """Bracketed sequential mode for models too big for base+variant
    co-residency (llama-1b: params+optimizer ~12 GB each): measure
    base, then each variant, then base AGAIN, all with teardown between
    builds. The bracketing bases bound session drift — if they
    disagree, the run says so instead of publishing a knob effect."""
    from bench_common import transformer_matmul_flops_per_token

    def measure(spec_label, kw, patch):
        import jax
        try:
            if patch is not None:
                with patch:
                    window, cfg, release = build(**kw)
            else:
                window, cfg, release = build(**kw)
        except Exception as e:  # noqa: BLE001 — OOM is a RESULT here
            msg = str(e)
            if "memory" in msg.lower() or "RESOURCE_EXHAUSTED" in msg:
                jax.clear_caches()
                return None, None, kw["batch"]
            raise
        s = [window() for _ in range(args.rounds)]
        release()
        return float(np.median(s)), cfg, kw["batch"]

    base_kw = {"batch": args.batch, "seq": args.seq, "inner": args.inner,
               "cfg": make_cfg(args.size), "vocab_chunk": args.vocab_chunk}
    base1, cfg, _ = measure("base", dict(base_kw), None)
    flops_tok = transformer_matmul_flops_per_token(cfg, args.seq)
    variants = []
    for spec in args.variants.split(","):
        label, kw, patch = parse_variant(spec.strip(), args)
        v, _, vbatch = measure(label, kw, patch)
        variants.append((label, v, vbatch))
        print(json.dumps({label: "oom" if v is None
                          else round(v * 1e3, 2)}), flush=True)
    base2, _, _ = measure("base", dict(base_kw), None)
    base = (base1 + base2) / 2
    drift_pct = abs(base2 - base1) / base * 100
    out = {"base_ms": round(base * 1e3, 2),
           "base_bracket_drift_pct": round(drift_pct, 2),
           "base_mfu": round(
               args.batch * args.seq / base * flops_tok / 197e12, 4)}
    for label, v, vbatch in variants:
        if v is None:
            out[label] = {"oom": True}
            continue
        tok = vbatch * args.seq / v
        out[label] = {
            "ms": round(v * 1e3, 2),
            "tok_s": round(tok),
            "mfu": round(tok * flops_tok / 197e12, 4),
            "vs_base": round((args.batch * args.seq / base) and
                             tok / (args.batch * args.seq / base), 4),
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
