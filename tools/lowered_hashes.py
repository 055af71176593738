"""Does a change leave the programs alone? sha256 of the lowered text of
five training steps, of ``sorted(sys.modules)`` after them, and of the three
serving programs as ``ServeEngine`` itself feeds them (dense, hybrid,
looped, latent attention with experts, window layers with experts, and the
decoder-hybrid-decoder, at the tests' sizes; greedy and sampled requests, one of them for a single
token), with a hash of the
tokens served. Run it from the root of
two trees and compare the lines (the set-up protocol, PERF.md §6):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python tools/lowered_hashes.py > here.txt
    (cd ../parent && python <this file> > ../parent.txt)

A line that differs names the program whose cache entry a warm machine no
longer finds. Equal lines are necessary, not sufficient: on the CPU the
kernels are interpreted, and lowered for the chip a Mosaic kernel's body
carries its call site's file names and line numbers into the text and the
cache key, so two trees share no entry for a program with a kernel unless
``JAX_TRACEBACK_IN_LOCATIONS_LIMIT=0`` (PERF.md §7).
"""

import dataclasses
import hashlib
import json
import os
import sys

sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


def training(out):
    """The trainers first, so that ``sys.modules`` is what a trainer
    imports."""
    import bench_common
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import mesh as mesh_mod

    hvd.init()
    for impl, axes in (("flash", dict(dp=1)), ("full", dict(dp=1)),
                       ("flash", dict(dp=8)), ("flash", dict(dp=4, tp=2))):
        n = int(np.prod(list(axes.values())))
        mesh = mesh_mod.build_mesh(devices=jax.devices()[:n], **axes)
        cfg = dataclasses.replace(tr.TransformerConfig.tiny(),
                                  attention_impl=impl)
        step, params, opt_state, toks, _ = \
            bench_common.build_transformer_step(mesh, 8, 32, cfg=cfg)
        name = ",".join(f"{k}={v}" for k, v in axes.items())
        out[f"train lm {name} {impl}"] = sha(
            step.lower(params, opt_state, toks).as_text())
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:8]),
                             hvd.mesh().axis_names[:1])
    step, params, opt_state, data = bench_common.build_step(
        "resnet50", mesh, 8, 32)
    out["train resnet50 dp=8"] = sha(
        step.lower(params, opt_state, data).as_text())
    mods = sorted(sys.modules)
    out["sys.modules after the training steps"] = "%d names, %s, serving %s" % (
        len(mods), sha("\n".join(mods)),
        any(m.startswith("horovod_tpu.serving") for m in mods))


def served_models():
    from horovod_tpu.models import hybrid, latent_moe, looped
    from horovod_tpu.models import transformer as tr
    key = jax.random.PRNGKey(0)
    cfg = tr.TransformerConfig.tiny(dtype=jnp.float32, attention_impl="full")
    yield "dense full float32", cfg, tr.init_params(cfg, key)[1]
    cfg = tr.TransformerConfig.tiny(attention_impl="flash")
    yield "dense flash", cfg, tr.init_params(cfg, key)[1]
    cfg = hybrid.HybridConfig.tiny(dtype=jnp.float32, max_seq_len=64,
                                   ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 4.0))
    yield "hybrid", cfg, hybrid.init_params(cfg, key)
    cfg = looped.LoopedConfig.tiny(max_seq_len=64, rope_theta=1e6,
                                   dtype=jnp.float32)
    yield "looped", cfg, looped.init_params(cfg, key)
    cfg = latent_moe.LatentMoEConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    yield "latent_moe", cfg, latent_moe.init_params(cfg, key)
    try:  # a tree from before the family: its lines are absent, no more
        from horovod_tpu.models import window_moe
    except ImportError:
        return
    cfg = window_moe.WindowMoEConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    yield "window_moe", cfg, window_moe.init_params(cfg, key)
    try:
        from horovod_tpu.models import sambay
    except ImportError:
        return
    cfg = sambay.SambaYConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    yield "sambay", cfg, sambay.init_params(cfg, key)


REQUESTS = [((5, 9, 17), 9, 0.0), ((4, 8, 15, 16, 23, 42, 1, 2, 3, 4), 13, 0.8),
            ((7, 7, 1), 6, 0.8), ((2, 7, 1, 8), 1, 0.0)]


def serving(out):
    """Lower each program with the very arguments the engine calls it
    with, just before the call."""
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue, Request

    programs = {n: getattr(engine_mod, n)
                for n in ("_prefill_jit", "_write_slot", "_decode_jit")}

    def spy(name, seen):
        def call(*a):
            key = name
            if name == "_prefill_jit":
                key += " s_pad=%d" % a[2].shape[1]
            elif name == "_write_slot":
                key += " s_pad=%d" % max(v.shape[2] for v in a[1].values())
            seen.setdefault(key, set()).add(
                sha(programs[name].lower(*a).as_text()))
            return programs[name](*a)
        return call

    for model, cfg, params in served_models():
        seen = {}
        for name in programs:
            setattr(engine_mod, name, spy(name, seen))
        try:
            # hvdlint: disable=HVD017(one bare engine: its programs are what is hashed)
            queue = AdmissionQueue(max_depth=64, admission_timeout_s=1e9)
            engine = engine_mod.ServeEngine(
                cfg, params, num_slots=2, max_len=48, kv_block=8, seed=3,
                queue=queue)
            for i, (prompt, new, temperature) in enumerate(REQUESTS):
                # hvdlint: disable=HVD017(one bare engine: its programs are what is hashed)
                engine.submit(Request(f"r{i}", prompt, max_new_tokens=new,
                                      temperature=temperature))
            results = engine.run_to_completion()
        finally:
            for name, program in programs.items():
                setattr(engine_mod, name, program)
        for key in sorted(seen):
            # one text a shape, however often and however it was fed
            assert len(seen[key]) == 1, (model, key, seen[key])
            out[f"serve {model} {key}"] = seen[key].pop()
        out[f"serve {model} tokens served"] = sha(json.dumps(sorted(
            (r.request_id, [int(t) for t in r.tokens]) for r in results)))


def main():
    out = {}
    training(out)
    serving(out)
    for name, value in out.items():
        print("%-46s %s" % (name, value))


if __name__ == "__main__":
    main()
