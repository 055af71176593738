"""The system under test for the Phi-4-mini-flash configuration: the
program's own decoder-hybrid-decoder (``horovod_tpu/models/sambay.py``:
Mamba-1 and window-512 differential attention, ONE full-attention K/V plane
that seven cross-attention layers share, gated memory units between them)
behind ``serving.ServeEngine``, the same engine, scheduler, queue and cache
manager as every other served model; the cache holds THREE classes of
state: the one plane at ``max_len`` a row, the window layers' rings, and
the Mamba layers' recurrent state and convolution windows.

Weights are the benchmark's (``lib/weights.py``, names from
``reference/phi4flash.py``), made on the device in bfloat16 in one jitted
call; this file nests them the way the program's parameter tree wants and
applies the configuration's ``assumed.init`` rules by its own code:
``A_log[n, c] = log(n + 1)`` (held state-major), ``D`` = 1, ``b_dt`` the
inverse softplus of a log-uniform dt drawn through the normal's
distribution function, the embedding times 2^``embed_gain_log2``, each
matrix named under ``gains_log2`` times its power of two, and the four
lambda vectors from the columns of their one leaf.
"""

import math
import time

import jax
import jax.numpy as jnp

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram


def depth(config, traffic):
    return config["num_hidden_layers"]


def sambay_config(config, layers, **overrides):
    from horovod_tpu.models import sambay
    if layers != config["num_hidden_layers"]:
        raise ValueError("the layer plan is the whole model's: "
                         f"{layers} of {config['num_hidden_layers']} layers")
    if config["mb_per_layer"] != 2 or config["mlp_bias"] or \
            config["lm_head_bias"] or not config["tie_word_embeddings"] or \
            config["hidden_act"] != "silu":
        raise ValueError("the program alternates Mamba with attention, has "
                         "no bias in the SwiGLU or the head, ties the head "
                         "and gates with silu")
    mamba = config["assumed"]["mamba"]
    kw = dict(vocab_size=config["vocab_size"], num_layers=layers,
              d_model=config["hidden_size"], d_ff=config["intermediate_size"],
              num_heads=config["num_attention_heads"],
              num_kv_heads=config["num_key_value_heads"],
              window=config["sliding_window"], d_state=mamba["d_state"],
              d_conv=mamba["d_conv"], expand=mamba["expand"],
              dt_rank=mamba["dt_rank"], ln_eps=config["layer_norm_eps"],
              max_seq_len=config["max_position_embeddings"],
              dtype=jnp.bfloat16, tie_embeddings=True,
              attention_impl="flash")
    kw.update(overrides)
    return sambay.SambaYConfig(**kw)


def mixer_vectors(config, noise_dt, d_state):
    """(A_log [d_state, d_inner], dt_bias, D) in float32: ``assumed.init``
    by this file's own code (the reference has its own)."""
    init = config["assumed"]["init"]
    noise = noise_dt.astype(jnp.float32)
    uniform = 0.5 * (1.0 + jax.lax.erf(noise / math.sqrt(2.0)))
    lo, hi = math.log(init["dt"]["min"]), math.log(init["dt"]["max"])
    dt = jnp.exp(lo + (hi - lo) * uniform)
    a_log = jnp.broadcast_to(
        jnp.log(jnp.arange(1, d_state + 1, dtype=jnp.float32))[:, None],
        (d_state, noise.shape[0]))
    return a_log, dt + jnp.log(-jnp.expm1(-dt)), \
        jnp.full(noise.shape, float(init["D"]), jnp.float32)


def to_tree(w, mcfg, config):
    """{reference name: x} -> the program's parameter tree."""
    from horovod_tpu.models import sambay
    embed = w["embed"] * jnp.asarray(
        2.0 ** config["assumed"]["init"]["embed_gain_log2"], w["embed"].dtype)

    gains = config["assumed"]["init"].get("gains_log2", {})

    def kernel(name):
        # the matrix's power-of-two gain, by the last part of its name
        log2 = gains.get(name.rpartition(".")[2], 0)
        return {"kernel": w[name] * jnp.asarray(2.0 ** log2, w[name].dtype)
                if log2 else w[name]}

    def biased(name):
        return dict(kernel(name), bias=w[name + ".bias"])

    def norm(name):
        return {"scale": w[name + ".scale"], "bias": w[name + ".bias"]}

    def diff(p):
        lam = w[p + "attn.lambda"]
        return {"lambda": {n: lam[:, j] for j, n in
                           enumerate(("q1", "k1", "q2", "k2"))},
                "subln": {"scale": w[p + "attn.subln.scale"]},
                "out": biased(p + "attn.o")}
    tree = {"embed": {"embedding": embed}, "ln_f": norm("ln_f")}
    for i, kind in enumerate(sambay.layer_kinds(mcfg)):
        p = f"layers.{i}."
        layer = {"ln_mix": norm(p + "ln_mix"), "ln_mlp": norm(p + "ln_mlp"),
                 "mlp": {n: kernel(p + "mlp." + n)
                         for n in ("gate", "up", "down")}}
        if kind == "mamba":
            a_log, dt_bias, skip = mixer_vectors(config, w[p + "mixer.dt"],
                                                 mcfg.d_state)
            layer["mixer"] = {
                "in_proj": kernel(p + "mixer.in_proj"),
                "conv": biased(p + "mixer.conv"),
                "x_proj": kernel(p + "mixer.x_proj"),
                "dt_proj": {"kernel": w[p + "mixer.dt_proj"],
                            "bias": dt_bias},
                "A_log": a_log, "D": skip,
                "out_proj": kernel(p + "mixer.out_proj")}
        elif kind == "gmu":
            layer["gmu"] = {"in_proj": kernel(p + "gmu.in_proj"),
                            "out_proj": kernel(p + "gmu.out_proj")}
        elif kind == "cross":
            layer["attn"] = dict(diff(p), q=biased(p + "attn.q"))
        else:
            layer["attn"] = dict(diff(p), qkv=biased(p + "attn.qkv"))
        tree[f"layer_{i}"] = layer
    return tree


def build_serve(run, clock=time.monotonic):
    # a program without the model fails here, before any weight
    from horovod_tpu.models import sambay
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    mcfg = sambay_config(config, layers,
                         **traffic.get("model_overrides", {}))
    shapes = ref.weight_shapes(config, layers)
    with run.setup_item("weights"):
        params = jax.jit(lambda k: to_tree(
            weights.make(shapes, k, jnp.bfloat16), mcfg, config))(
                tref.weights_key(run.seed))
        jax.block_until_ready(params)
    with run.setup_item("engine"):
        # every caller's request may wait at once (the queue's own depth
        # is 64: a closed loop of 96 would lose a third of its callers)
        queue = AdmissionQueue(
            max_depth=max(64, traffic["callers"]),
            admission_timeout_s=eng_kw["admission_timeout_s"], clock=clock)
        eng = engine_mod.ServeEngine(
            mcfg, params, num_slots=eng_kw["num_slots"],
            max_len=eng_kw["max_len"], kv_block=eng_kw["kv_block"],
            queue=queue, seed=0, clock=clock)

    def compiles():
        return {"prefill": engine_mod._prefill_jit._cache_size(),
                "decode": engine_mod._decode_jit._cache_size()}

    def free():
        # the weights and every cache array
        for leaf in jax.tree_util.tree_leaves((eng.params, eng.kv.arrays)):
            leaf.delete()

    kinds = sambay.layer_kinds(mcfg)
    state = eng.kv.bytes_by_kind()
    return ServeProgram(
        engine=eng, compiles=compiles, free=free,
        describe={"layers": layers, "layer_kinds": list(kinds),
                  "window": mcfg.window, "ring_len": mcfg.ring_len,
                  "plane_readers": eng.kv.readers,
                  "planes": eng.kv.planes, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"],
                  "decode_attention": "packed_decode_attention kernel"
                  if fa._packed_kernel_selected(
                      eng.kv.arrays["k"].shape, mcfg.lanes)
                  else "einsum",
                  "leaf_bytes": sum(x.nbytes for x in
                                    jax.tree_util.tree_leaves(params)),
                  "state_bytes": state,
                  "state_bytes_total": sum(state.values())})
