"""The system under test for the Ouro configuration: the program's own
looped decoder (``horovod_tpu/models/looped.py``: the dense block, a norm
closing each branch, the layer stack run ``total_ut_steps`` times over one
set of weights) behind ``serving.ServeEngine``, the same engine,
scheduler, queue and cache manager as every other served model; the cache
holds K/V per (pass, layer) plane.

Weights are the benchmark's (``lib/weights.py``, names from
``reference/ouro.py``), made on the device in bfloat16 in one jitted call;
this file nests them the way the program's parameter tree wants, and
concatenates the reference's separate q, k, v leaves into the program's
fused ``qkv`` kernel (the same mathematics; a concatenation of bfloat16
leaves rounds nothing).
"""

import time

import jax
import jax.numpy as jnp

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram

RMS_EPS = 1e-6  # the program's RMSNorm (serving/decode._rmsnorm)


def depth(config, traffic):
    return config["num_hidden_layers"][traffic["layout"]]


def looped_config(config, layers, **overrides):
    from horovod_tpu.models import looped
    if config["rms_norm_eps"] != RMS_EPS:
        raise ValueError(f"the program's norms have eps {RMS_EPS}, the "
                         f"configuration says {config['rms_norm_eps']}")
    if config["num_key_value_heads"] != config["num_attention_heads"] or \
            config["num_attention_heads"] * config["head_dim"] != \
            config["hidden_size"]:
        raise ValueError("the dense block's fused qkv wants as many "
                         "key/value as query heads, of hidden / heads")
    kw = dict(vocab_size=config["vocab_size"], num_layers=layers,
              num_heads=config["num_attention_heads"],
              d_model=config["hidden_size"],
              d_ff=config["intermediate_size"],
              passes=config["total_ut_steps"], sandwich_norm=True,
              rope_theta=float(config["rope_theta"]),
              exit_threshold=float(config["early_exit_threshold"]),
              max_seq_len=config["max_position_embeddings"],
              dtype=jnp.bfloat16,
              tie_embeddings=config["tie_word_embeddings"],
              attention_impl="flash")
    kw.update(overrides)
    return looped.LoopedConfig(**kw)


def to_tree(w, layers):
    """{reference name: x} -> the program's parameter tree."""
    tree = {"embed": {"embedding": w["embed"]},
            "ln_f": {"scale": w["ln_f.scale"]},
            "exit_gate": {"kernel": w["exit_gate.w"],
                          "bias": w["exit_gate.bias"]},
            "lm_head": {"kernel": w["head"]}}
    for i in range(layers):
        p = f"layers.{i}."
        qkv = jnp.concatenate([w[p + "attn." + n] for n in "qkv"], axis=1)
        tree[f"layer_{i}"] = {
            "ln_attn": {"scale": w[p + "ln_attn.scale"]},
            "attn": {"qkv": {"kernel": qkv},
                     "out": {"kernel": w[p + "attn.o"]}},
            "ln_attn_out": {"scale": w[p + "ln_attn_out.scale"]},
            "ln_mlp": {"scale": w[p + "ln_mlp.scale"]},
            "mlp": {n: {"kernel": w[p + "mlp." + n]}
                    for n in ("gate", "up", "down")},
            "ln_mlp_out": {"scale": w[p + "ln_mlp_out.scale"]}}
    return tree


def build_serve(run, clock=time.monotonic):
    # a program without the looped model fails here, before any weight
    from horovod_tpu.models import looped  # noqa: F401
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    lcfg = looped_config(config, layers,
                         **traffic.get("model_overrides", {}))
    shapes = ref.weight_shapes(config, layers)
    with run.setup_item("weights"):
        params = jax.jit(lambda k: to_tree(
            weights.make(shapes, k, jnp.bfloat16), layers))(
                tref.weights_key(run.seed))
        jax.block_until_ready(params)
    with run.setup_item("engine"):
        queue = AdmissionQueue(
            admission_timeout_s=eng_kw["admission_timeout_s"], clock=clock)
        eng = engine_mod.ServeEngine(
            lcfg, params, num_slots=eng_kw["num_slots"],
            max_len=eng_kw["max_len"], kv_block=eng_kw["kv_block"],
            queue=queue, seed=0, clock=clock)

    def compiles():
        return {"prefill": engine_mod._prefill_jit._cache_size(),
                "decode": engine_mod._decode_jit._cache_size()}

    def free():
        # the weights and every plane of the cache
        for leaf in jax.tree_util.tree_leaves((eng.params, eng.kv.arrays)):
            leaf.delete()

    return ServeProgram(
        engine=eng, compiles=compiles, free=free,
        # ``layers`` is the WEIGHT layers (what the counts are handed);
        # the cache holds ``planes`` = passes x layers of K/V
        describe={"layers": layers, "passes": lcfg.passes,
                  "planes": eng.kv.planes, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"],
                  "state_bytes": eng.kv.bytes_by_kind()})
