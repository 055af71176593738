"""The system under test for the Laguna-XS.2 configuration: the program's
own decoder with window and full attention layers, a per-head output gate
and dropless experts (``horovod_tpu/models/window_moe.py``) behind
``serving.ServeEngine``, the same engine, scheduler, queue and cache
manager as every other served model; the cache holds TWO classes of
positional state, K/V of the full layers at ``max_len`` a row and rings of
the window layers.

Weights are the benchmark's (``lib/weights.py``, names from
``reference/laguna.py``), made on the device in bfloat16 in one jitted
call; this file nests them the way the program's parameter tree wants and
applies the configuration's ``assumed.init`` rule by its own code: the
experts' three stacks times 2^``expert_gain_log2`` (exact in bfloat16),
because ``lib/weights.py`` scales a stack by its rows and not by one
expert's.
"""

import time

import jax
import jax.numpy as jnp

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def depth(config, traffic):
    return config["num_hidden_layers"][traffic["layout"]]


def rotary(law):
    """One kind of layer's ``rope_parameters`` as the program's law."""
    from horovod_tpu.models import window_moe
    if law["rope_type"] == "default":
        return window_moe.Rotary(
            theta=float(law["rope_theta"]),
            fraction=float(law["partial_rotary_factor"]))
    if law["rope_type"] != "yarn":
        raise ValueError(f"the program has no rope_type {law['rope_type']!r}")
    return window_moe.Rotary(
        theta=float(law["rope_theta"]),
        fraction=float(law["partial_rotary_factor"]),
        factor=float(law["factor"]),
        original_len=law["original_max_position_embeddings"],
        beta_fast=float(law["beta_fast"]), beta_slow=float(law["beta_slow"]),
        attention_factor=float(law["attention_factor"]))


def window_moe_config(config, layers, **overrides):
    from horovod_tpu.models import window_moe
    dense = [t == "dense" for t in config["mlp_layer_types"][:layers]]
    first_dense = sum(dense)
    if dense != [True] * first_dense + [False] * (layers - first_dense):
        raise ValueError("the program's dense layers lead the stack")
    if config["attention_bias"] or config["gating"] is not True or \
            config["moe_apply_router_weight_on_input"] or \
            config["shared_expert_intermediate_size"] <= 0:
        raise ValueError("the program has no attention bias, gates every "
                         "head's output, weighs the experts' outputs and "
                         "has one shared expert")
    kw = dict(vocab_size=config["vocab_size"], d_model=config["hidden_size"],
              head_dim=config["head_dim"],
              num_kv_heads=config["num_key_value_heads"],
              layer_types=tuple(KINDS[t]
                                for t in config["layer_types"][:layers]),
              heads_per_layer=tuple(
                  config["num_attention_heads_per_layer"][:layers]),
              window=config["sliding_window"],
              rope_full=rotary(config["rope_parameters"]["full_attention"]),
              rope_window=rotary(
                  config["rope_parameters"]["sliding_attention"]),
              d_ff=config["intermediate_size"], first_dense=first_dense,
              num_experts=config["num_experts"],
              experts_per_tok=config["num_experts_per_tok"],
              d_expert=config["moe_intermediate_size"],
              d_shared=config["shared_expert_intermediate_size"],
              route_scale=config["moe_routed_scaling_factor"],
              route_normalise=True, rms_eps=config["rms_norm_eps"],
              max_seq_len=config["max_position_embeddings"],
              dtype=jnp.bfloat16,
              tie_embeddings=config["tie_word_embeddings"],
              attention_impl="flash")
    kw.update(overrides)
    return window_moe.WindowMoEConfig(**kw)


def to_tree(w, layers, config):
    """{reference name: x} -> the program's parameter tree, the
    ``assumed.init`` gain on the experts' stacks."""
    gain = jnp.asarray(2.0 ** config["assumed"]["init"]["expert_gain_log2"],
                       w["head"].dtype)

    def kernel(name):
        return {"kernel": w[name]}

    def swiglu(prefix):
        return {"mlp": {n: kernel(prefix + n)
                        for n in ("gate", "up", "down")}}
    tree = {"embed": {"embedding": w["embed"]},
            "ln_f": {"scale": w["ln_f.scale"]},
            "lm_head": kernel("head")}
    for i in range(layers):
        p = f"layers.{i}."
        layer = {
            "ln_attn": {"scale": w[p + "ln_attn.scale"]},
            "ln_mlp": {"scale": w[p + "ln_mlp.scale"]},
            "attn": {"q": kernel(p + "attn.q"), "k": kernel(p + "attn.k"),
                     "v": kernel(p + "attn.v"),
                     "gate": kernel(p + "attn.gate"),
                     "out": kernel(p + "attn.o")}}
        if config["mlp_layer_types"][i] == "dense":
            layer.update(swiglu(p + "mlp."))
        else:
            layer["router"] = kernel(p + "router.w")
            layer["experts"] = {n: w[p + "experts." + n] * gain
                                for n in ("gate", "up", "down")}
            layer["shared"] = swiglu(p + "shared.")
        tree[f"layer_{i}"] = layer
    return tree


def build_serve(run, clock=time.monotonic):
    # a program without the model fails here, before any weight
    from horovod_tpu.models import window_moe  # noqa: F401
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    mcfg = window_moe_config(config, layers,
                             **traffic.get("model_overrides", {}))
    shapes = ref.weight_shapes(config, layers)
    with run.setup_item("weights"):
        params = jax.jit(lambda k: to_tree(
            weights.make(shapes, k, jnp.bfloat16), layers, config))(
                tref.weights_key(run.seed))
        jax.block_until_ready(params)
    with run.setup_item("engine"):
        queue = AdmissionQueue(
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

    return ServeProgram(
        engine=eng, compiles=compiles, free=free,
        describe={"layers": layers, "layer_types": list(mcfg.layer_types),
                  "heads_per_layer": list(mcfg.heads_per_layer),
                  "window": mcfg.window, "ring_len": mcfg.ring_len,
                  "expert_layers": mcfg.expert_layers,
                  "experts": mcfg.num_experts,
                  "experts_per_tok": mcfg.experts_per_tok,
                  "planes": eng.kv.planes, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"],
                  "state_bytes": eng.kv.bytes_by_kind()})
