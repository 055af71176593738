"""The system under test for the GLM-4.7-Flash configuration: the program's
own decoder with latent attention and dropless experts
(``horovod_tpu/models/latent_moe.py``) behind ``serving.ServeEngine``, the
same engine, scheduler, queue and cache manager as every other served
model; the cache holds ONE positional kind, the latent.

Weights are the benchmark's (``lib/weights.py``, names from
``reference/glm_moe_lite.py``), made on the device in bfloat16 in one
jitted call; this file nests them the way the program's parameter tree
wants and applies the configuration's ``assumed.init`` rule by its own
code: the experts' three stacks times 2^``expert_gain_log2`` (exact in
bfloat16), because ``lib/weights.py`` scales a stack by its rows and not
by one expert's, and the selection bias times 2^``bias_gain_log2``, so
that the seeded routing is near uniform.
"""

import time

import jax
import jax.numpy as jnp

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram


def depth(config, traffic):
    return config["num_hidden_layers"][traffic["layout"]]


def latent_moe_config(config, layers, **overrides):
    from horovod_tpu.models import latent_moe
    if config["topk_method"] != "noaux_tc" or config["n_group"] != 1 or \
            config["topk_group"] != 1:
        raise ValueError("the program routes noaux_tc with one group")
    if config["rope_scaling"] is not None or \
            config["partial_rotary_factor"] != 1 or \
            config["attention_bias"] or config["hidden_act"] != "silu" or \
            config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the program has no rotary scaling, no partial "
                         "rotary part of the rotary lanes, no attention "
                         "bias, SwiGLU, and as many key as query heads")
    kw = dict(vocab_size=config["vocab_size"], num_layers=layers,
              d_model=config["hidden_size"],
              num_heads=config["num_attention_heads"],
              q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
              nope_dim=config["qk_nope_head_dim"],
              rope_dim=config["qk_rope_head_dim"],
              v_dim=config["v_head_dim"],
              rope_theta=float(config["rope_theta"]),
              d_ff=config["intermediate_size"],
              first_dense=config["first_k_dense_replace"],
              num_experts=config["n_routed_experts"],
              experts_per_tok=config["num_experts_per_tok"],
              shared_experts=config["n_shared_experts"],
              d_expert=config["moe_intermediate_size"],
              route_scale=config["routed_scaling_factor"],
              route_normalise=config["norm_topk_prob"],
              rms_eps=config["rms_norm_eps"],
              max_seq_len=config["max_position_embeddings"],
              dtype=jnp.bfloat16,
              tie_embeddings=config["tie_word_embeddings"],
              attention_impl="flash")
    kw.update(overrides)
    return latent_moe.LatentMoEConfig(**kw)


def to_tree(w, layers, config):
    """{reference name: x} -> the program's parameter tree, the
    ``assumed.init`` gains on the experts' stacks and the selection bias."""
    init = config["assumed"]["init"]
    dtype = w["head"].dtype
    gain = jnp.asarray(2.0 ** init["expert_gain_log2"], dtype)
    bias_gain = jnp.asarray(2.0 ** init["bias_gain_log2"], dtype)

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
            "attn": {"q_a": kernel(p + "attn.q_a"),
                     "q_norm": {"scale": w[p + "attn.q_norm.scale"]},
                     "q_b": kernel(p + "attn.q_b"),
                     "kv_a": kernel(p + "attn.kv_a"),
                     "kv_norm": {"scale": w[p + "attn.kv_norm.scale"]},
                     "kv_b": kernel(p + "attn.kv_b"),
                     "out": kernel(p + "attn.o")}}
        if i < config["first_k_dense_replace"]:
            layer.update(swiglu(p + "mlp."))
        else:
            layer["router"] = {"kernel": w[p + "router.w"],
                               "bias": w[p + "router.bias"] * bias_gain}
            layer["experts"] = {n: w[p + "experts." + n] * gain
                                for n in ("gate", "up", "down")}
            layer["shared"] = swiglu(p + "shared.")
        tree[f"layer_{i}"] = layer
    return tree


def build_serve(run, clock=time.monotonic):
    # a program without the model fails here, before any weight
    from horovod_tpu.models import latent_moe  # noqa: F401
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    mcfg = latent_moe_config(config, layers,
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
        describe={"layers": layers, "expert_layers": mcfg.expert_layers,
                  "experts": mcfg.num_experts,
                  "experts_per_tok": mcfg.experts_per_tok,
                  "shared_experts": mcfg.shared_experts,
                  "planes": eng.kv.planes, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"],
                  "latent_numbers": mcfg.latent_dim,
                  "latent_lanes": mcfg.latent_lanes,
                  "state_bytes": eng.kv.bytes_by_kind()})
