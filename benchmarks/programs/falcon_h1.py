"""The system under test for the Falcon-H1 configuration: the program's
own hybrid block (``horovod_tpu/models/hybrid.py``: a Mamba-2 mixer and
grouped-query attention in parallel, then a SwiGLU, with the family's
multipliers) behind ``serving.ServeEngine``, the same engine, scheduler,
queue and cache manager as every other served model.

Weights are the benchmark's (``lib/weights.py``, names from
``reference/falcon_h1.py``), made on the device in bfloat16 in one jitted
call; a second call, which is given them materialised and donated, maps
them to the model's leaves by the rule the configuration file states
under ``assumed.init`` and nests them the way the program's parameter
tree wants.  (Two calls, because inside one XLA drops a float32 ->
bfloat16 -> float32 round trip on the TPU and the mixer's vectors would
be made from unrounded noise, which the reference never sees: PERF.md §6,
PR 26.)  The rule is written here by this file's own code; the reference
applies it by its own.
"""

import math
import time

import jax
import jax.numpy as jnp

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram


def depth(config, traffic):
    return config["num_hidden_layers"][traffic["layout"]]


def hybrid_config(config, layers, **overrides):
    from horovod_tpu.models import hybrid
    kw = dict(
        vocab_size=config["vocab_size"], num_layers=layers,
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"],
        ssm_groups=config["mamba_n_groups"],
        conv_width=config["mamba_d_conv"], chunk=config["mamba_chunk_size"],
        rms_eps=config["rms_norm_eps"],
        embedding_multiplier=config["embedding_multiplier"],
        lm_head_multiplier=config["lm_head_multiplier"],
        attention_in_multiplier=float(config["attention_in_multiplier"]),
        attention_out_multiplier=config["attention_out_multiplier"],
        key_multiplier=config["key_multiplier"],
        ssm_in_multiplier=config["ssm_in_multiplier"],
        ssm_out_multiplier=config["ssm_out_multiplier"],
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        max_seq_len=config["max_position_embeddings"],
        dtype=jnp.bfloat16, attention_impl="flash")
    if config["mamba_d_ssm"] != kw["ssm_heads"] * kw["ssm_head_dim"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads x mamba_d_head")
    kw.update(overrides)
    return hybrid.HybridConfig(**kw)


def _phi(noise):
    return 0.5 * (1.0 + jax.lax.erf(noise.astype(jnp.float32)
                                    / math.sqrt(2.0)))


def to_tree(w, layers, config):
    """{reference name: drawn leaf} -> the program's parameter tree, the
    ``assumed.init`` rule applied: a power-of-two gain on each matrix (in
    the leaf's own type: exact), the mixer's vectors from the drawn normal
    values through their distribution function, in float32."""
    init = config["assumed"]["init"]
    log2 = init["gains_log2"]

    def mat(name):
        leaf = w[name]
        g = log2.get(name.rpartition(".")[2], 0)
        return leaf if g == 0 else leaf * jnp.asarray(2.0 ** g, leaf.dtype)

    tree = {"embed": w["embed"], "ln_f": w["ln_f.scale"],
            "lm_head": mat("head")}
    a0, a1 = init["A"]["min"], init["A"]["max"]
    d0, d1 = math.log(init["dt"]["min"]), math.log(init["dt"]["max"])
    for i in range(layers):
        p = f"layers.{i}."
        dt = jnp.exp(d0 + (d1 - d0) * _phi(w[p + "mixer.dt"]))
        a = a0 + (a1 - a0) * _phi(w[p + "mixer.A"])
        tree[f"layer_{i}"] = {
            "ln_in": w[p + "ln_in.scale"], "ln_ff": w[p + "ln_ff.scale"],
            "attn": {n: mat(p + "attn." + n) for n in "qkvo"},
            "mixer": {
                "in_proj": mat(p + "mixer.in_proj"),
                "conv": w[p + "mixer.conv"],
                "conv_bias": w[p + "mixer.conv.bias"],
                "A_log": jnp.log(a),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.full_like(a, init["D"]),
                "norm": w[p + "mixer.norm.scale"],
                "out_proj": mat(p + "mixer.out_proj")},
            "mlp": {n: mat(p + "mlp." + n) for n in ("gate", "up", "down")}}
    return tree


def make_params(shapes, key, layers, config):
    """The program's parameter tree for ``key``, on the device."""
    drawn = jax.jit(lambda k: weights.make(shapes, k, jnp.bfloat16))(key)
    return jax.jit(lambda w: to_tree(w, layers, config),
                   donate_argnums=0)(drawn)


def build_serve(run, clock=time.monotonic):
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    hcfg = hybrid_config(config, layers,
                         **traffic.get("model_overrides", {}))
    shapes = ref.weight_shapes(config, layers)
    with run.setup_item("weights"):
        params = make_params(shapes, tref.weights_key(run.seed), layers,
                             config)
        jax.block_until_ready(params)
    with run.setup_item("engine"):
        queue = AdmissionQueue(
            admission_timeout_s=eng_kw["admission_timeout_s"], clock=clock)
        eng = engine_mod.ServeEngine(
            hcfg, params, num_slots=eng_kw["num_slots"],
            max_len=eng_kw["max_len"], kv_block=eng_kw["kv_block"],
            queue=queue, seed=0, clock=clock)

    def compiles():
        return {"prefill": engine_mod._prefill_jit._cache_size(),
                "decode": engine_mod._decode_jit._cache_size()}

    def free():
        # the weights and EVERY kind of state the cache holds
        for leaf in jax.tree_util.tree_leaves((eng.params, eng.kv.arrays)):
            leaf.delete()

    return ServeProgram(
        engine=eng, compiles=compiles, free=free,
        describe={"layers": layers, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"],
                  "state_bytes": eng.kv.bytes_by_kind()})
