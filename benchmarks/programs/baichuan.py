"""The system under test for the Baichuan-7B configuration: the program's
own ``TransformerLM`` (``TransformerConfig`` expresses the block exactly:
RMSNorm, fused qkv, rotary, SwiGLU, no biases, untied head) behind
``trainer.make_gspmd_step`` for training and ``serving.ServeEngine`` for
serving.  Weights and batch are the benchmark's (``lib/weights.py``, names
from ``reference/baichuan.py``), made on the device in one jitted call;
this file only nests them the way the program's parameter tree wants.
"""

import time

import jax
import jax.numpy as jnp
import optax

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import ServeProgram, TrainProgram
from benchmarks.reference import baichuan as ref


def depth(config, traffic):
    return config["num_hidden_layers"][traffic["layout"]]


def transformer_config(config, layers, **overrides):
    from horovod_tpu.models import transformer as tr
    kw = dict(vocab_size=config["vocab_size"], num_layers=layers,
              num_heads=config["num_attention_heads"],
              d_model=config["hidden_size"],
              d_ff=config["intermediate_size"],
              max_seq_len=config["max_position_embeddings"],
              dtype=jnp.bfloat16, tie_embeddings=False,
              attention_impl="flash")
    kw.update(overrides)
    return tr.TransformerConfig(**kw)


def to_tree(w, layers):
    """{reference name: x} -> the program's parameter tree."""
    tree = {"embed": {"embedding": w["embed"]},
            "ln_f": {"scale": w["ln_f.scale"]},
            "lm_head": {"kernel": w["head"]}}
    for i in range(layers):
        p = f"layers.{i}."
        tree[f"layer_{i}"] = {
            "ln_attn": {"scale": w[p + "ln_attn.scale"]},
            "attn": {"qkv": {"kernel": w[p + "qkv"]},
                     "out": {"kernel": w[p + "out"]}},
            "ln_mlp": {"scale": w[p + "ln_mlp.scale"]},
            "mlp": {"gate": {"kernel": w[p + "gate"]},
                    "up": {"kernel": w[p + "up"]},
                    "down": {"kernel": w[p + "down"]}}}
    return tree


def from_tree(tree, layers):
    out = {"embed": tree["embed"]["embedding"],
           "ln_f.scale": tree["ln_f"]["scale"],
           "head": tree["lm_head"]["kernel"]}
    for i in range(layers):
        p, t = f"layers.{i}.", tree[f"layer_{i}"]
        out[p + "ln_attn.scale"] = t["ln_attn"]["scale"]
        out[p + "qkv"] = t["attn"]["qkv"]["kernel"]
        out[p + "out"] = t["attn"]["out"]["kernel"]
        out[p + "ln_mlp.scale"] = t["ln_mlp"]["scale"]
        for n in ("gate", "up", "down"):
            out[p + n] = t["mlp"][n]["kernel"]
    return out


def train_step(config, traffic, devices):
    """The compiled step and what placing its arguments needs; shared with
    the ahead-of-time sizing (``tools/size_cells.py``)."""
    from horovod_tpu import trainer
    from horovod_tpu.models import transformer as tr
    from horovod_tpu.parallel import mesh as mesh_mod

    layers = depth(config, traffic)
    opt = traffic["optimizer"]
    mesh = mesh_mod.build_mesh(devices=devices, **traffic["mesh"])
    tcfg = transformer_config(config, layers,
                              **traffic.get("model_overrides", {}))
    model = tr.TransformerLM(tcfg)
    shapes = ref.weight_shapes(config, layers)
    abstract = to_tree({k: jax.ShapeDtypeStruct(s, jnp.float32)
                        for k, s in shapes.items()}, layers)
    specs = tr.param_specs(abstract)
    tx = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                     eps=opt["eps"], weight_decay=opt["weight_decay"],
                     mu_dtype=jnp.dtype(opt["mu_dtype"]))
    step, pshard, bshard = trainer.make_gspmd_step(
        tr.lm_loss_fn(model), tx, mesh, specs, tr.batch_spec(),
        params=abstract)
    return dict(step=step, pshard=pshard, bshard=bshard, mesh=mesh, tx=tx,
                specs=specs, shapes=shapes, abstract=abstract, tcfg=tcfg,
                layers=layers)


def build_train(run):
    import horovod_tpu as hvd
    from horovod_tpu import trainer

    config, traffic = run.config, run.traffic
    opt = traffic["optimizer"]
    hvd.init()
    built = train_step(config, traffic, run.devices)
    step, pshard, bshard = built["step"], built["pshard"], built["bshard"]
    mesh, tx, specs = built["mesh"], built["tx"], built["specs"]
    shapes, layers, tcfg = built["shapes"], built["layers"], built["tcfg"]
    with run.setup_item("weights"):
        params = jax.jit(
            lambda k: to_tree(weights.make(shapes, k, jnp.float32), layers),
            out_shardings=pshard)(tref.weights_key(run.seed))
        opt_state = trainer.init_opt_state(tx, params, mesh, specs)
        batch = jax.jit(lambda k: ref.make_batch(k, traffic, config),
                        out_shardings=bshard)(tref.batch_key(run.seed))
        jax.block_until_ready((params, opt_state, batch))

    b2 = opt["b2"]

    @jax.jit
    def first_grad_norms(opt_state):
        # after one AdamW step nu = (1 - b2) g^2, in float32
        nu = from_tree(optax.tree_utils.tree_get(opt_state, "nu"), layers)
        return {k: jnp.sqrt(jnp.sum(v) / (1.0 - b2)) for k, v in nu.items()}

    @jax.jit
    def delta_norms(params, key):
        w0 = weights.make(shapes, key, jnp.float32)
        now = from_tree(params, layers)
        return tref.leaf_norms({k: now[k] - w0[k] for k in w0})

    def floats(tree):
        return {k: float(v) for k, v in tree.items()}

    return TrainProgram(
        step=step, params=params, opt_state=opt_state, batch=batch,
        items_per_step=traffic["global_batch"] * traffic["seq_len"],
        chips=len(run.devices),
        first_grad_norms=lambda o: floats(first_grad_norms(o)),
        delta_norms=lambda p: floats(
            delta_norms(p, tref.weights_key(run.seed))),
        describe={"layers": layers, "mesh": traffic["mesh"],
                  "parameters": sum(int(jnp.prod(jnp.array(s)))
                                    for s in shapes.values()),
                  "batch": [traffic["global_batch"], traffic["seq_len"]],
                  "attention": tcfg.attention_impl})


def build_serve(run, clock=time.monotonic, to_tree=to_tree):
    """``to_tree`` is for a family that wraps this one: its reference names
    the leaves its own way (found by the family's name, as a family in
    another root has to), and its adapter nests them."""
    from horovod_tpu.serving import engine as engine_mod
    from horovod_tpu.serving.queue import AdmissionQueue

    config, traffic = run.config, run.traffic
    ref = run.registry.module("reference", traffic["family"])
    layers = depth(config, traffic)
    eng_kw = traffic["engine"]
    tcfg = transformer_config(config, layers,
                              **traffic.get("model_overrides", {}))
    shapes = ref.weight_shapes(config, layers)
    with run.setup_item("weights"):
        params = jax.jit(lambda k: to_tree(
            weights.make(shapes, k, jnp.bfloat16), layers))(
                tref.weights_key(run.seed))
        jax.block_until_ready(params)
    with run.setup_item("engine"):
        # the default queue rejects a request that waits 10 s; the first
        # run in a checkout compiles for longer than that during warm-up
        queue = AdmissionQueue(
            admission_timeout_s=eng_kw["admission_timeout_s"], clock=clock)
        eng = engine_mod.ServeEngine(
            tcfg, params, num_slots=eng_kw["num_slots"],
            max_len=eng_kw["max_len"], kv_block=eng_kw["kv_block"],
            queue=queue, seed=0, clock=clock)

    def compiles():
        return {"prefill": engine_mod._prefill_jit._cache_size(),
                "decode": engine_mod._decode_jit._cache_size()}

    def free():
        for leaf in jax.tree_util.tree_leaves(
                (eng.params, eng.kv.k, eng.kv.v)):
            leaf.delete()

    return ServeProgram(
        engine=eng, compiles=compiles, free=free,
        describe={"layers": layers, "slots": eng_kw["num_slots"],
                  "max_len": eng_kw["max_len"],
                  "kv_block": eng_kw["kv_block"]})
