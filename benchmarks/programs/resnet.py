"""The system under test for the ResNet-50 configuration: the program's
``models/resnet.py`` at 224 px in bf16, SGD with momentum through
``hvd.DistributedOptimizer``, behind ``trainer.make_data_parallel_step``
(shard_map + fused psum) - ``examples/bench_common.build_step``'s recipe
with the benchmark's seeded weights and batch in place of its zeros.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.lib import train_reference as tref
from benchmarks.lib import weights
from benchmarks.lib.program import TrainProgram
from benchmarks.reference import resnet as ref


def depth(config, traffic):
    """ResNet-50's depth is never cut."""
    return None


def _block_names():
    idx = 0
    for i, blocks in enumerate(ref.STAGES):
        for j in range(blocks):
            yield f"stage{i}.block{j}.", f"BottleneckBlock_{idx}"
            idx += 1


def to_tree(w):
    """{reference name: x} -> the program's (flax) parameter tree."""
    def bn(prefix):
        gain = ".branch_scale" if prefix.endswith("bn3") else ".scale"
        return {"scale": w[prefix + gain], "bias": w[prefix + ".bias"]}
    tree = {"conv_init": {"kernel": w["conv_init"]}, "bn_init": bn("bn_init"),
            "Dense_0": {"kernel": w["fc.kernel"], "bias": w["fc.bias"]}}
    for p, name in _block_names():
        block = {}
        for n in ("1", "2", "3"):
            block[f"Conv_{int(n) - 1}"] = {"kernel": w[p + "conv" + n]}
            block[f"BatchNorm_{int(n) - 1}"] = bn(p + "bn" + n)
        if p + "proj" in w:
            block["conv_proj"] = {"kernel": w[p + "proj"]}
            block["norm_proj"] = bn(p + "bn_proj")
        tree[name] = block
    return tree


def from_tree(tree):
    out = {"conv_init": tree["conv_init"]["kernel"],
           "bn_init.scale": tree["bn_init"]["scale"],
           "bn_init.bias": tree["bn_init"]["bias"],
           "fc.kernel": tree["Dense_0"]["kernel"],
           "fc.bias": tree["Dense_0"]["bias"]}
    for p, name in _block_names():
        block = tree[name]
        for n in ("1", "2", "3"):
            out[p + "conv" + n] = block[f"Conv_{int(n) - 1}"]["kernel"]
            gain = ".branch_scale" if n == "3" else ".scale"
            out[p + f"bn{n}" + gain] = block[f"BatchNorm_{int(n) - 1}"]["scale"]
            out[p + f"bn{n}.bias"] = block[f"BatchNorm_{int(n) - 1}"]["bias"]
        if "conv_proj" in block:
            out[p + "proj"] = block["conv_proj"]["kernel"]
            out[p + "bn_proj.scale"] = block["norm_proj"]["scale"]
            out[p + "bn_proj.bias"] = block["norm_proj"]["bias"]
    return out


def build_train(run):
    import horovod_tpu as hvd
    from horovod_tpu import models, trainer

    config, traffic = run.config, run.traffic
    opt = traffic["optimizer"]
    hvd.init()
    mesh = Mesh(np.asarray(run.devices), hvd.mesh().axis_names[:1])
    axis = mesh.axis_names[0]
    model = models.build(config["model"], num_classes=config["num_classes"],
                         dtype=jnp.bfloat16)
    px = config["image_size"]
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, px, px, 3), jnp.bfloat16),
                           train=False))
    shapes = ref.weight_shapes(config)
    want = jax.tree_util.tree_map(lambda a: a.shape, variables["params"])
    got = to_tree(dict(shapes))
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(got) or \
            jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(got):
        raise RuntimeError("reference/resnet.py's weight table no longer "
                           "matches the program's ResNet-50 parameter tree")
    # running statistics: read by apply(), unused with train=True
    batch_stats = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), variables["batch_stats"])

    tx = hvd.DistributedOptimizer(
        optax.sgd(opt["learning_rate"], momentum=opt["momentum"]))

    def loss_fn(p, b):
        imgs, lbls = b
        logits, _ = model.apply({"params": p, "batch_stats": batch_stats},
                                imgs, train=True, mutable=["batch_stats"])
        return trainer.softmax_cross_entropy(logits, lbls)

    step = trainer.make_data_parallel_step(loss_fn, tx, mesh, donate=True)
    rep = NamedSharding(mesh, P())
    with run.setup_item("weights"):
        params = jax.jit(
            lambda k: to_tree(weights.make(shapes, k, jnp.float32)),
            out_shardings=rep)(tref.weights_key(run.seed))
        opt_state = trainer.init_opt_state(tx, params, mesh)
        batch = jax.jit(lambda k: ref.make_batch(k, traffic, config),
                        out_shardings=NamedSharding(mesh, P(axis)))(
                            tref.batch_key(run.seed))
        jax.block_until_ready((params, opt_state, batch))

    @jax.jit
    def first_grad_norms(opt_state):
        # after one step of SGD with momentum the trace IS the gradient
        return tref.leaf_norms(from_tree(
            optax.tree_utils.tree_get(opt_state, "trace")))

    @jax.jit
    def delta_norms(params, key):
        w0 = weights.make(shapes, key, jnp.float32)
        now = from_tree(params)
        return tref.leaf_norms({k: now[k] - w0[k] for k in w0})

    def floats(tree):
        return {k: float(v) for k, v in tree.items()}

    return TrainProgram(
        step=step, params=params, opt_state=opt_state, batch=batch,
        items_per_step=traffic["global_batch"], chips=len(run.devices),
        first_grad_norms=lambda o: floats(first_grad_norms(o)),
        delta_norms=lambda p: floats(
            delta_norms(p, tref.weights_key(run.seed))),
        describe={"model": config["model"], "image_size": px,
                  "batch": traffic["global_batch"],
                  "parameters": sum(int(jnp.prod(jnp.array(s)))
                                    for s in shapes.values())})
