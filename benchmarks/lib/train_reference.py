"""The reference side of a training cell's correctness check.

Follows the program through its first steps on the same seeded weights and
the same resident batch, with a plain optimizer written out here (AdamW,
SGD with momentum), in float32 at ``highest`` matmul precision, and
returns what the check compares: each step's loss, the per-leaf norm of
the first gradient, and the per-leaf norm of the parameters' change.

Nothing here comes from the program.  The reference runs on the cell's
first chip alone.
"""

import functools
import json

import jax
import jax.numpy as jnp

from . import weights

WEIGHTS_STREAM, BATCH_STREAM = 1, 2


def weights_key(seed):
    return jax.random.fold_in(weights.seed_key(seed), WEIGHTS_STREAM)


def batch_key(seed):
    return jax.random.fold_in(weights.seed_key(seed), BATCH_STREAM)


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def _adamw(o):
    b1, b2, eps = o.get("b1", 0.9), o.get("b2", 0.999), o.get("eps", 1e-8)
    lr, wd = o["learning_rate"], o.get("weight_decay", 1e-4)
    mu_dtype = jnp.dtype(o.get("mu_dtype", "float32"))

    def init(w):
        return {"m": {k: jnp.zeros(v.shape, mu_dtype) for k, v in w.items()},
                "v": {k: jnp.zeros_like(v) for k, v in w.items()},
                "t": jnp.zeros((), jnp.int32)}

    def update(w, g, s):
        t = s["t"] + 1
        c1 = 1.0 - b1 ** t.astype(jnp.float32)
        c2 = 1.0 - b2 ** t.astype(jnp.float32)
        m = {k: b1 * s["m"][k].astype(jnp.float32) + (1 - b1) * g[k]
             for k in w}
        v = {k: b2 * s["v"][k] + (1 - b2) * jnp.square(g[k]) for k in w}
        new = {k: w[k] - lr * ((m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps)
                               + wd * w[k]) for k in w}
        m = {k: x.astype(mu_dtype) for k, x in m.items()}
        return new, {"m": m, "v": v, "t": t}
    return init, update


def _sgd(o):
    lr, mom = o["learning_rate"], o.get("momentum", 0.0)

    def init(w):
        return {"trace": {k: jnp.zeros_like(v) for k, v in w.items()}}

    def update(w, g, s):
        trace = {k: g[k] + mom * s["trace"][k] for k in w}
        return {k: w[k] - lr * trace[k] for k in w}, {"trace": trace}
    return init, update


OPTIMIZERS = {"adamw": _adamw, "sgd": _sgd}


def build_step(ref, cfg, layers, traffic, quant):
    """(step, opt_init): one plain reference step, to be jitted."""
    opt_init, opt_update = OPTIMIZERS[traffic["optimizer"]["name"]](
        traffic["optimizer"])
    kwargs = dict(traffic.get("reference", {}))
    rows_per_block = kwargs.pop("rows_per_block", None)
    loss_fn = functools.partial(ref.batch_loss, cfg=cfg, layers=layers,
                                quant=quant, **kwargs)

    def loss_and_grads(w, batch):
        rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if not rows_per_block or rows_per_block >= rows:
            return jax.value_and_grad(loss_fn)(w, batch)
        blocks = jax.tree_util.tree_map(
            lambda a: a.reshape((rows // rows_per_block, rows_per_block)
                                + a.shape[1:]), batch)

        def body(acc, block):
            loss, g = jax.value_and_grad(loss_fn)(w, block)
            return (acc[0] + loss,
                    {k: acc[1][k] + g[k] for k in g}), None
        zero = (jnp.zeros(()), {k: jnp.zeros_like(v) for k, v in w.items()})
        (loss, g), _ = jax.lax.scan(body, zero, blocks)
        n = rows // rows_per_block
        return loss / n, {k: v / n for k, v in g.items()}

    def step(w, state, batch):
        loss, g = loss_and_grads(w, batch)
        w, state = opt_update(w, g, state)
        return w, state, loss, leaf_norms(g)
    return step, opt_init


_PROGRAMS = {}  # one set of jitted programs per reference, for every seed


def _programs(ref, cfg, layers, traffic, quant, device):
    """The reference's jitted programs (weights, optimizer state, batch,
    step, change of the parameters); built once and kept, so that a caller
    that reads many seeds in one process (``control.py``) traces and
    compiles each only once."""
    key = (ref.__name__, json.dumps(cfg, sort_keys=True), layers,
           json.dumps(traffic, sort_keys=True), quant, device.id)
    if key not in _PROGRAMS:
        shapes = ref.weight_shapes(cfg, layers)
        step, opt_init = build_step(ref, cfg, layers, traffic, quant)
        here = jax.sharding.SingleDeviceSharding(device)

        def make_w(key):
            return weights.make(shapes, key, jnp.float32)

        def delta(w, key):
            w0 = make_w(key)
            return leaf_norms({k: w[k] - w0[k] for k in w})

        _PROGRAMS[key] = dict(
            make_w=jax.jit(make_w, out_shardings=here),
            opt_init=jax.jit(opt_init),
            make_batch=jax.jit(lambda k: ref.make_batch(k, traffic, cfg),
                               out_shardings=here),
            step=jax.jit(step, donate_argnums=(0, 1)),
            delta=jax.jit(delta))
    return _PROGRAMS[key]


def run(ref, cfg, layers, traffic, seed, devices, quant=None, steps=3):
    """{"losses": [...], "grad_norms": {leaf: x}, "delta_norms": {leaf: x}}
    after ``steps`` reference steps (python floats)."""
    p = _programs(ref, cfg, layers, traffic, quant, devices[0])
    with jax.default_matmul_precision("highest"):
        key = weights_key(seed)
        w = p["make_w"](key)
        state = p["opt_init"](w)
        batch = p["make_batch"](batch_key(seed))
        losses, grad_norms = [], None
        for i in range(steps):
            w, state, loss, gn = p["step"](w, state, batch)
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(v) for k, v in gn.items()}
        delta_norms = {k: float(v) for k, v in p["delta"](w, key).items()}
    for leaf in (jax.tree_util.tree_leaves((w, state, batch))):
        leaf.delete()
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}
