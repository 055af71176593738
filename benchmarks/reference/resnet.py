"""Plain reference for ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1,
50-layer) in the v1.5 form the program trains: the stride of each
down-sampling bottleneck sits on its 3x3 convolution.

Straightforward ``jax.numpy``/``lax`` in float32, nothing imported from the
program: 7x7/2 convolution, batch norm, ReLU, 3x3/2 max pool, four stages
of [3, 4, 6, 3] bottlenecks (1x1, 3x3, 1x1 with a projection shortcut
where the shape changes), global average pool, a dense head with bias.
Batch norm uses the statistics of the batch it is given (training mode,
biased variance, epsilon 1e-5), so a batch cannot be cut into blocks of
rows; each bottleneck is rematerialised instead so that float32
activations of a whole batch fit beside the parameters.

``quant="fp8"`` is the control of the correctness check, one precision
step below the configuration's bf16 compute: every convolution and matmul
operand rounded to float8 e4m3 and every convolution's incoming gradient
to e5m2, each with a per-tensor scale.
"""

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
STAGES = (3, 4, 6, 3)
WIDTH = 64


def weight_shapes(cfg, layers=None):
    """Ordered {name: shape}; ``layers`` is unused (depth is not cut)."""
    del layers
    shapes = {"conv_init": (7, 7, 3, WIDTH), "bn_init.scale": (WIDTH,),
              "bn_init.bias": (WIDTH,)}
    cin = WIDTH
    for i, blocks in enumerate(STAGES):
        f = WIDTH * 2 ** i
        for j in range(blocks):
            p = f"stage{i}.block{j}."
            for n, shape in (("1", (1, 1, cin, f)), ("2", (3, 3, f, f)),
                             ("3", (1, 1, f, 4 * f))):
                shapes[p + "conv" + n] = shape
                # the gain that closes the residual branch is seeded small
                gain = ".branch_scale" if n == "3" else ".scale"
                shapes[p + f"bn{n}" + gain] = (shape[-1],)
                shapes[p + f"bn{n}.bias"] = (shape[-1],)
            if cin != 4 * f:
                shapes[p + "proj"] = (1, 1, cin, 4 * f)
                shapes[p + "bn_proj.scale"] = (4 * f,)
                shapes[p + "bn_proj.bias"] = (4 * f,)
            cin = 4 * f
    shapes["fc.kernel"] = (cin, cfg["num_classes"])
    shapes["fc.bias"] = (cfg["num_classes"],)
    return shapes


def _round(x, dtype, top):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """An fp8 matmul operand: e4m3 with a per-tensor scale."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


_fp8.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_grad(y):
    """An fp8 matmul result: its cotangent is rounded to e5m2 before the
    two backward matmuls read it, as fp8 training does."""
    return y


_fp8_grad.defvjp(lambda y: (y, None),
                 lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def _operands(x, w, quant):
    if quant == "fp8":
        return _fp8(x), _fp8(w)
    if quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return x, w


def conv(x, w, stride, quant):
    x, w = _operands(x, w, quant)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return _fp8_grad(y) if quant == "fp8" else y


def batch_norm(x, scale, bias):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * scale + bias


def bottleneck(w, p, x, stride, quant):
    def cbn(name, bn, t, s):
        gain = ".branch_scale" if bn == "bn3" else ".scale"
        return batch_norm(conv(t, w[p + name], s, quant),
                          w[p + bn + gain], w[p + bn + ".bias"])
    y = jax.nn.relu(cbn("conv1", "bn1", x, 1))
    y = jax.nn.relu(cbn("conv2", "bn2", y, stride))
    y = cbn("conv3", "bn3", y, 1)
    if p + "proj" in w:
        x = cbn("proj", "bn_proj", x, stride)
    return jax.nn.relu(x + y)


def logits(w, images, cfg, quant=None):
    del cfg
    x = images.astype(jnp.float32)
    x = batch_norm(conv(x, w["conv_init"], 2, quant), w["bn_init.scale"],
                   w["bn_init.bias"])
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for i, blocks in enumerate(STAGES):
        for j in range(blocks):
            p = f"stage{i}.block{j}."
            stride = 2 if i > 0 and j == 0 else 1
            block = {k: v for k, v in w.items() if k.startswith(p)}
            x = jax.checkpoint(
                lambda bw, t, p=p, s=stride: bottleneck(bw, p, t, s, quant)
            )(block, x)
    x = jnp.mean(x, axis=(1, 2))
    x, k = _operands(x, w["fc.kernel"], quant)
    return jnp.matmul(x, k) + w["fc.bias"]


def make_batch(key, traffic, cfg):
    """One resident batch: bf16 images (so program and reference read the
    same values) and labels, every row different."""
    ki, kl = jax.random.split(key)
    n, px = traffic["global_batch"], cfg["image_size"]
    images = jax.random.normal(ki, (n, px, px, 3), jnp.bfloat16)
    labels = jax.random.randint(kl, (n,), 0, cfg["num_classes"], jnp.int32)
    return images, labels


def batch_loss(w, batch, cfg, layers=None, quant=None):
    """Mean softmax cross-entropy of one batch (images, labels)."""
    del layers
    images, labels = batch
    logp = jax.nn.log_softmax(logits(w, images, cfg, quant), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1)[:, 0])
