"""ResNet v1.5 family — the reference's headline benchmark model
(docs/benchmarks.md; examples/pytorch_synthetic_benchmark.py uses
torchvision resnet50).

TPU-first choices: NHWC layout (XLA's native conv layout on TPU), bf16
compute with fp32 params/batch-stats (MXU native), and a `num_classes`-last
head. Supports 18/34/50/101/152 depths like torchvision's resnet family.
"""

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BasicBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: tuple = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        # v1.5: stride on the 3x3, not the 1x1
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train=False):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 padding="SAME")
        # flax's norm: XLA runs its convert+reduce near bandwidth and
        # fuses the normalize into the conv epilogue, which a custom_vjp
        # boundary forbids (docs/benchmarks.md)
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 momentum=0.9, epsilon=1e-5,
                                 dtype=self.dtype)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i,
                                   conv=conv, norm=norm, act=nn.relu,
                                   strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=BasicBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckBlock)

MODELS = {"resnet18": ResNet18, "resnet34": ResNet34, "resnet50": ResNet50,
          "resnet101": ResNet101, "resnet152": ResNet152}
