"""ResNet family (flax.linen), TPU-first.

Capability parity with the torchvision zoo the reference instantiates by name
(``models.__dict__[args.arch]()``, reference distributed.py:21-23,134-139):
resnet18/34/50/101/152 plus the wide and ResNeXt variants, same
block/stage/width structure and BatchNorm placement as the torchvision
definitions, so top-1/top-5 oracles are comparable.

TPU-first choices:
- **NHWC** layout (XLA's native conv layout on TPU; MXU-friendly).
- ``dtype`` policy: params live in f32, compute may be bf16 — the
  apex-AMP-equivalent (SURVEY.md §7.1 "bf16 compute/param policy"); BatchNorm
  statistics always accumulate in f32.
- BatchNorm over a data-sharded batch under GSPMD computes *global* batch
  statistics (XLA inserts the cross-replica mean) — i.e. SyncBN semantics,
  strictly stronger than torch DDP's local-stats BN; documented delta.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.ops.fused_bn import FusedBatchNormAct

ModuleDef = Any


class BasicBlock(nn.Module):
    filters: int
    strides: int = 1
    expansion: int = 1
    groups: int = 1
    base_width: int = 64
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = FusedBatchNormAct

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3),
                      (self.strides, self.strides),
                      padding=[(1, 1), (1, 1)], use_bias=False)(x)
        y = self.norm(relu=True)(y)
        y = self.conv(self.filters, (3, 3), padding=[(1, 1), (1, 1)],
                      use_bias=False)(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * self.expansion, (1, 1),
                                 (self.strides, self.strides),
                                 use_bias=False)(residual)
            residual = self.norm()(residual)
        return nn.relu(y + residual)


class Bottleneck(nn.Module):
    filters: int
    strides: int = 1
    expansion: int = 4
    groups: int = 1
    base_width: int = 64
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = FusedBatchNormAct

    @nn.compact
    def __call__(self, x):
        residual = x
        width = int(self.filters * (self.base_width / 64.0)) * self.groups
        out_ch = self.filters * self.expansion
        y = self.conv(width, (1, 1), use_bias=False)(x)
        y = self.norm(relu=True)(y)
        y = self.conv(width, (3, 3), (self.strides, self.strides),
                      padding=[(1, 1), (1, 1)], use_bias=False,
                      feature_group_count=self.groups)(y)
        y = self.norm(relu=True)(y)
        y = self.conv(out_ch, (1, 1), use_bias=False)(y)
        # Zero-init the last BN scale so blocks start as identity
        # (torchvision zero_init_residual analogue; helps large-batch SGD).
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(out_ch, (1, 1),
                                 (self.strides, self.strides),
                                 use_bias=False)(residual)
            residual = self.norm()(residual)
        return nn.relu(y + residual)


class _SpaceToDepthStem(nn.Module):
    """7x7/s2/p3 stem conv, computed as a 4x4/s1 conv on 2x2-space-to-depth
    packed input — the MLPerf TPU ResNet trick.

    A 3-channel 224x224 conv leaves the MXU's 128-lane contraction dimension
    ~2% utilized; packing 2x2 spatial blocks into channels turns the same
    arithmetic into a 12-channel conv at 112x112 that XLA tiles far better.
    **Mathematically identical** to the standard stem (same 7x7 kernel
    parameters, zero-padded to 8x8 and repacked at trace time; even input
    sizes required): the parameter is still ``conv_init/kernel`` of shape
    (7, 7, 3, features), so checkpoints are interchangeable with the
    ``conv7`` stem — equivalence is asserted by tests/test_model_zoo.py.
    """

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        N, H, W, C = x.shape
        if H % 2 or W % 2:
            raise ValueError(
                f"space_to_depth stem needs even spatial dims, got {H}x{W}")
        w7 = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (7, 7, C, self.features), jnp.float32,
        )
        # Output row h' of the s2/p3 7x7 conv reads input rows 2h'-3..2h'+3.
        # Aligning the window to the packed grid means basing it at 2h'-4,
        # i.e. an 8x8 kernel whose first row/col is zero; tap j of that
        # kernel is tap j-1 of the 7x7 one.
        w8 = jnp.pad(w7, ((1, 0), (1, 0), (0, 0), (0, 0)))
        wp = (
            w8.reshape(4, 2, 4, 2, C, self.features)
            .transpose(0, 2, 1, 3, 4, 5)
            .reshape(4, 4, 4 * C, self.features)
        )
        xp = (
            x.reshape(N, H // 2, 2, W // 2, 2, C)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(N, H // 2, W // 2, 4 * C)
        )
        return jax.lax.conv_general_dilated(
            xp.astype(self.dtype), wp.astype(self.dtype),
            (1, 1), ((2, 1), (2, 1)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: Callable
    num_classes: int = 1000
    num_filters: int = 64
    groups: int = 1
    base_width: int = 64
    dtype: Any = jnp.float32
    stem: str = "conv7"  # "conv7" (torchvision) | "space_to_depth" (same math)
    # SyncBN under shard_map: psum BN moments over this mesh axis (torch
    # nn.SyncBatchNorm ≙).  None = per-shard statistics (torch DDP default).
    bn_axis_name: Any = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, dtype=self.dtype)
        norm_kw = dict(
            use_running_average=not train,
            momentum=0.9,           # torch BatchNorm2d momentum=0.1 ⇒ ema decay 0.9
            epsilon=1e-5,
        )
        if self.bn_axis_name is not None:
            # Set only for SyncBN: a per-shard model keeps the partial it
            # always had (FusedBatchNormAct's own default is None).
            norm_kw["axis_name"] = self.bn_axis_name
        norm = functools.partial(FusedBatchNormAct, **norm_kw)
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = _SpaceToDepthStem(self.num_filters, self.dtype,
                                  name="conv_init")(x)
        elif self.stem == "conv7":
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], use_bias=False,
                     name="conv_init")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}")
        x = norm(name="bn_init", relu=True)(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = 2 if i > 0 and j == 0 else 1
                x = self.block_cls(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    groups=self.groups,
                    base_width=self.base_width,
                    conv=conv,
                    norm=norm,
                )(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="fc")(x)
        return x


# Stage configurations mirror torchvision's resnet table.
resnet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=BasicBlock)
resnet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BasicBlock)
resnet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck)
resnet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck)
resnet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=Bottleneck)
wide_resnet50_2 = functools.partial(
    ResNet, stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck, base_width=128
)
wide_resnet101_2 = functools.partial(
    ResNet, stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck, base_width=128
)
resnext50_32x4d = functools.partial(
    ResNet, stage_sizes=[3, 4, 6, 3], block_cls=Bottleneck, groups=32, base_width=4
)
resnext101_32x8d = functools.partial(
    ResNet, stage_sizes=[3, 4, 23, 3], block_cls=Bottleneck, groups=32, base_width=8
)
