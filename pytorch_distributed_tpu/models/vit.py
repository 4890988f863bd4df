"""Vision Transformer family (flax.linen, TPU-first).

Beyond the reference's torchvision-0.4 zoo (its requirements.txt:2 predates
ViT), but squarely inside this framework's brief: where ResNet-50 training
is HBM-roofline-bound on TPU (see ROADMAP.md), a ViT is the MXU-native image
model — the whole network is large matmuls.  Architecture follows
torchvision's ``vit_b_16``-style encoder (class token, learned position
embeddings, pre-LN blocks, GELU MLP) so the ``-a vit_b_16`` gesture matches
what torchvision users expect.

TPU-first choices:
- patchify as reshape + one Dense (a pure-layout transform feeding a single
  [N·P², 3·p²]×[3·p², D] matmul — no conv im2col, tiles straight onto the
  MXU);
- bf16 compute policy with f32 LayerNorm/softmax accumulation and an f32
  head (same policy as the rest of the zoo);
- attention as one fused Pallas kernel forward and one backward
  (ops/short_attention.py) wherever the shape allows it: the
  ``[B, H, L, L]`` scores and probabilities never reach HBM;
- static shapes throughout: position embeddings take their grid shape from
  the init-time input (no image-size constructor knob to keep in sync); the
  class token rides as sequence position 0.

Reference anchor for the zoo surface: reference distributed.py:21-23
(arch-by-name instantiation); harness contract: ``__call__(images, train)``
like every image model here.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pytorch_distributed_tpu.ops.short_attention import (
    pick_attention,
    short_attention_on_mesh,
)


def attention(query, key, value, mask=None, broadcast_dropout=True,
              dropout_rng=None, dropout_rate=0.0, deterministic=False,
              dtype=None, precision=None, force_fp32_for_softmax=False, *,
              mesh: Optional[Mesh] = None):
    """``attention_fn`` of the encoder's ``MultiHeadDotProductAttention``:
    the fused one-block kernels where ``pick_attention`` says so, flax's
    dense attention otherwise, on ``[B, L, H, D]`` projections either way
    (the parameter tree is the module's and does not change).  No knob:
    the choice follows from the backend, the shapes, the mask and whether
    dropout acts on the probabilities.  On several devices the kernels
    need ``mesh`` to wrap themselves with (a Mosaic call cannot be
    partitioned); a model that was given none takes the dense path there,
    unless the caller is already inside a ``shard_map``."""
    _, length, heads, head_dim = query.shape
    impl = pick_attention(
        jax.default_backend(), length, heads, head_dim,
        dropout=dropout_rate > 0.0 and not deterministic,
        masked=mask is not None)
    blind = (mesh is None and jax.device_count() > 1
             and not jax.sharding.get_abstract_mesh().manual_axes)
    if impl == "fused" and not blind:
        query, key, value = nn.dtypes.promote_dtype(
            query, key, value, dtype=dtype)
        return short_attention_on_mesh(query, key, value, mesh)
    return nn.dot_product_attention(
        query, key, value, mask=mask, broadcast_dropout=broadcast_dropout,
        dropout_rng=dropout_rng, dropout_rate=dropout_rate,
        deterministic=deterministic, dtype=dtype, precision=precision,
        force_fp32_for_softmax=force_fp32_for_softmax)


class EncoderBlock(nn.Module):
    n_heads: int
    mlp_dim: int
    dropout: float = 0.0
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        h = nn.MultiHeadDotProductAttention(
            num_heads=self.n_heads,
            dtype=self.dtype,
            dropout_rate=self.dropout,
            deterministic=not train,
            # Zoo-wide numerics policy: softmax accumulates in f32 even
            # under the bf16 compute policy (same as transformer.py's
            # explicit f32 score path).
            force_fp32_for_softmax=True,
            # init wants shapes only: flax's own attention there, so that
            # no kernel is traced, compiled or loaded for a forward whose
            # output is thrown away
            attention_fn=(nn.dot_product_attention if self.is_initializing()
                          else functools.partial(attention, mesh=self.mesh)),
            name="self_attention",
        )(h, h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = x + h
        h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_fc1")(h)
        h = nn.gelu(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        h = nn.Dense(x.shape[-1], dtype=self.dtype, name="mlp_fc2")(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return x + h


class VisionTransformer(nn.Module):
    patch_size: int = 16
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    mlp_dim: int = 3072
    num_classes: int = 1000
    dropout: float = 0.0
    dtype: Any = jnp.float32
    # Checkpoint each encoder block: the backward recomputes block
    # internals instead of stashing them, cutting activation memory from
    # O(layers · k·L·D) to O(layers · L·D) block boundaries.  ViT-L/16 at
    # b128 stashes ~15 GB unchecked — past the chip's 16 GB HBM, so XLA
    # spills and the measured MFU collapses (11.9% vs vit_b's 46.5% on
    # v5e); remat trades ~1/3 more matmul FLOPs for staying resident.
    remat: bool = False
    # The mesh the step runs on (train/steps.py binds it): attention's
    # kernels wrap themselves in a shard_map over it on several devices.
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        N, H, W, C = x.shape
        p = self.patch_size
        if H % p or W % p:
            raise ValueError(
                f"image {H}x{W} not divisible by patch size {p}")
        x = x.astype(self.dtype)
        # Patchify: [N, H/p, p, W/p, p, C] -> [N, L, p*p*C] (layout only),
        # then embed with one Dense — the MXU-friendly conv-stem equivalent.
        gh, gw = H // p, W // p
        x = (
            x.reshape(N, gh, p, gw, p, C)
            .transpose(0, 1, 3, 2, 4, 5)
            .reshape(N, gh * gw, p * p * C)
        )
        x = nn.Dense(self.d_model, dtype=self.dtype, name="patch_embed")(x)

        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, self.d_model),
            jnp.float32,
        )
        x = jnp.concatenate(
            [jnp.broadcast_to(cls, (N, 1, self.d_model)).astype(x.dtype), x],
            axis=1,
        )
        # Position embeddings are shaped by the init-time input: stored in
        # GRID shape (1, gh, gw, D) — not flat token count — so applying at
        # a different resolution OR a different aspect ratio with the same
        # patch count fails loudly on param-shape mismatch instead of
        # silently reusing geometrically wrong positions.
        pos = self.param(
            "pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (1, gh, gw, self.d_model),
            jnp.float32,
        )
        cls_pos = self.param(
            "cls_pos_embedding",
            nn.initializers.normal(stddev=0.02),
            (1, 1, self.d_model),
            jnp.float32,
        )
        pos_seq = jnp.concatenate(
            [cls_pos, pos.reshape(1, gh * gw, self.d_model)], axis=1
        )
        x = x + pos_seq.astype(x.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)

        block_cls = EncoderBlock
        if self.remat:
            # static_argnums: train is a Python bool, not a tracer (arg 0
            # is the module instance under nn.remat's calling convention).
            block_cls = nn.remat(EncoderBlock, static_argnums=(2,))
        for i in range(self.n_layers):
            x = block_cls(
                self.n_heads, self.mlp_dim, self.dropout, self.dtype,
                self.mesh, name=f"encoder_{i}",
            )(x, train)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        # Classify from the class token (torchvision ViT convention).
        return nn.Dense(
            self.num_classes, dtype=jnp.float32, name="head"
        )(x[:, 0])


vit_b_16 = functools.partial(
    VisionTransformer, patch_size=16, d_model=768, n_layers=12, n_heads=12,
    mlp_dim=3072,
)
vit_b_32 = functools.partial(
    VisionTransformer, patch_size=32, d_model=768, n_layers=12, n_heads=12,
    mlp_dim=3072,
)
vit_l_16 = functools.partial(
    VisionTransformer, patch_size=16, d_model=1024, n_layers=24, n_heads=16,
    mlp_dim=4096,
)
