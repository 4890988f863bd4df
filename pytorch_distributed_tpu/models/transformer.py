"""Decoder-only transformer LM with optional ring-attention sequence
parallelism — the framework's long-context model family.

Beyond-reference capability (the reference is image-classification only,
SURVEY.md §5.7), first-class per the framework brief.  The same module runs:

- single-device / pure-DP with dense attention;
- sequence-parallel over a ``seq`` mesh axis via ``parallel/ring.py``'s ring
  attention (KV blocks rotate on ICI, online softmax, O(L/P) memory).

TPU-first choices: pre-LN blocks (stable in bf16), RoPE positions (position
math is local so sequence sharding needs no global gather), GELU MLP at 4×
width, f32 layernorm/softmax accumulation under a bf16 compute policy.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pytorch_distributed_tpu.models.vit import VisionTransformer
from pytorch_distributed_tpu.parallel.ring import dense_attention, ring_self_attention


def rope(x: jnp.ndarray, base: float = 10000.0, offset=0) -> jnp.ndarray:
    """Rotary position embedding over [B, L, H, D] (global positions — under
    GSPMD the position index is computed on the full array, so sequence
    sharding stays transparent).  ``offset`` shifts positions for KV-cached
    decoding (may be a traced scalar)."""
    B, L, H, D = x.shape
    half = D // 2
    freqs = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    pos = offset + jnp.arange(L, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]                               # [L, half]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def _pick_attention(L: int, attn_impl: str):
    """Shared 'auto' flash/dense policy — ops/flash_attention.py."""
    from pytorch_distributed_tpu.ops.flash_attention import pick_attention_impl

    return pick_attention_impl(L, attn_impl)


def _dense_cls(quant: str):
    """nn.Dense, or the int8 weight-only variant (models/quant.py)."""
    if not quant:
        return nn.Dense
    if quant == "int8":
        from pytorch_distributed_tpu.models.quant import QuantDense

        return QuantDense
    raise ValueError(f"unknown quant mode {quant!r} (expected '' or 'int8')")


class SelfAttention(nn.Module):
    n_heads: int
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    ring: bool = False
    attn_impl: str = "auto"  # auto | dense | flash
    decode: bool = False     # KV-cached autoregressive mode
    max_len: int = 0         # cache capacity (decode mode)
    sp_impl: str = "ring"    # ring | a2a (Ulysses-style all-to-all SP)
    quant: str = ""          # "" | "int8" weight-only (serving)
    flash_prefill: bool = False  # fused-kernel prompt prefill (decode mode)

    @nn.compact
    def __call__(self, x):
        B, L, C = x.shape
        D = C // self.n_heads
        dense = _dense_cls(self.quant)
        qkv = dense(3 * C, use_bias=False, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (B, L, self.n_heads, D)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        if self.decode:
            return self._decode_attend(q, k, v, B, L, C, D)
        q, k = rope(q), rope(k)
        if self.ring:
            if self.mesh is None:
                raise ValueError(
                    "sequence parallelism requires a mesh with a 'seq' axis")
            if self.sp_impl == "a2a":
                from pytorch_distributed_tpu.parallel.ulysses import (
                    a2a_self_attention,
                )

                out = a2a_self_attention(q, k, v, self.mesh, causal=True,
                                         inner=self.attn_impl)
            else:
                out = ring_self_attention(q, k, v, self.mesh, causal=True)
        elif _pick_attention(L, self.attn_impl) == "flash":
            from pytorch_distributed_tpu.ops.flash_attention import (
                flash_attention_on_mesh,
            )

            out = flash_attention_on_mesh(q, k, v, True, self.mesh)
        else:
            out = dense_attention(q, k, v, causal=True)
        out = out.reshape(B, L, C)
        return _dense_cls(self.quant)(
            C, use_bias=False, dtype=self.dtype, name="proj")(out)

    def _decode_attend(self, q, k, v, B, L, C, D):
        """KV-cached attention: new tokens' k/v land in the cache at the
        running index (prefill writes the whole prompt at once, generation
        steps write one token); q attends over the filled prefix with a
        static-shape mask.  Cache lives in the flax "cache" collection —
        created at ``init``, threaded by the caller via ``mutable``."""
        if self.max_len <= 0:
            raise ValueError("decode mode needs max_len > 0 (cache capacity)")
        # During init this variable doesn't exist yet: create the zeroed
        # cache but DON'T advance it — the returned cache must start at
        # index 0, not wherever the init trace's dummy tokens left it.
        initializing = not self.has_variable("cache", "cached_key")
        ck = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((B, self.max_len, self.n_heads, D), self.dtype))
        cv = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((B, self.max_len, self.n_heads, D), self.dtype))
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        if initializing:
            q, k = rope(q), rope(k)
            out = dense_attention(q, k, v, causal=True).reshape(B, L, C)
            return _dense_cls(self.quant)(
                C, use_bias=False, dtype=self.dtype, name="proj")(out)
        idx = ci.value
        q = rope(q, offset=idx)
        k = rope(k, offset=idx)
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0))
        ci.value = idx + L
        if L > 1 and self.flash_prefill:
            # Prefill via the fused kernel.  OPT-IN (generate() sets it):
            # assumes a multi-token block only arrives as THE prompt at
            # cache index 0 — then causal attention within the block is
            # the whole answer, no O(L·max_len) dense score tensor.
            # Chunked-prefill callers must leave this off: a later chunk
            # needs the masked cache attention below.
            from pytorch_distributed_tpu.ops.flash_attention import (
                flash_attention_on_mesh,
            )

            out = flash_attention_on_mesh(
                q, k, v, True, self.mesh).reshape(B, L, C)
            return _dense_cls(self.quant)(
                C, use_bias=False, dtype=self.dtype, name="proj")(out)
        keys, values = ck.value, cv.value                 # [B, Lmax, H, D]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32),
            keys.astype(jnp.float32)) / (D ** 0.5)
        kpos = jnp.arange(self.max_len)
        qpos = idx + jnp.arange(L)
        mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
        scores = jnp.where(mask, scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", w, values.astype(jnp.float32)
        ).astype(q.dtype).reshape(B, L, C)
        return _dense_cls(self.quant)(
            C, use_bias=False, dtype=self.dtype, name="proj")(out)


class Block(nn.Module):
    n_heads: int
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    ring: bool = False
    attn_impl: str = "auto"
    moe_experts: int = 0  # >0 replaces the dense MLP with an MoE layer
    moe_top_k: int = 1
    decode: bool = False
    max_len: int = 0
    sp_impl: str = "ring"
    quant: str = ""
    flash_prefill: bool = False

    @nn.compact
    def __call__(self, x):
        C = x.shape[-1]
        h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x)
        x = x + SelfAttention(self.n_heads, self.dtype, self.mesh, self.ring,
                              self.attn_impl, decode=self.decode,
                              max_len=self.max_len, sp_impl=self.sp_impl,
                              quant=self.quant,
                              flash_prefill=self.flash_prefill,
                              name="attn")(h)
        h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x)
        if self.moe_experts > 0:
            from pytorch_distributed_tpu.models.moe import MoEMLP

            h = MoEMLP(self.moe_experts, dtype=self.dtype,
                       top_k=self.moe_top_k, name="moe")(h)
        else:
            dense = _dense_cls(self.quant)
            h = dense(4 * C, dtype=self.dtype, name="fc1")(h)
            h = nn.gelu(h)
            h = dense(C, dtype=self.dtype, name="fc2")(h)
        return x + h


class TransformerLM(nn.Module):
    """Next-token LM.  ``__call__(tokens[B, L]) -> logits[B, L, vocab]``."""

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 8
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    ring: bool = False
    attn_impl: str = "auto"
    remat: bool = False  # rematerialize blocks: activations recomputed in
    #                      backward — O(sqrt) memory for long context
    #                      (the jax.checkpoint HBM/FLOPs trade, brief §HBM)
    moe_experts: int = 0  # >0: MoE MLP in every block (expert parallelism)
    moe_top_k: int = 1    # 1 = Switch routing; 2 = Mixtral-style top-2
    decode: bool = False  # KV-cached autoregressive inference mode
    max_len: int = 0      # cache capacity (decode mode)
    sp_impl: str = "ring"  # ring | a2a (Ulysses-style; parallel/ulysses.py)
    quant: str = ""        # "" | "int8" weight-only block kernels (serving;
    #                        params from models/quant.py:quantize_lm_params)
    flash_prefill: bool = False  # decode mode: fused-kernel prompt prefill
    #                              (single-block prompts only — generate())

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="embed")
        x = embed(tokens)
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.n_layers):
            x = block_cls(self.n_heads, self.dtype, self.mesh, self.ring,
                          self.attn_impl, self.moe_experts, self.moe_top_k,
                          decode=self.decode, max_len=self.max_len,
                          sp_impl=self.sp_impl, quant=self.quant,
                          flash_prefill=self.flash_prefill,
                          name=f"block_{i}")(x)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_hidden:
            # Pre-head hidden states for the fused tied-head+CE loss
            # (ops/fused_ce.py) — the [B, L, vocab] logits tensor never
            # materializes; the caller projects per row chunk against
            # params["embed"]["embedding"].
            return x
        # Tied output head (embed.attend) keeps params lean at long context.
        return embed.attend(x.astype(jnp.float32)).astype(jnp.float32)


def bind_mesh(model, mesh: Mesh):
    """The step builders' hook: a ``TransformerLM`` or a
    ``VisionTransformer`` built without a mesh learns the step's mesh
    here, so that attention can wrap its Pallas kernels for it
    (``flash_attention_on_mesh``, ``short_attention_on_mesh``).  A model
    that already carries a mesh (sequence parallelism), and any other
    model, is returned as it is."""
    if (isinstance(model, (TransformerLM, VisionTransformer))
            and model.mesh is None):
        return model.clone(mesh=mesh)
    return model


def transformer_lm(num_classes: int = 32000, dtype: Any = jnp.float32, **kw):
    """Registry adapter: ``num_classes`` plays the vocab-size role."""
    return TransformerLM(vocab_size=num_classes, dtype=dtype, **kw)
