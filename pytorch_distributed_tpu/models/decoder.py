"""A decoder LM built from a configuration file with the catalog's key names
(a Hugging Face ``config.json`` of the DeepSeek-V3 family): RMSNorm, latent
attention (MLA), SwiGLU, a per-layer choice of dense or expert feed-forward,
sigmoid-routed experts beside shared ones, an untied head.

``DecoderConfig.from_dict(json.load(f))`` reads the file; ``DecoderLM(config)``
is the model, with ``TransformerLM``'s call signature, so ``LMTrainer`` and
``make_lm_train_step`` take it as they take that one.  The next architecture
of the family is a file, not a class.  Training only: no cache, so no
serving (``serving/model.py`` keeps ``TransformerLM``); text only: no
vision tower.

One chip's share of a deployment: a file whose ``deployment`` group states
how many experts the router scores (``n_routed_experts``) and which is the
first one held here (``first_expert``), and whose own ``n_routed_experts``
and ``vocab_size`` count what this chip holds.  The router keeps its width,
the layer computes its own experts' part (``models/moe.py``
``RoutedExperts``), and the vocabulary is simply the smaller one.

The plain reference of these equations is ``tests/reference_decoder.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from pytorch_distributed_tpu.models.moe import RoutedExperts, _SwiGLU
from pytorch_distributed_tpu.obs.trace import scope


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The sizes a ``DecoderLM`` is built from; field names are the
    catalog's where it has one."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int            # the router's width
    experts_held: Tuple[int, int]    # (first, count) held here
    num_experts_per_tok: int
    n_shared_experts: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    rms_norm_eps: float
    rope_theta: float
    remat: bool = False
    bias_update_rate: float = 0.001  # noaux_tc's gamma (DeepSeek-V3's)
    seq_aux_alpha: float = 1e-4      # balance loss weight (DeepSeek-V3's)

    @classmethod
    def from_dict(cls, cfg: Dict[str, Any]) -> "DecoderConfig":
        unsupported = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "moe_layer_freq": (1,),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,)}
        for key, allowed in unsupported.items():
            if key in cfg and cfg[key] not in allowed:
                raise ValueError(
                    f"DecoderLM has no {key}={cfg[key]!r}; it implements "
                    f"{allowed[0]!r}")
        deployment = cfg.get("deployment", {})
        held = cfg["n_routed_experts"]
        assumed = cfg.get("training", {})
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(
            n_routed_experts=deployment.get("n_routed_experts", held),
            experts_held=(deployment.get("first_expert", 0), held),
            remat=bool(assumed.get("remat", False)),
            bias_update_rate=assumed.get("bias_update_rate", 0.001),
            seq_aux_alpha=(assumed.get("seq_aux_alpha", 1e-4)
                           if cfg.get("seq_aux", True) else 0.0),
            **{k: v for k, v in cfg.items()
               if k in fields and k != "n_routed_experts"})


def overlay(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    """``over`` laid on ``base``; a group (dict) is merged one level deep."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **value}
        else:
            out[key] = value
    return out


class RMSNorm(nn.Module):
    """``scale * x / sqrt(mean(x^2) + eps)`` in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return scale * x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + self.eps)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Half-split rotary embedding over the last axis of [B, L, H, D]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = (t.astype(jnp.float32) for t in jnp.split(x, 2, -1))
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def dense_attention(q, k, v, scale: float) -> jnp.ndarray:
    """Causal attention by explicit float32 scores; ``v`` may be narrower
    than ``q`` and ``k``."""
    L = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(L)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


class MLA(nn.Module):
    """Latent attention: keys and values rebuilt from one ``kv_lora_rank``
    latent a position plus one rotary key shared by all heads; the query is
    not compressed."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        from pytorch_distributed_tpu.ops.flash_attention import (
            flash_attention_on_mesh,
            pick_attention_impl,
        )

        c = self.config
        B, L, _ = x.shape
        H, nope, rot, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                            c.qk_rope_head_dim, c.v_head_dim)

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        q = dense(H * (nope + rot), "q_proj")(x).reshape(B, L, H, nope + rot)
        ckv = dense(c.kv_lora_rank + rot, "kv_a_proj")(x)
        latent, k_rope = jnp.split(ckv, [c.kv_lora_rank], -1)
        kv = dense(H * (nope + vd), "kv_b_proj")(
            RMSNorm(c.rms_norm_eps, name="kv_a_norm")(latent)
        ).reshape(B, L, H, nope + vd)
        k_nope, v = jnp.split(kv, [nope], -1)
        q_nope, q_rope = jnp.split(q, [nope], -1)
        q = jnp.concatenate([q_nope, rope(q_rope, c.rope_theta)], -1)
        k_rope = rope(k_rope[:, :, None, :], c.rope_theta)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, L, H, rot))], -1)
        scale = (nope + rot) ** -0.5
        if pick_attention_impl(L, self.attn_impl) == "flash":
            # 1024 x 1024 blocks: at L = 8192 and these head sizes the
            # forward kernel takes 17.4 ms against 27.8 at the kernel's
            # default 256 x 1024, backward 48.6 against 57.9; 2048 in
            # either place runs out of VMEM (my chip run, PR 26)
            out = flash_attention_on_mesh(
                q, k, v, True, self.mesh, block_q=1024, block_k=1024,
                scale=scale)
        else:
            out = dense_attention(q, k, v, scale)
        return dense(c.hidden_size, "o_proj")(out.reshape(B, L, H * vd))


class DecoderBlock(nn.Module):
    """``h = x + MLA(norm(x)); y = h + FFN(norm(h))``."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"
    expert_layer: bool = False

    @nn.compact
    def __call__(self, x):
        c = self.config
        with scope("mla"):
            x = x + MLA(c, self.dtype, self.mesh, self.attn_impl,
                        name="attn")(RMSNorm(c.rms_norm_eps,
                                             name="attn_norm")(x))
        h = RMSNorm(c.rms_norm_eps, name="ffn_norm")(x)
        if self.expert_layer:
            h = RoutedExperts(
                n_routed=c.n_routed_experts, top_k=c.num_experts_per_tok,
                width=c.moe_intermediate_size, held=c.experts_held,
                n_shared=c.n_shared_experts,
                scaling=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob,
                seq_aux_alpha=c.seq_aux_alpha, dtype=self.dtype,
                name="moe")(h)
        else:
            h = _SwiGLU(c.intermediate_size, self.dtype, name="mlp")(h)
        return x + h.astype(x.dtype)


class DecoderLM(nn.Module):
    """Next-token LM.  ``__call__(tokens[B, L]) -> logits[B, L, vocab]``,
    or the hidden rows before the head with ``return_hidden`` (the fused
    loss projects them against ``head_matrix`` chunk by chunk)."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"

    # the collection of non-gradient state (the experts' selection bias);
    # the train state keeps it in ``batch_stats``
    state_collection = "router"

    @property
    def vocab_size(self) -> int:
        return self.config.vocab_size

    @property
    def remat(self) -> bool:
        return self.config.remat

    @staticmethod
    def head_matrix(params):
        """The output head as ``[V, d]``, the layout of a tied embedding."""
        return params["head"]["weight"]

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        c = self.config
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     name="embed")(tokens)
        block_cls = nn.remat(DecoderBlock) if c.remat else DecoderBlock
        for i in range(c.num_hidden_layers):
            x = block_cls(c, self.dtype, self.mesh, self.attn_impl,
                          expert_layer=i >= c.first_k_dense_replace,
                          name=f"layer_{i}")(x)
        x = RMSNorm(c.rms_norm_eps, name="norm_f")(x)
        head = _Head(c.vocab_size, name="head")(c.hidden_size)
        if return_hidden:
            return x
        with scope("lm_head"):
            return jnp.einsum("bld,vd->blv", x.astype(self.dtype),
                              head.astype(self.dtype),
                              preferred_element_type=jnp.float32)

    def update_state(self, model_state, counters):
        """The step's non-gradient update: each expert layer's selection
        bias moves by the configuration's ``bias_update_rate`` towards the
        experts that saw fewer tokens than the mean (``noaux_tc``)."""
        rate = self.config.bias_update_rate

        def layer(state, seen):
            counts = seen["moe"]["expert_counts"][0]
            bias = state["moe"]["e_score_correction_bias"]
            return {"moe": {"e_score_correction_bias": bias + rate * jnp.sign(
                counts.mean() - counts)}}

        return {name: layer(state, counters[name])
                for name, state in model_state.items()}

    # the names of ``step_counters``: the loop books them on its `dispatch`
    # record, the benchmark's runner reads them from the step's metrics
    counter_names = ("routed_here", "rows_grouped", "expert_rows_max",
                     "expert_rows_mean", "bias_abs_max")

    def step_counters(self, model_state, counters):
        """The routing counters a step reports, each summed over the
        expert layers: ``routed_here`` (pairs on held experts),
        ``rows_grouped`` (rows the grouped products processed),
        ``expert_rows_max`` and ``expert_rows_mean`` (over the held
        experts); and ``bias_abs_max`` over all of them."""
        layers = [layer["moe"] for layer in counters.values()]

        def total(name):
            return sum(layer[name][0] for layer in layers)

        return {
            "routed_here": total("routed_here"),
            "rows_grouped": total("rows_grouped"),
            "expert_rows_max": total("rows_max"),
            "expert_rows_mean": (total("routed_here")
                                 / self.config.experts_held[1]),
            "bias_abs_max": jnp.max(jnp.stack([
                jnp.max(jnp.abs(s["moe"]["e_score_correction_bias"]))
                for s in model_state.values()])),
        }


class _Head(nn.Module):
    """The untied output head, stored ``[V, d]`` like an embedding (and
    like the checkpoint's ``lm_head.weight``)."""

    vocab_size: int

    @nn.compact
    def __call__(self, d_model: int):
        return self.param(
            "weight", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
            (self.vocab_size, d_model), jnp.float32)
