"""A decoder LM built from a configuration file with the catalog's key names
(a Hugging Face ``config.json``): RMSNorm, SwiGLU, rotary attention, a
head of its own or the embedding's (``tie_word_embeddings``).  The file's
keys choose the block, not a class:

- attention: ``kv_lora_rank`` present -> latent attention (``MLA``: one
  compressed latent a position, a rotary key shared by the heads);
  ``cca_time0`` present -> attention inside a compressed latent (``CCA``:
  ``num_attention_heads`` query heads over ``num_key_value_heads``
  key-value heads of ``head_dim``, never rebuilt to full width, two causal
  convolutions over queries and keys, rotary over ``partial_rotary_factor``
  of a head); neither -> plain multi-head attention (``MHA``) with the
  file's ``head_dim`` and rotary over the whole head;
- feed-forward: ``n_routed_experts`` (or ``num_experts``) present -> routed
  experts beside shared ones after ``first_k_dense_replace`` dense layers,
  scored by one linear map and a sigmoid, or with ``router_hidden_size`` by
  an MLP and a softmax whose state each block hands the next
  (``models/moe.py``); absent -> dense SwiGLU in every layer, and the file
  carries no expert key;
- residual path: ``norm_placement: "sandwich"`` -> a norm before and after
  attention and feed-forward (four a block); absent -> pre-norm (two);
  with ``cca_time0`` both the stream and the branch are scaled and shifted
  by learned vectors where they join (``_ScaledJoin``);
- depth: ``total_ut_steps`` T > 1 -> the whole stack runs T times over the
  same weights (a scan over the passes, the parameters broadcast), the
  final norm after every pass; each pass's output is an exit with its own
  logits, and one ``Linear(d, 1)`` exit gate turns the exits' hidden rows
  into a distribution over them (``exit_distribution``).

``from_dict`` refuses by name what the model does not implement: grouped
key-value heads under plain attention, windowed layers
(``use_sliding_window``, ``sliding_window``, a ``layer_types`` entry other
than ``full_attention`` or, with ``cca_time0``, ``hybrid``: no
``hybrid_sliding``), a compressed query (``q_lora_rank``), rotary length
scaling, biases (``attention_bias``, ``lm_head_bias``), grouped routing,
softmax scores from a linear router, two kinds of latent in one file,
several passes over routed experts.

``DecoderConfig.from_dict(json.load(f))`` reads the file; ``DecoderLM(config)``
is the model, with ``TransformerLM``'s call signature, so ``LMTrainer`` and
``make_lm_train_step`` take it as they take that one.  Training only: no
cache, so no serving and no early exit at inference (``serving/model.py``
keeps ``TransformerLM``); text only: no vision tower.

One chip's share of a deployment: a file whose ``deployment`` group states
how many experts the router scores (``n_routed_experts``) and which is the
first one held here (``first_expert``), and whose own ``n_routed_experts``
and ``vocab_size`` count what this chip holds.  The router keeps its width,
the layer computes its own experts' part (``models/moe.py``
``RoutedExperts``), and the vocabulary is simply the smaller one.

The plain references of these equations are ``tests/reference_decoder.py``
(latent attention, experts), ``tests/reference_ouro.py`` (the looped,
multi-exit decoder) and ``tests/reference_zaya1.py`` (attention in a
compressed latent, the MLP router, the scaled residual, the tied head).
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
    catalog's where it has one.  What a file leaves out is 0 (no latent,
    no experts, one pass) and chooses the plainer block."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    rms_norm_eps: float
    rope_theta: float
    intermediate_size: int = 0       # the dense feed-forward's, if any
    head_dim: int = 0                # plain heads (no ``kv_lora_rank``)
    num_key_value_heads: int = 0     # ``CCA``'s; 0: as many as query heads
    cca_time0: int = 0               # attention in a compressed latent:
    cca_time1: int = 0               # its two convolutions' kernel sizes
    partial_rotary_factor: float = 1.0
    tie_word_embeddings: bool = False
    kv_lora_rank: int = 0            # latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_routed_experts: int = 0        # the router's width
    router_hidden_size: int = 0      # > 0: an MLP scores, softmax (moe.py)
    experts_held: Tuple[int, int] = (0, 0)   # (first, count) held here
    moe_intermediate_size: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    first_k_dense_replace: int = 0   # from_dict: every layer, with no experts
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    sandwich_norm: bool = False      # ``norm_placement: "sandwich"``
    total_ut_steps: int = 1          # passes of the stack over one weight set
    exit_entropy_beta: float = 0.0   # weight of the exits' entropy in the loss
    remat: bool = False
    bias_update_rate: float = 0.001  # noaux_tc's gamma (DeepSeek-V3's)
    seq_aux_alpha: float = 1e-4      # balance loss weight (DeepSeek-V3's)

    @classmethod
    def from_dict(cls, cfg: Dict[str, Any]) -> "DecoderConfig":
        layers = cfg["num_hidden_layers"]
        cca, mlp_router = "cca_time0" in cfg, "router_hidden_size" in cfg
        kind = "hybrid" if cca else "full_attention"
        # a file's rotary group: that of its layers' kind, if it has kinds
        rotary = cfg.get("rope_parameters", {})
        rotary = rotary.get(kind, rotary)
        unsupported = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "scoring_func": ("softmax" if mlp_router else "sigmoid",),
            "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "moe_layer_freq": (1,),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "lm_head_bias": (False,),
            "use_sliding_window": (False,), "sliding_window": (None,),
            "layer_types": ([kind] * len(cfg.get("layer_types", ())),),
            "norm_placement": ("sandwich", "pre")}
        if cca:     # one kind of latent a file
            unsupported["kv_lora_rank"] = (None,)
        elif "kv_lora_rank" not in cfg:  # plain heads: no grouped ones
            unsupported["num_key_value_heads"] = (cfg["num_attention_heads"],)
        if mlp_router:  # its gate is the raw probability of the one pick
            unsupported["num_experts_per_tok"] = (1,)
        for key, allowed in unsupported.items():
            if key in cfg and cfg[key] not in allowed:
                raise ValueError(
                    f"DecoderLM has no {key}={cfg[key]!r}; it implements "
                    f"{allowed[0]!r}")
        if rotary.get("rope_type", "default") != "default":
            raise ValueError(
                f"DecoderLM has no rope_parameters={rotary!r}; it implements "
                "rope_type 'default'")
        deployment = cfg.get("deployment", {})
        held = cfg.get("n_routed_experts", cfg.get("num_experts", 0))
        if held and cfg.get("total_ut_steps", 1) > 1:
            raise ValueError(
                "DecoderLM has no total_ut_steps > 1 over n_routed_experts: "
                "the scan over the passes carries no routing state")
        assumed = cfg.get("training", {})
        fields = {f.name for f in dataclasses.fields(cls)}
        given = {k: v for k, v in cfg.items()
                 if k in fields and k != "n_routed_experts"}
        given.setdefault("first_k_dense_replace", 0 if held else layers)
        # the rotary group's settings over the file's top-level ones
        given.update({key: rotary[key] for key in (
            "rope_theta", "partial_rotary_factor") if key in rotary})
        return cls(
            n_routed_experts=deployment.get(
                "n_routed_experts", deployment.get("num_experts", held)),
            experts_held=(deployment.get("first_expert", 0), held),
            sandwich_norm=cfg.get("norm_placement") == "sandwich",
            exit_entropy_beta=assumed.get("exit_entropy_beta", 0.0),
            remat=bool(assumed.get("remat", False)),
            bias_update_rate=assumed.get("bias_update_rate", 0.001),
            seq_aux_alpha=(assumed.get("seq_aux_alpha", 1e-4)
                           if cfg.get("seq_aux", not mlp_router) else 0.0),
            **given)

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


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


def rope(x: jnp.ndarray, theta: float, factor: float = 1.0) -> jnp.ndarray:
    """Half-split rotary embedding over the last axis of [B, L, H, D], or
    over its first ``factor * D`` channels, the rest passed through."""
    if factor != 1.0:
        turned, kept = jnp.split(x, [int(x.shape[-1] * factor)], -1)
        return jnp.concatenate([rope(turned, theta), kept], -1)
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = (t.astype(jnp.float32) for t in jnp.split(x, 2, -1))
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def dense_attention(q, k, v, scale: float) -> jnp.ndarray:
    """Causal attention by explicit float32 scores; ``v`` may be narrower
    than ``q`` and ``k``, and ``k`` and ``v`` may have fewer heads (each
    then serves ``H / G`` query heads in a row)."""
    L = q.shape[1]
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], 2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(L)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# The flash kernels' (q, kv) block sizes at L = 8192, gradient of one call
# (forward + dq + dk/dv) over 32 batch-heads of 128 | 128 and over 64 of
# 192 | 128: 1024 x 1024 19.32 / 54.96 ms, 512 x 1024 20.68 / 58.34,
# 512 x 2048 20.96 / out of VMEM, 256 x 1024 (the kernel's default) 23.76 /
# 64.88, 2048 x 512 24.49 / out of VMEM, 1024 x 512 24.86 / 67.47,
# 512 x 512 25.81 / 68.11 (my chip run, PR 31): one size for both widths
FLASH_BLOCKS = (1024, 1024)


def attention_blocks(L: int, attn_impl: str) -> Tuple[int, int, int]:
    """``(visited, masked, subtiles_skipped)``: the score blocks one
    forward call of ``causal_attention`` visits a batch-head, those of them
    it masks and the sub-tiles above the diagonal it skips in them;
    ``(0, 0, 0)`` where it takes the dense path."""
    from pytorch_distributed_tpu.ops.flash_attention import (
        blocks_visited,
        pick_attention_impl,
    )

    if pick_attention_impl(L, attn_impl) != "flash":
        return 0, 0, 0
    return blocks_visited(L, *FLASH_BLOCKS)


def causal_attention(q, k, v, scale: float, mesh: Optional[Mesh],
                     attn_impl: str) -> jnp.ndarray:
    """The Pallas flash kernel where the shared policy picks it (a TPU at
    long, aligned L), explicit scores otherwise."""
    from pytorch_distributed_tpu.ops.flash_attention import (
        flash_attention_on_mesh,
        pick_attention_impl,
    )

    if pick_attention_impl(q.shape[1], attn_impl) == "flash":
        bq, bk = FLASH_BLOCKS
        return flash_attention_on_mesh(
            q, k, v, True, mesh, block_q=bq, block_k=bk, scale=scale)
    return dense_attention(q, k, v, scale)


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
        out = causal_attention(q, k, v, (nope + rot) ** -0.5, self.mesh,
                               self.attn_impl)
        return dense(c.hidden_size, "o_proj")(out.reshape(B, L, H * vd))


class MHA(nn.Module):
    """Plain multi-head attention: as many key and value heads as query
    heads, ``head_dim`` wide (not ``hidden_size / heads`` where the file
    says otherwise), rotary over the whole head."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.config
        B, L, _ = x.shape
        H = c.num_attention_heads
        hd = c.head_dim or c.hidden_size // H

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        q, k, v = (dense(H * hd, name)(x).reshape(B, L, H, hd)
                   for name in ("q_proj", "k_proj", "v_proj"))
        out = causal_attention(rope(q, c.rope_theta), rope(k, c.rope_theta), v,
                               hd ** -0.5, self.mesh, self.attn_impl)
        return dense(c.hidden_size, "o_proj")(out.reshape(B, L, H * hd))


def _shifted(x: jnp.ndarray, by: int, axis: int = 1) -> jnp.ndarray:
    """``x[t - by]`` along the sequence (``axis``), zeros before the first
    position."""
    if by == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (by, 0)
    return jax.lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[axis], axis=axis)


class CCA(nn.Module):
    """Attention inside a compressed latent: ``H`` query heads and ``G``
    key-value heads of ``head_dim`` straight from the projections (no
    projection rebuilds full-width heads; the output projection reads the
    ``H * head_dim`` latent).

    Queries and keys, stacked as ``H + G`` heads, pass two causal
    convolutions along the sequence (depthwise of ``cca_time0`` taps, then
    one ``head_dim x head_dim`` matrix a tap and a head, ``cca_time1``
    taps); the mean of a query head and its group's key head, from before
    the convolutions, is added back; each head is L2-normalised to length
    ``sqrt(head_dim)``, keys times a learned temperature a group; rotary
    turns the first ``partial_rotary_factor`` of a head.  The second half
    of a value's channels (its second half of heads) is projected from the
    position before.  Products in ``dtype``; the convolutions' sums, the
    means and the normalisation in float32."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        c = self.config
        B, L, _ = x.shape
        H, hd = c.num_attention_heads, c.head_dim
        G = c.num_key_value_heads or H
        rep, f32 = H // G, jnp.float32

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        q0 = dense(H * hd, "q_proj")(x).reshape(B, L, H, hd).astype(f32)
        k0 = dense(G * hd, "k_proj")(x).reshape(B, L, G, hd).astype(f32)
        with scope("cca_conv"):
            z = jnp.concatenate([q0, k0], 2)                   # [B, L, H+G, hd]
            taps = self.param("conv0_kernel", nn.initializers.lecun_normal(),
                              (c.cca_time0, (H + G) * hd), f32)
            taps = taps.reshape(c.cca_time0, H + G, hd)
            u = self.param("conv0_bias", nn.initializers.zeros,
                           (H + G, hd), f32) + sum(
                               taps[j] * _shifted(z, j)
                               for j in range(c.cca_time0))
            mix = self.param(
                "conv1_kernel", nn.initializers.lecun_normal(
                    in_axis=(1, 2), out_axis=3, batch_axis=(0,)),
                (H + G, c.cca_time1, hd, hd), f32).astype(self.dtype)
            # a matrix a head: the heads lead, as a batched product has them
            u = u.astype(self.dtype).transpose(2, 0, 1, 3)     # [H+G, B, L, hd]
            w = self.param("conv1_bias", nn.initializers.zeros,
                           (H + G, hd), f32) + sum(
                               jnp.einsum("hblc,hcd->hbld", _shifted(u, j, 2),
                                          mix[:, j],
                                          preferred_element_type=f32)
                               for j in range(c.cca_time1)
                           ).transpose(1, 2, 0, 3)
        q = w[:, :, :H] + 0.5 * (q0 + jnp.repeat(k0, rep, 2))
        k = w[:, :, H:] + 0.5 * (
            q0.reshape(B, L, G, rep, hd).mean(3) + k0)

        def unit(t):
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                     + 1e-12) * hd ** 0.5

        tau = self.param("temperature", nn.initializers.ones, (G,), f32)
        q = rope(unit(q), c.rope_theta, c.partial_rotary_factor)
        k = rope(unit(k) * tau[:, None], c.rope_theta,
                 c.partial_rotary_factor)
        half = G * hd // 2
        v = jnp.concatenate([dense(half, "v_proj")(x),
                             _shifted(dense(half, "v_shift_proj")(x), 1)],
                            -1).reshape(B, L, G, hd)
        with scope("attn"):
            out = causal_attention(
                q.astype(self.dtype), k.astype(self.dtype), v, hd ** -0.5,
                self.mesh, self.attn_impl)
        return dense(c.hidden_size, "o_proj")(out.reshape(B, L, H * hd))


class _ScaledJoin(nn.Module):
    """``(a1 * x + b1) + (a2 * branch + b2)``: the residual stream and the
    branch that joins it, each scaled and shifted by learned vectors
    (scales 1, shifts 0 at the start); float32, the stream's type out."""

    @nn.compact
    def __call__(self, x, branch):
        d, f32 = x.shape[-1], jnp.float32
        a1, a2 = (self.param(name, nn.initializers.ones, (d,), f32)
                  for name in ("stream_scale", "branch_scale"))
        b1, b2 = (self.param(name, nn.initializers.zeros, (d,), f32)
                  for name in ("stream_shift", "branch_shift"))
        return ((a1 * x.astype(f32) + b1)
                + (a2 * branch.astype(f32) + b2)).astype(x.dtype)


class DecoderBlock(nn.Module):
    """``h = x + Attn(norm(x)); y = h + FFN(norm(h))``; with
    ``sandwich_norm`` each branch's output is normalised too, by a scale of
    its own, before it joins the residual stream; with ``cca_time0`` stream
    and branch join through a ``_ScaledJoin``.  With an MLP router
    (``router_hidden_size``) it takes, beside the rows, the state of the
    router before its own and returns the rows and its router's state."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"
    expert_layer: bool = False

    @nn.compact
    def __call__(self, x, router_state=None):
        c = self.config

        def norm(name):
            return RMSNorm(c.rms_norm_eps, name=name)

        def joins(x, branch, name):
            if c.cca_time0:
                return _ScaledJoin(name=name.replace("out_norm", "join"))(
                    x, branch)
            if c.sandwich_norm:
                branch = norm(name)(branch)
            return x + branch.astype(x.dtype)

        kind, attention = (("mla", MLA) if c.kv_lora_rank else
                           ("cca", CCA) if c.cca_time0 else ("attn", MHA))
        with scope(kind):
            x = joins(x, attention(c, self.dtype, self.mesh, self.attn_impl,
                                   name="attn")(norm("attn_norm")(x)),
                      "attn_out_norm")
        h = norm("ffn_norm")(x)
        if self.expert_layer:
            h = RoutedExperts(
                n_routed=c.n_routed_experts, top_k=c.num_experts_per_tok,
                width=c.moe_intermediate_size, held=c.experts_held,
                n_shared=c.n_shared_experts,
                scaling=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob,
                seq_aux_alpha=c.seq_aux_alpha,
                router_hidden=c.router_hidden_size, eps=c.rms_norm_eps,
                dtype=self.dtype, name="moe")(h, router_state)
            if c.router_hidden_size:
                h, router_state = h
        else:
            h = _SwiGLU(c.intermediate_size, self.dtype, name="mlp")(h)
        x = joins(x, h, "ffn_out_norm")
        return (x, router_state) if c.router_hidden_size else x


def exit_distribution(gate_logits: jnp.ndarray):
    """``gate_logits`` [T, ...]: the exit gate's output after each of T
    passes.  With ``lam_t = sigmoid(z_t)`` the chance of leaving at exit t
    having stayed until it, ``p_t = lam_t * prod_{j<t}(1 - lam_j)`` and the
    last exit takes what is left, ``p_T = prod_{j<T}(1 - lam_j)`` (``z_T``
    is unused).  Returns ``p`` and its entropy ``-sum_t p_t log p_t``,
    computed from log-sigmoids so that a saturated gate gives 0, not NaN."""
    z = gate_logits[:-1].astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z), 0)        # log prod_{j<=t}
    stayed = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], 0)
    log_p = jnp.concatenate([jax.nn.log_sigmoid(z) + stayed, stay[-1:]], 0)
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, 0)


class _ExitGate(nn.Module):
    """``Linear(d, 1)`` in float32 on the vector unit: a sum of products,
    no matrix pass that would round its operands."""

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return jnp.sum(x.astype(jnp.float32) * kernel[:, 0], -1) + bias[0]


class DecoderLM(nn.Module):
    """Next-token LM.  ``__call__(tokens[B, L]) -> logits[B, L, vocab]``,
    or the hidden rows before the head with ``return_hidden`` (the fused
    loss projects them against ``head_matrix`` chunk by chunk).

    With ``total_ut_steps`` T > 1 the logits are the last exit's, and
    ``return_hidden`` gives every exit's rows ``[T, B, L, d]``; the forward
    pass then sows each row's weight in the loss (``exits/weight``
    [T, B, L], the exit distribution: the gate learns through it), the
    entropy term of the objective (``losses/exit_entropy``:
    ``-beta * mean H(p)``) and the exits' counters."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"

    @property
    def state_collection(self) -> Optional[str]:
        """The collection of non-gradient state (the experts' selection
        bias), which the train state keeps in ``batch_stats``; a model
        without experts has none."""
        return "router" if self.config.expert_layers else None

    @property
    def vocab_size(self) -> int:
        return self.config.vocab_size

    @property
    def remat(self) -> bool:
        return self.config.remat

    @property
    def n_exits(self) -> int:
        return self.config.total_ut_steps

    def head_matrix(self, params):
        """The output head as ``[V, d]``: its own weight, or with
        ``tie_word_embeddings`` the embedding."""
        if self.config.tie_word_embeddings:
            return params["embed"]["embedding"]
        return params["head"]["weight"]

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        c = self.config
        # which attention the blocks below run at this length: a constant
        # of the compiled program
        self.sow("counters", "attn_blocks", jnp.array(
            attention_blocks(tokens.shape[1], self.attn_impl), jnp.int32))
        embed = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                         name="embed")
        if c.tie_word_embeddings:
            # rows gathered as they are stored and rounded after, so that
            # the lookup's part of the embedding's gradient is summed in
            # float32 like the head's part it joins (``nn.Embed`` rounds the
            # table first and sums the rows of a frequent id in ``dtype``)
            x = jnp.take(embed.embedding, tokens, axis=0).astype(self.dtype)
        else:
            x = embed(tokens)
        block_cls = nn.remat(DecoderBlock) if c.remat else DecoderBlock

        def one_pass(lm, x, _=None):
            """The whole stack and the final norm, once: the next pass's
            input and, the same rows, this pass's exit."""
            with scope("ut_pass"):
                x = x.astype(self.dtype)   # norm_f hands on float32
                router_state = None
                for i in range(c.num_hidden_layers):
                    block = block_cls(
                        c, self.dtype, self.mesh, self.attn_impl,
                        expert_layer=i >= c.first_k_dense_replace,
                        name=f"layer_{i}", parent=lm)
                    if c.router_hidden_size:
                        x, router_state = block(x, router_state)
                    else:
                        x = block(x)
                x = RMSNorm(c.rms_norm_eps, name="norm_f", parent=lm)(x)
            return x, x

        if c.total_ut_steps == 1:
            last, _ = one_pass(self, x)
        else:
            # A scan over the passes, the parameters broadcast: the body
            # is compiled once, so the step's code is a third of four
            # unrolled passes' (612 MB at six layers) and the program fits
            # a compile cache.  Against unrolled passes it stepped 0.6%
            # slower (2,283.9 against 2,271.1 ms) and held 0.66 GB more
            # (14.669 against 14.010 GB: the gradients' accumulators ride
            # in the loop's carry) at six layers, four passes and 2 x 8,192
            # tokens; a capture still names the kernels ``attn.<n>`` (my
            # chip runs, PR 30)
            last, exits = nn.scan(
                one_pass, variable_broadcast="params",
                split_rngs={"params": False}, length=c.total_ut_steps)(
                    self, x.astype(jnp.float32), None)
        head = (embed.embedding if c.tie_word_embeddings
                else _Head(c.vocab_size, name="head")(c.hidden_size))
        if c.total_ut_steps > 1:
            with scope("exit_gate"):
                p, entropy = exit_distribution(
                    _ExitGate(name="exit_gate")(exits))
            self.sow("exits", "weight", p)
            self.sow("losses", "exit_entropy",
                     -c.exit_entropy_beta * jnp.mean(entropy))
            self.sow("counters", "exit_p", jnp.mean(p, (1, 2)))
            self.sow("counters", "exit_entropy", jnp.mean(entropy))
        if return_hidden:
            # The rows the fused loss reads are written once: left to
            # itself the compiler folds the final norm into the loss's
            # input a second time, from the layers' float32 streams, and
            # keeps those alive across the loss's loop, beside the head's
            # gradient accumulator that loop holds.  That is the depth-first
            # schedule's doing, which a program differentiating through
            # the loss gets from the compiler's default (0.5 GB of the
            # ZAYA1 cell's comparison program); the train step, on the
            # ``list`` schedule, compiles to the same bytes with and
            # without the barrier, so it goes once nothing compiles the
            # loss's gradient under the default (PERF.md 6 and 7, PR 33).
            return jax.lax.optimization_barrier(
                exits if c.total_ut_steps > 1 else last)
        with scope("lm_head"):
            return jnp.einsum("bld,vd->blv", last.astype(self.dtype),
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

    ROUTING_COUNTERS = ("routed_here", "rows_grouped", "expert_rows_max",
                        "expert_rows_mean", "bias_abs_max")
    # a router that picks one expert a token puts a layer's tokens on the
    # held experts all or nothing, so it reports the share and its gate
    TOP1_COUNTERS = ("held_share_pct", "held_share_min_pct",
                     "held_share_max_pct", "gate_mean", "router_entropy")

    @property
    def counter_names(self) -> Tuple[str, ...]:
        """The names of ``step_counters``: the loop books them on its
        ``dispatch`` record, the benchmark's runners read them from the
        step's metrics."""
        exits = range(1, self.n_exits + 1) if self.n_exits > 1 else ()
        c = self.config
        return (("attn_blocks_visited", "attn_blocks_masked",
                 "attn_subtiles_skipped")
                + (self.ROUTING_COUNTERS if c.expert_layers else ())
                + (self.TOP1_COUNTERS
                   if c.expert_layers and c.router_hidden_size else ())
                + (("block_applications", "exit_entropy") if exits else ())
                + tuple(f"exit_p_{t}" for t in exits)
                + tuple(f"loss_exit_{t}" for t in exits))

    def step_counters(self, model_state, counters):
        """The counters a step reports.  ``attn_blocks_visited``,
        ``attn_blocks_masked`` and ``attn_subtiles_skipped``: the score
        blocks one forward call of the causal attention visits a
        batch-head, those of them the diagonal crosses
        (``ops/flash_attention.py`` ``block_schedule``), and the sub-tiles
        above the diagonal it skips in those (``subtile``); 0 on the dense
        path; constants of the compiled step.
        Routing, each summed over the
        expert layers: ``routed_here`` (pairs on held experts),
        ``rows_grouped`` (rows the grouped products processed),
        ``expert_rows_max`` and ``expert_rows_mean`` (over the held
        experts); and ``bias_abs_max`` over all of them.  With an MLP
        router: ``held_share_pct`` (pairs on held experts over all pairs,
        the layers' mean), ``held_share_min_pct`` and
        ``held_share_max_pct`` over the layers, ``gate_mean`` (the mean
        probability of the expert picked) and ``router_entropy`` (the mean
        entropy of the router's distribution), both layer means.  A looped
        model's:
        ``block_applications`` (passes x layers: the program's structure,
        a constant of the compiled step and no device reading),
        ``exit_p_t`` (the batch's mean of each exit's weight),
        ``exit_entropy``, and ``loss_exit_t``, each exit's own mean
        cross-entropy (``counters["exit_losses"]``, from the step)."""
        visited, masked, skipped = counters["attn_blocks"][0]
        out = {"attn_blocks_visited": visited, "attn_blocks_masked": masked,
               "attn_subtiles_skipped": skipped}
        if self.config.expert_layers:
            layers = [layer["moe"] for name, layer in counters.items()
                      if name.startswith("layer_")]

            def total(name):
                return sum(layer[name][0] for layer in layers)

            out.update({
                "routed_here": total("routed_here"),
                "rows_grouped": total("rows_grouped"),
                "expert_rows_max": total("rows_max"),
                "expert_rows_mean": (total("routed_here")
                                     / self.config.experts_held[1]),
                "bias_abs_max": jnp.max(jnp.stack([
                    jnp.max(jnp.abs(s["moe"]["e_score_correction_bias"]))
                    for s in model_state.values()])),
            })
            if self.config.router_hidden_size:
                share = 100.0 * jnp.stack([
                    layer["routed_here"][0] / layer["expert_counts"][0].sum()
                    for layer in layers])
                out.update({
                    "held_share_pct": share.mean(),
                    "held_share_min_pct": share.min(),
                    "held_share_max_pct": share.max(),
                    "gate_mean": total("gate_mean") / len(layers),
                    "router_entropy": total("router_entropy") / len(layers),
                })
        if self.n_exits > 1:
            out["block_applications"] = jnp.int32(
                self.n_exits * self.config.num_hidden_layers)
            out["exit_entropy"] = counters["exit_entropy"][0]
            for t in range(self.n_exits):
                out[f"exit_p_{t + 1}"] = counters["exit_p"][0][t]
                out[f"loss_exit_{t + 1}"] = counters["exit_losses"][t]
        return out


class _Head(nn.Module):
    """The untied output head, stored ``[V, d]`` like an embedding (and
    like the checkpoint's ``lm_head.weight``)."""

    vocab_size: int

    @nn.compact
    def __call__(self, d_model: int):
        return self.param(
            "weight", nn.initializers.lecun_normal(in_axis=-1, out_axis=-2),
            (self.vocab_size, d_model), jnp.float32)
