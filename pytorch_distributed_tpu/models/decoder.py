"""A decoder LM built from a configuration file with the catalog's key names
(a Hugging Face ``config.json``): RMSNorm, SwiGLU, rotary attention, an
untied head.  The file's keys choose the block, not a class:

- attention: ``kv_lora_rank`` present -> latent attention (``MLA``: one
  compressed latent a position, a rotary key shared by the heads); absent ->
  plain multi-head attention (``MHA``) with the file's ``head_dim`` and
  rotary over the whole head;
- feed-forward: ``n_routed_experts`` present -> sigmoid-routed experts beside
  shared ones after ``first_k_dense_replace`` dense layers; absent -> dense
  SwiGLU in every layer, and the file carries no expert key;
- residual path: ``norm_placement: "sandwich"`` -> a norm before and after
  attention and feed-forward (four a block); absent -> pre-norm (two);
- depth: ``total_ut_steps`` T > 1 -> the whole stack runs T times over the
  same weights (a scan over the passes, the parameters broadcast), the
  final norm after every pass; each pass's output is an exit with its own
  logits, and one ``Linear(d, 1)`` exit gate turns the exits' hidden rows
  into a distribution over them (``exit_distribution``).

``from_dict`` refuses by name what the model does not implement: grouped-query
heads (``num_key_value_heads`` other than ``num_attention_heads``), windowed
layers (``use_sliding_window``, ``sliding_window``, a ``layer_types`` entry
other than ``full_attention``), a compressed query (``q_lora_rank``), rotary
length scaling, biases, a tied head, grouped or softmax routing, several
passes over routed experts.

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
(latent attention, experts) and ``tests/reference_ouro.py`` (the looped,
multi-exit decoder).
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
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    rms_norm_eps: float
    rope_theta: float
    head_dim: int = 0                # plain heads (no ``kv_lora_rank``)
    kv_lora_rank: int = 0            # latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_routed_experts: int = 0        # the router's width
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
        unsupported = {
            "q_lora_rank": (None,), "rope_scaling": (None,),
            "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
            "n_group": (1,), "topk_group": (1,), "moe_layer_freq": (1,),
            "hidden_act": ("silu",), "attention_bias": (False,),
            "tie_word_embeddings": (False,),
            "use_sliding_window": (False,), "sliding_window": (None,),
            "layer_types": (["full_attention"] * len(
                cfg.get("layer_types", ())),),
            "norm_placement": ("sandwich", "pre")}
        if "kv_lora_rank" not in cfg:  # latent attention has no such heads
            unsupported["num_key_value_heads"] = (cfg["num_attention_heads"],)
        for key, allowed in unsupported.items():
            if key in cfg and cfg[key] not in allowed:
                raise ValueError(
                    f"DecoderLM has no {key}={cfg[key]!r}; it implements "
                    f"{allowed[0]!r}")
        deployment = cfg.get("deployment", {})
        held = cfg.get("n_routed_experts", 0)
        if held and cfg.get("total_ut_steps", 1) > 1:
            raise ValueError(
                "DecoderLM has no total_ut_steps > 1 over n_routed_experts: "
                "the scan over the passes carries no routing state")
        assumed = cfg.get("training", {})
        fields = {f.name for f in dataclasses.fields(cls)}
        given = {k: v for k, v in cfg.items()
                 if k in fields and k != "n_routed_experts"}
        given.setdefault("first_k_dense_replace", 0 if held else layers)
        return cls(
            n_routed_experts=deployment.get("n_routed_experts", held),
            experts_held=(deployment.get("first_expert", 0), held),
            sandwich_norm=cfg.get("norm_placement") == "sandwich",
            exit_entropy_beta=assumed.get("exit_entropy_beta", 0.0),
            remat=bool(assumed.get("remat", False)),
            bias_update_rate=assumed.get("bias_update_rate", 0.001),
            seq_aux_alpha=(assumed.get("seq_aux_alpha", 1e-4)
                           if cfg.get("seq_aux", True) else 0.0),
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


# The flash kernels' (q, kv) block sizes at L = 8192, gradient of one call
# (forward + dq + dk/dv) over 32 batch-heads of 128 | 128 and over 64 of
# 192 | 128: 1024 x 1024 19.32 / 54.96 ms, 512 x 1024 20.68 / 58.34,
# 512 x 2048 20.96 / out of VMEM, 256 x 1024 (the kernel's default) 23.76 /
# 64.88, 2048 x 512 24.49 / out of VMEM, 1024 x 512 24.86 / 67.47,
# 512 x 512 25.81 / 68.11 (my chip run, PR 31): one size for both widths
FLASH_BLOCKS = (1024, 1024)


def attention_blocks(L: int, attn_impl: str) -> Tuple[int, int]:
    """``(visited, masked)``: the score blocks one forward call of
    ``causal_attention`` visits a batch-head and those of them it masks;
    ``(0, 0)`` where it takes the dense path."""
    from pytorch_distributed_tpu.ops.flash_attention import (
        blocks_visited,
        pick_attention_impl,
    )

    if pick_attention_impl(L, attn_impl) != "flash":
        return 0, 0
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


class DecoderBlock(nn.Module):
    """``h = x + Attn(norm(x)); y = h + FFN(norm(h))``; with
    ``sandwich_norm`` each branch's output is normalised too, by a scale of
    its own, before it joins the residual stream."""

    config: DecoderConfig
    dtype: Any = jnp.float32
    mesh: Optional[Mesh] = None
    attn_impl: str = "auto"
    expert_layer: bool = False

    @nn.compact
    def __call__(self, x):
        c = self.config

        def norm(name):
            return RMSNorm(c.rms_norm_eps, name=name)

        def joins(x, branch, name):
            if c.sandwich_norm:
                branch = norm(name)(branch)
            return x + branch.astype(x.dtype)

        kind, attention = ("mla", MLA) if c.kv_lora_rank else ("attn", MHA)
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
                seq_aux_alpha=c.seq_aux_alpha, dtype=self.dtype,
                name="moe")(h)
        else:
            h = _SwiGLU(c.intermediate_size, self.dtype, name="mlp")(h)
        return joins(x, h, "ffn_out_norm")


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

    @staticmethod
    def head_matrix(params):
        """The output head as ``[V, d]``, the layout of a tied embedding."""
        return params["head"]["weight"]

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        c = self.config
        # which attention the blocks below run at this length: a constant
        # of the compiled program
        self.sow("counters", "attn_blocks", jnp.array(
            attention_blocks(tokens.shape[1], self.attn_impl), jnp.int32))
        x = nn.Embed(c.vocab_size, c.hidden_size, dtype=self.dtype,
                     name="embed")(tokens)
        block_cls = nn.remat(DecoderBlock) if c.remat else DecoderBlock

        def one_pass(lm, x, _=None):
            """The whole stack and the final norm, once: the next pass's
            input and, the same rows, this pass's exit."""
            with scope("ut_pass"):
                x = x.astype(self.dtype)   # norm_f hands on float32
                for i in range(c.num_hidden_layers):
                    x = block_cls(
                        c, self.dtype, self.mesh, self.attn_impl,
                        expert_layer=i >= c.first_k_dense_replace,
                        name=f"layer_{i}", parent=lm)(x)
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
        head = _Head(c.vocab_size, name="head")(c.hidden_size)
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
            return exits if c.total_ut_steps > 1 else last
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

    @property
    def counter_names(self) -> Tuple[str, ...]:
        """The names of ``step_counters``: the loop books them on its
        ``dispatch`` record, the benchmark's runners read them from the
        step's metrics."""
        exits = range(1, self.n_exits + 1) if self.n_exits > 1 else ()
        return (("attn_blocks_visited", "attn_blocks_masked")
                + (self.ROUTING_COUNTERS if self.config.expert_layers else ())
                + (("block_applications", "exit_entropy") if exits else ())
                + tuple(f"exit_p_{t}" for t in exits)
                + tuple(f"loss_exit_{t}" for t in exits))

    def step_counters(self, model_state, counters):
        """The counters a step reports.  ``attn_blocks_visited`` and
        ``attn_blocks_masked``: the score blocks one forward call of the
        causal attention visits a batch-head, and those of them the
        diagonal crosses (``ops/flash_attention.py`` ``block_schedule``; 0
        and 0 on the dense path; constants of the compiled step).
        Routing, each summed over the
        expert layers: ``routed_here`` (pairs on held experts),
        ``rows_grouped`` (rows the grouped products processed),
        ``expert_rows_max`` and ``expert_rows_mean`` (over the held
        experts); and ``bias_abs_max`` over all of them.  A looped model's:
        ``block_applications`` (passes x layers: the program's structure,
        a constant of the compiled step and no device reading),
        ``exit_p_t`` (the batch's mean of each exit's weight),
        ``exit_entropy``, and ``loss_exit_t``, each exit's own mean
        cross-entropy (``counters["exit_losses"]``, from the step)."""
        visited, masked = counters["attn_blocks"][0]
        out = {"attn_blocks_visited": visited, "attn_blocks_masked": masked}
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
