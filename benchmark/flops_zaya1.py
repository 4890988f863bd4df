"""Operations a training step of the ZAYA1 decoder needs, from the
configuration file's own sizes (``configs/zaya1-8b-ep2.json``).

A multiply-add is 2 operations, backward is twice forward, nothing is
recomputed (the blocks are rematerialised and the fused loss recomputes its
logits: the chip executes more), the optimizer's few operations per
parameter, the norms, the residual scales and the depthwise convolution's
``cca_time0`` multiply-adds a channel are left out.  Causal attention is
half the square: a token sees ``seq_len / 2`` keys on average; the grouped
key-value heads change the bytes, not the products (every query head meets
every key of its group).  The tied head is counted once forward: the
embedding's lookup is no product.

The routed experts are counted at ``HELD_SHARE``, the share of tokens
whose one pick is an expert held here: 0.5, the uniform expectation of 8 of
16.  It is a parameter of the count and no reading: a seeded router's share
is anything from none to all, a layer (PERF.md 6, PR 32).
"""

from __future__ import annotations

from typing import Dict

HELD_SHARE = 0.5


def attention_flops_per_token(cfg: Dict) -> float:
    d, h, g, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    projections = 2.0 * (d * h * hd + d * g * hd      # W_q, W_k
                         + d * g * hd                 # W_v1 and W_v2
                         + h * hd * d)                # W_o
    convolution = 2.0 * cfg["cca_time1"] * (h + g) * hd * hd
    scores = 2.0 * h * (hd + hd) * cfg["training"]["seq_len"] / 2
    return projections + convolution + scores


def router_flops_per_token(cfg: Dict) -> float:
    d, r = cfg["hidden_size"], cfg["router_hidden_size"]
    routed_all = cfg.get("deployment", {}).get(
        "num_experts", cfg["num_experts"])
    return 2.0 * (d * r + 2 * r * r + r * routed_all)


def forward_flops_per_token(cfg: Dict, held_share: float = HELD_SHARE
                            ) -> float:
    d = cfg["hidden_size"]
    expert = 2.0 * 3 * d * cfg["moe_intermediate_size"]
    layer = (attention_flops_per_token(cfg) + router_flops_per_token(cfg)
             + cfg["num_experts_per_tok"] * held_share * expert)
    return cfg["num_hidden_layers"] * layer + 2.0 * d * cfg["vocab_size"]


def train_flops_per_item(cfg: Dict) -> float:
    """Forward plus backward operations for one token."""
    return 3.0 * forward_flops_per_token(cfg)
