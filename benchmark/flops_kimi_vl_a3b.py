"""Operations a training step of the configured decoder needs, from the
configuration file's own sizes (``configs/kimi-vl-a3b-ep8.json``).

A multiply-add is 2 operations, backward is twice forward, nothing is
recomputed (the blocks are rematerialised: the chip executes about a third
more), the optimizer's few operations per parameter are left out.  Causal
attention is half the square: a token sees ``seq_len / 2`` keys on average.
The routed experts are counted at the uniform expectation: of a token's
``num_experts_per_tok`` choices, ``held / routed`` fall on experts held
here (6 * 8 / 64 = 0.75).  This counts the work, not the implementation:
192 for scores and 128 for values, whatever the kernel pads to.
"""

from __future__ import annotations

from typing import Dict


def _sizes(cfg: Dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return d, h, qk, cfg["v_head_dim"]


def forward_flops_per_token(cfg: Dict) -> float:
    d, h, qk, vd = _sizes(cfg)
    rank, rot = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    seq = cfg["training"]["seq_len"]
    projections = 2.0 * (d * h * qk + d * (rank + rot)
                         + rank * h * (cfg["qk_nope_head_dim"] + vd)
                         + h * vd * d)
    scores = 2.0 * h * (qk + vd) * seq / 2
    dense = 2.0 * 3 * d * cfg["intermediate_size"]
    routed_all = cfg.get("deployment", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])
    width = cfg["moe_intermediate_size"]
    expert = (2.0 * 3 * d * width * cfg["n_shared_experts"]
              + 2.0 * d * routed_all
              + 2.0 * 3 * d * width * cfg["num_experts_per_tok"]
              * cfg["n_routed_experts"] / routed_all)
    layers, lead = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (layers * (projections + scores) + lead * dense
            + (layers - lead) * expert + 2.0 * d * cfg["vocab_size"])


def train_flops_per_item(cfg: Dict) -> float:
    """Forward plus backward operations for one token."""
    return 3.0 * forward_flops_per_token(cfg)
