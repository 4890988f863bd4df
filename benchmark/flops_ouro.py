"""Operations a training step of the looped decoder needs, from the
configuration file's own sizes (``configs/ouro-2.6b.json``).

A multiply-add is 2 operations, backward is twice forward, nothing is
recomputed (every block application is rematerialised: the chip executes
about a third more), the optimizer's few operations per parameter and the
exit gate's ``d`` per exit are left out.  Causal attention is half the
square: a token sees ``seq_len / 2`` keys on average.  The stack runs
``total_ut_steps`` times and every pass has its own exit, so a token costs
that many block stacks and that many heads: this counts applications, not
parameters.
"""

from __future__ import annotations

from typing import Dict


def block_flops_per_token(cfg: Dict) -> float:
    """One application of one block to one token, forward."""
    d, h, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    projections = 2.0 * 4 * d * h * hd
    scores = 2.0 * h * (hd + hd) * cfg["training"]["seq_len"] / 2
    return projections + scores + 2.0 * 3 * d * cfg["intermediate_size"]


def forward_flops_per_token(cfg: Dict) -> float:
    passes = cfg["total_ut_steps"]
    return passes * (cfg["num_hidden_layers"] * block_flops_per_token(cfg)
                     + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_item(cfg: Dict) -> float:
    """Forward plus backward operations for one token."""
    return 3.0 * forward_flops_per_token(cfg)
