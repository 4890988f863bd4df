"""What one call of a causal attention kernel with grouped key-value heads
computes: operations and bytes from its shapes, and which of the three
calls an event of a capture is.

``attention_cost.py`` for a call whose K and V have fewer heads than Q.
That file takes a call's batch-heads from its first result, which is right
while every array has ``B * H`` of them; a grouped call's arrays do not
(K, V and, in a kernel that sums a group inside, dK and dV have ``B * G``),
so here the caller says how many query and key-value heads the
configuration has and the counts follow from those, whatever the call's
first result is.  The counts are of the work, not of the implementation:
causal attention is half the square over ``B * H`` query heads (every
query head meets every key of its group), a multiply-add is 2 operations,
Q, O, dO and dQ move at ``B * H`` heads and K, V, dK and dV at ``B * G``,
each read or written once at its own width, the softmax statistics as one
float32 a query row.  So a kernel that a later PR swaps in (one that sums
a group inside, or one that repeats K and V in memory) is read against the
same floor.
"""

from __future__ import annotations

from typing import Dict, Tuple

import attention_cost

KERNEL = attention_cost.KERNEL


def head_counts(cfg: Dict) -> Tuple[int, int, int]:
    """Query heads, key-value heads and the head's width."""
    return (cfg["num_attention_heads"],
            cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            cfg["head_dim"])


# ``fwd``, ``dq`` or ``dkv``: that file reads the kind from how many results
# a call has and whether the second is the float32 statistics beside an
# output of another type, which holds for a dk/dv pass that leaves the sum
# over a group outside and so writes two float32 results
call_kind = attention_cost.call_kind


def call_cost(kind: str, batch: int, heads: int, kv_heads: int, seq: int,
              d: int, itemsize: int = 2, causal: bool = True
              ) -> Tuple[float, float]:
    """``(operations, bytes)`` of one call over ``batch`` sequences of
    ``seq`` positions, ``heads`` query heads over ``kv_heads`` key-value
    heads of ``d`` channels."""
    square = batch * heads * seq * seq * (0.5 if causal else 1.0)
    products = {"fwd": 2 * d, "dq": 3 * d, "dkv": 4 * d}[kind]
    q_rows, kv_rows = batch * heads * seq, batch * kv_heads * seq
    moved = q_rows * d * itemsize + 2 * kv_rows * d * itemsize   # Q, K, V
    if kind == "fwd":       # + O, and the row statistics
        moved += q_rows * d * itemsize + q_rows * 4
    else:                   # + dO, statistics and delta in; gradients out
        moved += q_rows * d * itemsize + 2 * q_rows * 4
        moved += (q_rows * d if kind == "dq" else 2 * kv_rows * d) * itemsize
    return 2.0 * square * products, float(moved)


def floor_seconds(kind: str, batch: int, heads: int, kv_heads: int, seq: int,
                  d: int, peaks: Dict[str, float], itemsize: int = 2) -> float:
    """The least time the chip could take for the call: the larger of its
    operations over the peak FLOP/s and its bytes over the peak bytes/s."""
    flops, moved = call_cost(kind, batch, heads, kv_heads, seq, d, itemsize)
    return max(flops / peaks["flops_per_s_bf16"],
               moved / peaks["hbm_bytes_per_s"])
