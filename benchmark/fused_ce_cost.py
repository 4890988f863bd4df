"""What the differentiated fused loss computes in one step: operations and
bytes from the cell's configuration and traffic files.

The counts are of the work, not of the implementation, in the manner of
``attention_cost.py``: the loss with its gradient is three head products
over every loss row (the logits ``h E^T``, ``dh = dlogit E`` and
``dE += dlogit^T h``; a multiply-add is 2 operations), and the algorithm
is chunked, so a chunk reads ``E`` once and its rows once, writes its
float32 logits once and reads them once, reads and writes the float32
``dE`` accumulator once, and writes its ``dh`` rows.  A looped decoder's
every exit has its own rows.  So an implementation a later PR swaps in is
read against the same floor.
"""

from __future__ import annotations

from typing import Dict, Tuple


def loss_rows(cfg: Dict, traffic: Dict) -> int:
    """Loss rows a chip's step holds: exits x sequences x (L - 1)."""
    return (cfg.get("total_ut_steps", 1) * traffic["sequences_per_chip"]
            * (traffic["seq_len"] - 1))


def step_cost(rows: int, d: int, vocab: int, chunks: int,
              itemsize: int = 2) -> Tuple[float, float]:
    """``(operations, bytes)`` of one step's loss and its gradients."""
    products = 3 * 2.0 * rows * d * vocab
    head = chunks * vocab * d * itemsize          # E, once a chunk
    accumulator = chunks * 2 * vocab * d * 4      # dE: read, add, write
    logits = 2 * rows * vocab * 4                 # written once, read once
    hidden = 2 * rows * d * itemsize              # h in, dh out
    return products, float(head + accumulator + logits + hidden)


def floor_seconds(cfg: Dict, traffic: Dict, peaks: Dict[str, float]) -> float:
    """The least time the chip could take for one step's loss: the larger
    of its operations over the peak FLOP/s and its bytes over the peak
    bytes/s."""
    ops, moved = step_cost(loss_rows(cfg, traffic), cfg["hidden_size"],
                           cfg["vocab_size"],
                           cfg["training"]["fused_ce_chunks"])
    return max(ops / peaks["flops_per_s_bf16"],
               moved / peaks["hbm_bytes_per_s"])
