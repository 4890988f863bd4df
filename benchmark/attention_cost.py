"""What one call of a causal attention kernel computes: operations and
bytes from its shapes, and which of the three calls (forward, the
backward's dq pass, its dk/dv pass) an event of a capture is.

The counts are of the work, not of the implementation: causal attention is
half the square, a multiply-add is 2 operations, every operand is read once
and every result written once at its own width (the softmax statistics as
one float32 a row, whatever lanes a kernel pads them to).  So a kernel that
a later PR swaps in is read against the same floor.

- forward: ``S = Q K^T`` and ``O = P V``;
- dq pass: ``S`` again, ``dP = dO V^T``, ``dQ = dS K``;
- dk/dv pass: ``S`` again, ``dP``, ``dV = P^T dO``, ``dK = dS^T Q``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import trace_reduce

KERNEL = re.compile(r"^%?attn(\.\d+)?$")   # a Mosaic call under scope "attn"
_ARRAY = re.compile(r"\b(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def head_sizes(cfg: Dict) -> Tuple[int, int]:
    """Query/key and value head widths of a configuration: plain heads, or
    a latent-attention file's two."""
    if "kv_lora_rank" in cfg:
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    return cfg["head_dim"], cfg["head_dim"]


def results(text: str) -> List[Tuple[str, int, int, int]]:
    """The arrays an instruction produces, ``(dtype, BH, L, D)`` each, from
    its text ``%attn.3 = (bf16[32,8192,128]{...}, f32[...]{...})
    custom-call(...)``."""
    return [(t, int(a), int(b), int(c))
            for t, a, b, c in _ARRAY.findall(trace_reduce.op_name(text))]


def call_kind(text: str) -> Optional[str]:
    """``fwd`` (the output and float32 row statistics), ``dq`` (one
    result) or ``dkv`` (two results of the operands' type); ``None`` for an
    event that is no attention kernel's."""
    if not KERNEL.match(text.partition(" = ")[0].strip()):
        return None
    out = results(text)
    if len(out) == 1:
        return "dq"
    if len(out) == 2:
        return "fwd" if out[1][0] == "f32" != out[0][0] else "dkv"
    return None


def call_cost(kind: str, bh: int, seq: int, d_qk: int, d_v: int,
              itemsize: int = 2, causal: bool = True) -> Tuple[float, float]:
    """``(operations, bytes)`` of one call over ``bh`` batch-heads of
    ``seq`` positions."""
    square = bh * seq * seq * (0.5 if causal else 1.0)
    products = {"fwd": d_qk + d_v, "dq": 2 * d_qk + d_v,
                "dkv": 2 * d_qk + 2 * d_v}[kind]
    rows = bh * seq
    qkv = rows * (2 * d_qk + d_v) * itemsize
    if kind == "fwd":      # + O, and the row statistics
        moved = qkv + rows * d_v * itemsize + rows * 4
    else:                  # + dO, statistics and delta in; the gradients out
        grads = d_qk if kind == "dq" else d_qk + d_v
        moved = qkv + rows * d_v * itemsize + 2 * rows * 4 \
            + rows * grads * itemsize
    return 2.0 * square * products, float(moved)


def floor_seconds(kind: str, bh: int, seq: int, d_qk: int, d_v: int,
                  peaks: Dict[str, float], itemsize: int = 2) -> float:
    """The least time the chip could take for the call: the larger of its
    operations over the peak FLOP/s and its bytes over the peak bytes/s."""
    flops, moved = call_cost(kind, bh, seq, d_qk, d_v, itemsize)
    return max(flops / peaks["flops_per_s_bf16"],
               moved / peaks["hbm_bytes_per_s"])
