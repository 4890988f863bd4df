"""Shared measurement harness for train-step throughput benchmarks.

One implementation of the timing discipline used by ``bench.py`` (the
driver headline) and ``experiments/arch_bench.py`` (the zoo table), so the
two can never drift apart on the subtle part: dispatch is asynchronous, so
the clock starts and stops only after ``jax.block_until_ready`` on the
step's outputs — the barrier of the TPU runtime.
"""

from __future__ import annotations

import time
from typing import Tuple


def measure_train_step(step, state, device_batch, lr,
                       iters: int = 20, warmup: int = 3) -> Tuple[float, object]:
    """Seconds per compiled train-step call, ``block_until_ready``
    synchronized.

    ``step(state, device_batch, lr) -> (state, metrics)`` with a scalar
    ``metrics["loss"]``.  Returns ``(sec_per_step, final_state)``; raises
    FloatingPointError if the final loss is not finite.
    """
    import jax
    import numpy as np

    for _ in range(warmup):
        state, metrics = step(state, device_batch, lr)
    if warmup:
        jax.block_until_ready((state, metrics))  # drain before t0
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, device_batch, lr)
    jax.block_until_ready((state, metrics))
    dt = (time.perf_counter() - t0) / iters
    loss = float(metrics["loss"])
    if not np.isfinite(loss):
        raise FloatingPointError(f"train step produced loss {loss}")
    return dt, state


def looks_like_oom(err: BaseException) -> bool:
    """Heuristic: is this a memory/VMEM-capacity failure a smaller batch
    could fix (vs a deterministic error retrying cannot)?"""
    text = f"{type(err).__name__}: {err}"
    needles = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
               "OOM", "Attempting to allocate", "vmem", "VMEM",
               "exceeds the limit", "Ran out of memory")
    return any(n in text for n in needles)
