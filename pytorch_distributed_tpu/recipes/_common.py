"""Shared recipe runner — the once-written equivalent of the reference's
byte-identical per-script harness block (SURVEY.md §0)."""

from __future__ import annotations

import random
from typing import Optional

import numpy as np

from pytorch_distributed_tpu.parallel import DistContext, data_parallel_mesh, initialize
from pytorch_distributed_tpu.train.config import Config, parse_config
from pytorch_distributed_tpu.train.trainer import Trainer
from pytorch_distributed_tpu.utils.compile_cache import enable_compile_cache


def seed_everything(seed: Optional[int]) -> None:
    """Reference main() seeding (distributed.py:116-124).  XLA programs are
    deterministic given fixed PRNG keys, so no cudnn.deterministic analogue
    is needed — the seed flows into jax.random.PRNGKey and the samplers."""
    if seed is not None:
        random.seed(seed)
        np.random.seed(seed)


def run_recipe(
    description: str,
    argv=None,
    precision_default: Optional[str] = None,
    explicit_collectives: bool = False,
    wire_dtype=None,
    grad_compress_default: Optional[str] = None,
    zero_default: Optional[str] = None,
    epoch_csv_default: Optional[str] = None,
    bootstrap: bool = True,
) -> float:
    cfg: Config = parse_config(argv, description=description)
    enable_compile_cache()
    seed_everything(cfg.seed)
    if cfg.precision is None:  # explicit --precision always wins
        cfg.precision = precision_default or "fp32"
    if cfg.grad_compress is None:  # explicit --grad-compress always wins
        cfg.grad_compress = grad_compress_default
    if cfg.zero is None:  # explicit --zero always wins
        cfg.zero = zero_default
    if epoch_csv_default is not None and cfg.epoch_csv is None:
        cfg.epoch_csv = epoch_csv_default
    ctx = initialize() if bootstrap else DistContext(0, 1, None)
    mesh = data_parallel_mesh()
    trainer = Trainer(
        cfg,
        mesh=mesh,
        ctx=ctx,
        explicit_collectives=explicit_collectives,
        wire_dtype=wire_dtype,
    )
    return trainer.fit()
