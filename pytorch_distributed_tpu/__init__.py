"""pytorch_distributed_tpu — a TPU-native distributed training framework.

A brand-new framework with the capability matrix of
tczhangzhi/pytorch-distributed (see /root/repo/SURVEY.md): one canonical
ImageNet-classification training harness offered as a matrix of
interchangeable distributed-training recipes, built idiomatically on
JAX/XLA for TPU:

- ``parallel/``  — device meshes over ICI/DCN, ``jax.distributed`` bootstrap,
  collective helpers, sequence-parallel ring attention.  Replaces the
  reference's NCCL / Horovod / SLURM rendezvous stacks (SURVEY.md §5.8).
- ``data/``      — sharded, epoch-reshuffled, double-buffered input pipeline.
  Replaces ``DistributedSampler`` + the apex CUDA-stream ``data_prefetcher``
  (reference apex_distributed.py:115-169).
- ``models/``    — model registry (ResNet family and friends) mirroring the
  torchvision-zoo introspection surface (reference distributed.py:21-23).
- ``ops/``       — loss / metric ops and Pallas TPU kernels.
- ``train/``     — the canonical harness: meters, LR schedule, SGD, jitted
  SPMD train/eval steps, checkpointing, epoch driver
  (reference distributed.py:228-395).
- ``recipes/``   — one entry point per reference script, same flag surface.
- ``utils/``     — CSV timers and TPU telemetry (reference statistics.sh).
"""

__version__ = "0.1.0"

from pytorch_distributed_tpu import models  # noqa: F401  (registry import)
