"""Multi-process / multi-host bootstrap.

One ``initialize()`` replaces the reference's four rendezvous mechanisms
(SURVEY.md §2.3 "Rendezvous" row):

- env-var launcher (``--local_rank`` from ``torch.distributed.launch``,
  reference distributed.py:73-76,132)
- explicit TCP (``tcp://127.0.0.1:23456``, multiprocessing_distributed.py:132-135)
- SLURM env + shared-file store (distributed_slurm_main.py:124-131,137-140)
- Horovod/MPI (horovod_distributed.py:125-127)

On TPU pods ``jax.distributed.initialize()`` auto-discovers coordinator,
process count and index from the TPU metadata; for CPU/GPU clusters (and the
SLURM-equivalent recipe) we derive them from the environment the same way the
reference's slurm script does, minus its world-size/rank inconsistency
(SURVEY.md §3.5 "latent inconsistency" — we always count *processes*).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax


@dataclasses.dataclass(frozen=True)
class DistContext:
    """Who am I in the job?  (reference args.nprocs / local_rank analogue)."""

    process_index: int
    process_count: int
    coordinator: Optional[str]

    @property
    def is_primary(self) -> bool:
        """Rank-0 guard for checkpointing/logging (reference
        distributed.py:218 ``if args.local_rank == 0``)."""
        return self.process_index == 0


def _first_slurm_host(nodelist: str) -> str:
    """First hostname of a SLURM nodelist, dashed-hostname safe.

    ``scontrol show hostnames`` is authoritative (handles every compressed
    form); the fallback only expands the bracket range — it never splits on
    ``-`` outside brackets, so ``tpu-host[01-04]`` → ``tpu-host01`` and
    ``gpu-node-01`` stays intact (round-1 advisor finding)."""
    if not nodelist:
        return "127.0.0.1"
    try:
        import subprocess

        out = subprocess.run(
            ["scontrol", "show", "hostnames", nodelist],
            capture_output=True, text=True, timeout=10,
        )
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.split()[0]
    except (OSError, subprocess.SubprocessError):
        pass
    head = nodelist.split(",")[0]
    if "[" in head:
        prefix, rest = head.split("[", 1)
        first_token = rest.rstrip("]").split(",")[0].split("-")[0]
        return prefix + first_token
    return head


def _slurm_env() -> Optional[dict]:
    """Derive multi-host topology from SLURM (reference
    distributed_slurm_main.py:124-128), fixed to count processes not nodes."""
    if "SLURM_PROCID" not in os.environ:
        return None
    nodelist = os.environ.get("SLURM_STEP_NODELIST", os.environ.get("SLURM_NODELIST", ""))
    first = _first_slurm_host(nodelist)
    return {
        "process_id": int(os.environ["SLURM_PROCID"]),
        "num_processes": int(os.environ.get("SLURM_NTASKS", os.environ.get("SLURM_NPROCS", "1"))),
        "coordinator_address": f"{first}:{os.environ.get('PTD_TPU_PORT', '12355')}",
    }


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> DistContext:
    """Initialize multi-process JAX if the job is multi-process; no-op for the
    single-process recipes (dataparallel-equivalent).

    Resolution order: explicit args → ``PTD_TPU_*`` env vars (our launcher
    contract, the ``torch.distributed.launch`` env:// analogue) → SLURM env →
    TPU-pod auto-detect (bare ``jax.distributed.initialize()`` when
    ``JAX_COORDINATOR_ADDRESS`` or TPU metadata provides one) → single process.
    """
    env = os.environ
    if coordinator_address is None and "PTD_TPU_COORDINATOR" in env:
        coordinator_address = env["PTD_TPU_COORDINATOR"]
        num_processes = int(env.get("PTD_TPU_NUM_PROCESSES", "1"))
        process_id = int(env.get("PTD_TPU_PROCESS_ID", "0"))
    if coordinator_address is None:
        slurm = _slurm_env()
        if slurm is not None and slurm["num_processes"] > 1:
            coordinator_address = slurm["coordinator_address"]
            num_processes = slurm["num_processes"]
            process_id = slurm["process_id"]

    if coordinator_address is not None and (num_processes or 1) > 1:
        platforms = jax.config.jax_platforms or ""
        if "cpu" in platforms.split(","):
            # Multi-process CPU meshes (the test/e2e simulation path) need
            # the gloo collectives implementation — the default XLA CPU
            # client refuses cross-process computations outright.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    elif env.get("JAX_COORDINATOR_ADDRESS"):
        # TPU pod: runtime metadata fills in everything.
        jax.distributed.initialize()

    return DistContext(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        coordinator=coordinator_address,
    )


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()
