#!/usr/bin/env python
"""Per-architecture training throughput across the model zoo, on the chip.

The reference's surface is "any torchvision arch by name" (``models.__dict__
[args.arch]()``, distributed.py:134-139) but its single published experiment
times only one arch.  This sweep puts a real number on a representative
slice of the 36-arch zoo: full compiled train step (fwd+bwd+SGD, bf16
compute, f32 BN/softmax), synthetic in-device data, one chip — the same
discipline as bench.py, minus the resnet50-specific space-to-depth stem so
every row is the arch's *default* config (the tuned resnet50 headline lives
in BENCH_*.json).

Per-arch global batch starts at 256 and halves on OOM/VMEM-capacity
failure (deterministic errors fail the arch immediately); the fallback
batch is recorded in the row.  Inception runs its canonical 299 input;
everything else 224.

Run on the TPU chip:
    PYTHONPATH=/root/repo python experiments/arch_bench.py
"""

from __future__ import annotations

import json
import os

import numpy as np

ITERS = int(os.environ.get("ARCH_BENCH_ITERS", "10"))
ARCHS = os.environ.get(
    "ARCH_BENCH_ARCHS",
    "alexnet,vgg16_bn,resnet18,resnet34,resnet50,resnet101,resnet152,"
    "wide_resnet50_2,resnext50_32x4d,densenet121,mobilenet_v2,"
    "inception_v3,vit_b_16",
).split(",")


def bench_arch(arch: str):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.parallel import data_parallel_mesh
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step

    from pytorch_distributed_tpu.utils.benchstep import (
        looks_like_oom,
        measure_train_step,
    )

    image = 299 if arch == "inception_v3" else 224
    mesh = data_parallel_mesh()
    rng = np.random.default_rng(0)
    last_err = None
    for batch in (256, 128, 64):
        try:
            device_batch = {
                "images": jnp.asarray(
                    rng.normal(size=(batch, image, image, 3)),
                    dtype=jnp.bfloat16),
                "labels": jnp.asarray(
                    rng.integers(0, 1000, size=batch).astype(np.int32)),
                "weights": jnp.ones((batch,), jnp.float32),
            }
            model = models.create_model(
                arch, num_classes=1000, dtype=jnp.bfloat16)
            variables = model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3)),
                train=False)
            n_params = sum(
                x.size for x in jax.tree_util.tree_leaves(
                    variables["params"]))
            state = TrainState.create(variables, sgd_init(variables["params"]))
            step = make_train_step(model, mesh)
            dt, _ = measure_train_step(
                step, state, device_batch, jnp.float32(0.1), iters=ITERS)
            return {
                "img_per_sec_per_chip": round(
                    batch / dt / jax.device_count(), 1),
                "ms_per_step": round(dt * 1e3, 2),
                "batch": batch,
                "image": image,
                "params_m": round(n_params / 1e6, 1),
            }
        except Exception as e:  # noqa: BLE001
            if not looks_like_oom(e):
                raise  # deterministic failure — halving cannot fix it
            last_err = e  # OOM/VMEM: halve the batch and retry
    raise RuntimeError(f"{arch} failed at every batch: {last_err!r}")


def main() -> int:
    import jax

    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "RESULTS_archs.json")
    # Resumable: keep rows already measured by a previous (partial) run so
    # a stall or timeout never costs completed archs.
    results = {}
    if os.path.exists(path):
        with open(path) as f:
            results = {k: v for k, v in json.load(f)["configs"].items()
                       if "error" not in v}

    def write():
        out = {
            "meta": {
                "platform": jax.default_backend(),
                "iters": ITERS,
                "precision": "bf16 compute, f32 BN/LN/softmax",
                "what": "full train step (fwd+bwd+SGD) per zoo arch, "
                        "default stem/config, synthetic in-device data, "
                        "one chip",
                "note": "resnet50's tuned (space-to-depth) headline is "
                        "BENCH_*.json; this table is the arch-by-name "
                        "surface (reference distributed.py:134-139) "
                        "measured as-is",
            },
            "configs": results,
        }
        tmp = path + ".tmp"  # atomic: a mid-write kill must not eat rows
        with open(tmp, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)

    for arch in ARCHS:
        if arch in results:
            print(f"{arch}: kept from previous run", flush=True)
            continue
        try:
            row = bench_arch(arch)
        except Exception as e:  # noqa: BLE001 — record and continue
            print(f"{arch}: FAILED {repr(e)[:200]}", flush=True)
            results[arch] = {"error": repr(e)[:200]}
            write()
            continue
        results[arch] = row
        print(f"{arch}: {row['img_per_sec_per_chip']:,} img/s/chip  "
              f"({row['ms_per_step']} ms @ b{row['batch']}, "
              f"{row['params_m']}M params)", flush=True)
        write()
    print("wrote RESULTS_archs.json", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
