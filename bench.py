#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput per chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count", ...}.  Needs a TPU: with none
(absent, held by another process, failing to initialise) it exits
non-zero, says why and prints no number.

Baseline: the reference's only published experiment (BASELINE.md) — its
fastest recipe (apex AMP+DDP) does an ImageNet epoch (1,281,167 images) in
1186.5 s on 4× V100, i.e. ~270 images/sec/GPU.  ``vs_baseline`` is
our images/sec/chip divided by that per-device number.

Synthetic in-device data (no host IO) so the number isolates the compiled
step: forward + loss + backward + SGD update at global batch 256, bf16
compute policy — the same step the tpu_native recipe runs, with the
space-to-depth stem (mathematically identical to conv7, see models/resnet.py)
and bf16 image feed (what the u8-wire loader path delivers after device-side
normalize).

Roofline note (round-2 profile, scripts/profile_trace.py on the real v5e):
the step moves ~68 GB/step at ~690-750 GB/s effective against a ~819 GB/s
HBM peak — ResNet-50 b256 bf16 is **memory-bound** on this chip (arithmetic
intensity ~29-60 FLOP/byte vs the chip's ~240 balance point), so throughput
is capped near ~3,080 img/s at current traffic; conv fusions alone account
for 55.4 GB/step already running at 699 GB/s.  Batch 512, larger scoped
VMEM, and f32 feeds all measured slower.
"""

import json
import os
import sys
import time

METRIC = "resnet50_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"
REFERENCE_IMGS_PER_SEC_PER_DEVICE = 1281167 / 1186.5 / 4  # ≈ 269.9 (BASELINE.md)
BATCH = 256
IMAGE = 224


def _plan_prediction(device: dict, imgs_per_sec_per_chip: float) -> dict:
    """The autoplan cost model's predicted MFU for the exact benched
    config on the benched chip, next to the MFU the measured rate implies
    — stamped into the payload and the ``captured`` bench_event so the
    staleness report (scripts/obs_report.py) can show prediction drift."""
    from pytorch_distributed_tpu.obs.flops import (
        chip_peak_flops,
        image_step_cost,
    )
    from pytorch_distributed_tpu.plan import predicted_mfu, resnet50_spec

    n_chips = device["count"]
    predicted = predicted_mfu(
        "resnet50", n_chips, chip=device["kind"],
        spec=resnet50_spec(batch=BATCH, image_size=IMAGE))
    if predicted is None:  # no feasible plan for this chip count
        return {}
    step_s = BATCH / (imgs_per_sec_per_chip * n_chips)
    cost = image_step_cost("resnet50", BATCH, IMAGE)
    measured = (100.0 * cost.model_flops
                / (step_s * n_chips * chip_peak_flops(device["kind"])))
    return {"predicted_mfu": round(predicted, 2),
            "measured_mfu": round(measured, 2),
            "prediction_drift_pct": round(
                100.0 * (measured - predicted) / predicted, 1)}


def main() -> None:
    from pytorch_distributed_tpu.utils.chip import require_tpu
    from pytorch_distributed_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    try:
        device = require_tpu()
    except RuntimeError as e:
        sys.exit(f"bench.py: no TPU, no number: {e}")
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts"))
    from benchlib import bench_event

    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.parallel import data_parallel_mesh
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import (
        make_train_step,
        state_shardings,
    )
    from pytorch_distributed_tpu.utils.benchstep import measure_train_step

    mesh = data_parallel_mesh()
    rng = np.random.default_rng(0)
    device_batch = {
        "images": jnp.asarray(
            rng.normal(size=(BATCH, IMAGE, IMAGE, 3)), dtype=jnp.bfloat16
        ),
        "labels": jnp.asarray(rng.integers(0, 1000, size=BATCH).astype(np.int32)),
        "weights": jnp.ones((BATCH,), jnp.float32),
    }
    model = models.create_model(
        "resnet50", num_classes=1000, dtype=jnp.bfloat16,
        stem="space_to_depth",
    )
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, IMAGE, IMAGE, 3)), train=False
    )
    # placed as the step returns it, or the second call compiles again
    state = jax.device_put(
        TrainState.create(variables, sgd_init(variables["params"])),
        state_shardings(mesh))
    step = make_train_step(model, mesh)
    dt, _ = measure_train_step(step, state, device_batch, jnp.float32(0.1),
                               iters=20)
    imgs_per_sec_per_chip = BATCH / dt / device["count"]

    payload = {
        "metric": METRIC,
        "value": round(imgs_per_sec_per_chip, 1),
        "unit": UNIT,
        "vs_baseline": round(
            imgs_per_sec_per_chip / REFERENCE_IMGS_PER_SEC_PER_DEVICE, 3),
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
    }
    payload.update(_plan_prediction(device, imgs_per_sec_per_chip))
    bench_event("captured",
                captured_at=time.strftime("%Y-%m-%dT%H:%M:%S%z"), **payload)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
