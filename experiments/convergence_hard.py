#!/usr/bin/env python
"""Convergence oracle that can FAIL: 100-class low-SNR accuracy curves.

The round-2 oracle (experiments/convergence.py) saturates — 6 easy classes
hit 100% by epoch 2, so fp32/bf16/accum/collective numerics could not be
distinguished beyond gross breakage (VERDICT r2 "What's weak" #2).  This
experiment rebuilds the reference's accuracy oracle (per-epoch val top-1,
reference distributed.py:212,321-322) on a task hard enough to sit well
below the ceiling:

- **a hue wheel** (class c → hue c/CLASSES; 25 classes × 64 imgs/class)
  with per-image hue jitter at 0.45× the class spacing.  Hue is global, so
  the signal survives RandomResizedCrop + flip (position/texture codes do
  not), and the jitter puts an ANALYTIC ceiling on top-1:
  P(correct) = erf(spacing / (2·sqrt(2)·jitter·spacing)) =
  erf(1/(2·sqrt(2)·0.45)) ~= 73% — the curve plateaus mid-range by
  construction, where numerics differences would actually move it;
- configs: fp32, bf16, bf16+accum, explicit-collectives+bf16-wire
  (the Horovod-recipe analogue), and **1-device DP vs 8-device DP**
  (the data-parallel invariance claim, run in a subprocess with a 1-device
  mesh);
- pass criteria: every curve learns (final well above chance), NO curve
  saturates (the oracle keeps its discriminating power), and the final
  top-1 spread across configs stays within the noise window.

Writes ``RESULTS_convergence_hard.json``.  The short CI version lives in
tests/test_convergence_short.py.

Run (CPU 8-device mesh, ~40-60 min on one core):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=/root/repo python experiments/convergence_hard.py
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import jax
import numpy as np

# Steer to the simulated CPU mesh when asked (same dance as
# __graft_entry__.py).
if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass

CLASSES = int(os.environ.get("CONVH_CLASSES", "25"))
PER_CLASS_TRAIN = int(os.environ.get("CONVH_PER_CLASS", "64"))
PER_CLASS_VAL = int(os.environ.get("CONVH_PER_CLASS_VAL", "20"))
IMAGE = 32
EPOCHS = int(os.environ.get("CONVH_EPOCHS", "18"))
BATCH = 32
NOISE = float(os.environ.get("CONVH_NOISE", "0.10"))   # per-pixel noise sigma
TINT = float(os.environ.get("CONVH_TINT", "0.45"))     # hue signal strength
# Per-image hue jitter as a fraction of the class spacing (1/CLASSES):
# the irreducible confusion that pins the plateau below the ceiling.
# P(top-1) ~= erf(1 / (2*sqrt(2)*JITTER)) -> 0.34 gives ~86%... 0.5 ~ 68%.
# NOISE/TINT/LR set how FAST the curve rises; only JITTER (relative to the
# class spacing) sets the ceiling — the round-3 run (tint .25, noise .15,
# constant lr .06, 8 epochs) was still mid-rise at 11-14%, so round 4
# strengthens the signal and adds a cosine schedule to reach the plateau,
# where the spread gate has teeth (VERDICT r3).  Class-count note: the first
# round-4 attempt kept 100 classes at 16 imgs/class — train top-1 reached
# ~65% (≈ ceiling) while val pinned at ~25%: pure memorization of the tiny
# per-class sample, not hue reading.  25 classes × 64 imgs/class has the
# SAME epoch cost and the SAME analytic ceiling (jitter is a fraction of
# spacing), but 4× the per-class data — the generalization-gap fix.
JITTER = float(os.environ.get("CONVH_JITTER", "0.45"))
LR = float(os.environ.get("CONVH_LR", "0.12"))
CEILING = (100.0 if JITTER == 0 else
           100.0 * math.erf(1.0 / (2.0 * math.sqrt(2.0) * JITTER)))


def make_dataset(root: str, seed: int = 0) -> None:
    """Hue-wheel classes under per-image hue jitter and pixel noise —
    learnable, but the jitter caps top-1 well below 100% (see module
    docstring for the analytic ceiling)."""
    from PIL import Image

    rng = np.random.default_rng(seed)
    for split, per in (("train", PER_CLASS_TRAIN), ("val", PER_CLASS_VAL)):
        for c in range(CLASSES):
            d = os.path.join(root, split, f"class{c:03d}")
            os.makedirs(d, exist_ok=True)
            for i in range(per):
                # class hue + irreducible per-image jitter (the plateau knob)
                hue = c / CLASSES + rng.normal(0.0, JITTER / CLASSES)
                img = rng.normal(0.45, NOISE, size=(IMAGE, IMAGE, 3))
                tint = np.array([
                    0.5 + 0.5 * np.cos(2 * np.pi * (hue + k / 3.0))
                    for k in range(3)
                ])
                img += TINT * tint
                arr = (np.clip(img, 0, 1) * 255).astype(np.uint8)
                Image.fromarray(arr).save(os.path.join(d, f"{i:03d}.jpg"),
                                          quality=92)


def oracle_estimator_top1(root: str) -> float:
    """Top-1 of the Bayes-style hue reader on the ACTUAL val JPEGs.

    The generator is known (class hue + jitter + pixel noise + JPEG), so
    the best any model could do is read the hue back off the pixels and
    pick the nearest class.  Mean RGB projects the tint template out of
    the noise optimally (noise is iid per pixel); the cos/sin projection
    inverts hue from the three channel means.  The gap between this and
    the analytic ceiling (which assumes PERFECT hue recovery) is
    estimation loss the images themselves impose — quantifying how much
    of the network-vs-ceiling slack is achievable at all (VERDICT r4
    weak 5)."""
    from PIL import Image

    correct = total = 0
    vroot = os.path.join(root, "val")
    for cname in sorted(os.listdir(vroot)):
        c = int(cname.replace("class", ""))
        d = os.path.join(vroot, cname)
        for fn in os.listdir(d):
            v = np.asarray(Image.open(os.path.join(d, fn)),
                           np.float32).mean(axis=(0, 1)) / 255.0
            # v_k ~= base + TINT*(0.5 + 0.5*cos(2pi(hue + k/3)))
            k = np.arange(3) / 3.0
            a = float(np.sum(v * np.cos(2 * np.pi * k)))
            b = float(np.sum(v * np.sin(2 * np.pi * k)))
            # cos(2pi(hue+k/3)) = cos(2pi hue)cos(2pi k/3)
            #                     - sin(2pi hue)sin(2pi k/3)
            # => a = (3/4)TINT cos(2pi hue), b = -(3/4)TINT sin(2pi hue)
            hue = (np.arctan2(-b, a) / (2 * np.pi)) % 1.0
            pred = int(np.round(hue * CLASSES)) % CLASSES
            correct += int(pred == c)
            total += 1
    return 100.0 * correct / max(total, 1)


def run_config(data_root: str, tmpdir: str, name: str, precision: str,
               accum: int, explicit: bool, sync_bn: bool = False):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    cfg = Config(
        data=data_root, arch="resnet18", batch_size=BATCH, epochs=EPOCHS,
        # No warmup: LR 0.12 from epoch 0 proved stable (fp32 leg rising
        # cleanly), and the cached-curve fingerprint below predates the
        # warmup-ramp fix in train/lr.py — warmup 0 keeps every config on
        # the identical schedule the first legs ran.
        lr=LR, lr_schedule="cosine", lr_warmup_epochs=0,
        print_freq=1000, seed=0, image_size=IMAGE,
        precision=precision, accum_steps=accum,
        checkpoint_dir=os.path.join(tmpdir, name),
        workers=2, sync_bn=sync_bn,
    )
    t = Trainer(cfg, explicit_collectives=explicit,
                grad_compress="bf16" if explicit else None)
    curve = []
    for epoch in range(EPOCHS):
        t.train_epoch(epoch)
        curve.append(round(float(t.validate()), 3))
        print(f"[{name}] epoch {epoch}: top-1 {curve[-1]}", flush=True)
    return curve


CONFIGS = (
    # name, precision, accum, explicit_collectives, sync_bn
    ("fp32", "fp32", 1, False, False),
    ("bf16", "bf16", 1, False, False),
    # accum=4: BATCH(32)/accum must stay a multiple of the 8-device data
    # axis (the strided-microbatch constraint, train/steps.py) — 32/4 = 8.
    ("bf16_accum4", "bf16", 4, False, False),
    ("explicit_bf16wire", "fp32", 1, True, False),
    # --sync-bn (round 5): psum'd BN moments close the measured 18-point
    # per-shard-BN gap — this leg must rejoin the SyncBN-family spread.
    ("explicit_bf16wire_syncbn", "fp32", 1, True, True),
    # dp1_fp32 runs ONLY in the re-exec'd child (1-device mesh): same
    # global batch, one device — the DP-invariance leg.
    ("dp1_fp32", "fp32", 1, False, False),
)

# The explicit-collectives step deliberately uses PER-SHARD BatchNorm
# statistics (torch-DDP semantics, train/steps.py:103-107) — at this
# matrix's batch 32 / 8 shards that is BN over 4 samples, a genuinely
# different estimator, not a numerics difference.  Its curve is reported
# as a measured SEMANTIC delta vs the SyncBN family (round 4: −18 top-1
# points at plateau), outside the numerics spread gate.  (The reference's
# own regime is ~800 samples/GPU, where local BN is benign — the delta
# here is the small-per-shard-batch worst case, quantified.)
PERSHARD_BN = {"explicit_bf16wire"}


def main() -> int:
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    out_path = os.path.abspath(os.path.join(here, "..",
                                            "RESULTS_convergence_hard.json"))
    # The trailing tag is an OPAQUE cache key for the schedule; bump it
    # whenever run_config's schedule args change or stale curves get reused.
    fingerprint = [CLASSES, PER_CLASS_TRAIN, PER_CLASS_VAL, IMAGE, EPOCHS,
                   BATCH, NOISE, TINT, JITTER, LR, "cosine_warmup1"]
    only = os.environ.get("CONVH_ONLY", "")
    data_root = os.environ.get("CONVH_DATA", "")

    results = {}
    prior_meta = {}
    if os.path.exists(out_path):  # accumulate across partial runs
        try:
            with open(out_path) as f:
                prior = json.load(f)
            if prior.get("fingerprint") == fingerprint:
                results = prior.get("curves", {})
                prior_meta = prior.get("meta", {})
        except ValueError:
            pass

    def save():
        with open(out_path, "w") as f:
            json.dump({"meta": meta, "fingerprint": fingerprint,
                       "curves": results}, f, indent=1)

    meta = {
        "oracle": "per-epoch val top-1, sharded exact eval "
                  "(reference distributed.py:212,321-322)",
        "dataset": f"{CLASSES}-class low-SNR synthetic ImageFolder (JPEG), "
                   f"{CLASSES * PER_CLASS_TRAIN} train / "
                   f"{CLASSES * PER_CLASS_VAL} val, {IMAGE}px, "
                   f"noise {NOISE} tint {TINT} hue-jitter {JITTER}x spacing",
        "arch": "resnet18",
        "epochs": EPOCHS,
        "batch": BATCH,
        "lr": f"{LR} cosine, no warmup",
        "chance_pct": 100.0 / CLASSES,
        "analytic_ceiling_pct": round(CEILING, 2),
    }

    with tempfile.TemporaryDirectory() as tmp:
        if not data_root:
            data_root = os.path.join(tmp, "data")
            print("=== generating dataset ===", flush=True)
            make_dataset(data_root)
        is_child = bool(os.environ.get("CONVH_CHILD"))
        # Resume-aware: the oracle is a fixed function of the dataset —
        # reuse the recorded value instead of re-decoding every val JPEG
        # each invocation (children inherit it via the merged file).
        for k in ("oracle_estimator_top1", "achievable_pct",
                  "achievable_note", "achievable_conclusion"):
            if k in prior_meta:
                meta[k] = prior_meta[k]
        if "oracle_estimator_top1" not in meta and not is_child:
            meta["oracle_estimator_top1"] = round(
                oracle_estimator_top1(data_root), 2)
            meta["achievable_pct"] = meta["oracle_estimator_top1"]
            meta["achievable_note"] = (
                "top-1 of the known-generator hue-reader applied to the "
                "actual val JPEGs (mean-RGB -> least-squares hue -> nearest "
                "class): the ceiling the IMAGES support after pixel noise + "
                "JPEG, vs the analytic no-estimation-error ceiling "
                f"{round(CEILING, 2)} — network plateaus near the former "
                "mean the slack is estimation loss, not optimization")
        for name, precision, accum, explicit, sync_bn in CONFIGS:
            if only and name not in only.split(","):
                continue
            if name in results:
                print(f"=== {name}: cached ===", flush=True)
                continue
            if name.startswith("dp1_") and not is_child:
                # 1-device DP: same global batch on a 1-device mesh,
                # re-exec'd — the device count is fixed at backend init.
                print(f"=== {name} (subprocess, 1-device mesh) ===",
                      flush=True)
                env = dict(os.environ)
                env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
                env["CONVH_ONLY"] = name
                env["CONVH_DATA"] = data_root
                env["CONVH_CHILD"] = "1"
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__)], env=env)
                if r.returncode not in (0, 1):
                    print(f"{name} subprocess failed rc={r.returncode}")
                with open(out_path) as f:
                    results = json.load(f).get("curves", results)
                continue
            print(f"=== {name} ===", flush=True)
            results[name] = run_config(data_root, tmp, name, precision,
                                       accum, explicit, sync_bn)
            save()

    save()
    if os.environ.get("CONVH_CHILD"):
        return 0  # parent applies the gates over the merged file
    print(json.dumps({"curves": results}, indent=1))
    # Gates are applied AT THE PLATEAU (VERDICT r3): each final is the mean of
    # the last 3 epochs (cosine tail, LR≈0 — epoch noise is smallest there).
    finals = {k: round(float(np.mean(v[-3:])), 3) for k, v in results.items()}
    ok = True
    floor = 0.62 * CEILING  # relative so CONVH_JITTER stays tunable
    for k, curve in results.items():
        v = finals[k]
        # Per-shard-BN runs learn a noisier objective (see PERSHARD_BN
        # note): they must still clearly learn, but their floor is the
        # semantics-delta floor, not the SyncBN-family one.
        k_floor = 8 * meta["chance_pct"] if k in PERSHARD_BN else floor
        if v < k_floor:
            print(f"FAIL: {k} plateau top-1 {v} < {k_floor:.1f} "
                  f"(ceiling {CEILING:.1f})")
            ok = False
        if v > CEILING + 4.0:  # above the analytic ceiling = generator leak
            print(f"FAIL: {k} plateau top-1 {v} exceeds analytic ceiling "
                  f"{CEILING:.1f}+4")
            ok = False
        if len(curve) >= 6:  # plateaued: last-3 mean within 3 of prior-3 mean
            rise = float(np.mean(curve[-3:]) - np.mean(curve[-6:-3]))
            if rise > 3.0:
                print(f"FAIL: {k} still climbing at the end "
                      f"(+{rise:.2f} points over last 3 epochs)")
                ok = False
    sync = {k: v for k, v in finals.items() if k not in PERSHARD_BN}
    spread = max(sync.values()) - min(sync.values()) if sync else 0.0
    if sync:
        # Numerics gate, at plateau where it has teeth: bf16 compute,
        # in-graph accumulation, 1-vs-8-device DP must NOT move the curve.
        if spread > 5.0:
            print(f"FAIL: SyncBN-family plateau spread {spread:.2f} > 5")
            ok = False
    # The semantic delta is only meaningful against the fp32 anchor
    # (partial CONVH_ONLY runs may lack it — report nothing rather than
    # an absolute score mislabeled as a delta).
    deltas = ({k: round(finals[k] - finals["fp32"], 2)
               for k in finals if k in PERSHARD_BN}
              if "fp32" in finals else {})
    print("convergence_hard:", "OK" if ok else "MISMATCH",
          f"plateau_finals={finals} syncbn_spread={spread:.2f} "
          f"pershard_bn_delta={deltas} ceiling={CEILING:.1f}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
