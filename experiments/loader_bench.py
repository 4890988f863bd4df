#!/usr/bin/env python
"""Input-pipeline throughput: what can the host actually feed?

Round-1 gap (VERDICT "What's missing" #3): the train-step bench excludes
host IO, and nothing measured whether the loader can sustain chip feed
rates (~2,500 img/s for ResNet-50 bf16 on one v5e chip).  This bench
generates an ImageNet-shaped synthetic JPEG ImageFolder (real JPEG decode
work) and measures ``DataLoader`` throughput in every wire mode, both
decode backends.

Writes ``RESULTS_loader.json`` at the repo root and prints one line per
mode.  Pure host work — runs anywhere:

    PYTHONPATH=/root/repo python experiments/loader_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_IMAGES = int(os.environ.get("LOADER_BENCH_IMAGES", "512"))
SRC = int(os.environ.get("LOADER_BENCH_SRC", "320"))  # source jpeg size
BATCH = 64
IMAGE = 224


def make_tree(root: str, n: int) -> None:
    from PIL import Image

    rng = np.random.default_rng(0)
    per = n // 4
    for c in range(4):
        d = os.path.join(root, "train", f"c{c}")
        os.makedirs(d, exist_ok=True)
        for i in range(per):
            arr = rng.integers(0, 256, size=(SRC, SRC, 3), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i:04d}.jpg"),
                                      quality=85)


def bench_native_threads(root: str, n_threads: int) -> float:
    """Raw native decode+crop+resize rate at a fixed thread count: the
    scaling axis for 'can the host feed the chip at N cores'."""
    import glob

    from pytorch_distributed_tpu.data.native import decode_crop_resize_batch

    files = sorted(glob.glob(os.path.join(root, "train", "*", "*.jpg")))
    blobs = [open(f, "rb").read() for f in files[:N_IMAGES]]
    # center-crop params (deterministic: scaling is the variable here)
    decode_crop_resize_batch(blobs[:BATCH], IMAGE, n_threads=n_threads)  # warm
    t0 = time.perf_counter()
    n = 0
    for lo in range(0, len(blobs), BATCH):
        chunk = blobs[lo:lo + BATCH]
        decode_crop_resize_batch(chunk, IMAGE, n_threads=n_threads)
        n += len(chunk)
    return n / (time.perf_counter() - t0)


def bench_mode(root: str, batch_mode: str, transform_kind: str,
               workers: int, worker_type: str = "thread") -> float:
    from pytorch_distributed_tpu.data import DataLoader, ImageFolder
    from pytorch_distributed_tpu.data import transforms as T

    if transform_kind == "f32":
        tf = T.train_transform(IMAGE)
    elif transform_kind == "u8":
        tf = T.train_transform_u8(IMAGE)
    else:
        tf = None  # native decode path supplies its own
    ds = ImageFolder(os.path.join(root, "train"), transform=tf,
                     native_decode=transform_kind == "native",
                     image_size=IMAGE)
    loader = DataLoader(ds, BATCH, num_workers=workers, drop_last=True,
                        batch_mode=batch_mode,
                        random_flip=batch_mode != "f32",
                        worker_type=worker_type)
    # warm one epoch fragment, then time a full pass
    it = iter(loader)
    next(it)
    t0 = time.perf_counter()
    n = 0
    for batch in loader:
        n += int(batch["weights"].sum())
    dt = time.perf_counter() - t0
    return n / dt


def main() -> int:
    import tempfile

    workers = int(os.environ.get("LOADER_BENCH_WORKERS",
                                 str(os.cpu_count() or 2)))
    results = {}
    with tempfile.TemporaryDirectory() as tmp:
        make_tree(tmp, N_IMAGES)
        for name, mode, kind in (
            ("pil_f32", "f32", "f32"),
            ("pil_u8_host_native_norm", "u8_host", "u8"),
            ("pil_u8_wire", "u8_wire", "u8"),
            ("native_decode_u8_host", "u8_host", "native"),
            ("native_decode_u8_wire", "u8_wire", "native"),
        ):
            try:
                rate = bench_mode(tmp, mode, kind, workers)
            except Exception as e:  # modes may be unavailable (no .so)
                print(f"{name}: SKIP ({e})")
                continue
            results[name] = round(rate, 1)
            print(f"{name}: {rate:,.0f} img/s ({workers} workers)", flush=True)

        # Process workers: the GIL-proof mode for the PIL path (reference
        # DataLoader worker processes, reference distributed.py:176-180).
        try:
            rate = bench_mode(tmp, "u8_wire", "u8", max(2, workers),
                              worker_type="process")
            results["pil_u8_wire_proc_workers"] = round(rate, 1)
            print(f"pil_u8_wire_proc_workers: {rate:,.0f} img/s", flush=True)
        except Exception as e:
            print(f"pil_u8_wire_proc_workers: SKIP ({e})")

        # Native decode thread scaling: on an N-core host the decode is
        # embarrassingly parallel (per-image, shared-nothing); the table
        # shows per-thread efficiency on THIS host and the extrapolated
        # core count needed to hit chip feed rate.
        scaling = {}
        try:
            for nt in (1, 2, 4, 8):
                scaling[str(nt)] = round(bench_native_threads(tmp, nt), 1)
                print(f"native_threads={nt}: {scaling[str(nt)]:,.1f} img/s",
                      flush=True)
        except Exception as e:
            print(f"native thread scaling: SKIP ({e})")

    # Per-core rate = the 1-thread rate (aggregate max would over-count on
    # multi-core hosts where threads actually run in parallel).
    per_core = scaling.get("1") if scaling else None
    out = {
        "meta": {
            "images": N_IMAGES, "src_px": SRC, "out_px": IMAGE,
            "batch": BATCH, "workers": workers,
            "cpus": os.cpu_count(),
            "note": "synthetic ImageNet-shaped JPEGs; feed target is "
                    "~2500 img/s/chip (ResNet-50 bf16, July 2026 reading)",
        },
        "img_per_sec": results,
        "native_thread_scaling": {
            "img_per_sec_by_threads": scaling,
            "note": "shared-nothing per-image decode, measured on a "
                    f"{os.cpu_count()}-core host; per_core = the 1-thread "
                    "rate.  Threads beyond the core count only time-slice "
                    "(flat aggregate = zero contention overhead), so N "
                    "physical cores scale the rate ~linearly",
            "per_core_img_per_sec": per_core,
            "cores_needed_for_2500_img_per_sec": (
                int(np.ceil(2500 / per_core)) if per_core else None
            ),
        },
    }
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "RESULTS_loader.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
