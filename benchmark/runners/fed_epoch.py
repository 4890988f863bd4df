"""Runner `fed_epoch`: the recipe as a user types it, fed from JPEG files.

``recipes/tpu_native.main`` builds the ``Trainer`` (its own argument
parsing, mesh, model, loaders, feeder, compiled step); only ``Trainer.fit``
is replaced, by this runner's measurement around ``Trainer.train_epoch``,
so that no evaluation and no checkpoint are paid for.  Everything is
observed from outside, as ``chip_smoke._spy_placement`` does: the feeder's
iterator (``data_wait``), ``DeviceFeeder._put`` (``h2d``, and where each
batch was placed), the compiled step (losses, the labels it was given).
The window is cut through hooks ``train_epoch`` already has: the
``profiler`` argument's ``step_begin`` marks and drains, the preemption
flag ends the epoch.

The JPEG set is data on disk, like ImageNet: written once under
``benchmark/cache/datasets/`` from the seed in the traffic file, and reused
by every later run in the checkout.  ``--seed`` drives the weights, the
sampler's order and the augmentation.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402


# ------------------------------------------------------------- the JPEG set

def _write_jpeg(path: str, seed: int, k: int, width: int, height: int,
                quality: int) -> None:
    """Smooth colour fields plus fine noise: a 500x375 file of ~110 KB at
    quality 90, the size of an average ImageNet file, so that decoding
    costs what it costs there."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng((seed, k))
    coarse = Image.fromarray(rng.integers(0, 256, (8, 10, 3), dtype=np.uint8))
    medium = Image.fromarray(rng.integers(
        0, 256, (max(1, height // 6), max(1, width // 6), 3), dtype=np.uint8))
    pixels = (np.asarray(coarse.resize((width, height), Image.BICUBIC),
                         np.int16) * 3
              + np.asarray(medium.resize((width, height), Image.BILINEAR),
                           np.int16)) // 4
    pixels += rng.integers(-40, 41, (height, width, 3), dtype=np.int16)
    Image.fromarray(np.clip(pixels, 0, 255).astype(np.uint8)).save(
        path, "JPEG", quality=quality)


def ensure_dataset(spec: dict) -> str:
    """``<root>/train/<class>/<entry>.jpg`` and a one-file ``val``; returns
    the root.  ``files`` distinct JPEGs, hard-linked up to ``entries``."""
    want = {k: spec[k] for k in ("seed", "classes", "files", "width",
                                 "height", "jpeg_quality", "entries")}
    key = hashlib.sha256(json.dumps(want, sort_keys=True).encode()
                         ).hexdigest()[:16]
    root = os.path.join(harness.CACHE, "datasets", key)
    done = os.path.join(root, "complete.json")
    if os.path.exists(done):
        return root
    t = time.perf_counter()
    classes, files, entries = spec["classes"], spec["files"], spec["entries"]
    if files % classes or entries % files:
        raise ValueError("files must be a multiple of classes, and entries "
                         "of files")
    names = [f"class_{c:04d}" for c in range(classes)]
    for name in names:
        os.makedirs(os.path.join(root, "train", name), exist_ok=True)

    def one(k: int) -> None:
        folder = os.path.join(root, "train", names[k % classes])
        first = os.path.join(folder, f"img_{k:06d}_000.jpg")
        if not os.path.exists(first):
            _write_jpeg(first + ".tmp", spec["seed"], k, spec["width"],
                        spec["height"], spec["jpeg_quality"])
            os.replace(first + ".tmp", first)
        for copy in range(1, entries // files):
            link = os.path.join(folder, f"img_{k:06d}_{copy:03d}.jpg")
            if not os.path.exists(link):
                os.link(first, link)

    with ThreadPoolExecutor(max(1, (os.cpu_count() or 2) - 1)) as pool:
        list(pool.map(one, range(files)))
    os.makedirs(os.path.join(root, "val", names[0]), exist_ok=True)
    link = os.path.join(root, "val", names[0], "img_000000_000.jpg")
    if not os.path.exists(link):
        os.link(os.path.join(root, "train", names[0], "img_000000_000.jpg"),
                link)
    with open(done, "w") as f:
        json.dump(want, f)
    harness.say("dataset_written", root=os.path.relpath(root, harness.ROOT),
                seconds=time.perf_counter() - t)
    return root


def folder_labels(root: str):
    """The label of every entry of ``<root>/train``, in ``ImageFolder``'s
    order (classes sorted, files sorted inside each class), worked out by
    the harness from the folder itself."""
    import numpy as np

    train = os.path.join(root, "train")
    labels = []
    for label, name in enumerate(sorted(
            d for d in os.listdir(train)
            if os.path.isdir(os.path.join(train, d)))):
        count = sum(1 for f in os.listdir(os.path.join(train, name))
                    if f.lower().endswith(".jpg"))
        labels.extend([label] * count)
    return np.asarray(labels, np.int32)


def expected_labels(labels, seed: int, epoch: int, batch: int, steps: int):
    """What the seeded sampler names for the first ``steps`` batches of an
    epoch: ``DistributedShardSampler``'s permutation on one process, which
    visits every entry once (nothing dropped, nothing sent twice)."""
    import numpy as np

    order = np.random.default_rng((seed, epoch)).permutation(len(labels))
    return labels[order[:steps * batch]].reshape(steps, batch)


# ------------------------------------------------------ watching from outside

class _TimedFeeder:
    """``trainer.feeder`` with a ``data_wait`` span around each ``next()``
    of the iterator it hands to ``train_epoch``."""

    def __init__(self, inner, spans):
        self._inner, self._spans = inner, spans

    def __call__(self, host_iter):
        return _TimedIter(self._inner(host_iter), self._spans)


class _TimedIter:
    def __init__(self, inner, spans):
        self._inner, self._spans = inner, spans

    def __next__(self):
        with self._spans("data_wait"):
            return next(self._inner)

    def close(self):
        self._inner.close()


class _Flag:
    """What ``train_epoch`` polls as its preemption guard."""

    triggered = False


class _Window:
    """``train_epoch``'s ``profiler`` argument: ``step_begin`` is called at
    the top of every step.  Drains and marks at the first step after
    warm-up; drains and marks again at the first step past ``seconds``;
    then raises the flag, in a traced run after a few seconds more under
    the profiler."""

    def __init__(self, cell, trainer, flag, warmup: int, tracer):
        self.cell, self.trainer, self.flag = cell, trainer, flag
        self.warmup, self.tracer = warmup, tracer
        self.t0 = self.t1 = None
        self.first = self.last = None
        self.begun = []   # perf_counter at every step_begin of the window

    def _drain(self):
        import jax

        with self.cell.spans("block"):
            jax.block_until_ready(self.trainer.state)

    def step_begin(self, epoch: int, i: int) -> None:
        tr = self.tracer
        if self.t1 is not None:
            if tr is not None and tr.running and not tr.open():
                tr.stop()
                self.flag.triggered = True
        elif self.t0 is None:
            if i >= self.warmup:
                self._drain()
                self.t0, self.first = time.perf_counter(), i
        else:
            self.begun.append(time.perf_counter())
            if self.begun[-1] - self.t0 >= self.cell.seconds:
                self._drain()
                self.t1, self.last = time.perf_counter(), i
                if tr is None:
                    self.flag.triggered = True
                else:
                    tr.start()


def build_trainer(argv):
    """The ``Trainer`` exactly as ``recipes/tpu_native.main(argv)`` builds
    it; ``fit`` is replaced for the call, so nothing trains yet."""
    from pytorch_distributed_tpu.recipes import _common, tpu_native

    held = {}

    def keep_instead_of_fit(self):
        held["trainer"] = self
        return 0.0

    fit, _common.Trainer.fit = _common.Trainer.fit, keep_instead_of_fit
    try:
        tpu_native.main(argv)
    finally:
        _common.Trainer.fit = fit
    return held["trainer"]


def run(cell: harness.Cell) -> harness.Run:
    import jax
    import numpy as np

    from pytorch_distributed_tpu.data.loader import DeviceFeeder

    cfg, traffic, spans = cell.config, cell.traffic, cell.spans
    if len(jax.devices()) != cell.chips:
        raise RuntimeError(
            f"the recipe spans every device it finds ({len(jax.devices())}); "
            f"this cell wants a machine with exactly {cell.chips}")
    cores = os.cpu_count() or 2
    workers = (max(1, cores - 2) if traffic["workers"] == "host_cores-2"
               else int(traffic["workers"]))
    harness.say("host", cores=cores, loader_workers=workers,
                worker_type=traffic["worker_type"])
    root = ensure_dataset(traffic["dataset"])
    batch_size = traffic["batch_per_chip"] * cell.chips

    # h2d: DeviceFeeder._put, wrapped on the class before the Trainer makes
    # its feeder; also records where every batch was placed
    placements = []
    inner_put = DeviceFeeder._put

    def put(self, batch):
        with spans("h2d"):
            out = inner_put(self, batch)
        placements.append(harness.placed_everywhere(out, cell.devices))
        return out

    opt = cfg["optimizer"]
    argv = ["--data", root, "-a", cfg["arch"], "-b", str(batch_size),
            "--image-size", str(cfg["image_size"]),
            "--wire", traffic["wire"], "-j", str(workers),
            "--worker-type", traffic["worker_type"],
            "--seed", str(cell.seed), "--epochs", "1",
            "--lr", str(opt["lr"]), "--momentum", str(opt["momentum"]),
            "--wd", str(opt["weight_decay"]),
            "--precision", cfg["precision"],
            "--epoch-csv", os.path.join(harness.CACHE, "fed_epoch.csv"),
            "--checkpoint-dir", os.path.join(harness.CACHE, "fed_runs")]
    DeviceFeeder._put = put
    trainer = build_trainer(argv)
    if trainer.cfg.num_classes != cfg["num_classes"]:
        raise RuntimeError(f"the folder has {trainer.cfg.num_classes} "
                           f"classes, the configuration {cfg['num_classes']}")

    ref = harness.reference_check(trainer.model, cfg, trainer.state.params,
                                  trainer.state.batch_stats, cell.seed)
    harness.say("reference", **ref)
    checks = {"agrees_with_reference": ref["ok"]}

    seen = []   # (loss, labels) of every step, left on the device
    inner_step = trainer.train_step

    def step(state, batch, lr):
        with spans("dispatch"):
            out = inner_step(state, batch, lr)
        seen.append((out[1]["loss"], batch["labels"]))
        return out

    trainer.train_step = step
    trainer.feeder = _TimedFeeder(trainer.feeder, spans)
    trainer.preempt = flag = _Flag()
    tracer = harness.TraceWindow(cell) if cell.trace else None
    window = _Window(cell, trainer, flag, traffic["warmup_steps"], tracer)
    epoch_len = len(trainer.train_loader)
    try:
        completed, stopped = trainer.train_epoch(0, profiler=window)
    finally:
        DeviceFeeder._put = inner_put
        if tracer and tracer.running:
            tracer.stop()
    if window.t1 is None:
        raise RuntimeError(
            f"the epoch ({epoch_len} batches) ended after {completed} steps, "
            f"before the window did: raise `entries` in the traffic file")

    steps = window.last - window.first
    losses = [float(l) for l, _ in seen[window.first:window.last]]
    failed = sum(1 for v in losses if not math.isfinite(v))
    got = np.stack([np.asarray(lab) for _, lab in seen[:window.last]])
    want = expected_labels(folder_labels(root), cell.seed, 0, batch_size,
                           window.last)
    checks["losses_finite"] = failed == 0
    checks["labels_are_the_samplers"] = bool(np.array_equal(got, want))
    checks["batch_on_every_device"] = bool(placements) and all(placements)
    checks["no_compile_in_window"] = cell.compiles.inside(
        window.t0, window.t1) == 0
    harness.say("losses", first=losses[:3], last=losses[-3:], n=len(losses))
    harness.say("epoch", batches=epoch_len, completed=completed,
                stopped_by_flag=stopped, window_steps=steps)
    gaps = sorted(b - a for a, b in zip(window.begun, window.begun[1:]))
    if gaps:
        harness.say("step_intervals_ms", n=len(gaps), **{
            name: 1e3 * gaps[min(len(gaps) - 1, int(q * len(gaps)))]
            for name, q in (("min", 0.0), ("p25", 0.25), ("p50", 0.5),
                            ("p75", 0.75), ("max", 1.0))})
    return harness.Run(
        items=steps * batch_size, window_start=window.t0,
        window_end=window.t1, attempted=steps, failed=failed, checks=checks,
        # its own name: host threads pace this run, and its spread, a
        # hundred times a resident cell's, must not set their bound
        end_to_end={"fed_throughput_per_chip": steps * batch_size / (
            window.t1 - window.t0) / cell.chips},
        trace_file=tracer.file if tracer else None,
        notes={"batch": batch_size, "loader_workers": workers,
               "host_cores": cores, "step_program": "jit_global_step"})
