"""DataLoader / DeviceFeeder behavior on the simulated 8-device mesh."""

import numpy as np
import pytest

from pytorch_distributed_tpu.data import (
    DataLoader,
    DeviceFeeder,
    DistributedShardSampler,
    SyntheticImageDataset,
)
from pytorch_distributed_tpu.parallel import data_parallel_mesh


def _loader(n=24, bsz=8, **kw):
    ds = SyntheticImageDataset(length=n, num_classes=5, image_size=8)
    return DataLoader(ds, batch_size=bsz, sampler=DistributedShardSampler(n, shuffle=False), **kw)


def _kept(batches):
    """Every batch of an iterator, each copied as it is drawn: a
    process-fed batch views shared memory that the next draw rewrites."""
    return [{k: v.copy() for k, v in batch.items()} for batch in batches]


def test_feeder_shards_batches_over_data_axis():
    feeder = DeviceFeeder(data_parallel_mesh())
    batches = list(feeder(iter(_loader())))
    assert len(batches) == 3
    b = batches[0]
    assert b["images"].shape == (8, 8, 8, 3)
    assert b["images"].sharding.spec == (("data",) + b["images"].sharding.spec[1:]) or str(
        b["images"].sharding.spec
    ).startswith("PartitionSpec('data'")


def test_feeder_raises_on_indivisible_batch_in_consumer():
    """Regression: a producer-thread failure must surface at the consumer,
    not silently truncate the epoch (found by verification probe)."""
    feeder = DeviceFeeder(data_parallel_mesh())
    with pytest.raises(ValueError, match="must divide"):
        next(iter(feeder(iter(_loader(bsz=12)))))


def test_feeder_early_exit_stops_producer_thread():
    """Breaking out of the epoch loop (or closing the generator) must not
    leave the producer thread blocked on a full prefetch queue."""
    import threading

    before = {t.ident for t in threading.enumerate()}
    feeder = DeviceFeeder(data_parallel_mesh())
    it = feeder(iter(_loader(n=64)))
    next(it)  # producer running, queue filling
    it.close()  # early exit mid-epoch
    leaked = [
        t for t in threading.enumerate()
        if t.ident not in before and t.is_alive()
    ]
    for t in leaked:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in leaked)


def test_final_batch_padding_and_mask():
    loader = _loader(n=20, bsz=8)  # 3 batches, last has 4 real samples
    batches = list(iter(loader))
    assert len(batches) == 3
    assert batches[-1]["weights"].tolist() == [1, 1, 1, 1, 0, 0, 0, 0]
    # Padding slots are zeros, not garbage.
    assert np.all(batches[-1]["images"][4:] == 0)


def test_epoch_changes_augmentation_not_content_order():
    ds = SyntheticImageDataset(length=8, num_classes=5, image_size=8)
    sampler = DistributedShardSampler(8, shuffle=False)
    loader = DataLoader(ds, batch_size=8, sampler=sampler)
    loader.set_epoch(0)
    b0 = next(iter(loader))
    loader.set_epoch(1)
    b1 = next(iter(loader))
    # No transform ⇒ identical content regardless of epoch.
    np.testing.assert_array_equal(b0["images"], b1["images"])
    np.testing.assert_array_equal(b0["labels"], b1["labels"])


def test_transform_rng_varies_by_epoch():
    from pytorch_distributed_tpu.data.transforms import train_transform

    ds = SyntheticImageDataset(
        length=8, num_classes=5, image_size=32, transform=train_transform(size=16)
    )
    sampler = DistributedShardSampler(8, shuffle=False)
    loader = DataLoader(ds, batch_size=8, sampler=sampler)
    loader.set_epoch(0)
    b0 = next(iter(loader))
    loader.set_epoch(1)
    b1 = next(iter(loader))
    assert not np.array_equal(b0["images"], b1["images"])


def test_process_workers_match_thread_workers():
    """worker_type='process' (spawn pool, GIL-proof PIL path) must produce
    byte-identical batches to the thread pool — same per-sample RNG keys."""
    from pytorch_distributed_tpu.data.transforms import train_transform

    ds = SyntheticImageDataset(
        length=20, num_classes=5, image_size=32,
        transform=train_transform(size=16),
    )
    batches = {}
    for wt in ("thread", "process"):
        sampler = DistributedShardSampler(20, shuffle=True, seed=3)
        loader = DataLoader(ds, batch_size=8, sampler=sampler,
                            num_workers=2, worker_type=wt)
        loader.set_epoch(1)
        batches[wt] = _kept(loader)
        loader.close()
    assert len(batches["thread"]) == len(batches["process"])
    for a, b in zip(batches["thread"], batches["process"]):
        np.testing.assert_array_equal(a["images"], b["images"])
        np.testing.assert_array_equal(a["labels"], b["labels"])
        np.testing.assert_array_equal(a["weights"], b["weights"])


def _reference_batches(loader, start):
    """The batches of the loader's current epoch, assembled the plain way:
    a zero-filled array, one row copy a sample, the flip by fancy index."""
    from pytorch_distributed_tpu.data.native import normalize_batch
    from pytorch_distributed_tpu.data.transforms import (
        IMAGENET_MEAN,
        IMAGENET_STD,
    )

    indices, valid = loader.sampler.shard()
    bsz, epoch = loader.batch_size, loader.sampler.epoch
    u8 = loader.batch_mode != "f32"
    for b in range(start, len(loader)):
        idx = indices[b * bsz:(b + 1) * bsz]
        val = valid[b * bsz:(b + 1) * bsz]
        samples = [
            loader.dataset.get(
                int(i), np.random.default_rng((loader.seed, epoch, int(i))))
            for i, v in zip(idx, val) if v
        ]
        images = np.zeros((bsz,) + samples[0][0].shape,
                          np.uint8 if u8 else np.float32)
        labels = np.zeros(bsz, np.int32)
        weights = np.zeros(bsz, np.float32)
        for i, (image, label) in enumerate(samples):
            images[i], labels[i], weights[i] = image, label, 1.0
        if u8 and loader.random_flip:
            flip = np.random.default_rng(
                (loader.seed, epoch, b, 1)).random(bsz) < 0.5
            fidx = np.nonzero(flip)[0]
            images[fidx] = images[fidx, :, ::-1, :]
        if loader.batch_mode == "u8_host":
            images = normalize_batch(images, IMAGENET_MEAN, IMAGENET_STD)
        yield {"images": images, "labels": labels, "weights": weights}


@pytest.mark.parametrize("start", [0, 1], ids=["epoch", "resumed"])
@pytest.mark.parametrize("worker_type", ["thread", "process"])
@pytest.mark.parametrize("batch_mode", ["f32", "u8_host", "u8_wire"])
def test_batches_equal_a_plain_assembly(batch_mode, worker_type, start):
    """Rows placed by whoever fetched them (loader.py ``_Rows.place``) give
    the bytes of a serial copy-and-flip: every mode and worker type, a
    padded trailing batch, a resumed epoch, two epochs, flip on and off."""
    from pytorch_distributed_tpu.data.transforms import (
        train_transform,
        train_transform_u8,
    )

    stack = train_transform(size=16) if batch_mode == "f32" else (
        train_transform_u8(16))
    ds = SyntheticImageDataset(length=20, num_classes=5, image_size=32,
                               transform=stack)
    loader = DataLoader(
        ds, batch_size=8, num_workers=3, seed=7, batch_mode=batch_mode,
        worker_type=worker_type,
        sampler=DistributedShardSampler(20, shuffle=True, seed=3))
    try:
        for epoch in (0, 1):
            for flip in (False, True):
                loader.set_epoch(epoch)
                loader.random_flip = flip
                got = _kept(loader.iter_batches(start))
                want = list(_reference_batches(loader, start))
                assert len(got) == len(want) == 3 - start
                assert got[-1]["weights"].tolist() == [1] * 4 + [0] * 4
                for g, w in zip(got, want):
                    assert sorted(g) == sorted(w)
                    for key in w:
                        assert g[key].dtype == w[key].dtype, key
                        np.testing.assert_array_equal(g[key], w[key], key)
        if batch_mode == "u8_wire":  # the draw does flip some rows
            loader.random_flip = False
            plain = _kept(loader.iter_batches(start))
            assert any((g["images"] != p["images"]).any()
                       for g, p in zip(got, plain))
    finally:
        loader.close()


class _OddSamples:
    """Eight uint8 samples; the third raises, or comes back float32."""

    def __init__(self, fault):
        self.fault = fault

    def __len__(self):
        return 8

    def __getitem__(self, index):
        if index == 2 and self.fault == "raises":
            raise OSError("sample 2 cannot be read")
        dtype = (np.float32 if index == 2 and self.fault == "float32"
                 else np.uint8)
        return np.full((4, 4, 3), index, dtype), index


@pytest.mark.parametrize("worker_type", ["thread", "process"])
@pytest.mark.parametrize("fault,error", [("raises", OSError),
                                         ("float32", TypeError)])
def test_a_bad_sample_surfaces_at_the_iterator(fault, error, worker_type):
    loader = DataLoader(_OddSamples(fault), batch_size=4, num_workers=2,
                        batch_mode="u8_wire", worker_type=worker_type)
    message = ("sample 2 cannot be read" if fault == "raises"
               else "batch_mode 'u8_wire' needs uint8 samples")
    try:
        with pytest.raises(error, match=message):
            next(iter(loader))
    finally:
        loader.close()


class _Together:
    """Sixteen samples at a time leave ``__getitem__`` in the same instant."""

    def __init__(self, n):
        import threading

        self.n = n
        self.barrier = threading.Barrier(16)

    def __len__(self):
        return self.n

    def __getitem__(self, index):
        self.barrier.wait(10.0)
        return np.full((8, 8, 3), index % 251, np.uint8), index


def test_rows_survive_more_workers_than_cores_racing_to_allocate():
    """A batch's array is allocated by the first worker to land a row, under
    a lock: a second allocation would drop the rows already written.  Sixteen
    workers reach ``place`` together, the interpreter switching every
    microsecond, 200 batches."""
    import sys

    ds = _Together(3200)
    loader = DataLoader(ds, batch_size=16, num_workers=16,
                        batch_mode="u8_wire",
                        sampler=DistributedShardSampler(3200, shuffle=False))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        got = list(loader)
    finally:
        sys.setswitchinterval(interval)
    assert len(got) == 200
    for b, batch in enumerate(got):
        want = np.arange(16 * b, 16 * b + 16)
        np.testing.assert_array_equal(batch["labels"], want)
        np.testing.assert_array_equal(batch["images"][:, 0, 0, 0], want % 251)


# ------------------------------------------- worker processes, shared memory

def _stack_for(batch_mode, size=16):
    from pytorch_distributed_tpu.data.transforms import (
        train_transform,
        train_transform_u8,
    )

    return (train_transform(size=size) if batch_mode == "f32"
            else train_transform_u8(size))


def _twins(batch_mode, n=52, bsz=8, workers=3, **kw):
    """The same loader fed by threads and by processes: seven batches a
    epoch, the last padded, more than the ring holds."""
    ds = SyntheticImageDataset(length=n, num_classes=5, image_size=32,
                               transform=_stack_for(batch_mode))
    return [DataLoader(ds, batch_size=bsz, num_workers=workers, seed=7,
                       batch_mode=batch_mode, worker_type=worker_type,
                       sampler=DistributedShardSampler(n, shuffle=True,
                                                       seed=3), **kw)
            for worker_type in ("thread", "process")]


@pytest.mark.parametrize("start", [0, 3], ids=["epoch", "resumed"])
@pytest.mark.parametrize("batch_mode", ["f32", "u8_host", "u8_wire"])
def test_process_fed_batches_are_the_thread_fed_bytes(batch_mode, start):
    """Rows written by worker processes into shared memory are, byte for
    byte, the rows worker threads write: two epochs, the flip drawn, a
    padded trailing batch, the ring (three buffers) gone round twice."""
    from pytorch_distributed_tpu.data.loader import SharedBatch

    threads, processes = _twins(batch_mode, random_flip=True)
    try:
        for epoch in (0, 1):
            threads.set_epoch(epoch)
            processes.set_epoch(epoch)
            drawn = 0
            for want, got in zip(threads.iter_batches(start),
                                 processes.iter_batches(start)):
                assert isinstance(got, SharedBatch)
                assert sorted(got) == sorted(want)
                for key in want:
                    assert got[key].dtype == want[key].dtype, key
                    np.testing.assert_array_equal(got[key], want[key], key)
                drawn += 1
            assert drawn == 7 - start
            assert got["weights"].tolist() == [1] * 4 + [0] * 4
            assert not got["labels"][4:].any()  # a buffer used before
            if batch_mode != "u8_host":  # which normalises the zeros
                assert not got["images"][4:].any()
    finally:
        processes.close()


def _settled(loader, seconds=60.0):
    """Wait until the workers have filled every batch they were given."""
    import time

    tasks = [t for _, _, batch in loader._inflight for t in batch]
    limit = time.monotonic() + seconds
    while not all(t.done() for t in tasks):
        assert time.monotonic() < limit
        time.sleep(0.01)
    return len(tasks)


def _held_until_the_next_draw(loader):
    """What ``_iter_process`` promises: a drawn batch keeps its bytes while
    the workers fill the batches ahead of it, until the next one is drawn;
    then its buffer goes back to them."""
    from pytorch_distributed_tpu.data.loader import _AHEAD

    want = _kept(_twins("u8_wire")[0])
    batches = iter(loader)
    views = []
    for b, kept in enumerate(want):
        batch = next(batches)
        views.append(batch["images"])
        ahead = _settled(loader)  # everything in flight has landed
        assert (ahead > 0) == (b + 1 < len(want))
        assert len(loader._inflight) == min(_AHEAD, len(want) - 1 - b)
        for key in kept:
            np.testing.assert_array_equal(batch[key], kept[key], key)
    # the ring has _AHEAD + 1 buffers: batch b's is rewritten with b + 3's
    assert np.shares_memory(views[0], views[_AHEAD + 1])
    assert not np.shares_memory(views[0], views[1])
    assert next(batches, None) is None


def _fetch_spans_carry_the_workers_counts(loader):
    """``placed`` of ``samples``: every row was written by a worker, none by
    the producer; the workers' clocks come back with the rows."""
    from pytorch_distributed_tpu.obs.trace import RECORDER

    RECORDER.clear()
    drawn = len(_kept(loader))
    fetches = [r for r in RECORDER.records() if r.name == "fetch"]
    assembles = [r for r in RECORDER.records() if r.name == "assemble"]
    assert [r.id for r in fetches] == list(range(drawn)) == [
        r.id for r in assembles]
    for r in fetches[:-1]:
        assert r.fields["placed"] == r.fields["samples"] == 8
    assert fetches[-1].fields["placed"] == 4  # the padded batch's samples
    for r in fetches:
        assert r.fields["sample_wall_s"] > 0 and r.fields["sample_cpu_s"] > 0


def _a_second_iteration_takes_the_ring(loader):
    first = iter(loader)
    next(first)
    want = _kept(_twins("u8_wire")[0])
    for kept, got in zip(want, loader):  # settles the first one's tasks
        np.testing.assert_array_equal(got["images"], kept["images"])
    with pytest.raises(RuntimeError, match="a later iteration"):
        next(first)


@pytest.mark.parametrize("behaviour", [
    _held_until_the_next_draw, _fetch_spans_carry_the_workers_counts,
    _a_second_iteration_takes_the_ring], ids=lambda f: f.__name__.strip("_"))
def test_process_fed_epoch(behaviour):
    loader = _twins("u8_wire")[1]
    try:
        behaviour(loader)
    finally:
        loader.close()


def test_feeder_copies_a_shared_batch_before_the_next_is_drawn():
    """``DeviceFeeder._put`` ends its copy of a ``SharedBatch`` before it
    returns (and on the CPU, whose client aliases host memory, copies it
    first): what reaches the devices is what thread workers deliver, over
    more batches than the ring holds."""
    threads, processes = _twins("u8_wire", n=64, random_flip=True)
    feeder = DeviceFeeder(data_parallel_mesh())
    try:
        want = list(feeder(iter(threads)))
        got = list(feeder(iter(processes)))  # all eight held to the end
        assert len(got) == len(want) == 8
        for g, w in zip(got, want):
            for key in w:
                np.testing.assert_array_equal(np.asarray(g[key]),
                                              np.asarray(w[key]), key)
    finally:
        processes.close()


class _Fatal:
    """A transform that takes its process down at the given sample."""

    def __init__(self, at):
        self.at = at

    def __call__(self, image, rng):
        import os

        if int(image[0, 0, 0]) == self.at:
            os._exit(1)
        return image


class _Numbered:
    """Sample ``i`` is a uint8 image filled with ``i``."""

    def __init__(self, n, transform=None):
        self.n, self.transform = n, transform

    def __len__(self):
        return self.n

    def get(self, index, rng):
        image = np.full((4, 4, 3), index, np.uint8)
        if self.transform is not None:
            image = self.transform(image, rng)
        return image, index


def test_rows_survive_more_worker_processes_than_cores():
    """Twelve worker processes, tasks of one row, 150 batches through three
    buffers: every row of every batch is the sample the sampler named, so
    no task wrote into a buffer that was not its own."""
    import time

    ds = _Numbered(2400)
    loader = DataLoader(ds, batch_size=16, num_workers=12,
                        batch_mode="u8_wire", worker_type="process",
                        sampler=DistributedShardSampler(2400, shuffle=False))
    t, drawn = time.monotonic(), 0
    try:
        for b, batch in enumerate(loader):
            want = np.arange(16 * b, 16 * b + 16)
            np.testing.assert_array_equal(batch["labels"], want)
            np.testing.assert_array_equal(batch["images"][:, 3, 3, 2],
                                          want % 256)
            drawn += 1
            assert time.monotonic() - t < 300.0
    finally:
        loader.close()
    assert drawn == 150


def _linked(segment):
    """Whether the loader's segment still has its name under /dev/shm."""
    import os

    assert segment.startswith("psm_")
    return os.path.exists(os.path.join("/dev/shm", segment))


def _workers():
    import multiprocessing

    return {p.pid for p in multiprocessing.active_children()}


def test_a_killed_worker_raises_at_the_iterator_and_leaves_nothing():
    """``os._exit`` inside a transform, mid-epoch: the epoch raises within
    the test's own limit instead of waiting for rows that never come, the
    pool and the segment are gone, and the next epoch runs on new ones."""
    import time

    before = _workers()
    ds = _Numbered(64, _Fatal(at=37))
    loader = DataLoader(ds, batch_size=8, num_workers=3,
                        batch_mode="u8_wire", worker_type="process")
    t = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="worker process died"):
            for batch in loader:
                first = loader._ring.spec[0]
                assert _linked(first) and time.monotonic() - t < 120.0
        assert time.monotonic() - t < 120.0
        assert not _linked(first) and _workers() == before
        loader.dataset = _Numbered(64)  # no fatal sample: a new pool
        assert [int(b["labels"][0]) for b in loader] == list(range(0, 64, 8))
        second = loader._ring.spec[0]
        assert second != first and _linked(second)
    finally:
        loader.close()
    assert not _linked(second) and _workers() == before


@pytest.mark.parametrize("how", ["close", "abandoned", "exit"])
def test_no_segment_and_no_worker_outlive_the_loader(how):
    """After ``close()`` mid-epoch, after an iterator and its loader are
    dropped mid-epoch, and after a process that never closed its loader has
    exited (the atexit reaper), nothing of the loader is left under
    ``/dev/shm`` and none of its workers is alive; nothing is warned of."""
    import gc
    import os
    import subprocess
    import sys

    before = _workers()
    if how == "exit":
        done = subprocess.run([sys.executable, "-c", (
            "import numpy as np, os\n"
            "from pytorch_distributed_tpu.data import DataLoader, "
            "SyntheticImageDataset\n"
            "if __name__ == '__main__':\n"
            "    ds = SyntheticImageDataset(length=64, num_classes=5, "
            "image_size=8)\n"
            "    loader = DataLoader(ds, batch_size=8, num_workers=2, "
            "worker_type='process')\n"
            "    it = iter(loader); next(it)\n"
            "    print('SEGMENT', loader._ring.spec[0], flush=True)\n")],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert done.returncode == 0, done.stderr[-2000:]
        segment = done.stdout.split("SEGMENT ")[1].split()[0]
        assert "resource_tracker" not in done.stderr
        assert "Error" not in done.stderr, done.stderr[-2000:]
    else:
        loader = _loader(n=64, num_workers=2, worker_type="process")
        batches = iter(loader)
        held = next(batches)
        kept = {k: v.copy() for k, v in held.items()}
        segment = loader._ring.spec[0]
        assert _linked(segment) and len(_workers() - before) == 2
        if how == "close":
            loader.close()
            for key in kept:  # unlinked, and mapped for as long as held
                np.testing.assert_array_equal(held[key], kept[key], key)
        del held, batches, loader
        gc.collect()
    assert not _linked(segment) and _workers() == before


def test_a_ring_that_dev_shm_cannot_hold_is_refused_with_the_sizes(
        monkeypatch):
    import os
    from types import SimpleNamespace

    monkeypatch.setattr(os, "statvfs", lambda path: SimpleNamespace(
        f_bavail=1, f_frsize=4096))
    loader = _loader(worker_type="process")
    with pytest.raises(OSError, match=(
            r"4096 bytes free; 3 batch buffers of 8 x \(8, 8, 3\) float32 "
            r"and a dataset of \d+ bytes need \d+")):
        next(iter(loader))
    assert loader._pool is None and loader._ring is None


def test_process_workers_refuse_a_native_decode_dataset():
    ds = SyntheticImageDataset(length=8, num_classes=5, image_size=8)
    ds.native_decode = True
    with pytest.raises(ValueError, match="worker_type='thread'"):
        DataLoader(ds, batch_size=4, worker_type="process")
