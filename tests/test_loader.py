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
        batches[wt] = list(loader)
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
                got = list(loader.iter_batches(start))
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
            plain = list(loader.iter_batches(start))
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
