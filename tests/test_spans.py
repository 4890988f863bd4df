"""obs/trace.py's host spans: the recorder itself, then each call site
(``AsyncFeeder``, ``DataLoader.iter_batches``, ``Trainer.train_epoch``)."""

import json
import statistics
import threading
import time

import numpy as np
import pytest

from pytorch_distributed_tpu.obs.trace import RECORDER, SpanRecorder, span


@pytest.fixture(autouse=True)
def fresh_ring():
    RECORDER.clear()
    yield
    RECORDER.enabled = True
    RECORDER.clear()


def _named(name, records=None):
    return [r for r in (RECORDER.records() if records is None else records)
            if r.name == name]


def _self_time(record, records):
    return (record.end - record.start) - sum(
        r.end - r.start for r in records if r.parent == record.serial)


def test_nesting_gives_parent_and_self_time():
    with span("outer", id=7):
        time.sleep(0.01)
        with span("inner", rows=3):
            time.sleep(0.02)
        with span("inner"):
            pass
    records = RECORDER.records()
    (outer,) = _named("outer")
    first, second = _named("inner")
    assert [r.name for r in records] == ["inner", "inner", "outer"]  # by exit
    assert outer.parent is None and outer.id == 7
    assert first.parent == second.parent == outer.serial
    assert first.id == 7, "a span without an id takes the enclosing span's"
    assert first.fields == {"rows": 3} and second.fields == {}
    assert outer.start <= first.start <= first.end <= second.start <= outer.end
    assert first.end - first.start >= 0.02
    assert 0.01 <= _self_time(outer, records) < outer.end - outer.start - 0.02 + 1e-9


def test_two_threads_keep_separate_stacks():
    inside = threading.Event()
    release = threading.Event()

    def other():
        with span("theirs"):
            inside.set()
            assert release.wait(5.0)

    t = threading.Thread(target=other)
    with span("mine"):
        t.start()
        assert inside.wait(5.0)
        with span("mine_child"):  # entered while `theirs` is open elsewhere
            pass
        release.set()
        t.join(5.0)
    assert not t.is_alive()
    (mine,), (child,), (theirs,) = (_named("mine"), _named("mine_child"),
                                    _named("theirs"))
    assert child.parent == mine.serial
    assert theirs.parent is None
    assert theirs.thread != mine.thread == child.thread


def test_ring_is_bounded_and_records_clip():
    small = SpanRecorder(maxlen=4)
    assert RECORDER._ring.maxlen == 32768
    assert small._ring.maxlen == 4
    marks = []
    for k in range(6):
        marks.append(time.perf_counter())
        with span("tick", id=k):
            time.sleep(0.002)
    marks.append(time.perf_counter())
    assert [r.id for r in RECORDER.records()] == list(range(6))
    assert [r.id for r in RECORDER.records(t0=marks[2])] == [2, 3, 4, 5]
    assert [r.id for r in RECORDER.records(t1=marks[2])] == [0, 1]
    assert [r.id for r in RECORDER.records(marks[1], marks[3])] == [1, 2]
    # a record that straddles an edge overlaps the interval
    mid = RECORDER.records()[3]
    at = (mid.start + mid.end) / 2
    assert [r.id for r in RECORDER.records(at, at)] == [3]
    # the ring drops the oldest
    for r in RECORDER.records():
        small._ring.append(r)
    assert [r.id for r in small.records()] == [2, 3, 4, 5]


def test_disabled_records_nothing_and_shares_one_noop():
    RECORDER.enabled = False
    a, b = span("x"), span("y", id=1, rows=2)
    assert a is b
    with a as entered:
        entered.set(rows=1)
    assert RECORDER.records() == []
    RECORDER.enabled = True
    with span("x"):
        pass
    assert len(RECORDER.records()) == 1


def test_a_span_costs_under_ten_microseconds():
    clock = time.perf_counter
    costs = []
    for _ in range(10_000):
        t = clock()
        with span("cost"):
            pass
        costs.append(clock() - t)
    assert statistics.median(costs) < 10e-6


def test_async_feeder_spans_share_ids_across_threads():
    from pytorch_distributed_tpu.data.loader import AsyncFeeder

    def slow_source():
        for i in range(4):
            time.sleep(0.02)
            yield i

    # slow source: the consumer waits on an empty queue
    assert list(AsyncFeeder(lambda x: x, prefetch=1)(slow_source())) == [
        0, 1, 2, 3]
    waits, produced = _named("data_wait"), _named("produce")
    assert [r.id for r in waits] == [0, 1, 2, 3, 4]  # the last takes `stop`
    assert sum(r.end - r.start for r in waits) > 0.04
    assert [r.id for r in produced] == [0, 1, 2, 3, 4]
    assert {r.thread for r in produced}.isdisjoint({r.thread for r in waits})
    for name in ("host_batch", "queue_full"):
        for r in _named(name):
            parent = next(p for p in produced if p.serial == r.parent)
            assert r.id == parent.id
    assert sum(r.end - r.start for r in _named("host_batch")) > 0.06

    # slow consumer: the producer waits on a full queue
    RECORDER.clear()
    for _ in AsyncFeeder(lambda x: x, prefetch=1)(iter(range(4))):
        time.sleep(0.02)
    assert sum(r.end - r.start for r in _named("queue_full")) > 0.02
    assert sorted(r.id for r in _named("queue_full")) == [0, 1, 2, 3]
    assert sum(r.end - r.start for r in _named("data_wait")[1:]) < 0.02


class _WorkDataset:
    """Samples that cost CPU time and waiting time both."""

    def __init__(self, n=12, size=8):
        self.n, self.size = n, size

    def __len__(self):
        return self.n

    def __getitem__(self, index):
        rng = np.random.default_rng(index)
        image = rng.standard_normal((self.size, self.size, 3)).astype(
            np.float32)
        for _ in range(20):
            image = np.tanh(image)
        time.sleep(0.002)
        return image, index % 4


def test_loader_records_fetch_counts_and_assemble():
    from pytorch_distributed_tpu.data.loader import DataLoader
    from pytorch_distributed_tpu.data.sampler import DistributedShardSampler

    ds = _WorkDataset()
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        sampler=DistributedShardSampler(len(ds), 1, 0,
                                                        shuffle=False))
    batches = list(loader.iter_batches(1))
    assert len(batches) == 2
    fetches, assembles = _named("fetch"), _named("assemble")
    assert [r.id for r in fetches] == [0, 1] == [r.id for r in assembles]
    for r in fetches:
        assert r.fields["samples"] == 4
        assert r.fields["placed"] == 4  # each row written by its worker
        assert 0 < r.fields["sample_cpu_s"] <= r.fields["sample_wall_s"]
        assert r.fields["sample_wall_s"] >= 4 * 0.002
    for fetch, assemble in zip(fetches, assembles):
        assert fetch.end <= assemble.start


def test_train_epoch_steps_have_their_children_and_dump_reads(tmp_path):
    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    cfg = Config(arch="resnet18", batch_size=16, epochs=1, lr=0.1,
                 print_freq=2, synthetic=True, synthetic_length=48,
                 image_size=32, num_classes=8, seed=0,
                 checkpoint_dir=str(tmp_path), workers=2)
    trainer = Trainer(cfg)
    RECORDER.clear()
    completed, preempted = trainer.train_epoch(0)
    assert (completed, preempted) == (3, False)

    records = RECORDER.records()
    steps = _named("step", records)
    assert [r.id for r in steps] == [0, 1, 2]
    loop_thread = steps[0].thread
    for step in steps:
        children = [r for r in records if r.parent == step.serial]
        assert [r.name for r in sorted(children, key=lambda r: r.start)] == [
            "data_wait", "dispatch", "host_sync", "host_sync", "host_sync"]
        assert all(r.thread == loop_thread and r.id == step.id
                   for r in children)
        assert 0 <= _self_time(step, records) < step.end - step.start
    # the producer's side of the same three batches, on another thread
    for name in ("produce", "host_batch", "fetch", "assemble", "put",
                 "queue_full"):
        found = _named(name, records)
        assert sorted(r.id for r in found)[:3] == [0, 1, 2], name
        assert all(r.thread != loop_thread for r in found), name

    path = tmp_path / "spans.jsonl"
    assert RECORDER.dump(str(path)) == len(records)
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(lines) == len(records)
    assert set(lines[0]) == {"serial", "name", "start", "end", "thread",
                             "id", "parent", "fields"}
    assert [l["name"] for l in lines] == [r.name for r in records]


def test_fit_writes_spans_beside_the_profile(tmp_path):
    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    cfg = Config(arch="resnet18", batch_size=16, epochs=1, lr=0.1,
                 print_freq=2, synthetic=True, synthetic_length=32,
                 image_size=32, num_classes=8, seed=0,
                 checkpoint_dir=str(tmp_path), workers=2,
                 profile_dir=str(tmp_path / "profile"),
                 profile_steps="1:2")
    Trainer(cfg).fit()
    lines = [json.loads(line) for line in
             (tmp_path / "profile" / "spans.jsonl").read_text().splitlines()]
    assert {"step", "data_wait", "dispatch", "host_sync", "produce",
            "fetch", "put"} <= {l["name"] for l in lines}
