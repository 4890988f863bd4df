"""The trace reduction: interval arithmetic on made-up intervals, and the
whole reduction on a small capture recorded on a v5e host with four chips
(``record_fixture.py`` says how), against sums made the slow way."""

import os

import pytest

import trace_reduce as tr
from conftest import HERE

FIXTURE = os.path.join(HERE, "fixtures", "tiny_dp4.xplane.pb.gz")


def test_interval_arithmetic():
    merged = tr.union([(5, 6), (0, 2), (1, 3), (3, 3), (2.5, 2.75)])
    assert merged == [(0, 3), (5, 6)]
    assert tr.measure(merged) == 4
    assert tr.gaps(merged, -1, 7) == [(-1, 0), (3, 5), (6, 7)]
    assert tr.gaps(merged, 1, 5.5) == [(3, 5)]
    assert tr.intersect(merged, [(2, 5.5)]) == [(2, 3), (5, 5.5)]
    assert tr.clip([(0, 3), (5, 6)], 2, 5) == [(2, 3)]


def test_names():
    text = ("%fusion.51 = (f32[256]{0:T(256)S(1)}, bf16[256,56,56,256]"
            "{3,0,2,1:T(8,128)(2,1)}) fusion(bf16[1]{0} %p), kind=kOutput")
    assert tr.op_name(text) == "fusion.51 (f32[256], bf16[256,56,56,256])"
    assert not tr.is_collective(text)
    assert tr.is_collective("%all-reduce-start.3 = f32[8]{0} all-reduce-"
                            "start(f32[8]{0} %x)")
    assert tr.is_collective("%all-reduce-done.3 = f32[8]{0} all-reduce-done()")
    assert not tr.is_collective("%convert_reduce_fusion.7 = f32[8]{0} fusion()")


def test_reduce_on_made_up_events():
    """One device, two steps of one program, one collective half hidden."""
    ops = [(0.0, 1.0, "%fusion.1 = f32[8]{0} fusion()"),
           (1.0, 1.5, "%all-reduce-done.1 = f32[8]{0} all-reduce-done()"),
           (3.0, 4.0, "%fusion.1 = f32[8]{0} fusion()"),
           (4.0, 4.5, "%all-reduce-done.1 = f32[8]{0} all-reduce-done()")]
    flights = [(0.5, 1.5, "%all-reduce-start.1 = f32[8]{0} all-reduce-start()"),
               (3.5, 4.5, "%all-reduce-start.1 = f32[8]{0} all-reduce-start()")]
    modules = [(0.0, 1.5, "jit_step(1)"), (3.0, 4.5, "jit_step(1)")]
    host = [(0.0, 5.0, "bench:window", "python"),
            (1.4, 3.1, "bench:data_wait", "python"),
            (4.4, 5.0, "bench:block", "python")]
    out = tr.reduce({"devices": {0: {"ops": ops, "async": flights,
                                     "modules": modules}}, "host": host})
    assert out["window_s"] == 5.0 and out["busy_s"] == pytest.approx(3.0)
    assert out["steps"] == 2 and out["step_s"] == pytest.approx(1.5)
    assert out["collective_s"] == pytest.approx(2.0)
    assert out["collective_exposed_s"] == pytest.approx(1.0)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"data_wait": 1.5, "block": 0.5})
    assert out["device_ops"][0] == ["fusion.1 f32[8]", pytest.approx(2.0)]


@pytest.fixture(scope="module")
def capture():
    return tr.load(FIXTURE)


def _slow_cover(intervals, lo, hi, step=2e-7):
    """Seconds of [lo, hi] covered by any interval, by sampling."""
    import numpy as np

    grid = np.arange(lo, hi, step)
    hit = np.zeros(grid.shape, bool)
    for s, e in intervals:
        hit |= (grid >= s) & (grid < e)
    return hit.sum() * step


def test_fixture_is_a_four_chip_v5e_capture(capture):
    assert sorted(capture["devices"]) == [0, 1, 2, 3]
    for dev in capture["devices"].values():
        assert dev["ops"] and dev["modules"]
        assert any(tr.is_collective(n) for _, _, n in dev["ops"] + dev["async"])
    names = {n for _, _, n, _ in capture["host"] if n.startswith("bench:")}
    assert names == {"bench:window", "bench:data_wait", "bench:dispatch",
                     "bench:block"}


def test_fixture_reduces_to_the_slow_sums(capture):
    out = tr.reduce(capture, step_program="jit_tiny_step")
    lo, hi = next((s, e) for s, e, n, _ in capture["host"]
                  if n == "bench:window")
    # the window starts with the first device operation inside the span
    lo = min(s for dev in capture["devices"].values()
             for s, _, _ in dev["modules"] + dev["ops"] if s >= lo)
    assert out["window_s"] == pytest.approx(hi - lo)
    assert out["devices"] == 4 and out["steps"] == 6
    assert out["step_program"] == "jit_tiny_step"
    # read off the capture by hand (PR 22): a step of 0.555 ms on each
    # chip, of which the one gradient all-reduce (16 MB) takes 0.283 ms
    assert out["step_s"] == pytest.approx(555e-6, rel=0.01)
    assert out["collective_s"] / out["steps"] == pytest.approx(283e-6,
                                                               rel=0.02)
    assert out["device_ops"][0][0].startswith("all-reduce.2 ")
    busy, coll, exposed = [], [], []
    for dev in capture["devices"].values():
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in dev["ops"]]
        busy.append(_slow_cover([(s, e) for s, e, _ in ops], lo, hi))
        c = [(s, e) for s, e, n in ops if tr.is_collective(n)] + [
            (max(s, lo), min(e, hi)) for s, e, n in dev["async"]
            if tr.is_collective(n)]
        k = [(s, e) for s, e, n in ops if not tr.is_collective(n)]
        coll.append(_slow_cover(c, lo, hi))
        exposed.append(coll[-1] - (_slow_cover(c, lo, hi) + _slow_cover(
            k, lo, hi) - _slow_cover(c + k, lo, hi)))
    tol = dict(rel=0.01, abs=5e-6)
    assert out["busy_s"] == pytest.approx(sum(busy) / 4, **tol)
    assert out["collective_s"] == pytest.approx(sum(coll) / 4, **tol)
    assert out["collective_exposed_s"] == pytest.approx(sum(exposed) / 4,
                                                        **tol)
    assert 0 < out["collective_exposed_s"] <= out["collective_s"]
    # the device sat idle while the host slept under bench:data_wait: the
    # five sleeps of 2 ms after the first step are most of the idle time,
    # and are booked there
    gaps = dict(out["idle_gaps"])
    assert gaps["data_wait"] >= 5 * 0.002 * 0.9
    assert gaps["data_wait"] == max(gaps.values())
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=0.02)
