"""``program_spans.py`` and the readers on it, on hand-made records: window
clipping, self time, medians, counts, the clock offset, and the ``none``
share against a made-up trace with two idle gaps."""

import os
import types
from typing import NamedTuple

import pytest

import harness
import program_spans as ps
import trace_reduce as tr
from conftest import BENCH


class Rec(NamedTuple):
    serial: int
    name: str
    start: float
    end: float
    thread: int = 1
    id: int = 0
    parent: int = None
    fields: dict = {}


def _step(serial, start, end, children):
    """A `step` and its children ``(name, start, end)``."""
    out = [Rec(serial * 10 + k + 1, n, s, e, parent=serial * 10)
           for k, (n, s, e) in enumerate(children)]
    return out + [Rec(serial * 10, "step", start, end)]


RECORDS = (
    _step(1, 8.0, 12.0, [("data_wait", 8.5, 10.5), ("dispatch", 10.5, 11.0),
                         ("host_sync", 11.5, 12.0)])
    + _step(2, 12.0, 16.0, [("data_wait", 12.0, 13.0),
                            ("dispatch", 13.0, 15.0),
                            ("host_sync", 15.0, 15.5)])
    + _step(3, 16.0, 22.0, [("data_wait", 16.0, 21.0)])
    + [Rec(900, "fetch", 10.2, 14.2, thread=2,
           fields={"samples": 4, "sample_wall_s": 8.0, "sample_cpu_s": 2.0}),
       Rec(901, "fetch", 14.5, 17.5, thread=2,
           fields={"samples": 4, "sample_wall_s": 4.0, "sample_cpu_s": 1.0}),
       Rec(902, "fetch", 17.6, 30.0, thread=2,
           fields={"samples": 4, "sample_wall_s": 99.0, "sample_cpu_s": 99.0}),
       Rec(903, "put", 14.2, 14.3, thread=2),
       Rec(904, "put", 17.5, 17.8, thread=2),
       Rec(905, "put", 19.0, 19.2, thread=2)])
WINDOW = (10.0, 20.0)


def test_clipping_self_time_medians_and_counts():
    t0, t1 = WINDOW
    assert ps.seconds(RECORDS, "data_wait", t0, t1) == pytest.approx(
        0.5 + 1.0 + 4.0)
    assert ps.seconds(RECORDS, "dispatch", t0, t1) == pytest.approx(2.5)
    assert ps.seconds(RECORDS, "host_sync", t0, t1) == pytest.approx(1.0)
    # step 1: [10, 12] less 0.5 + 0.5 + 0.5; step 2: 4 less 3.5; step 3: 0
    assert ps.self_seconds(RECORDS, "step", t0, t1) == pytest.approx(
        0.5 + 0.5 + 0.0)
    # the four parts make the window
    assert sum((ps.seconds(RECORDS, n, t0, t1) for n in ps.LOOP_LEAVES),
               ps.self_seconds(RECORDS, "step", t0, t1)) == pytest.approx(
        t1 - t0)
    # medians and counts take the spans that lie wholly inside
    assert ps.durations(RECORDS, "fetch", t0, t1) == pytest.approx([4.0, 3.0])
    assert sorted(ps.durations(RECORDS, "put", t0, t1)) == pytest.approx(
        [0.1, 0.2, 0.3])
    assert ps.field_sums(RECORDS, "fetch", t0, t1,
                         ("sample_cpu_s", "sample_wall_s")) == {
        "sample_cpu_s": 3.0, "sample_wall_s": 12.0}
    for missing in (ps.seconds, ps.self_seconds):
        assert missing(RECORDS, "queue_full", t0, t1) is None
    assert ps.durations(RECORDS, "queue_full", t0, t1) == []
    assert ps.field_sums(RECORDS, "put", t0, t1, ("samples",)) is None


def _view(monkeypatch, records, spans=(), reduced=None, trace_file=None):
    class Ring:
        def records(self, t0=None, t1=None):
            return [r for r in records if r.start <= t1 and r.end >= t0]

    monkeypatch.setattr(ps, "recorder",
                        lambda: Ring() if records is not None else None)
    run = harness.Run(items=0, window_start=WINDOW[0], window_end=WINDOW[1],
                      attempted=0, failed=0, checks={},
                      trace_file=trace_file,
                      notes={"step_program": "jit_step"})
    held = harness.Spans()
    held.records.extend(spans)
    return types.SimpleNamespace(
        run=run, reduced=reduced, cell=types.SimpleNamespace(spans=held))


def _read(metric, view):
    return harness.load_module(os.path.join(
        BENCH, "layer_metrics", metric + ".py")).read(view)


WINDOW_METRICS = {
    "loop_data_wait_pct": 55.0, "loop_dispatch_pct": 25.0,
    "loop_host_sync_pct": 10.0, "loop_self_pct": 10.0,
    "feeder_put_ms_per_batch": 200.0, "loader_fetch_ms_per_batch": 3500.0,
    "loader_fetch_running_pct": 25.0,
}


@pytest.mark.parametrize("metric", sorted(WINDOW_METRICS))
def test_readers_on_the_window(monkeypatch, metric):
    assert _read(metric, _view(monkeypatch, RECORDS)) == pytest.approx(
        WINDOW_METRICS[metric])


@pytest.mark.parametrize("metric", sorted(WINDOW_METRICS) + [
    "feeder_queue_full_pct", "loader_assemble_ms_per_batch",
    "idle_unattributed_pct"])
def test_no_recorder_or_no_record_means_no_value(monkeypatch, metric):
    """The parent commit has no recorder; a span may have no record."""
    assert _read(metric, _view(monkeypatch, None)) is None
    assert _read(metric, _view(monkeypatch, [])) is None


def _two_gap_trace():
    """One device, three steps of a second each, idle in [11, 13] and
    [14, 17] of the capture's clock, which is perf_counter less 10: the
    harness's record of the window starts at 19.5."""
    op = "%fusion.1 = f32[8]{0} fusion()"
    runs = [(10.0, 11.0), (13.0, 14.0), (17.0, 18.0)]
    return {"devices": {0: {"ops": [(s, e, op) for s, e in runs],
                            "async": [],
                            "modules": [(s, e, "jit_step(1)")
                                        for s, e in runs]}},
            "host": [(9.5, 18.0, "bench:window", "python"),
                     (10.9, 13.1, "bench:data_wait", "python"),
                     (10.0, 19.0, "ptd:step", "python"),
                     (10.0, 19.0, "ptd:produce", "loader")]}


def test_clock_offset_and_the_none_share(monkeypatch):
    trace = _two_gap_trace()
    offset = ps.capture_offset(trace["host"], "bench:window", 19.5)
    assert offset == pytest.approx(-10.0)
    assert ps.capture_offset([], "bench:window", 19.5) is None
    # on perf_counter: the first gap [21, 23] lies in a data_wait, the
    # second [24, 27] in the loop's own code but for a dispatch of 0.5 s
    records = [Rec(1, "data_wait", 20.9, 23.05), Rec(2, "dispatch", 23.1, 24.0),
               Rec(3, "dispatch", 26.4, 26.9), Rec(4, "step", 20.0, 29.0),
               Rec(5, "produce", 20.0, 29.0, thread=2)]
    placed = ps.loop_events(records, offset)
    assert [(n, round(s, 6)) for s, _e, n, _l in placed] == [
        ("loop:data_wait", 10.9), ("loop:dispatch", 13.1),
        ("loop:dispatch", 16.4)]
    trace["host"].extend(placed)
    gaps = tr.reduce(trace, span_prefix=ps.LOOP_PREFIX,
                     window_span="bench:window")["idle_gaps"]
    assert dict(gaps) == pytest.approx({"data_wait": 2.0, "dispatch": 3.0})
    assert ps.unattributed_share(gaps) == 0.0
    assert ps.unattributed_share([["none", 3.0], ["data_wait", 1.0],
                                  ["within_program", 5.0]]) == 0.75
    assert ps.unattributed_share([["within_program", 5.0]]) is None

    # the reader, on the same capture: without the second dispatch the
    # second gap has no leaf over it and goes to `none`
    monkeypatch.setattr(tr, "load", lambda path: _two_gap_trace())
    view = _view(monkeypatch, records[:2] + records[3:],
                 spans=[("window", 19.5, 28.0)], reduced={"any": 1},
                 trace_file="made-up.xplane.pb")
    assert _read("idle_unattributed_pct", view) == pytest.approx(60.0)
    # a rehearsal has no reduction, an untraced run no capture
    view.reduced = None
    assert _read("idle_unattributed_pct", view) is None


def test_against_the_programs_recorder():
    """The real ``RECORDER``, clipped through ``window_records``."""
    import time

    from pytorch_distributed_tpu.obs.trace import RECORDER, span

    t0 = time.perf_counter()
    with span("step", id=0):
        with span("data_wait"):
            time.sleep(0.02)
        time.sleep(0.01)
    t1 = time.perf_counter()
    assert ps.recorder() is RECORDER
    view = types.SimpleNamespace(run=harness.Run(
        items=0, window_start=t0, window_end=t1, attempted=0, failed=0,
        checks={}))
    names = [r.name for r in ps.window_records(view)]
    assert names[-2:] == ["data_wait", "step"]
    waited = ps.share_pct(view, "data_wait")
    own = ps.share_pct(view, "step", self_time=True)
    assert waited > own > 0 and waited + own <= 100.0
