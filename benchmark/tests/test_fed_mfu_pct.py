"""``fed_mfu_pct``: ``mfu_pct``'s arithmetic under the fed cell's name, on a
hand-made run; and in ``BENCHMARK.json``, beside the metric it moves."""

import json
import os
import types

import pytest

import harness
from conftest import BENCH, ROOT


def _read(metric, view):
    return harness.load_module(os.path.join(
        BENCH, "layer_metrics", metric + ".py")).read(view)


def _view(peaks):
    run = harness.Run(items=1200, window_start=10.0, window_end=12.0,
                      attempted=0, failed=0, checks={})
    return types.SimpleNamespace(
        run=run, peaks=peaks, flops_per_item=25e9,
        cell=types.SimpleNamespace(chips=1))


def test_share_of_the_peak_and_nothing_without_peaks():
    view = _view({"flops_per_s_bf16": 200e12})
    # 600 items/s x 25 GFLOP over 200 TFLOP/s
    assert _read("fed_mfu_pct", view) == pytest.approx(7.5)
    assert _read("fed_mfu_pct", view) == _read("mfu_pct", view)
    assert _read("fed_mfu_pct", _view(None)) is None  # a rehearsal


def test_entry_lists_the_cells_that_report_what_it_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == "fed_mfu_pct")
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert moved["name"] == "fed_throughput_per_chip"
    assert entry["workloads"] == moved["workloads"]
    assert (entry["unit"], entry["better"], entry["layer"]) == (
        "%", "higher", "models")
