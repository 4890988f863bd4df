"""``scope_times.py`` and its readers on a made-up capture: two devices,
two whole steps each and one cut by the window's edge, a loop whose event
covers its body's, joined to a made-up scope map; and what the readers give
a program that has no ``compiled_scopes`` (the commit before PR 34)."""

import json
import os
import types

import pytest

import fused_ce_cost
import harness
import scope_times
import trace_reduce
from conftest import BENCH, ROOT
from pytorch_distributed_tpu.obs import trace as program_trace

READERS = ("step_forward_ms", "step_backward_ms", "step_recompute_ms",
           "step_optimizer_ms", "scope_unmapped_pct", "fused_ce_roofline")


def _step(t0):
    """One step's events, 10 ms long from ``t0`` (seconds)."""
    ms = lambda a, b, text: (t0 + a * 1e-3, t0 + b * 1e-3, text)  # noqa: E731
    return [
        ms(0.0, 3.0, "%while.4 = (s32[], bf16[8]{0}) while(%tuple.1)"),
        ms(0.5, 1.5, "%attn.7 = (bf16[2,8,4]{2,1,0}, f32[2,8]{1,0}) "
                     "custom-call(%q)"),
        ms(1.5, 2.5, "%fusion.9 = bf16[8]{0} fusion(%x), kind=kLoop"),
        ms(3.0, 5.0, "%while.13 = (s32[], f32[16,8]{1,0}) while(%tuple.2)"),
        ms(3.0, 5.0, "%fusion.20 = f32[4,16]{1,0} fusion(%h), kind=kOutput"),
        ms(5.0, 8.0, "%fusion.30 = bf16[8]{0} fusion(%g), kind=kLoop"),
        ms(8.0, 9.0, "%fusion.31 = bf16[8]{0} fusion(%g), kind=kLoop"),
        ms(9.0, 9.5, "%fusion.40 = f32[8]{0} fusion(%p), kind=kLoop"),
        ms(9.5, 10.0, "%copy.50 = f32[8]{0} copy(%p)"),
    ]


SCOPES = {
    "while.4": (("lm_forward", "ut_pass"), "forward"),
    "attn.7": (("lm_forward", "ut_pass", "attn"), "forward"),
    "fusion.9": (("lm_forward", "ut_pass"), "forward"),
    "while.13": (("lm_forward", "fused_ce"), "forward"),
    "fusion.20": (("lm_forward", "fused_ce"), "forward"),
    "fusion.30": (("lm_forward", "ut_pass", "attn"), "backward"),
    "fusion.31": (("lm_forward", "ut_pass"), "recompute"),
    "fusion.40": (("optimizer",), "optimizer"),
    # copy.50: not in the map at all
}


@pytest.fixture
def view(tmp_path, monkeypatch):
    """A traced run's view whose capture is the made-up one."""
    def device(shift):
        starts = [0.010 + shift, 0.020 + shift, 0.030 + shift]
        return {"modules": [(t, t + 0.010, "jit_step(123)") for t in starts]
                + [(0.001, 0.002, "jit_other(9)")],
                "ops": [ev for t in starts for ev in _step(t)],
                "async": []}

    # the window ends inside the third step: two whole steps a device
    made = {"devices": {0: device(0.0), 1: device(0.0005)},
            "host": [(0.005, 0.035, harness.WINDOW_SPAN, "python")]}
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "load", lambda p: made)
    monkeypatch.setattr(scope_times, "_KEPT", {})
    monkeypatch.setattr(
        program_trace, "compiled_scopes",
        lambda program: {k: program_trace.ScopeOf(*v)
                         for k, v in SCOPES.items()}, raising=False)
    monkeypatch.setitem(program_trace.STEP_PROGRAMS, "jit_step",
                        types.SimpleNamespace(recompiled=False))
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic",
                           "resident-lm-2x8192-zipf.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    run = harness.Run(items=0, window_start=0.0, window_end=1.0, attempted=1,
                      failed=0, checks={}, trace_file=str(path),
                      notes={"step_program": "jit_step"})
    cell = types.SimpleNamespace(config=config, traffic=traffic,
                                 compiles=harness.CompileLog())
    return types.SimpleNamespace(cell=cell, run=run, reduced=None,
                                 peaks=peaks, flops_per_item=0.0)


def _reader(name):
    return harness.load_module(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


def test_the_join_sums_self_time_by_phase_and_scope(view, capsys):
    got = scope_times.read(view)
    assert got["steps"] == 2
    assert got["by_phase"] == pytest.approx({
        "forward": 5.0, "backward": 3.0, "recompute": 1.0,
        "optimizer": 0.5, "unknown": 0.5})
    # the loop keeps what its body leaves; the loss's loop is all body
    assert got["by_scope"] == pytest.approx({
        ("ut_pass", "forward"): 2.0, ("attn", "forward"): 1.0,
        ("fused_ce", "forward"): 2.0, ("attn", "backward"): 3.0,
        ("ut_pass", "recompute"): 1.0, ("optimizer", "optimizer"): 0.5,
        (scope_times.UNSCOPED, "unknown"): 0.5})
    assert got["scope_ms"]["attn"] == pytest.approx(4.0)
    # a block's scope holds more than its kernel: the Mosaic call apart
    assert got["kernel_ms"] == pytest.approx({"attn": 1.0})
    # the phases add up to the whole steps' busy time, counted once
    assert sum(got["by_phase"].values()) == pytest.approx(got["busy_ms"])
    assert got["total_ms"] == pytest.approx(10.0)
    assert got["unmapped_ms"] == pytest.approx(0.5)
    assert scope_times.read(view) is got
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("[bench] scopes ")]
    assert len(lines) == 1  # once a run, however many readers ask
    said = json.loads(lines[0][len("[bench] scopes "):])
    assert said["unnamed"] == [["copy.50 f32[8]", pytest.approx(0.5)]]
    assert said["by_scope"][0] == ["attn", "backward", pytest.approx(3.0)]
    assert said["recompiled"] is False and said["instructions"] == 8
    assert said["cache_missed"] == 0


def test_the_six_readers(view):
    got = {name: _reader(name).read(view) for name in READERS}
    assert got["step_forward_ms"] == pytest.approx(5.0)
    assert got["step_backward_ms"] == pytest.approx(3.0)
    assert got["step_recompute_ms"] == pytest.approx(1.0)
    assert got["step_optimizer_ms"] == pytest.approx(0.5)
    assert got["scope_unmapped_pct"] == pytest.approx(5.0)
    floor_ms = 1e3 * fused_ce_cost.floor_seconds(
        view.cell.config, view.cell.traffic, view.peaks)
    assert got["fused_ce_roofline"] == pytest.approx(100 * floor_ms / 2.0)


def test_a_step_without_the_loss_scope_has_no_roofline(view, monkeypatch):
    bare = {k: v for k, v in SCOPES.items()
            if "fused_ce" not in v[0]}
    monkeypatch.setattr(
        program_trace, "compiled_scopes",
        lambda program: {k: program_trace.ScopeOf(*v)
                         for k, v in bare.items()})
    assert _reader("fused_ce_roofline").read(view) is None
    assert _reader("scope_unmapped_pct").read(view) == pytest.approx(25.0)
    # a phase the step does not have reads zero, not nothing
    assert scope_times.phase_ms(view, "pp_hop") == 0.0


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_compiled_scopes_gives_every_reader_none(
        view, monkeypatch, name):
    """The parent commit's ``obs/trace.py``: the run's line is as it was."""
    monkeypatch.delattr(program_trace, "compiled_scopes")
    assert _reader(name).read(view) is None


def test_an_unregistered_step_or_no_capture_gives_none(view, monkeypatch):
    def unknown(program):
        raise LookupError(program)

    monkeypatch.setattr(program_trace, "compiled_scopes", unknown)
    assert scope_times.read(view) is None
    monkeypatch.setattr(scope_times, "_KEPT", {})
    view.run.trace_file = None
    assert scope_times.read(view) is None
    assert _reader("step_forward_ms").read(view) is None


def test_the_loss_floor_of_ouros_cell(view):
    """65,528 loss rows (4 exits x 2 sequences x 8,191) against 49,152 ids
    at width 2,048: three products of 13.19 TFLOP, 200.9 ms at the chip's
    peak; the bytes (the head and its float32 gradient sixteen times, the
    logits chunks once each way) take a quarter of that: compute-bound."""
    cfg, traffic = view.cell.config, view.cell.traffic
    rows = fused_ce_cost.loss_rows(cfg, traffic)
    assert rows == 65528
    ops, moved = fused_ce_cost.step_cost(rows, 2048, 49152, 16)
    assert ops == 3 * 2 * 65528 * 2048 * 49152
    assert moved == (16 * 49152 * 2048 * 2 + 16 * 2 * 49152 * 2048 * 4
                     + 2 * 65528 * 49152 * 4 + 2 * 65528 * 2048 * 2)
    floor = fused_ce_cost.floor_seconds(cfg, traffic, view.peaks)
    assert floor == pytest.approx(0.2009, rel=1e-3)
    assert moved / view.peaks["hbm_bytes_per_s"] < 0.3 * floor
