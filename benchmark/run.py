#!/usr/bin/env python3
"""One run of one benchmark cell: one process, one result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file, ``traffic/<traffic>.json``,
the runner that file names (``runners/<runner>.py``) and one reader per
per-layer metric (``layer_metrics/<metric>.py``).  Adding a cell, a
configuration, a traffic mix, a runner or a per-layer metric is adding
files and an entry; no file here is edited for it.

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  No TPU, or fewer chips than the cell
asks for, is a non-zero exit and no result line.  ``--rehearse`` is the
harness's own switch for the sandbox: tiny sizes on virtual CPU devices,
to find wrong paths and shardings; it prints no metric as a number.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


@dataclasses.dataclass
class View:
    """What a per-layer metric's reader is given."""

    cell: Any                       # harness.Cell
    run: Any                        # harness.Run
    reduced: Optional[Dict[str, Any]]   # trace_reduce.reduce(), if traced
    peaks: Optional[Dict[str, float]]   # peaks.json's entry for this chip
    flops_per_item: float           # flops.train_flops_per_item(config)


def _named(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _overlay(base: dict, rehearse: bool) -> dict:
    """A file's ``rehearse`` group laid over it: the tiny sizes."""
    out = {k: v for k, v in base.items() if k != "rehearse"}
    if rehearse:
        for key, value in base.get("rehearse", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def _claim_devices(chips: int, rehearse: bool) -> None:
    """Pin the platform before JAX starts.  On the chip: the TPU or an
    error, never the CPU.  Rehearsing: `chips` virtual CPU devices."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
        return
    from pytorch_distributed_tpu.utils.chip import require_tpu

    try:
        found = require_tpu()
    except RuntimeError as e:
        raise SystemExit(f"run.py: no TPU, no number: {e}")
    if found["platform"] != "tpu" or found["count"] < chips:
        raise SystemExit(f"run.py: the cell needs {chips} TPU chip(s), "
                         f"JAX reports {found}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = _named(bench["workloads"], args.workload, "workload")
    config_entry = _named(bench["configs"], workload["config"], "config")
    seconds = (args.seconds if args.seconds is not None
               else float(bench["run_seconds"]))
    _claim_devices(workload["chips"], args.rehearse)

    import jax

    import flops
    import harness
    import trace_reduce
    from pytorch_distributed_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    # every program is kept, not only those that took a second to compile:
    # the recipe starts dozens of small ones, and each run is a new process
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = harness.CompileLog().install()

    config = _overlay(harness.load_json(
        os.path.join(ROOT, config_entry["file"])), args.rehearse)
    traffic = _overlay(harness.load_json(os.path.join(
        HERE, "traffic", workload["traffic"] + ".json")), args.rehearse)
    devices = jax.devices()[:workload["chips"]]
    kind = devices[0].device_kind
    peaks = harness.load_json(os.path.join(HERE, "peaks.json")).get(kind)
    if peaks is None and not args.rehearse:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "benchmark/peaks.json")
    harness.say("start", workload=workload["name"], seed=args.seed,
                seconds=seconds, trace=args.trace, rehearse=args.rehearse,
                platform=devices[0].platform, kind=kind,
                devices=len(jax.devices()), compile_cache=cache_dir)

    cell = harness.Cell(
        name=workload["name"], config=config, traffic=traffic,
        chips=workload["chips"], seed=args.seed, seconds=seconds,
        trace=bool(args.trace), devices=devices,
        spans=harness.Spans(), compiles=compiles)
    runner = harness.load_module(
        os.path.join(HERE, "runners", traffic["runner"] + ".py"))
    run = runner.run(cell)

    device = harness.device_report(devices)
    values = dict(run.end_to_end)
    values["setup_s"] = run.window_start - T0
    if device["memory_peak_bytes"] is not None:
        values["hbm_peak_gb"] = device["memory_peak_bytes"] / 1e9
    harness.say("compilation", backend_compile_s=compiles.seconds(),
                programs=len(compiles.backend),
                cache_misses=len(compiles.misses),
                cache_hits=len(compiles.hits),
                inside_window=compiles.inside(run.window_start,
                                              run.window_end))
    harness.say("window", seconds=run.window_s, items=run.items,
                checks=run.checks,
                end_to_end=values, notes=run.notes)

    breakdown = None
    if args.trace:
        reduced = None
        if run.trace_file:
            try:
                reduced = trace_reduce.reduce(
                    trace_reduce.load(run.trace_file),
                    span_prefix=harness.SPAN_PREFIX,
                    window_span=harness.WINDOW_SPAN,
                    step_program=run.notes.get("step_program"))
            except ValueError as e:
                if not args.rehearse:  # a CPU capture has no device plane
                    raise
                harness.say("trace_unread", why=str(e))
        if reduced is not None:
            harness.say("trace", **{k: v for k, v in reduced.items()
                                    if k not in ("device_ops", "idle_gaps")})
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        view = View(cell=cell, run=run, reduced=reduced, peaks=peaks,
                    flops_per_item=flops.train_flops_per_item(config))
        wanted = [m for m in bench["per_layer"]
                  if _reports(m, workload["name"])]
        values = {}
        for metric in wanted:
            reader = harness.load_module(os.path.join(
                HERE, "layer_metrics", metric["name"] + ".py"))
            value = reader.read(view)
            if value is not None:  # nothing to read: left out of the line
                values[metric["name"]] = value
    else:
        wanted = [m for m in bench["end_to_end"]
                  if _reports(m, workload["name"])]
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing and not args.rehearse:
            raise SystemExit(f"run.py: the runner gave no {missing}")

    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {name: {"value": (None if args.rehearse else float(values[name])),
                      "unit": units[name]}
               for name in units if name in values}
    result = {"correct": all(run.checks.values()) and run.failed == 0,
              "attempted": run.attempted, "failed": run.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearsal"] = True  # sizes and device are not the cell's
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
