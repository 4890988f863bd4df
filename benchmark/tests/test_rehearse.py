"""A ``--rehearse`` run of every cell (both runners; the four-chip cell on
four virtual CPU devices), and a fifth cell added as data alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _run(root, workload, trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", "4", "--seconds", "5",
         "--trace", str(trace), "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)["workloads"]


@pytest.mark.parametrize("cell", _cells(), ids=lambda c: c["name"])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace):
    line = _run(ROOT, cell["name"], trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell["chips"]
    # a rehearsal prints no metric as a number
    assert line["metrics"] and all(
        m["value"] is None for m in line["metrics"].values())


def test_no_tpu_means_no_result_line():
    """Without --rehearse the sandbox has no chip: non-zero, no line."""
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         _cells()[0]["name"], "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert not any(line.startswith("{")
                   for line in done.stdout.splitlines())


def test_a_fifth_cell_is_data_only(tmp_path):
    """vit-b16 under a new traffic file: one new file, and in
    BENCHMARK.json one entry and its name on the metrics it reports; no edit
    to a file under benchmark/, and the cell runs."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    os.symlink(os.path.join(ROOT, "pytorch_distributed_tpu"),
               root / "pytorch_distributed_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(root / "benchmark" / "traffic" / "resident-b128.json",
              "w") as f:
        json.dump({"runner": "resident_step", "batch_per_chip": 128,
                   "warmup_steps": 4,
                   "rehearse": {"batch_per_chip": 4, "warmup_steps": 2}}, f)
    bench["workloads"].append({
        "name": "vit-b16-b128", "config": "vit-b16",
        "traffic": "resident-b128", "chips": 1,
        "why": "half the batch: leaves the chip room"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "vit-b16-b256" in metric.get("workloads", []):
            metric["workloads"].append("vit-b16-b128")  # reports what it does
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    line = _run(str(root), "vit-b16-b128", 0)
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) >= {"throughput_per_chip", "setup_s"}
