"""BENCHMARK.json against the contract's limits and against the files."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 2 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_text(bench):
    every = (bench["configs"] + bench["workloads"] + bench["end_to_end"]
             + bench["per_layer"])
    for entry in every:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            if key in entry:
                text = entry[key]
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text, (entry["name"], key)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for group in (bench["configs"], bench["workloads"],
                  bench["end_to_end"] + bench["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_are_wired(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        # a per-layer metric is reported only where the metric it moves is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for cell in cells:
        reported = [m for m in bench["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_every_name_resolves_to_a_file(bench):
    for path in bench["paths"]:
        assert PATH.match(path) and os.path.isdir(os.path.join(ROOT, path))
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert os.path.exists(os.path.join(
            BENCH, "reference", cfg["reference"] + ".py"))
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "runners", traffic["runner"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    for folder, _, names in os.walk(BENCH):
        if "cache" in os.path.relpath(folder, BENCH).split(os.sep) \
                or "__pycache__" in folder:
            continue
        for name in names:
            assert PATH.match(name), os.path.join(folder, name)
