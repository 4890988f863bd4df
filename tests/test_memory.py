"""HBM memory ledger + live-range watermark (obs/memory.py).

Layers under test:
- the **±10% parity fence** (the ISSUE-11 acceptance bar): for every
  recipe-matrix step the static watermark peak reconstructed from the
  compiled HLO text must land within ±10% of the compiler's own
  ``memory_analysis()`` ground truth — lowerings come off the
  session-shared ``get_lowering`` fixture, so the ledgering adds zero
  compiles beyond test_shardlint's sweep (and asserts exactly that via
  the process-wide compile counter);
- **ZeRO reclaim from the ledger alone**: the ``opt_state`` class peak
  of the replicated steps must be >= 3.5x the wus-sharded steps' —
  the ``--zero wus`` memory win reproduced without touching a live
  array shard;
- **fused-CE ordering**: the compiler must rank the three LM CE variants
  fused+dp-sharded < fused+replicated < unfused, strictly, at a
  vocabulary where the loss sets the step's peak (three compiles of
  its own, of the sweep's recipe builder at 256 ids);
- the **shardlint memory budget**: a planted oversized peak against the
  checked-in baseline must come back as an error-severity
  ``memory-budget`` finding (and an undershoot as info);
- the **obs_report --diff fence**: a planted per-step ``peak_hbm_bytes``
  regression at identical step time must exit 1;
- analytic model fences (obs/flops.py ``train_mem_peak`` /
  ``lm_train_mem_peak`` vs the ledger, ±15%);
- serialization: mem_ledger.json round-trip, the Perfetto counter track;
- heartbeat memory: ``beat(mem_bytes=...)`` round-trips through
  ``read_heartbeats`` and shows up in ``find_stragglers`` reasons;
- ``scripts/benchlib.bench_staleness`` aging (satellite: bench results
  age out with a WARN instead of silently going stale);
- ``scripts/obs_memory.py --selftest`` end to end (separate process,
  no jax import on that path).
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from pytorch_distributed_tpu.analysis import core, report
from pytorch_distributed_tpu.obs import comms, flops, heartbeat, memory, timeline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import benchlib  # noqa: E402
import obs_report  # noqa: E402

BASELINE = os.path.join(ROOT, "pytorch_distributed_tpu", "analysis",
                        "baseline.json")


def _ledger(low):
    return memory.ledger_from_hlo_text(
        low.text, step=low.name, mesh_shape=low.mesh_shape,
        arg_classes=memory.arg_classes_of(low.args),
        measured_peak_bytes=comms.compiled_peak_bytes(low.compiled))


# ------------------------------------------------- parity fence (±10%)

@pytest.mark.parametrize("name", list(core.RECIPES))
def test_watermark_parity(get_lowering, name):
    """The acceptance fence: the static watermark peak vs the compiler's
    ``memory_analysis()``, within ±10% on every recipe step."""
    lg = _ledger(get_lowering(name))
    assert lg.peak_bytes > 0 and lg.n_instructions > 0
    assert lg.measured_peak_bytes > 0
    res = lg.residual_pct()
    assert res <= 10.0, (name, lg.peak_bytes, lg.measured_peak_bytes, res)
    # the watermark curve is internally consistent: monotone indices,
    # its max is the peak, and the peak index points into the schedule
    idxs = [i for i, _ in lg.watermark]
    assert idxs == sorted(idxs)
    assert max(b for _, b in lg.watermark) == lg.peak_bytes
    assert 0 <= lg.peak_index < lg.n_instructions
    # arguments+outputs never exceed the peak (they are resident there)
    assert lg.argument_bytes + lg.output_bytes - lg.donated_bytes \
        <= lg.peak_bytes


def test_top_buffers_attribution(get_lowering):
    """Attribution plumbing on a real lowering: top buffers carry class,
    phase, and shape; params/opt_state classes both appear at peak for
    the explicit image step."""
    lg = _ledger(get_lowering("train_image_explicit"))
    top = lg.top_buffers(16)
    assert top and all(b.bytes > 0 for b in top)
    assert top == sorted(top, key=lambda b: (-b.bytes, b.name))
    classes = {b.klass for b in top}
    assert "params" in classes and "opt_state" in classes, classes
    cp = lg.class_peaks()
    for k in ("params", "opt_state", "activations", "output"):
        assert cp.get(k, 0) > 0, cp
    # live_at(peak) sums to the watermark level at the peak
    live = lg.live_at(lg.peak_index)
    assert sum(b.bytes for b in live) == lg.peak_bytes


# --------------------------------------------- ZeRO reclaim (>= 3.5x)

@pytest.mark.parametrize("repl,zero", [
    ("train_image_explicit", "train_image_zero"),
    ("lm_train_dp", "train_lm_zero"),
])
def test_zero_opt_state_reclaim(get_lowering, repl, zero):
    """--zero wus reclaims the optimizer state: the ledger's opt_state
    class peak, read from the compiled HLO alone, shows the (N-1)/N
    shard reclaim (>= 3.5x on the 4-way mesh)."""
    lg_r = _ledger(get_lowering(repl))
    lg_z = _ledger(get_lowering(zero))
    opt_r = lg_r.class_peaks().get("opt_state", 0)
    opt_z = lg_z.class_peaks().get("opt_state", 0)
    assert opt_r > 0 and opt_z > 0
    ratio = opt_r / opt_z
    assert ratio >= 3.5, (repl, zero, opt_r, opt_z, ratio)
    # and the overall peak moves the right way too
    assert lg_z.peak_bytes < lg_r.peak_bytes


# -------------------------------------------- fused-CE peak ordering

def _ce_ledger(fused_ce_mode, vocab):
    """The DP LM step of the sweep's recipe builder at another vocabulary,
    compiled here (no sweep consumer: nothing of it is cached or counted)."""
    step, args, _, mesh = core._recipe_lm_train(fused_ce_mode, vocab=vocab)
    compiled = step.lower(*args).compile()
    return memory.ledger_from_hlo_text(
        compiled.as_text(), step=f"lm_ce_{fused_ce_mode}_v{vocab}",
        mesh_shape=dict(mesh.shape),
        arg_classes=memory.arg_classes_of(args),
        measured_peak_bytes=comms.compiled_peak_bytes(compiled))


def test_fused_ce_peak_ordering():
    """The compiler's own peaks (``memory_analysis()``) rank the LM CE
    variants: fused+dp-sharded < fused+replicated < the unfused step,
    strictly.

    Ranked at 256 ids, not the sweep's 64: there a device's 32-wide
    hidden rows outweigh its [N, V] logits, the step's peak is in the
    block and not the loss, and the replicated variant and the unfused
    step are level to 200 bytes of 407 KB whatever the loss does (more
    rows move nothing).  At 256 the loss sets the peak: the unfused step
    holds the logits and their gradient whole, the fused loss a chunk of
    each beside the [V, D] float32 accumulator, which the dp variant
    shards; the three are 13% and 3% (18.5 KB) apart."""
    lg_un = _ce_ledger(None, 256)
    lg_rep = _ce_ledger("replicated", 256)
    lg_dp = _ce_ledger("dp", 256)
    assert lg_dp.measured_peak_bytes < lg_rep.measured_peak_bytes \
        < lg_un.measured_peak_bytes, (
            lg_dp.measured_peak_bytes, lg_rep.measured_peak_bytes,
            lg_un.measured_peak_bytes)
    # the watermark resolves the fused dp-vs-replicated accumulator gap
    assert lg_dp.peak_bytes < lg_rep.peak_bytes


# --------------------------------------- shardlint memory budget fence

def test_planted_budget_regression_is_error(get_lowering):
    """A baseline whose pinned peak is 20% below the current lowering
    must produce an error-severity memory-budget finding; one 20% above
    reads as a stale-baseline info."""
    get_lowering("train_image_explicit")  # share the session compile
    rep = core.analyze_recipe("train_image_explicit")
    entry = report.load_baseline(BASELINE)["train_image_explicit"]
    peak = sum(rep.memory.values())
    assert peak > 0

    planted = dict(entry, peak_hbm_bytes=int(peak / 1.2))
    findings = report.diff_against_baseline(rep, planted)
    errs = [f for f in findings
            if f.kind == "memory-budget" and f.severity == "error"]
    assert errs, findings
    assert "peak HBM budget exceeded" in errs[0].message

    stale = dict(entry, peak_hbm_bytes=int(peak * 1.2))
    findings = report.diff_against_baseline(rep, stale)
    infos = [f for f in findings
             if f.kind == "memory-budget" and f.severity == "info"]
    assert infos and not [f for f in findings
                          if f.kind == "memory-budget"
                          and f.severity == "error"]

    # the checked-in baseline itself is clean within the 2% slack
    real = report.diff_against_baseline(rep, entry)
    assert not [f for f in real if f.kind == "memory-budget"
                and f.severity == "error"], real


def test_baseline_pins_peak_for_every_meshed_step():
    """Every meshed recipe's baseline entry carries the peak pin; a new
    recipe landing without one would silently skip the budget fence."""
    base = report.load_baseline(BASELINE)
    missing = [n for n, e in base.items()
               if "peak_hbm_bytes" not in e or e["peak_hbm_bytes"] <= 0]
    assert not missing, missing


# ------------------------------------------------ diff fence (exit 1)

def _write_run(path, peak_bytes):
    from pytorch_distributed_tpu.obs.metrics import MetricsLogger

    with MetricsLogger(path, flush_every=50) as log:
        for i in range(30):
            log.log_step(i, step_time=0.010, n_items=128, lr=0.1,
                         extra={"peak_hbm_bytes": float(peak_bytes),
                                "mem_residual_pct": 4.0})


def test_diff_exit_1_on_planted_peak_regression(tmp_path, capsys):
    """Identical step time, but the per-step compiled peak grew 25% —
    a layout change silently re-replicating state.  ``obs_report
    --diff`` must exit 1 on the peak_hbm_bytes row."""
    base = str(tmp_path / "base.jsonl")
    bad = str(tmp_path / "bad.jsonl")
    _write_run(base, peak_bytes=160_000)
    _write_run(bad, peak_bytes=200_000)
    rc = obs_report.main(["--diff", base, bad])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "REGRESS" in out and "peak_hbm_bytes" in out
    assert obs_report.main(["--diff", base, base]) == 0
    capsys.readouterr()
    rc_json = obs_report.main(["--diff", base, bad, "--format", "json"])
    js = json.loads(capsys.readouterr().out)
    assert rc_json == 1 and js["overall"] == "REGRESS"
    by_name = {r["metric"]: r for r in js["metrics"]}
    assert by_name["peak_hbm_bytes"]["verdict"] == "REGRESS"
    assert by_name["step_time_p50"]["verdict"] == "PASS"


# ---------------------------------------------- serialization round-trip

def test_ledger_roundtrips_through_json(get_lowering, tmp_path):
    lg = _ledger(get_lowering("lm_train_dp"))
    path = str(tmp_path / "mem_ledger.json")
    memory.write_ledgers(path, [lg])
    back = memory.load_ledgers(path)[lg.step]
    assert back.peak_bytes == lg.peak_bytes
    assert back.peak_index == lg.peak_index
    assert back.measured_peak_bytes == lg.measured_peak_bytes
    assert back.watermark == lg.watermark
    assert back.mesh_shape == lg.mesh_shape
    fields = back.metrics_fields()
    assert fields["mem_peak_bytes"] == lg.peak_bytes
    # the raw dict keeps the full breakdowns the lossy reload drops
    raw = json.load(open(path))[lg.step]
    assert raw["class_peaks"] == lg.class_peaks()
    assert raw["phase_peaks"] == lg.phase_peaks()


def test_trainer_metrics_fields(get_lowering):
    """The fields the trainers stamp into metrics.jsonl under
    --mem-ledger are the ones obs_report's memory section reads."""
    lg = _ledger(get_lowering("train_image_explicit"))
    fields = lg.metrics_fields()
    for key in ("mem_peak_bytes", "mem_temp_peak_bytes",
                "mem_residual_pct"):
        assert key in fields, fields
    assert fields["mem_peak_bytes"] == lg.peak_bytes
    assert fields["mem_temp_peak_bytes"] == lg.temp_peak_bytes
    assert abs(fields["mem_residual_pct"]) <= 10.0


# ------------------------------------------------ Perfetto counter track

def test_watermark_counter_track(get_lowering):
    """The merged Chrome trace carries the watermark as a "C" (counter)
    track: one event per change point, ts spanning the step window,
    max level equal to the ledger peak."""
    lg = _ledger(get_lowering("train_image_explicit"))
    events = memory.watermark_counter_events(lg, 1000.0, 2000.0, pid=7)
    assert len(events) == len(lg.watermark)
    assert all(e["ph"] == "C" and e["pid"] == 7 for e in events)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    assert 1000.0 <= ts[0] and ts[-1] <= 2000.0, (ts[0], ts[-1])
    assert max(e["args"]["bytes"] for e in events) == lg.peak_bytes
    # and through the timeline merge path (obs_timeline --mem-ledger)
    tl = timeline.parse_xspace_bytes(timeline.encode_xspace([{
        "name": "/host:CPU",
        "lines": [{"name": "tf_XLATfrtCpuClient/0",
                   "timestamp_ns": 1_000_000,
                   "events": [{"name": "fusion.1", "offset_ps": 0,
                               "duration_ps": 50_000_000}]}],
    }], hostname="host0"), source="rank0")
    merged = timeline.to_chrome_trace([(0, tl)], mem_ledgers=[lg])
    counters = [e for e in merged["traceEvents"] if e.get("ph") == "C"]
    assert len(counters) == len(lg.watermark)
    assert counters[0]["name"].startswith("hbm_watermark")


# --------------------------------------------- zero extra compiles

def test_mem_ledger_rides_lowering_cache(get_lowering):
    """The whole memory sweep is free once shardlint has lowered the
    step: mem_ledger_for must not trigger a single extra compile."""
    get_lowering("train_image_explicit")
    get_lowering("lm_train_dp")
    before = get_lowering.compile_count()
    core.mem_ledger_for("train_image_explicit")
    core.mem_ledger_for("lm_train_dp")
    core.analyze_recipe("train_image_explicit")
    assert get_lowering.compile_count() == before
    # and the conftest cache dir has the artifacts subprocesses read
    assert (get_lowering.cache_dir / "train_image_explicit.hlo").exists()
    meta = json.loads(
        (get_lowering.cache_dir / "train_image_explicit.json").read_text())
    assert meta["measured_peak_bytes"] > 0
    assert "params" in meta["arg_classes"]


# --------------------------------------------- analytic model (±15%)

def test_analytic_image_mem_fence(get_lowering):
    """obs/flops.py first-principles peak model vs the ledger for the
    explicit image step, ±15%."""
    lg = _ledger(get_lowering("train_image_explicit"))
    # TinyMLP: Dense(192->32) + Dense(32->10); batch 16 of 8x8x3 images
    pb = 4 * (192 * 32 + 32 + 32 * 10 + 10)
    act = 4 * 4 * (192 + 32 + 32 + 10)
    data = 16 * 8 * 8 * 3 * 4 / 4 + 16 + 16 + 8
    pred = flops.train_mem_peak(pb, act, data, dp=4, zero=False,
                                explicit_sync=True, metric_bytes=112.0)
    res = flops.mem_residual_pct(pred.peak_bytes, lg.peak_bytes)
    assert res <= 15.0, (pred.peak_bytes, lg.peak_bytes, res)

    lg_z = _ledger(get_lowering("train_image_zero"))
    pred_z = flops.train_mem_peak(pb, act, data, dp=4, zero=True,
                                  explicit_sync=True, metric_bytes=112.0)
    assert pred_z.peak_bytes < pred.peak_bytes
    assert lg_z.peak_bytes < lg.peak_bytes


def test_analytic_lm_mem_fence(get_lowering):
    """lm_train_mem_peak vs the GSPMD LM DP step and its wus twin."""
    lg = _ledger(get_lowering("lm_train_dp"))
    pred = flops.lm_train_mem_peak(64, 32, 1, 4, 8, 16, dp=4)
    res = flops.mem_residual_pct(pred.peak_bytes, lg.peak_bytes)
    assert res <= 15.0, (pred.peak_bytes, lg.peak_bytes, res)

    lg_z = _ledger(get_lowering("train_lm_zero"))
    pred_z = flops.lm_train_mem_peak(64, 32, 1, 4, 8, 16, dp=4, zero=True)
    res_z = flops.mem_residual_pct(pred_z.peak_bytes, lg_z.peak_bytes)
    assert res_z <= 15.0, (pred_z.peak_bytes, lg_z.peak_bytes, res_z)
    # the model agrees with the ledger about the direction of the win
    assert pred_z.peak_bytes < pred.peak_bytes


# ------------------------------------------------ heartbeat memory

def test_heartbeat_memory_roundtrip(tmp_path):
    """beat(mem_bytes=...) -> read_heartbeats -> find_stragglers: the
    flagged rank's reason names its memory."""
    hb = str(tmp_path / "hb")
    now = None
    for pid, step, mem in ((0, 20, 100 << 20), (1, 10, 900 << 20)):
        w = heartbeat.HeartbeatWriter(hb, pid, interval_s=0.0)
        assert w.beat(step, mem_bytes=mem)
    beats = heartbeat.read_heartbeats(hb)
    assert beats[0]["mem"] == 100 << 20
    assert beats[1]["mem"] == 900 << 20
    flagged = heartbeat.find_stragglers(beats, now=now, max_step_lag=3)
    assert 1 in flagged and 0 not in flagged
    assert "mem 900 MiB" in flagged[1], flagged
    # mem is optional: a beat without it neither crashes nor reports it
    w = heartbeat.HeartbeatWriter(hb, 2, interval_s=0.0)
    w.beat(1)
    beats = heartbeat.read_heartbeats(hb)
    assert "mem" not in beats[2]
    flagged = heartbeat.find_stragglers(beats, max_step_lag=3)
    assert "mem" not in flagged[2]


def test_sample_process_memory():
    """On this (Linux, jax-imported) host the sampler returns a positive
    byte count — RSS fallback at worst."""
    m = heartbeat.sample_process_memory()
    assert m is not None and m > 0


# --------------------------------------------- bench staleness aging

def test_bench_staleness_aging(tmp_path):
    lkg = tmp_path / "BENCH_LKG.json"
    ev = tmp_path / "bench_events.jsonl"
    now = 1_700_000_000.0

    # no files at all -> no guess
    assert benchlib.bench_staleness(str(lkg), str(ev), now=now) is None

    lkg.write_text(json.dumps({
        "metric": "img_steps_per_s",
        "captured_at": "2023-11-04T22:13:20+0000"}))  # == now - 10 days
    st = benchlib.bench_staleness(str(lkg), str(ev), now=now)
    assert st["metric"] == "img_steps_per_s"
    assert st["days_stale"] == pytest.approx(10.0, abs=0.2)
    assert st["stale_events"] == 0

    # stale/failed events count but never refresh the last-good mark
    with open(ev, "w") as f:
        f.write(json.dumps({"bench_event": "stale", "t": now - 100}) + "\n")
        f.write(json.dumps({"bench_event": "failed", "t": now - 50}) + "\n")
    st = benchlib.bench_staleness(str(lkg), str(ev), now=now)
    assert st["stale_events"] == 2
    assert st["days_stale"] == pytest.approx(10.0, abs=0.2)

    # an explicit captured event DOES refresh it
    with open(ev, "a") as f:
        f.write(json.dumps({"bench_event": "captured", "t": now - 86400,
                            "captured_at": "yesterday"}) + "\n")
    st = benchlib.bench_staleness(str(lkg), str(ev), now=now)
    assert st["days_stale"] == pytest.approx(1.0, abs=1e-6)
    assert st["last_good"] == "yesterday"


# --------------------------------------------------- CLI selftest (tier-1)

def test_obs_memory_selftest_subprocess():
    """The ledger CLI end to end on the checked-in HLO fixture — fast
    (no jax import on this path)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "obs_memory.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "selftest OK" in out.stdout


def test_obs_memory_cli_on_cached_hlo(get_lowering, tmp_path):
    """The CLI consumes the conftest cache's HLO artifact of a real
    recipe step in a separate process — pure text re-analysis, no
    recompile, no jax."""
    get_lowering("train_image_explicit")
    hlo = get_lowering.cache_dir / "train_image_explicit.hlo"
    out_json = str(tmp_path / "mem_ledger.json")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "obs_memory.py"),
         str(hlo), "--json", out_json],
        capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ledger train_image_explicit: peak" in out.stdout
    d = json.load(open(out_json))["train_image_explicit"]
    lg = _ledger(get_lowering("train_image_explicit"))
    assert d["peak_bytes"] == lg.peak_bytes
    assert d["watermark"] == [list(p) for p in lg.watermark]
