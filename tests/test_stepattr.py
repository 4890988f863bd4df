"""Step-time attribution + roofline plane (ISSUE 20).

The tentpole contract under test: with ``--step-attr`` every step's wall
time decomposes exactly —

    step_time == compute + exposed_comm + host_sync + data_wait + other

— reconciling to <= 0.5% of the p50 step time on real runs (image
GSPMD, image explicit-collectives, LM), because the recorder's windows
are *constructed* to close the identity (residual-clamped ``other``,
``block_until_ready`` fencing the device window, log_step accrual
aligned to the next step's dt).  Around the recorder: the roofline
classifier's labels on synthetic ledgers, the byte-split conservation
law, the planner profile round-trip, the jax-free CLI, the obs_report
``--diff`` composition fences, and the loader/heartbeat data-wait leg.
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from pytorch_distributed_tpu.obs import stepattr
from pytorch_distributed_tpu.obs.metrics import MetricsLogger, read_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- recorder units

def test_identity_closes_by_construction():
    """Window sums never exceed the step: the residual lands in
    ``other`` (>= 0) and the recon error is exactly the overshoot."""
    sa = stepattr.StepAttr()
    with sa.data_wait():
        time.sleep(0.010)
    with sa.device():
        time.sleep(0.005)
    with sa.host_sync():
        time.sleep(0.002)
    f = sa.fields(0.030)
    total = sum(f[f"attr_{c}_ms"] for c in stepattr.COMPONENTS)
    # the identity: components sum to the step time (4dp rounding slack)
    assert total == pytest.approx(30.0, abs=0.01), f
    assert f["attr_data_wait_ms"] >= 10.0
    assert f["attr_other_ms"] >= 0.0
    assert f["attr_recon_err_ms"] == 0.0
    assert f["data_wait_share"] == pytest.approx(
        100.0 * f["attr_data_wait_ms"] / 30.0, abs=0.01)
    # windows reset per step: a second fields() on an idle step is clean
    f2 = sa.fields(0.001)
    assert f2["attr_device_ms"] == 0.0 and f2["attr_data_wait_ms"] == 0.0


def test_residual_clamp_measures_overshoot_only():
    """When the windows overshoot dt (mis-measured step), ``other``
    clamps to zero and recon_err records the overshoot — the identity
    still sums to step_time + recon_err, never silently under-reports."""
    sa = stepattr.StepAttr()
    with sa.device():
        time.sleep(0.010)
    f = sa.fields(0.004)  # dt shorter than the device window
    assert f["attr_other_ms"] == 0.0
    assert f["attr_recon_err_ms"] > 0.0
    total = sum(f[f"attr_{c}_ms"] for c in stepattr.COMPONENTS)
    assert total == pytest.approx(4.0 + f["attr_recon_err_ms"], abs=0.02)


def test_device_split_ledger_vs_timeline():
    """Without a timeline the exposed-comm estimate comes from the wire
    ledger (assumed overlap); a measured exposure overrides it and the
    summary records the provenance."""
    sa = stepattr.StepAttr(comm_bytes_per_step=1e9, link_bytes_per_s=1e11)
    # ledger estimate: 1e9 B / 1e11 B/s = 10 ms of comm; at the assumed
    # 0.6 overlap, 4 ms is exposed — capped by the device window
    compute, exposed, comm = sa._split_device(50.0)
    assert comm == pytest.approx(10.0)
    assert exposed == pytest.approx(4.0)
    assert compute == pytest.approx(46.0)
    # tiny device window: exposure cannot exceed it
    compute, exposed, comm = sa._split_device(2.0)
    assert exposed == pytest.approx(2.0) and compute == 0.0
    # a measured exposure fraction replaces the assumption
    sa.set_exposure(0.10, comm_frac=0.25, source="timeline")
    compute, exposed, comm = sa._split_device(40.0)
    assert exposed == pytest.approx(4.0)
    assert comm == pytest.approx(10.0)
    assert sa.exposure_source == "timeline"


def test_exposure_from_timeline():
    """The timeline bridge: analyze_steps-style per-step stats become the
    measured exposure/comm fractions for ``set_exposure``."""
    stat = types.SimpleNamespace(window_ns=100e6, exposed_ns=5e6,
                                 comm_ns=20e6)
    got = stepattr.exposure_from_timeline([stat, stat])
    assert got is not None
    assert got["exposed_frac"] == pytest.approx(0.05)
    assert got["comm_frac"] == pytest.approx(0.20)
    # no device streams ever opened -> nothing to measure
    assert stepattr.exposure_from_timeline([]) is None
    empty = types.SimpleNamespace(window_ns=0, exposed_ns=0, comm_ns=0)
    assert stepattr.exposure_from_timeline([empty]) is None


def test_split_step_bytes_conserves_the_cost_model():
    """The fwd/bwd/update byte split must conserve StepCost.bytes
    (24*params + activations) exactly — the roofline re-apportions, it
    never invents traffic."""
    params, act = 1e6, 3e7
    total = 24.0 * params + act
    split = stepattr.split_step_bytes(total, params)
    assert sum(split.values()) == pytest.approx(total)
    assert split["update"] == pytest.approx(12.0 * params)
    assert split["backward"] >= split["forward"]


# ------------------------------------------------------------------ roofline

def _mk_records(n=10, step_ms=100.0, comp=62.0, exp=8.0, sync=5.0,
                data=20.0, other=5.0, with_phases=True):
    recs = []
    if with_phases:
        prof = stepattr.phase_profile(
            {"forward": 1e9, "backward": 2e9, "update": 1e7},
            {"forward": 1e7, "backward": 2e7, "update": 1e8},
            comm_bytes=1e6, peak_flops=1e12, hbm_bw=1e11, link_bw=1e10)
        recs.append(dict(stepattr.phase_event_fields(prof),
                         ft_event="stepattr_phases", t=0.0, process=0))
    for i in range(n):
        recs.append({
            "step": i, "t": float(i), "process": 0, "kind": "step",
            "step_time": step_ms / 1e3,
            "attr_compute_ms": comp, "attr_exposed_comm_ms": exp,
            "attr_host_sync_ms": sync, "attr_data_wait_ms": data,
            "attr_other_ms": other, "attr_device_ms": comp + exp,
            "attr_comm_ms": max(exp, 10.0), "attr_recon_err_ms": 0.01,
            "data_wait_share": 100.0 * data / step_ms})
    return recs


def test_roofline_labels_on_synthetic_ledgers():
    """Every bound class pins: fwd/bwd clear the ridge (compute-bound),
    the optimizer streams state (hbm-bound), grad_sync is the wire
    (comm-bound), host components are host-bound; fix-first ranks by
    headroom."""
    recs = _mk_records()
    summ = stepattr.summarize(recs)
    assert summ is not None and summ["steps"] == 10
    assert summ["dominant"] == "compute"
    assert summ["recon_err_pct_p50"] <= 0.5
    ev = stepattr.phase_event(recs)
    assert ev is not None and isinstance(ev["phases"], list)
    roof = stepattr.roofline(summ, ev)
    assert roof["ridge_flops_per_byte"] == pytest.approx(10.0)
    labels = {p["phase"]: p["label"] for p in roof["phases"]}
    assert labels["forward"] == "compute-bound"
    assert labels["backward"] == "compute-bound"
    assert labels["update"] == "hbm-bound"
    assert labels["grad_sync"] == "comm-bound"
    assert labels["data_wait"] == "host-bound"
    assert labels["host_sync"] == "host-bound"
    # fix-first is sorted by headroom, descending
    head = [p["headroom_ms"] for p in roof["fix_first"]]
    assert head == sorted(head, reverse=True) and head[0] > 0


def test_phase_event_rides_the_metrics_logger(tmp_path):
    """The phases list must survive the logger's float-coercing flush:
    phase_event_fields JSON-encodes it, phase_event decodes it back."""
    prof = stepattr.phase_profile({"forward": 1e9}, {"forward": 1e7},
                                  peak_flops=1e12, hbm_bw=1e11)
    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(path, flush_every=1) as log:
        log.log_event("stepattr_phases",
                      **stepattr.phase_event_fields(prof))
    back = stepattr.phase_event(read_metrics(path))
    assert back is not None
    assert back["phases"] == prof["phases"]
    assert back["peak_flops"] == prof["peak_flops"]


def test_attr_profile_round_trip(tmp_path):
    """summarize -> write_attr -> load_attr carries the planner-facing
    fields; a non-profile JSON is rejected loudly."""
    summ = stepattr.summarize(_mk_records())
    p = str(tmp_path / "attr.json")
    prof = stepattr.write_attr(p, summ)
    back = stepattr.load_attr(p)
    assert back["kind"] == "stepattr_profile"
    assert back["bottleneck"] == summ["dominant"]
    assert back["attr_source"] == p
    assert back["step_ms_p50"] == pytest.approx(prof["step_ms_p50"])
    bogus = str(tmp_path / "b.json")
    with open(bogus, "w") as f:
        json.dump({"overlap": 0.5}, f)
    with pytest.raises(ValueError):
        stepattr.load_attr(bogus)


# ------------------------------------------------- live trainers (the fence)

ATTR_KEYS = tuple(f"attr_{c}_ms" for c in stepattr.COMPONENTS) + (
    "attr_device_ms", "attr_comm_ms", "attr_recon_err_ms",
    "data_wait_share")


def _assert_attr_run(path, min_steps):
    recs = read_metrics(path)
    steps = stepattr.step_records(recs)
    assert len(steps) >= min_steps, f"{len(steps)} attr step(s)"
    for r in steps:
        for k in ATTR_KEYS:
            assert k in r, k
    summ = stepattr.summarize(recs)
    assert summ is not None
    # THE acceptance fence: the identity reconciles to <= 0.5% of the
    # p50 step time on a real run
    assert summ["recon_err_pct_p50"] <= 0.5, summ
    # shares are per-component p50s over the step p50 — medians of a
    # skewed run (compile-heavy step 0) don't sum exactly, but must stay
    # in the same ballpark as the closed identity
    assert 75.0 <= sum(summ["shares_pct"].values()) <= 125.0, summ
    # the one-time phases event is booked and anchors a roofline
    ev = stepattr.phase_event(recs)
    assert ev is not None, "trainer must book stepattr_phases once"
    assert stepattr.roofline(summ, ev)["fix_first"]
    assert len([r for r in recs
                if r.get("ft_event") == "stepattr_phases"]) == 1
    return summ


def test_lm_trainer_identity_fence(tmp_path):
    """A real LM fit with step_attr=True stamps the attr_* fields on
    every step and reconciles inside the fence."""
    import jax

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.train.lm import (
        LMTrainer,
        SyntheticTokenDataset,
    )

    mesh = build_mesh(MeshSpec(("data",), (2,)), jax.devices()[:2])
    model = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=1)
    ds = SyntheticTokenDataset(64, 16, 32, seed=0)
    path = str(tmp_path / "lm.jsonl")
    hb = str(tmp_path / "hb")
    with mesh:
        t = LMTrainer(model, mesh, ds, batch_size=4, lr=0.05, seed=0,
                      eval_dataset=None, metrics_jsonl=path, hb_dir=hb,
                      hb_interval_s=0.0, step_attr=True)
        t.fit(6, print_freq=3)
    summ = _assert_attr_run(path, 6)
    # on the tiny CPU model, compute dominates — the loader is synthetic
    assert summ["data_wait_share_p50"] < 60.0, summ
    # heartbeats carry the data_wait EMA for the straggler classifier
    from pytorch_distributed_tpu.obs import read_heartbeats

    beats = read_heartbeats(hb)
    assert beats[0].get("data_wait") is not None


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["gspmd", "explicit"])
def test_image_trainer_identity_fence(tmp_path, explicit):
    """The image trainer closes the same identity on both step flavors
    (GSPMD and explicit shard_map collectives)."""
    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    cfg = Config(arch="resnet18", batch_size=8, epochs=1, lr=0.1,
                 print_freq=2, synthetic=True, synthetic_length=24,
                 image_size=32, num_classes=4, seed=0,
                 checkpoint_dir=str(tmp_path), workers=0,
                 metrics_jsonl=str(tmp_path / "m.jsonl"),
                 step_attr=True)
    Trainer(cfg, explicit_collectives=explicit).fit()
    _assert_attr_run(str(tmp_path / "m.jsonl"), 3)


# ----------------------------------------------------- loader + straggler leg

def test_async_feeder_accounts_waits():
    """AsyncFeeder meters how long the consumer blocked on its queue —
    the data-wait signal when prefetch is on (``data_wait`` spans in
    obs/trace.py's recorder)."""
    from pytorch_distributed_tpu.data.loader import AsyncFeeder
    from pytorch_distributed_tpu.obs.trace import RECORDER

    t0 = time.perf_counter()

    def slow_src():
        for i in range(4):
            time.sleep(0.01)
            yield i

    f = AsyncFeeder(lambda it: it, prefetch=1)
    got = list(f(slow_src()))
    assert got == [0, 1, 2, 3]
    waits = [r for r in RECORDER.records(t0) if r.name == "data_wait"]
    assert [r.id for r in waits] == [0, 1, 2, 3, 4]  # the last takes the end
    assert all(r.end >= r.start for r in waits)
    # the slow source made the consumer wait
    assert sum(r.end - r.start for r in waits) > 0.02


def test_find_stragglers_names_input_starved_ranks(tmp_path):
    """A lagging slow rank whose data_wait EMA explains the slowdown is
    named input-starved (loader, not device); an equally slow rank with
    no data wait stays a plain slow rank."""
    from pytorch_distributed_tpu.obs.heartbeat import (
        HeartbeatWriter,
        find_stragglers,
        read_heartbeats,
    )

    d = str(tmp_path)
    now = time.time()
    # three fast front-runners pin the fleet-median EMA low; two ranks
    # lag with a fat EMA — one starved by its loader, one just slow
    fleet = ((0, 20, 0.010, None), (3, 20, 0.010, None),
             (4, 20, 0.010, None), (1, 10, 0.050, 45.0),
             (2, 10, 0.050, 1.0))
    for pid, step, ema, dw in fleet:
        w = HeartbeatWriter(d, process_index=pid, interval_s=0.0,
                            world=5)
        w.beat(step, step_time_ema=ema, data_wait_ms=dw)
    reasons = find_stragglers(read_heartbeats(d), now=now)
    assert 1 in reasons and 2 in reasons and 0 not in reasons
    assert "input-starved" in reasons[1], reasons[1]
    assert "loader, not device" in reasons[1]
    assert "input-starved" not in reasons[2], reasons[2]
    assert "slow rank" in reasons[2]


# ------------------------------------------------------------ CLI + report

def test_obs_roofline_selftest_is_jax_free():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_roofline.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "obs_roofline selftest: OK" in out.stdout


def test_obs_roofline_fixture_render():
    """The checked-in fixture renders the attribution + roofline report
    and exports the planner profile."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_roofline.py"),
         "--metrics-jsonl",
         os.path.join(REPO, "tests", "data", "stepattr_fixture.jsonl"),
         "--json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["dominant"] == "compute"
    assert doc["recon_err_pct_p50"] <= 0.5
    labels = {p["phase"]: p["label"] for p in doc["roofline"]["phases"]}
    assert labels["update"] == "hbm-bound"


def _write_attr_jsonl(path, comp, sync, data, other, steps=10):
    """A run whose every step is 100 ms with the given composition."""
    exp = 100.0 - comp - sync - data - other
    with MetricsLogger(path, flush_every=1) as log:
        prof = stepattr.phase_profile({"forward": 1e9}, {"forward": 1e7},
                                      peak_flops=1e12, hbm_bw=1e11)
        log.log_event("stepattr_phases",
                      **stepattr.phase_event_fields(prof))
        for i in range(steps):
            log.log_step(i, step_time=0.100, n_items=8, lr=1e-3,
                         scalars={"loss": 2.0},
                         extra={"attr_compute_ms": comp,
                                "attr_exposed_comm_ms": exp,
                                "attr_host_sync_ms": sync,
                                "attr_data_wait_ms": data,
                                "attr_other_ms": other,
                                "attr_device_ms": comp + exp,
                                "attr_comm_ms": exp,
                                "attr_recon_err_ms": 0.0,
                                "data_wait_share": data})


def test_diff_catches_composition_regressions(tmp_path):
    """Same p50 step time, worse composition: the data_wait_share_p95
    and host_sync_ms_p95 rows must flip the diff to exit 1 — and pass in
    the improvement direction (the fences obs_report --selftest also
    pins, here as the user-facing CLI contract)."""
    base = str(tmp_path / "base.jsonl")
    bad = str(tmp_path / "bad.jsonl")
    _write_attr_jsonl(base, comp=62.0, sync=3.0, data=8.0, other=19.0)
    _write_attr_jsonl(bad, comp=42.0, sync=12.0, data=30.0, other=8.0)
    rep = os.path.join(REPO, "scripts", "obs_report.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    worse = subprocess.run(
        [sys.executable, rep, "--diff", base, bad],
        capture_output=True, text=True, timeout=120, env=env)
    assert worse.returncode == 1, worse.stdout + worse.stderr
    assert "data_wait_share_p95" in worse.stdout
    assert "host_sync_ms_p95" in worse.stdout
    better = subprocess.run(
        [sys.executable, rep, "--diff", bad, base],
        capture_output=True, text=True, timeout=120, env=env)
    assert better.returncode == 0, better.stdout + better.stderr


def test_obs_report_folds_the_attribution_section(tmp_path):
    """The single-run report grows '== attribution ==' with the fence
    numbers, and stays silent without --step-attr records."""
    mpath = str(tmp_path / "m.jsonl")
    _write_attr_jsonl(mpath, comp=62.0, sync=3.0, data=8.0, other=19.0)
    rep = os.path.join(REPO, "scripts", "obs_report.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, rep, "--metrics-jsonl", mpath],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "== attribution ==" in out.stdout
    assert "dominant: compute" in out.stdout
    assert "data_wait_share" in out.stdout


# --------------------------------------------------------------- alert rule

def test_data_wait_share_alert_latches_and_clears():
    """The declarative rule: fires past max_pct after warmup, latches,
    clears on recovery — per process."""
    from pytorch_distributed_tpu.obs.alerts import AlertEngine, Rule

    eng = AlertEngine([Rule("data_wait_share", "dw", "warn",
                            {"max_pct": 25.0, "warmup_steps": 2})])
    fired = eng.observe({"step": 1, "process": 0, "step_time": 0.1,
                         "data_wait_share": 90.0})
    assert fired == []  # warmup
    fired = eng.observe({"step": 3, "process": 0, "step_time": 0.1,
                         "data_wait_share": 40.0})
    assert [a.name for a in fired] == ["dw"]
    assert "input-starved" in fired[0].detail
    # latched: no re-fire while still breaching
    assert eng.observe({"step": 4, "process": 0, "step_time": 0.1,
                        "data_wait_share": 41.0}) == []
    assert eng.active()
    # recovery clears
    eng.observe({"step": 5, "process": 0, "step_time": 0.1,
                 "data_wait_share": 5.0})
    assert not eng.active()
