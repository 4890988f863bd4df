"""Efficiency accounting (ISSUE 5): analytic FLOPs vs XLA cost_analysis
parity, MFU/HFU plumbing through both trainers, the goodput/badput
ledger, the recompile watchdog, heartbeat slow-vs-dead discrimination,
and the obs_report --diff regression fence."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------- analytic FLOPs parity
def test_resnet_flops_parity_vs_cost_analysis():
    """Analytic image step cost within +-10% of the compiler's own count
    for a tiny resnet on the 4-way CPU mesh (ISSUE acceptance)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.obs.flops import (
        image_step_cost,
        xla_step_flops,
    )
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step

    mesh = build_mesh(MeshSpec(("data",), (4,)), jax.devices()[:4])
    B, IM, NC = 8, 32, 8
    model = models.create_model("resnet18", num_classes=NC)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, IM, IM, 3)), train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    step = make_train_step(model, mesh)
    batch = {"images": jnp.zeros((B, IM, IM, 3)),
             "labels": jnp.zeros((B,), jnp.int32),
             "weights": jnp.ones((B,), jnp.float32)}

    cost = image_step_cost("resnet18", B, IM, NC)
    # the analytic param count backs the optimizer term — sanity it first
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(variables["params"]))
    assert cost.params == pytest.approx(n_params, rel=0.01)

    est = cost.per_device_flops(4)
    xla = xla_step_flops(step, state, batch, jnp.float32(0.1))
    assert 0.9 <= xla / est <= 1.1, (xla, est, xla / est)


def test_lm_flops_parity_vs_cost_analysis():
    """Analytic LM step cost within +-10% of cost_analysis for a tiny LM
    on the 4-way CPU mesh (ISSUE acceptance)."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.obs.flops import (
        lm_step_cost_for,
        xla_step_flops,
    )
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.parallel.tp import (
        replicated_like,
        shard_state,
    )
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = build_mesh(MeshSpec(("data",), (4,)), jax.devices()[:4])
    V, D, H, L, B, S = 64, 64, 4, 2, 8, 32
    model = TransformerLM(vocab_size=V, d_model=D, n_heads=H, n_layers=L,
                          attn_impl="dense")
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((4, S), jnp.int32))["params"]
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    specs = replicated_like(params)
    state = shard_state(
        TrainState.create({"params": params}, sgd_init(params)), specs, mesh)
    step = make_lm_train_step(model, mesh, specs)

    cost = lm_step_cost_for(model, B, S)
    assert cost.params == pytest.approx(n_params, rel=0.01)
    est = cost.per_device_flops(4)
    xla = xla_step_flops(step, state, jnp.zeros((B, S), jnp.int32),
                         jnp.float32(0.1))
    assert 0.9 <= xla / est <= 1.1, (xla, est, xla / est)


def test_step_cost_taxes_and_reporter():
    """Remat's recompute inflates hardware FLOPs only (HFU < MFU
    denominator relationship), the fused loss recomputes nothing (its
    gradient is taken in the pass that has the logits), and the reporter
    turns seconds into percentages with the expected arithmetic."""
    from pytorch_distributed_tpu.obs.flops import (
        MFUReporter,
        image_step_cost,
        lm_step_cost,
    )

    plain = lm_step_cost(256, 64, 2, 8, 32)
    fused = lm_step_cost(256, 64, 2, 8, 32, fused_ce=True)
    remat = lm_step_cost(256, 64, 2, 8, 32, remat=True)
    assert plain.hardware_flops == plain.model_flops
    assert fused.hardware_flops == fused.model_flops
    assert fused.breakdown["recompute"] == 0.0
    assert remat.hardware_flops > remat.model_flops
    # fused-CE trims the head to the loss rows: model FLOPs drop slightly
    assert fused.model_flops < plain.model_flops

    vit = image_step_cost("vit_b_16", 4, 224, 1000)
    vit_r = image_step_cost("vit_b_16", 4, 224, 1000, remat=True)
    # the ~1/3-extra-matmul remat tax (models/vit.py)
    tax = (vit_r.hardware_flops - vit.model_flops) / vit.model_flops
    assert 0.2 < tax < 0.4

    with pytest.raises(ValueError, match="analytic FLOPs model"):
        image_step_cost("densenet121", 8, 32, 8)

    rep = MFUReporter(plain, n_devices=4, peak_per_chip=1e12)
    fields = rep.fields(0.5)
    assert fields["mfu"] == pytest.approx(
        100.0 * plain.model_flops / 0.5 / 4e12)
    assert fields["hfu"] >= fields["mfu"]
    assert fields["model_tflops"] > 0


def test_device_peak_flops_table_and_override(monkeypatch):
    from pytorch_distributed_tpu.obs.flops import (
        CPU_FALLBACK_PEAK,
        device_peak_flops,
    )

    class FakeDev:
        def __init__(self, kind):
            self.device_kind = kind

    assert device_peak_flops(FakeDev("TPU v5e")) == 197e12
    assert device_peak_flops(FakeDev("TPU v4")) == 275e12
    assert device_peak_flops(FakeDev("cpu")) == CPU_FALLBACK_PEAK
    with pytest.raises(ValueError, match="weird accelerator"):
        device_peak_flops(FakeDev("weird accelerator"))
    monkeypatch.setenv("PTD_TPU_PEAK_FLOPS", "123e9")
    assert device_peak_flops(FakeDev("TPU v4")) == 123e9


# -------------------------------------------------------- recompile watchdog
def test_watchdog_flags_planted_recompile():
    """A dynamic-shape recompile after warmup raises exactly one anomaly
    event (ISSUE acceptance)."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.obs import MetricsLogger, RecompileWatchdog

    import jax

    events = []
    obs = MetricsLogger(None)
    obs.register(events.append)
    f = jax.jit(lambda x: x * 2 + 1)
    # inputs built OUTSIDE the watched region (array creation is itself a
    # tiny compile — the trainers' feeders run outside the watch too)
    x8, x9 = jnp.ones(8), jnp.ones(9)
    with RecompileWatchdog(obs=obs) as wd:
        with wd.watch("step_fn", step=0):
            f(x8).block_until_ready()              # warmup compile
        with wd.watch("step_fn", step=1):
            f(x8).block_until_ready()              # cached: no compile
        assert wd.compiles.get("step_fn") == 1 and not wd.anomalies
        with wd.watch("step_fn", step=2):
            f(x9).block_until_ready()              # planted dynamic shape
    assert wd.compiles["step_fn"] == 2
    assert len(wd.anomalies) == 1, wd.anomalies
    a = wd.anomalies[0]
    assert a["label"] == "step_fn" and a["step"] == 2
    assert a["duration_s"] > 0
    # the anomaly reached the metrics stream as a recompile ft_event
    recs = [e for e in events if e.get("ft_event") == "recompile"]
    assert len(recs) == 1 and recs[0]["label"] == "step_fn"
    # unattributed compiles are counted but never flagged
    g = jax.jit(lambda x: x - 1)
    g(jnp.ones(3)).block_until_ready()
    assert len(wd.anomalies) == 1


def test_watchdog_uninstall_stops_counting():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.obs import RecompileWatchdog

    wd = RecompileWatchdog().install()
    wd.uninstall()
    f = jax.jit(lambda x: x + 3)
    with wd.watch("dead"):
        f(jnp.ones(4)).block_until_ready()
    assert "dead" not in wd.compiles


# ------------------------------------------------------------ goodput ledger
def _step_rec(step, t, st=1.0):
    return {"step": step, "t": t, "process": 0, "step_time": st,
            "step_time_ema": st, "step_time_p50": st, "step_time_p95": st,
            "step_time_max": st}


def test_goodput_ledger_taxonomy():
    """Synthetic JSONL with skip/rollback/preempt events lands in the
    right badput buckets (ISSUE acceptance)."""
    from pytorch_distributed_tpu.obs.goodput import compute_goodput

    t0 = 1000.0
    records = [_step_rec(i, t0 + i + 1) for i in range(10)]
    records += [
        {"ft_event": "skip", "step": 7, "t": t0 + 8.1},
        {"ft_event": "rollback", "step": 9, "restored_step": 5,
         "t": t0 + 10.1, "lr_scale": 0.5},
        {"ft_event": "preempt", "step": 9, "t": t0 + 10.2},
    ]
    # resumed run: 30s restart gap, then 3 more steps
    records += [_step_rec(10 + i, t0 + 40.2 + i) for i in range(3)]
    rep = compute_goodput(records)
    assert rep.steps == 13
    assert rep.counts["nan_skip"] == 1
    assert rep.badput_s["nan_skip"] == pytest.approx(1.0)
    # rollback discards steps 6..9, minus step 7 already booked as skip
    assert rep.counts["rollback_discard"] == 1
    assert rep.badput_s["rollback_discard"] == pytest.approx(3.0)
    assert rep.counts["preempt_gap"] == 1
    assert rep.badput_s["preempt_gap"] == pytest.approx(30.0, abs=0.2)
    # productive = 13 steps - 1 skip - 3 discarded
    assert rep.productive_s == pytest.approx(9.0)
    assert 0 < rep.goodput_pct < 100


def test_goodput_stall_detection_and_summary():
    from pytorch_distributed_tpu.obs.goodput import (
        compute_goodput,
        summarize_goodput,
    )

    t0 = 0.0
    records = [_step_rec(i, t0 + i + 1, st=1.0) for i in range(5)]
    # 20s unexplained gap before step 5 (data starvation)
    records += [_step_rec(5 + i, t0 + 25.0 + i, st=1.0) for i in range(3)]
    rep = compute_goodput(records)
    assert rep.counts["stall"] == 1
    assert rep.badput_s["stall"] == pytest.approx(19.0, abs=0.2)
    lines = summarize_goodput(records)
    text = "\n".join(lines)
    assert "== goodput ==" in text and "badput/stall" in text
    assert "goodput" in text
    # tiny jitter below the floor is NOT a stall
    clean = [_step_rec(i, i * 1.1, st=1.0) for i in range(10)]
    assert compute_goodput(clean).counts["stall"] == 0


def test_goodput_tracker_live_sink():
    from pytorch_distributed_tpu.obs import MetricsLogger
    from pytorch_distributed_tpu.obs.goodput import GoodputTracker

    log = MetricsLogger(None)
    tracker = log.register(GoodputTracker())
    for i in range(5):
        log.log_step(i, step_time=0.5, n_items=8)
    log.log_event("skip", step=3, consecutive=1)
    log.flush()
    rep = tracker.report()
    assert rep.steps == 5 and rep.counts["nan_skip"] == 1
    assert "goodput" in tracker.format_summary()
    log.close()


# ------------------------------------------- heartbeat slow-vs-dead satellite
def test_find_stragglers_slow_vs_dead():
    from pytorch_distributed_tpu.obs import find_stragglers

    now = 1000.0
    beats = {
        0: {"pid": 0, "step": 50, "t": now - 1, "ema": 0.010},
        # lagging with FRESH beats and a fat EMA: a slow rank
        1: {"pid": 1, "step": 40, "t": now - 2, "ema": 0.055},
        # stale beats: dead or hung, with its last ft_event on record
        2: {"pid": 2, "step": 50, "t": now - 300, "ema": 0.010,
            "last_ft": "preempt"},
        3: {"pid": 3, "step": 49, "t": now - 1, "ema": 0.011},
    }
    flagged = find_stragglers(beats, now=now, max_step_lag=3, max_age_s=60)
    assert set(flagged) == {1, 2}
    assert "slow rank" in flagged[1] and "ema" in flagged[1]
    assert "dead or hung" in flagged[2]
    assert "last ft_event: preempt" in flagged[2]
    # without EMAs the legacy reasons still work
    legacy = {0: {"pid": 0, "step": 50, "t": now - 1},
              1: {"pid": 1, "step": 40, "t": now - 2}}
    flagged = find_stragglers(legacy, now=now, max_step_lag=3, max_age_s=60)
    assert "step lag 10" in flagged[1] and "slow rank" not in flagged[1]


def test_heartbeat_beats_carry_ema_and_ft(tmp_path):
    from pytorch_distributed_tpu.obs import HeartbeatWriter, read_heartbeats

    w = HeartbeatWriter(str(tmp_path), 0, interval_s=0.0)
    w.beat(3, step_time_ema=0.02, last_ft="rollback")
    beats = read_heartbeats(str(tmp_path))
    assert beats[0]["ema"] == pytest.approx(0.02)
    assert beats[0]["last_ft"] == "rollback"


# ---------------------------------------------------- bench staleness events
def test_benchlib_bench_event_and_report_fold(tmp_path, monkeypatch):
    """A stale-probe bench_event lands in the metrics-stream schema and
    obs_report folds it into a '== bench ==' section."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import benchlib
    import obs_report

    path = str(tmp_path / "bench_events.jsonl")
    monkeypatch.setenv("BENCH_EVENTS_JSONL", path)
    benchlib.bench_event("stale", reason="device discovery hung >40s",
                         last_good="2026-07-31T06:32:08+0000",
                         metric="resnet50_train_images_per_sec_per_chip",
                         value=2511.3)
    recs, malformed = obs_report.load_metrics(path)
    assert malformed == 0 and recs[0]["bench_event"] == "stale"
    assert recs[0]["t"] > 0  # same time-stamped JSONL schema as obs records
    lines = obs_report.summarize_bench(recs)
    text = "\n".join(lines)
    assert "== bench ==" in text and "stale" in text
    assert "last good 2026-07-31" in text and "hung" in text
    # unwritable path: on the benchmark path a failure is a failure
    monkeypatch.setenv("BENCH_EVENTS_JSONL",
                       str(tmp_path / "no" / "such" / "dir" / "x.jsonl"))
    with pytest.raises(OSError):
        benchlib.bench_event("stale", reason="r")


# ------------------------------------------------------- obs_report diff fence
def test_obs_report_diff_verdicts(tmp_path):
    """REGRESS on a synthetically slowed run, PASS on identical runs, and
    malformed-line counting (ISSUE acceptance)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import obs_report

    def write_run(path, st):
        with open(path, "w") as f:
            for i in range(20):
                f.write(json.dumps(_step_rec(i, 100.0 + i * st, st=st)
                                   | {"throughput": 64 / st,
                                      "mfu": 30.0 * 0.01 / st}) + "\n")

    a = str(tmp_path / "a.jsonl")
    b = str(tmp_path / "b.jsonl")
    write_run(a, 0.010)
    write_run(b, 0.013)
    with open(b, "a") as f:
        f.write('{"step": 20, "step_ti')  # torn tail
    rc = obs_report.main(["--diff", a, b])
    assert rc == 1  # regression fence trips
    rc = obs_report.main(["--diff", a, a])
    assert rc == 0
    recs, malformed = obs_report.load_metrics(b)
    assert len(recs) == 20 and malformed == 1
    text, regressed = obs_report.diff_report(recs, recs)
    assert not regressed and "overall: PASS" in text


# ----------------------------------------------- trainer wiring (LM fast path)
def test_lm_trainer_mfu_goodput_watchdog_clean_run(tmp_path):
    """A clean LMTrainer run with --mfu/--goodput/--watch-recompiles on:
    MFU/HFU fields in every record, a silent watchdog (no post-warmup
    recompiles), and a live goodput summary."""
    import jax

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.obs import read_metrics
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.train.lm import (
        LMTrainer,
        SyntheticTokenDataset,
    )

    mesh = build_mesh(MeshSpec(("data",), (2,)), jax.devices()[:2])
    model = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=1)
    ds = SyntheticTokenDataset(16, 16, 32, seed=0)
    path = str(tmp_path / "lm.jsonl")
    with mesh:
        t = LMTrainer(model, mesh, ds, batch_size=4, lr=0.05, seed=0,
                      eval_dataset=None, metrics_jsonl=path,
                      mfu=True, goodput=True, watch_recompiles=True)
        t.fit(4, print_freq=2)
    recs = [r for r in read_metrics(path) if "ft_event" not in r]
    assert len(recs) == 4
    for r in recs:
        assert r["mfu"] > 0 and r["hfu"] >= r["mfu"]
        assert r["model_tflops"] > 0
    assert t.watchdog.compiles.get("lm_step") == 1
    assert t.watchdog.anomalies == []
    assert t._goodput.report().steps == 4
    # no recompile events polluted the stream
    assert not any(r.get("ft_event") == "recompile"
                   for r in read_metrics(path))


# ------------------------------------------ image trainer clean 2-epoch (slow)
@pytest.mark.slow
def test_image_trainer_watchdog_silent_two_epochs(tmp_path):
    """The watchdog stays silent across a clean 2-epoch image run with all
    efficiency surfaces on (ISSUE acceptance: no false positives), and the
    JSONL carries MFU fields for the resnet family."""
    from pytorch_distributed_tpu.obs import read_metrics
    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    cfg = Config(arch="resnet18", batch_size=16, epochs=2, lr=0.1,
                 print_freq=2, synthetic=True, synthetic_length=32,
                 image_size=32, num_classes=8, seed=0,
                 checkpoint_dir=str(tmp_path), workers=2,
                 metrics_jsonl=str(tmp_path / "m.jsonl"),
                 hb_dir=str(tmp_path / "hb"), hb_interval_s=0.0,
                 mfu=True, goodput=True, watch_recompiles=True)
    tr = Trainer(cfg)
    tr.fit()
    assert tr.watchdog.anomalies == [], tr.watchdog.anomalies
    assert tr.watchdog.compiles.get("train_step") == 1
    assert tr.watchdog.compiles.get("eval_step", 0) >= 1
    recs = [r for r in read_metrics(str(tmp_path / "m.jsonl"))
            if "ft_event" not in r]
    assert len(recs) == 4  # 32 samples / batch 16, 2 epochs
    assert all(r["mfu"] > 0 and r["hfu"] >= r["mfu"] for r in recs)
    # beats carry the EMA for the slow-vs-dead monitor
    from pytorch_distributed_tpu.obs import read_heartbeats

    beats = read_heartbeats(str(tmp_path / "hb"))
    assert beats[0].get("ema", 0) > 0
