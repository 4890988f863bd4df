"""Live telemetry plane: alert rules + per-rank metric export (ISSUE 14).

Tier-1 (no mesh): rule parsing/validation, per-kind engine semantics on
planted record streams (fire / latch / re-arm), the heartbeat and
bench-staleness legs, the emit round-trip through a real
``MetricsLogger`` JSONL (goodput + report folding), the HTTP exporter
round-trip over a real ephemeral socket, the recipe-flag lint, and the
``obs_live --selftest`` subprocess (which also proves the aggregator
stays jax-free).  The 2-process live-fleet test at the bottom is
``slow``-marked: two real rank processes export metrics, one dies, and
``obs_live --once`` must raise the step-time and dead-rank alerts
within two aggregation cycles and book them into the shared JSONL.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
import time
import urllib.request
from datetime import datetime, timedelta, timezone

import pytest

from pytorch_distributed_tpu.obs.alerts import (
    AlertEngine,
    AlertRuleError,
    RULE_KINDS,
    Rule,
    alerts_data,
    dead_ranks_from_events,
    default_rules,
    evaluate_stream,
    load_rules,
    summarize_alerts,
)
from pytorch_distributed_tpu.obs.export import (
    MetricsExporter,
    parse_prometheus,
    sample_value,
)
from pytorch_distributed_tpu.obs.goodput import compute_goodput
from pytorch_distributed_tpu.obs.metrics import MetricsLogger, read_metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS_LIVE = os.path.join(REPO, "scripts", "obs_live.py")


def step_rec(step, st=0.010, proc=0, t=None, **extra):
    """A minimal metrics record with uniform step-time quantiles."""
    rec = {"step": step, "t": time.time() if t is None else t,
           "process": proc, "step_time": st, "step_time_ema": st,
           "step_time_p50": st, "step_time_p95": st, "step_time_max": st}
    rec.update(extra)
    return rec


# --------------------------------------------------------------- the rules --

def test_load_rules_roundtrip(tmp_path):
    p = tmp_path / "rules.json"
    p.write_text(json.dumps({"rules": [
        {"kind": "step_time_p95", "name": "st", "severity": "page",
         "max_ms": 25.0, "quantile": "p50", "warmup_steps": 3},
        {"kind": "dead_rank", "max_age_s": 30.0},
        {"kind": "bench_stale", "max_days": 7.0, "lkg_path": "/x.json"},
    ]}))
    rules = load_rules(str(p))
    assert [(r.kind, r.name, r.severity) for r in rules] == [
        ("step_time_p95", "st", "page"), ("dead_rank", "dead_rank", "warn"),
        ("bench_stale", "bench_stale", "warn")]
    assert rules[0].params == {"max_ms": 25.0, "quantile": "p50",
                               "warmup_steps": 3}
    # a bare top-level list works too
    p.write_text(json.dumps([{"kind": "hang"}]))
    assert load_rules(str(p))[0].kind == "hang"


@pytest.mark.parametrize("payload,needle", [
    ([{"kind": "nope"}], "unknown kind"),
    ([{"kind": "step_time_p95"}], "max_ms"),
    ([{"kind": "hang", "max_ms": 1}], "unknown parameter"),
    ([{"kind": "hang", "severity": "fatal"}], "severity"),
    ([{"kind": "step_time_p95", "max_ms": 1, "quantile": "p99"}],
     "quantile"),
    ([{"kind": "step_time_p95", "max_ms": "fast"}], "number"),
    ([{"kind": "bench_stale", "max_days": 1, "lkg_path": 3}], "path"),
    ([{"kind": "hang"}, {"kind": "hang"}], "duplicate"),
    (["hang"], "expected an object"),
    ({"not_rules": []}, "expected"),
])
def test_malformed_rules_raise(tmp_path, payload, needle):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(payload))
    with pytest.raises(AlertRuleError) as ei:
        load_rules(str(p))
    assert needle in str(ei.value)


def test_unreadable_rules_raise(tmp_path):
    with pytest.raises(AlertRuleError, match="cannot read"):
        load_rules(str(tmp_path / "absent.json"))
    p = tmp_path / "garbage.json"
    p.write_text("{not json")
    with pytest.raises(AlertRuleError, match="not valid JSON"):
        load_rules(str(p))


def test_default_rules_are_valid_and_named_uniquely():
    rules = default_rules()
    names = [r.name for r in rules]
    assert len(names) == len(set(names))
    for r in rules:
        assert r.kind in RULE_KINDS
        assert r.severity in ("warn", "page")
    assert {r.kind for r in rules} >= {"dead_rank", "slow_rank", "hang",
                                       "recompile", "bench_stale"}


# -------------------------------------------------------------- the engine --

def test_step_time_rule_fires_latches_and_rearms():
    eng = AlertEngine([Rule("step_time_p95", "st", "page",
                            {"max_ms": 15.0, "warmup_steps": 2})])
    assert eng.observe(step_rec(0, st=0.050)) == []  # warmup suppresses
    assert eng.observe(step_rec(2)) == []            # under ceiling
    fired = eng.observe(step_rec(3, st=0.020))
    assert len(fired) == 1
    a = fired[0]
    assert (a.name, a.severity, a.step, a.rank) == ("st", "page", 3, 0)
    assert a.value == pytest.approx(20.0)
    assert a.threshold == 15.0
    assert "20.0ms > 15ms" in a.detail
    assert eng.observe(step_rec(4, st=0.030)) == []  # latched
    assert eng.active() and eng.active()[0].name == "st"
    assert eng.observe(step_rec(5)) == []            # recovery clears
    assert not eng.active()
    assert len(eng.observe(step_rec(6, st=0.020))) == 1  # re-armed
    assert len(eng.history) == 2


def test_step_time_quantile_selects_the_field():
    eng = AlertEngine([Rule("step_time_p95", "st", "warn",
                            {"max_ms": 15.0, "quantile": "p50",
                             "warmup_steps": 0})])
    rec = step_rec(5, st=0.010)
    rec["step_time_p50"] = 0.040  # only the chosen quantile breaches
    (a,) = eng.observe(rec)
    assert a.value == pytest.approx(40.0) and "p50" in a.detail


def test_step_time_latch_is_per_rank():
    eng = AlertEngine([Rule("step_time_p95", "st", "warn",
                            {"max_ms": 15.0, "warmup_steps": 0})])
    fired = eng.observe(step_rec(3, st=0.020, proc=0))
    fired += eng.observe(step_rec(3, st=0.020, proc=1))
    assert sorted(a.rank for a in fired) == [0, 1]
    assert eng.observe(step_rec(4, st=0.020, proc=1)) == []  # latched


def test_exposed_comm_and_mem_peak_rules():
    eng = AlertEngine([
        Rule("exposed_comm", "comm", "warn", {"max_ms": 2.0}),
        Rule("mem_peak", "mem", "page", {"max_bytes": 1 << 20}),
    ])
    assert eng.observe(step_rec(1, exposed_comm_ms=1.0,
                                mem_peak_bytes=1000)) == []
    fired = eng.observe(step_rec(2, exposed_comm_ms=3.5,
                                 mem_peak_bytes=2 << 20))
    assert {a.name for a in fired} == {"comm", "mem"}
    comm = next(a for a in fired if a.name == "comm")
    assert comm.value == pytest.approx(3.5) and comm.threshold == 2.0
    mem = next(a for a in fired if a.name == "mem")
    assert "MiB" in mem.detail
    # records without the fields leave both rules inert
    assert eng.observe(step_rec(3)) == []
    assert len(eng.active()) == 2  # still latched: no recovery signal yet


def test_goodput_floor_rule_needs_min_steps_then_fires():
    eng = AlertEngine([Rule("goodput_floor", "gp", "warn",
                            {"min_pct": 50.0, "min_steps": 5})])
    t0 = 1000.0
    fired = []
    for i in range(8):  # 0.2 s productive out of each 1 s of wall time
        fired += eng.observe(step_rec(i, st=0.2, t=t0 + i))
    assert len(fired) == 1
    assert fired[0].value < 50.0 and fired[0].threshold == 50.0


def test_hang_and_recompile_event_rules():
    eng = AlertEngine([Rule("hang", "hang", "page", {}),
                       Rule("recompile", "rc", "warn", {"max_events": 1})])
    (a,) = eng.observe({"ft_event": "hang", "step": 7, "process": 0,
                        "t": 1.0, "collective": "all-reduce",
                        "elapsed_s": 12.0})
    assert a.severity == "page" and "all-reduce" in a.detail
    assert eng.observe({"ft_event": "recompile", "step": 8, "t": 2.0,
                        "process": 0}) == []  # within budget
    (b,) = eng.observe({"ft_event": "recompile", "step": 9, "t": 3.0,
                        "process": 0})
    assert b.value == 2.0 and b.threshold == 1.0


@pytest.fixture
def no_bench_history(monkeypatch, tmp_path):
    """The default ``bench_stale`` rule ages the checkout's own
    ``bench_events.jsonl``; point it at nothing, so a test of the default
    rule set does not depend on when ``bench.py`` last ran here."""
    monkeypatch.setenv("BENCH_EVENTS_JSONL", str(tmp_path / "none.jsonl"))


def test_engine_never_alerts_on_alert_events(no_bench_history):
    eng = AlertEngine(default_rules())
    assert eng.observe({"ft_event": "alert", "alert": "hang",
                        "rule": "hang", "t": 1.0, "process": 0}) == []
    assert not eng.active()


def test_dead_and_slow_rank_rules_over_heartbeats():
    now = time.time()
    beats = {
        0: {"pid": 0, "step": 20, "t": now, "ema": 0.010},
        1: {"pid": 1, "step": 20, "t": now - 120.0, "ema": 0.010},
        2: {"pid": 2, "step": 10, "t": now, "ema": 0.050},
        3: {"pid": 3, "step": 20, "t": now, "ema": 0.010},
    }
    eng = AlertEngine([
        Rule("dead_rank", "dead", "page", {"max_age_s": 60.0}),
        Rule("slow_rank", "slow", "warn",
             {"max_step_lag": 3, "slow_ema_factor": 2.0,
              "max_age_s": 60.0}),
    ])
    fired = eng.observe_heartbeats(beats, now=now)
    got = {(a.name, a.rank) for a in fired}
    assert got == {("dead", 1), ("slow", 2)}
    assert "dead or hung" in next(a for a in fired if a.name == "dead").detail
    # latched across cycles; recovery clears
    assert eng.observe_heartbeats(beats, now=now) == []
    beats[1]["t"] = now
    beats[2].update(step=20, ema=0.010)
    assert eng.observe_heartbeats(beats, now=now) == []
    assert not eng.active()


def test_bench_stale_rule(tmp_path):
    lkg = tmp_path / "BENCH_LKG.json"
    stamp = (datetime.now(timezone.utc)
             - timedelta(days=20)).strftime("%Y-%m-%dT%H:%M:%S%z")
    lkg.write_text(json.dumps({"metric": "tok/s", "value": 1.0,
                               "captured_at": stamp}))
    params = {"max_days": 14.0, "lkg_path": str(lkg),
              "events_path": str(tmp_path / "absent_events.jsonl")}
    eng = AlertEngine([Rule("bench_stale", "stale", "warn", dict(params))])
    (a,) = eng.check_bench()
    assert a.value == pytest.approx(20.0, abs=0.1) and a.threshold == 14.0
    # a fresh capture clears it
    lkg.write_text(json.dumps({"metric": "tok/s", "value": 1.0,
                               "captured_at": datetime.now(timezone.utc)
                               .strftime("%Y-%m-%dT%H:%M:%S%z")}))
    eng2 = AlertEngine([Rule("bench_stale", "stale", "warn", dict(params))])
    assert eng2.check_bench() == []


def test_evaluate_stream_one_shot(no_bench_history):
    now = time.time()
    recs = ([step_rec(i) for i in range(5)]
            + [{"ft_event": "hang", "step": 5, "t": now, "process": 0}])
    beats = {0: {"pid": 0, "step": 5, "t": now - 300.0}}
    eng = evaluate_stream(recs, default_rules(), beats=beats, now=now)
    assert {a.kind for a in eng.history} == {"hang", "dead_rank"}


# ---------------------------------------------------------- emit round-trip --

def test_emit_books_alert_ft_events_that_every_fold_sees(tmp_path):
    mpath = tmp_path / "metrics.jsonl"
    log = MetricsLogger(str(mpath), flush_every=1)
    eng = AlertEngine([Rule("step_time_p95", "st", "warn",
                            {"max_ms": 50.0, "quantile": "p50",
                             "warmup_steps": 2})],
                      emit=lambda **f: log.log_event("alert", **f))
    log.register(eng)
    for i in range(6):
        log.log_step(i, 0.2)  # p50 200 ms > 50 ms after warmup
    log.close()

    records = read_metrics(str(mpath))
    events = [r for r in records if r.get("ft_event") == "alert"]
    assert len(events) == 1, "one breach episode → one booked alert"
    e = events[0]
    assert (e["alert"], e["rule"], e["severity"]) == \
        ("st", "step_time_p95", "warn")
    assert e["value"] > e["threshold"] == 50.0
    # the goodput ledger, the report section, and the JSON fold all see it
    assert compute_goodput(records).alerts == 1
    summary = "\n".join(summarize_alerts(records))
    assert "== alerts ==" in summary and "st" in summary
    data = alerts_data(records)
    assert data["total"] == 1 and data["by_name"]["st"]["count"] == 1


def test_emit_errors_never_reach_the_training_loop():
    def bomb(**_f):
        raise RuntimeError("sink exploded")

    eng = AlertEngine([Rule("step_time_p95", "st", "warn",
                            {"max_ms": 1.0, "warmup_steps": 0})],
                      emit=bomb)
    (a,) = eng.observe(step_rec(1, st=0.5))  # fired, emit swallowed
    assert a.name == "st"
    # evaluation errors are swallowed too once an emit is wired
    eng.observe({"step_time": "not-a-number", "step": 2})


def test_dead_ranks_from_events_respects_since_t():
    evs = [
        {"ft_event": "alert", "rule": "dead_rank", "rank": 1, "t": 10.0},
        {"ft_event": "alert", "rule": "dead_rank", "rank": 1, "t": 20.0},
        {"ft_event": "alert", "rule": "dead_rank", "rank": 2, "t": 5.0},
        {"ft_event": "alert", "rule": "slow_rank", "rank": 3, "t": 30.0},
    ]
    assert dead_ranks_from_events(evs) == {1: 20.0, 2: 5.0}
    assert dead_ranks_from_events(evs, since_t=10.0) == {1: 20.0}
    assert dead_ranks_from_events(evs, since_t=25.0) == {}


# -------------------------------------------------------------- the export --

def test_exporter_http_roundtrip_on_ephemeral_port():
    eng = AlertEngine([Rule("step_time_p95", "st", "page",
                            {"max_ms": 15.0, "warmup_steps": 0})])
    eng.observe(step_rec(41, st=0.020, proc=7))
    exp = MetricsExporter(0, rank=7, engine=eng)
    exp.update(step_rec(41, st=0.020, proc=7, throughput=51200.0,
                        loss=2.5))
    exp.update({"ft_event": "rollback", "t": time.time(), "process": 7})
    exp.update({"ft_event": "alert", "t": time.time(), "process": 7,
                "alert": "st", "rule": "step_time_p95"})
    exp.start()
    try:
        assert exp.port != 0, "port 0 must resolve to the bound port"
        base = f"http://127.0.0.1:{exp.port}"
        with urllib.request.urlopen(f"{base}/metrics", timeout=2.0) as r:
            assert r.status == 200
            samples = parse_prometheus(r.read().decode())
        assert sample_value(samples, "ptd_up", rank=7) == 1.0
        assert sample_value(samples, "ptd_step", rank=7) == 41.0
        assert sample_value(samples, "ptd_step_time_seconds", rank=7,
                            stat="last") == pytest.approx(0.020)
        assert sample_value(samples, "ptd_metric", rank=7,
                            field="loss") == 2.5
        assert sample_value(samples, "ptd_metric", rank=7,
                            field="throughput") == 51200.0
        assert sample_value(samples, "ptd_ft_events_total", rank=7,
                            kind="rollback") == 1.0
        assert sample_value(samples, "ptd_alerts_total", rank=7) == 1.0
        assert sample_value(samples, "ptd_alert_firing", rank=7,
                            rule="st", severity="page") == 1.0
        with urllib.request.urlopen(f"{base}/healthz", timeout=2.0) as r:
            health = json.loads(r.read())
        assert health["ok"] is True and health["rank"] == 7
        try:
            urllib.request.urlopen(f"{base}/nope", timeout=2.0)
        except urllib.error.HTTPError as e:
            assert e.code == 404
        else:
            raise AssertionError("unknown path must 404")
    finally:
        exp.stop()
    exp.stop()  # idempotent


def test_exporter_healthz_503_before_first_record():
    exp = MetricsExporter(0, rank=0)
    exp.start()
    try:
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/healthz", timeout=2.0)
        except urllib.error.HTTPError as e:
            assert e.code == 503
        else:
            raise AssertionError("no record yet must read not-ok")
    finally:
        exp.stop()


def test_parse_prometheus_handles_quoted_labels():
    text = ('ptd_metric{rank="0",field="a,b"} 1.5\n'
            '# a comment\n'
            'ptd_up{rank="0"} 1\n'
            'garbage line without a value\n')
    samples = parse_prometheus(text)
    assert ("ptd_metric", {"rank": "0", "field": "a,b"}, 1.5) in samples
    assert sample_value(samples, "ptd_up", rank=0) == 1.0


def test_exporter_is_a_metrics_logger_sink(tmp_path):
    """Registered twice (lifecycle + per-record), the exporter serves the
    latest drained record with zero work in ``log_step`` itself."""
    log = MetricsLogger(str(tmp_path / "m.jsonl"), flush_every=1)
    exp = MetricsExporter(0, rank=0)
    log.register(exp)          # start/stop pair → started here
    log.register(exp.update)   # callable → per-record sink
    assert exp.running
    log.log_step(3, 0.01)
    samples = parse_prometheus(exp.render())
    assert sample_value(samples, "ptd_step", rank=0) == 3.0
    log.close()
    assert not exp.running, "close() must stop the owned exporter"


# ------------------------------------------------------------- the CLI leg --

def test_obs_live_selftest_subprocess():
    """The aggregator's own checks pass in a clean process — including
    its assertion that jax never gets imported."""
    proc = subprocess.run([sys.executable, OBS_LIVE, "--selftest"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "obs_live selftest: OK" in proc.stdout


# --------------------------------------------------- the live fleet (slow) --

_DRIVER = textwrap.dedent("""\
    import argparse, importlib.util, json, os, sys, time

    def load(name):
        alias = f"_ptd_obs_{name}"
        if alias in sys.modules:
            return sys.modules[alias]
        spec = importlib.util.spec_from_file_location(
            alias, os.path.join(OBS, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[alias] = mod
        spec.loader.exec_module(mod)
        return mod

    ap = argparse.ArgumentParser()
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--hb-dir", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rules", required=True)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--step-time", type=float, default=0.1)
    ap.add_argument("--die-at", type=int, default=None)
    ap.add_argument("--linger", type=float, default=30.0)
    args = ap.parse_args()

    OBS = os.environ["PTD_OBS_DIR"]
    metrics = load("metrics"); heartbeat = load("heartbeat")
    export = load("export"); alerts = load("alerts")
    assert "jax" not in sys.modules

    log = metrics.MetricsLogger(args.out, process_index=args.rank,
                                flush_every=1)
    eng = alerts.AlertEngine(alerts.load_rules(args.rules),
                             emit=lambda **f: log.log_event("alert", **f),
                             process_index=args.rank)
    eng._bench_checked = True  # no bench anchor in this fleet
    exp = export.MetricsExporter(args.port, rank=args.rank, engine=eng)
    log.register(exp); log.register(exp.update); log.register(eng)
    hb = heartbeat.HeartbeatWriter(args.hb_dir, args.rank, interval_s=0.0,
                                   world=2)
    print(f"rank {args.rank} ready on :{exp.port}", flush=True)
    for step in range(args.steps):
        time.sleep(args.step_time)
        log.log_step(step, args.step_time)
        hb.beat(step, step_time_ema=log.ema)
        if args.die_at is not None and step >= args.die_at:
            os._exit(1)  # no close(), no final beat: a real death
    log.close()
    time.sleep(args.linger)
""")


def _free_ports(n):
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


@pytest.mark.slow
def test_live_fleet_alerts_within_two_cycles(tmp_path):
    """Two real rank processes: rank 1 dies mid-run, rank 0 drags every
    step past the rule ceiling.  ``obs_live --once`` (the aggregation
    cycle) must surface both alerts within two cycles, exit 1, and book
    the dead rank into the shared JSONL that goodput/obs_report fold."""
    hb = tmp_path / "hb"
    rules = tmp_path / "rules.json"
    rules.write_text(json.dumps({"rules": [
        {"kind": "step_time_p95", "name": "step_time", "severity": "warn",
         "quantile": "p50", "max_ms": 50.0, "warmup_steps": 3},
        {"kind": "dead_rank", "severity": "page", "max_age_s": 2.0},
    ]}))
    driver = tmp_path / "driver.py"
    driver.write_text(_DRIVER)
    env = dict(os.environ, PTD_OBS_DIR=os.path.join(
        REPO, "pytorch_distributed_tpu", "obs"))
    ports = _free_ports(2)
    outs = [str(tmp_path / f"metrics-{r}.jsonl") for r in (0, 1)]
    procs = []
    try:
        for rank, die in ((0, None), (1, 6)):
            cmd = [sys.executable, str(driver), "--rank", str(rank),
                   "--port", str(ports[rank]), "--hb-dir", str(hb),
                   "--out", outs[rank], "--rules", str(rules),
                   "--steps", "60", "--step-time", "0.1"]
            if die is not None:
                cmd += ["--die-at", str(die)]
            procs.append(subprocess.Popen(cmd, env=env,
                                          stdout=subprocess.PIPE,
                                          stderr=subprocess.STDOUT,
                                          text=True))
        deadline = time.time() + 30.0
        while procs[1].poll() is None and time.time() < deadline:
            time.sleep(0.2)
        assert procs[1].poll() is not None, "rank 1 never died"
        time.sleep(2.5)  # let rank 1's last beat age past max_age_s

        booked = str(tmp_path / "aggregated.jsonl")
        cycles = 0
        for cycles in (1, 2):  # "within two aggregation cycles"
            once = subprocess.run(
                [sys.executable, OBS_LIVE, "--ports", str(ports[0]),
                 "--world", "1", "--hb-dir", str(hb), "--rules",
                 str(rules), "--alerts-jsonl", booked, "--once"],
                capture_output=True, text=True, timeout=60)
            if once.returncode == 1 and "dead_rank" in once.stdout \
                    and "step_time" in once.stdout:
                break
        else:
            raise AssertionError(
                f"alerts not firing after {cycles} cycles:\n{once.stdout}"
                f"\n{once.stderr}")

        # the aggregator booked the death rank 1 could never book itself
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)

    agg = read_metrics(booked)
    assert 1 in dead_ranks_from_events(agg), \
        "obs_live must book the dead_rank alert into the shared JSONL"

    # rank 0's own engine booked the step-time breach live
    r0 = read_metrics(outs[0])
    mine = [e for e in r0 if e.get("ft_event") == "alert"]
    assert any(e["rule"] == "step_time_p95" for e in mine), mine
    # and every fold sees the combined story
    combined = r0 + agg
    assert compute_goodput(combined).alerts >= 2
    summary = "\n".join(summarize_alerts(combined))
    assert "step_time" in summary and "dead_rank" in summary
    rep = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         "--metrics-jsonl", outs[0]],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert "== alerts ==" in rep.stdout, rep.stdout + rep.stderr
