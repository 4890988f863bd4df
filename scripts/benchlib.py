"""Shared timing + event helpers for the benchmark/profiling scripts.

Sync discipline: dispatch is asynchronous, so every timed window opens and
closes on ``jax.block_until_ready`` of the call's outputs — the barrier of
the TPU runtime.
"""

import json
import os
import time


def timed_tree(fn, *args, iters=5, warmup=2):
    """Mean seconds/call of ``fn(*args)``, whose output may be any pytree
    of device arrays (a scalar, a grad tree, an optimizer update)."""
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


timed_scalar = timed_tree  # the probes' name for a scalar-valued ``fn``


def timed_step_loop(step, state, batch, lr, iters=20, warmup=3):
    """Warmup + timed loop over a stateful train step
    ``state, met = step(state, batch, lr)``.  Threads the state (donated
    steps consume it), so returns ``(mean_seconds, final_state)``."""
    import jax

    for _ in range(warmup):
        state, met = step(state, batch, lr)
    jax.block_until_ready((state, met))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, met = step(state, batch, lr)
    jax.block_until_ready((state, met))
    return (time.perf_counter() - t0) / iters, state


def bench_event(kind, path=None, **fields):
    """Append one structured ``bench_event`` record to a JSONL file in the
    metrics-stream schema (``{"bench_event": kind, "t": ..., ...}``) —
    ``scripts/obs_report.py`` folds it into the run summary alongside step
    and ft_event records, and ``bench_staleness`` ages the newest
    ``captured`` one.  ``path`` defaults to ``$BENCH_EVENTS_JSONL`` or the
    git-ignored ``bench_events.jsonl`` next to this repo's ``bench.py``."""
    if path is None:
        path = _default_events_path()
    rec = {"bench_event": str(kind), "t": time.time()}
    rec.update(fields)
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    return rec


def _default_events_path():
    return os.environ.get("BENCH_EVENTS_JSONL") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_events.jsonl")


def parse_lkg_time(stamp):
    """``captured_at`` (``%Y-%m-%dT%H:%M:%S%z``) -> epoch seconds, or None
    on anything unparseable."""
    from datetime import datetime

    try:
        return datetime.strptime(str(stamp), "%Y-%m-%dT%H:%M:%S%z").timestamp()
    except (TypeError, ValueError):
        return None


def bench_staleness(lkg_path=None, events_path=None, now=None):
    """Days since the benchmark's last capture.

    Every successful ``bench.py`` run appends a ``captured`` event to
    ``bench_events.jsonl``; the newest one is the last-good mark.
    ``lkg_path`` names an optional JSON record with a ``captured_at``
    stamp, read the same way; ``stale``/``failed`` events are only
    counted.  Both files are optional: a missing events log is the common
    case on a fresh checkout, and with no parseable timestamp anywhere the
    answer is ``None`` rather than a guess.  Returns ``{"metric", "last_good",
    "days_stale", "stale_events"}``, plus the planner-drift fields
    bench.py stamps on a capture (``predicted_mfu``/``measured_mfu``/
    ``prediction_drift_pct`` — plan/planner.py ``predicted_mfu`` vs the
    measured step) when the freshest capture carries them."""
    if lkg_path is None:
        lkg_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_LKG.json")
    if events_path is None:
        events_path = _default_events_path()
    metric, last_good_t, last_good = None, None, None
    drift = {}

    def _drift_fields(rec):
        return {k: rec[k] for k in ("predicted_mfu", "measured_mfu",
                                    "prediction_drift_pct")
                if rec.get(k) is not None}

    try:
        with open(lkg_path) as f:
            lkg = json.load(f)
        metric = lkg.get("metric")
        last_good = lkg.get("captured_at")
        last_good_t = parse_lkg_time(last_good)
        drift = _drift_fields(lkg)
    except (OSError, ValueError):
        pass
    stale_events = 0
    try:
        with open(events_path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(rec, dict) or "bench_event" not in rec:
                    continue
                kind = str(rec["bench_event"])
                if kind in ("stale", "failed"):
                    stale_events += 1
                elif kind == "captured" and rec.get("t") is not None:
                    t = float(rec["t"])
                    if last_good_t is None or t > last_good_t:
                        last_good_t, last_good = t, rec.get("captured_at")
                        drift = _drift_fields(rec)
                    metric = rec.get("metric", metric)
    except OSError:
        pass
    if last_good_t is None:
        return None
    if now is None:
        now = time.time()
    out = {
        "metric": metric,
        "last_good": last_good,
        "days_stale": max(0.0, (now - last_good_t) / 86400.0),
        "stale_events": stale_events,
    }
    out.update(drift)
    return out
