"""Unified observability layer (SURVEY.md §0: the reference's entire story
is three ``.item()`` calls per batch plus a 500 ms nvidia-smi CSV).

- ``metrics``   — ``MetricsLogger``: one structured JSONL record per step
  (step-time EMA/percentiles, throughput, loss/lr, in-graph grad/param
  norms), with lazy device-scalar conversion and sink registration so the
  epoch CSV and telemetry sampler hang off one entry point.
- ``trace``     — ``span()``/``RECORDER``: host spans of the run loop, the
  feeder and the loader, kept in a bounded in-memory ring and mirrored as
  ``ptd:<name>`` TraceAnnotations; ``scope()``: TraceAnnotation +
  named_scope for in-graph names; ``compiled_scopes()``: which of those
  names and which phase each instruction of a compiled step belongs to
  (what a TPU capture's events join to); ``ProfileWindow``: epoch/step-
  windowed profiler capture.
- ``heartbeat`` — per-process ``{pid, step, t, ema, last_ft}`` beats to a
  shared run directory + cross-process straggler detection that tells
  *slow* ranks from *dead* ones (stdlib-only monitor).
- ``flops``     — analytic per-step FLOPs/bytes models for the registered
  model families, cross-checkable against XLA ``cost_analysis()``, a
  per-chip peak table, and the ``MFUReporter`` that turns step seconds
  into MFU/HFU fields.
- ``goodput``   — the goodput/badput ledger over the metrics JSONL
  (nan-skips, rollback discards, preemption gaps, recompiles, stalls).
- ``watchdog``  — ``RecompileWatchdog``: jax.monitoring-hooked counter
  that flags any post-warmup recompilation of a jitted step-fn.
- ``comms``     — the static communication ledger: every collective in a
  compiled step with payload/wire bytes, replica-group fan-out, and jax
  scope attribution (``CommLedger``), emitted per run as
  ``comm_ledger.json`` and stamped into the metrics JSONL.
- ``flightrec`` — per-rank crash forensics: a bounded in-memory event ring
  (step/collective/ft/membership events, ~zero hot-path cost) dumped
  atomically to ``flightrec_rank<k>.json`` on any death path, plus the
  collective-hang watchdog daemon; ``scripts/postmortem.py`` merges the
  per-rank dumps into a cross-rank root-cause report.
- ``timeline``  — the runtime side: a pure-python XPlane decoder turning
  profiler captures into per-stream spans, per-step comm/compute/overlap
  accounting (exposed-comm), heartbeat-based cross-rank clock alignment,
  and Chrome-trace/Perfetto export (``scripts/obs_timeline.py``).
- ``export``    — the live plane, rank side: a stdlib HTTP exporter
  serving the latest drained record as Prometheus text exposition on
  ``--metrics-port`` (one daemon thread, zero hot-path syncs).
- ``alerts``    — declarative alert rules over the same stream (step-time
  / goodput / exposed-comm / memory ceilings, dead/slow rank, hang,
  recompile anomaly, bench staleness), latched per episode and booked as
  ``alert`` ft_events; ``scripts/obs_live.py`` is the fleet aggregator
  (scrape every rank + heartbeats → dashboard, exit-1-on-alert for CI).
- ``reqtrace``  — the request-scoped plane for the serving engine: a
  bounded per-request span recorder with a propagatable
  ``TraceContext``, exact TTFT/e2e critical-path attribution
  (queue wait / prefill / preempt-redo / defrag), tail-based sampling,
  and Perfetto request tracks; ``scripts/obs_trace.py`` is the
  jax-free analyzer CLI.

``scripts/obs_report.py`` folds a run's JSONL + heartbeats + telemetry CSV
into one human-readable summary (``--format json`` for machines), and
``--diff A B`` fences two runs against each other with PASS/REGRESS
verdicts — step time, throughput, MFU, goodput, exposed comm, wire bytes.
"""

from pytorch_distributed_tpu.obs.comms import (
    CommEntry,
    CommLedger,
    ledger_from_hlo_text,
    ledger_from_jitted,
    load_ledgers,
    wire_bytes,
    write_ledgers,
)
from pytorch_distributed_tpu.obs.flops import (
    CommCost,
    MFUReporter,
    StepCost,
    comm_residual_pct,
    device_peak_flops,
    image_comm_bytes,
    image_step_cost,
    lm_comm_bytes,
    lm_step_cost,
    lm_step_cost_for,
    xla_step_flops,
)
from pytorch_distributed_tpu.obs.timeline import (
    Span,
    StepComm,
    Timeline,
    aggregate_steps,
    analyze_steps,
    clock_offsets_from_heartbeats,
    marry_ledger,
    parse_xspace,
    to_chrome_trace,
)
from pytorch_distributed_tpu.obs.alerts import (
    Alert,
    AlertEngine,
    AlertRuleError,
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
from pytorch_distributed_tpu.obs.flightrec import (
    FlightRecorder,
    FlightSignalDump,
    HangWatchdog,
)
from pytorch_distributed_tpu.obs.goodput import (
    GoodputTracker,
    compute_goodput,
    summarize_goodput,
)
from pytorch_distributed_tpu.obs.heartbeat import (
    HeartbeatWriter,
    find_stragglers,
    fleet_rollup,
    read_heartbeats,
    sample_process_memory,
)
from pytorch_distributed_tpu.obs.metrics import (
    REQUIRED_FIELDS,
    MetricsLogger,
    read_metrics,
)
from pytorch_distributed_tpu.obs.trace import (
    RECORDER,
    ProfileWindow,
    capture,
    parse_span,
    scope,
    span,
)
from pytorch_distributed_tpu.obs.watchdog import RecompileWatchdog

__all__ = [
    "REQUIRED_FIELDS",
    "MetricsLogger",
    "read_metrics",
    "HeartbeatWriter",
    "read_heartbeats",
    "find_stragglers",
    "sample_process_memory",
    "scope",
    "span",
    "RECORDER",
    "capture",
    "parse_span",
    "ProfileWindow",
    "StepCost",
    "MFUReporter",
    "image_step_cost",
    "lm_step_cost",
    "lm_step_cost_for",
    "xla_step_flops",
    "device_peak_flops",
    "GoodputTracker",
    "compute_goodput",
    "summarize_goodput",
    "RecompileWatchdog",
    "FlightRecorder",
    "FlightSignalDump",
    "HangWatchdog",
    "CommEntry",
    "CommLedger",
    "ledger_from_hlo_text",
    "ledger_from_jitted",
    "load_ledgers",
    "wire_bytes",
    "write_ledgers",
    "CommCost",
    "comm_residual_pct",
    "image_comm_bytes",
    "lm_comm_bytes",
    "Span",
    "StepComm",
    "Timeline",
    "aggregate_steps",
    "analyze_steps",
    "clock_offsets_from_heartbeats",
    "marry_ledger",
    "parse_xspace",
    "to_chrome_trace",
    "fleet_rollup",
    "Alert",
    "AlertEngine",
    "AlertRuleError",
    "Rule",
    "alerts_data",
    "dead_ranks_from_events",
    "default_rules",
    "evaluate_stream",
    "load_rules",
    "summarize_alerts",
    "MetricsExporter",
    "parse_prometheus",
    "sample_value",
]
