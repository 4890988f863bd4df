"""From a profiler capture (``.xplane.pb``) to the numbers the metrics read.

Written against captures of this repo's train steps on a TPU v5e (one chip
and four), read with ``jax.profiler.ProfileData``; nothing of the program
is imported.  What such a capture holds, as looked at by hand in PR 22:

- one plane ``/device:TPU:<n>`` per chip, with the lines
  ``XLA Modules`` (one event per run of a compiled program, named
  ``jit_<function>(<fingerprint>)``),
  ``XLA Ops`` (one event per HLO operation the TensorCore ran, named by
  the instruction's text: ``%fusion.51 = (f32[256]{...}, ...) fusion(...)``),
  ``Async XLA Ops`` (copies and collectives in flight, from ``-start`` to
  ``-done``, beside whatever ``XLA Ops`` runs meanwhile), and ``Steps``;
- a plane ``/host:CPU`` with one line per thread; a ``TraceAnnotation``
  is an event of its own name on the line of the thread that entered it;
- every ``start_ns`` counts from the start of the capture, on one clock
  for host and devices.

Busy is the union of the ``XLA Ops`` intervals; idle is the rest of the
window.  The window is the harness's ``bench:window`` span, from the first
program or operation the device starts inside it: the profiler is still starting when the span
opens, and what it delays (0.1 s in one capture of three) is not the
program's idleness.  A collective
is an operation whose instruction name says so (``all-reduce``,
``all-gather``, ``reduce-scatter``, ``all-to-all``, ``collective-permute``,
with ``-start``/``-done`` and fused forms); its time is the union of those
intervals on both lines, and the exposed part is where no other operation
of ``XLA Ops`` runs.
"""

from __future__ import annotations

import gzip
import re
import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_LAYOUT = re.compile(r"\{[^{}]*\}")


# ------------------------------------------------------------------ intervals

def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(merged: Sequence[Interval]) -> float:
    return sum(e - s for s, e in merged)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What of [lo, hi] the merged intervals leave uncovered."""
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


# -------------------------------------------------------------------- reading

def op_name(text: str) -> str:
    """``%fusion.51 = (f32[256]{0:T(256)}, bf16[...]{...}) fusion(...)`` ->
    ``fusion.51 (f32[256], bf16[...])``: the instruction's name and what it
    produces, without layouts and operands."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not rest:
        return name[:120]
    rest = _LAYOUT.sub("", rest)
    depth, cut = 0, len(rest)
    for k, ch in enumerate(rest):
        if ch in "([":
            depth += 1
        elif ch in ")]":
            depth -= 1
        elif ch == " " and depth == 0:
            cut = k
            break
    return f"{name} {rest[:cut]}"[:120]


def is_collective(text: str) -> bool:
    name = text.partition(" = ")[0].lstrip("%")
    return any(c in name for c in COLLECTIVES)


def load(path: str) -> Dict[str, Any]:
    """The capture as plain lists, times in seconds from its start:
    ``{"devices": {n: {"modules", "ops", "async"}}, "host": [...]}`` with
    every event a ``(start, end, name)``, host events ``(start, end, name,
    line)``.  ``path`` may be gzipped."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    lines_of = {"XLA Modules": "modules", "XLA Ops": "ops",
                "Async XLA Ops": "async"}
    devices: Dict[int, Dict[str, list]] = {}
    host: List[Tuple[float, float, str, str]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"modules": [], "ops": [], "async": []})
            for line in plane.lines:
                key = lines_of.get(line.name)
                if key:
                    dev[key].extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    (ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9, ev.name,
                     line.name)
                    for ev in line.events)
    return {"devices": devices, "host": host}


# ------------------------------------------------------------------- reducing

def reduce(trace: Dict[str, Any], span_prefix: str = "bench:",
           window_span: str = "bench:window",
           step_program: Optional[str] = None, top: int = 10,
           window: Optional[Interval] = None) -> Dict[str, Any]:
    """The traced window's numbers; times in seconds, averaged over the
    devices where a device has its own.

    ``step_program``: the start of the step's name on ``XLA Modules``
    (``jit_global_step``); by default the program that took most time.
    ``window``: read this interval instead of the ``window_span``.

    An idle gap between two operations of one running program is booked as
    ``within_program``; one outside any program goes to the harness span
    that covers most of it (``none`` if no span does)."""
    host = trace["host"]
    if window is None:
        windows = [(s, e) for s, e, n, _ in host if n == window_span]
        if len(windows) != 1:
            raise ValueError(f"expected one {window_span!r} span in the "
                             f"capture, found {len(windows)}")
        window = windows[0]
    lo, hi = window
    if not trace["devices"]:
        raise ValueError("the capture has no /device:TPU:<n> plane")
    starts = [s for dev in trace["devices"].values()
              for s, e, _ in dev["modules"] + dev["ops"] if lo <= s < hi]
    if not starts:
        raise ValueError("no device operation inside the traced window")
    lo = min(starts)
    spans = [(s, e, n[len(span_prefix):]) for s, e, n, _ in host
             if n.startswith(span_prefix) and n != window_span]

    per_device = []
    op_seconds: Dict[str, float] = {}
    gap_seconds: Dict[str, float] = {}
    for dev_id in sorted(trace["devices"]):
        dev = trace["devices"][dev_id]
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in dev["ops"]
               if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for s, e, _ in ops])
        for s, e, n in ops:
            key = op_name(n)
            op_seconds[key] = op_seconds.get(key, 0.0) + (e - s)
        running = union(clip([(s, e) for s, e, _ in dev["modules"]], lo, hi))
        idle = gaps(busy, lo, hi)
        within = measure(intersect(idle, running))
        if within:
            gap_seconds["within_program"] = gap_seconds.get(
                "within_program", 0.0) + within
        for s, e in gaps(union(busy + running), lo, hi):
            best, best_overlap = "none", 0.0
            for ss, se, name in spans:
                overlap = min(e, se) - max(s, ss)
                if overlap > best_overlap:
                    best, best_overlap = name, overlap
            gap_seconds[best] = gap_seconds.get(best, 0.0) + (e - s)

        totals: Dict[str, float] = {}
        for s, e, n in dev["modules"]:
            if s >= lo and e <= hi:
                base = n.split("(")[0]
                totals[base] = totals.get(base, 0.0) + (e - s)
        program = step_program or (max(totals, key=totals.get)
                                   if totals else None)
        steps = [e - s for s, e, n in dev["modules"]
                 if program and n.startswith(program) and s >= lo and e <= hi]

        coll = union(
            [(s, e) for s, e, n in ops if is_collective(n)]
            + clip([(s, e) for s, e, n in dev["async"] if is_collective(n)],
                   lo, hi))
        compute = union([(s, e) for s, e, n in ops if not is_collective(n)])
        per_device.append({
            "device": dev_id,
            "busy_s": measure(busy),
            "step_program": program,
            "steps": len(steps),
            "step_s": statistics.median(steps) if steps else None,
            "steps_total_s": sum(steps),
            "collective_s": measure(coll),
            "collective_exposed_s": measure(coll) - measure(
                intersect(coll, compute)),
        })

    n_dev = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device) / n_dev  # noqa: E731
    stepped = [d for d in per_device if d["steps"]]
    out: Dict[str, Any] = {
        "window_s": hi - lo,
        "busy_s": mean("busy_s"),
        "devices": n_dev,
        "step_program": stepped[0]["step_program"] if stepped else None,
        "steps": (sum(d["steps"] for d in stepped) / len(stepped)
                  if stepped else 0),
        "step_s": (sum(d["step_s"] for d in stepped) / len(stepped)
                   if stepped else None),
        "steps_total_s": mean("steps_total_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "device_ops": sorted(
            ([k, v / n_dev] for k, v in op_seconds.items()),
            key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(
            ([k, v / n_dev] for k, v in gap_seconds.items()),
            key=lambda kv: -kv[1])[:top],
        "per_device": per_device,
    }
    return out
