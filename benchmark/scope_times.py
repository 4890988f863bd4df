"""The traced window's device time by phase and by scope of the program.

A capture's ``XLA Ops`` event is named by the instruction of the step's
optimized module that ran (``%fusion.2066 = ...``), and the program can say
which of its ``scope()`` names and which phase (forward, backward,
recompute, optimizer) each instruction of that module belongs to
(``obs/trace.py`` ``compiled_scopes``: from the module's own ``op_name``
metadata).  The two join on the instruction's name.

Read: the whole runs of the step program inside ``bench:window``, as
``layer_metrics/flash_attn_roofline.py`` takes them; every event's **self
time** (its duration less the events it contains on the same line: a
``while``'s event covers its body's); milliseconds a step by phase and by
innermost scope and phase, the devices' steps pooled; a scope's custom
calls (its Mosaic kernels) apart, since a block's scope holds its
projections too.  One earlier line,
``[bench] scopes {...}``, once a run however many readers ask.  A program
without ``compiled_scopes`` (a commit before PR 34), a step the program
did not register, or no device plane in the capture gives ``None``.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import harness
import trace_reduce

Event = Tuple[float, float, str]
UNSCOPED = "(none)"
_KEPT: Dict[str, Optional[Dict[str, Any]]] = {}  # trace file -> read()'s


def instruction(text: str) -> str:
    """``%fusion.51 = (f32[256]{...}) fusion(...)`` -> ``fusion.51``."""
    return text.partition(" = ")[0].strip().lstrip("%")


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """``(name, self seconds)`` of the events of one line, by start: an
    event's duration less those of the events directly inside it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, _ in events]
    open_: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while open_ and events[open_[-1]][1] <= s:
            open_.pop()
        if open_ and e <= events[open_[-1]][1]:
            own[open_[-1]] -= e - s
        open_.append(i)
    return [(events[i][2], own[i]) for i in order]


def read(view) -> Optional[Dict[str, Any]]:
    """``{"steps", "by_phase", "by_scope", "total_ms", "unmapped_ms", ...}``
    in milliseconds a step, or ``None``; kept for the run's other readers."""
    key = view.run.trace_file
    if key not in _KEPT:
        _KEPT[key] = _read(view)
    return _KEPT[key]


def _read(view) -> Optional[Dict[str, Any]]:
    run = view.run
    program = run.notes.get("step_program")
    if not run.trace_file or not program \
            or not os.path.exists(run.trace_file):
        return None
    trace = trace_reduce.load(run.trace_file)
    spans = [(s, e) for s, e, n, _ in trace["host"]
             if n == harness.WINDOW_SPAN]
    if len(spans) != 1:
        return None
    lo, hi = spans[0]
    steps, busy, timed = 0, 0.0, []
    for dev in trace["devices"].values():
        # whole runs of the step program inside the window, so that the
        # milliseconds a step are of whole steps
        whole = [(s, e) for s, e, n in dev["modules"]
                 if n.startswith(program) and lo <= s and e <= hi]
        inside = [ev for ev in dev["ops"]
                  if any(a <= ev[0] and ev[1] <= b for a, b in whole)]
        steps += len(whole)
        busy += trace_reduce.measure(
            trace_reduce.union([(s, e) for s, e, _ in inside]))
        timed.extend(self_times(inside))
    if not steps or not timed:
        return None

    from pytorch_distributed_tpu.obs import trace as program_trace

    compiled_scopes = getattr(program_trace, "compiled_scopes", None)
    if compiled_scopes is None:
        return None
    missed = len(view.cell.compiles.misses)
    t0 = time.perf_counter()
    try:
        scopes = compiled_scopes(program)
    except LookupError:
        return None
    map_s = time.perf_counter() - t0
    # the map's own compile should be answered (by the process, or by the
    # persistent cache): a miss means another module was compiled, whose
    # instructions need not be numbered like those of the one that ran
    missed = len(view.cell.compiles.misses) - missed

    per_step = 1e3 / steps
    by_phase: Dict[str, float] = {}
    by_scope: Dict[Tuple[str, str], float] = {}
    by_name: Dict[str, float] = {}  # scope, its phases together
    kernels: Dict[str, float] = {}  # scope -> its custom calls' (Mosaic)
    unnamed: Dict[str, float] = {}
    total = unmapped = 0.0
    for text, seconds in timed:
        found = scopes.get(instruction(text))
        phase = found.phase if found else "unknown"
        scope = found.scopes[-1] if found and found.scopes else UNSCOPED
        ms = seconds * per_step
        total += ms
        by_phase[phase] = by_phase.get(phase, 0.0) + ms
        by_scope[scope, phase] = by_scope.get((scope, phase), 0.0) + ms
        by_name[scope] = by_name.get(scope, 0.0) + ms
        if " custom-call(" in text:
            kernels[scope] = kernels.get(scope, 0.0) + ms
        if scope == UNSCOPED:
            unmapped += ms
            name = trace_reduce.op_name(text)
            unnamed[name] = unnamed.get(name, 0.0) + ms
    out = {"steps": steps / len(trace["devices"]), "by_phase": by_phase,
           "by_scope": by_scope, "scope_ms": by_name, "kernel_ms": kernels,
           "total_ms": total, "busy_ms": busy * per_step,
           "unmapped_ms": unmapped}
    largest = lambda d, n: sorted(d.items(), key=lambda kv: -kv[1])[:n]  # noqa: E731
    harness.say(
        "scopes", steps=out["steps"], by_phase=by_phase,
        by_scope=[[s, p, ms] for (s, p), ms in largest(by_scope, 20)],
        scope_ms=dict(largest(by_name, 20)), kernel_ms=kernels,
        total_ms=total, busy_ms=out["busy_ms"], unmapped_ms=unmapped,
        unnamed=[[n, ms] for n, ms in largest(unnamed, 5)],
        instructions=len(scopes), map_s=map_s, cache_missed=missed,
        recompiled=program_trace.STEP_PROGRAMS[program].recompiled)
    return out


def phase_ms(view, phase: str) -> Optional[float]:
    """What the ``step_<phase>_ms`` readers return."""
    found = read(view)
    return None if found is None else found["by_phase"].get(phase, 0.0)
