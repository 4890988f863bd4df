"""The program's own spans (``pytorch_distributed_tpu/obs/trace.py``), as
the per-layer metrics read them.

The program keeps every closed ``span()`` in a process-wide ring on
``time.perf_counter``, the clock ``Run.window_start``/``window_end`` are
on, so a reader clips the records to the measured window directly.  A
record is ``(serial, name, start, end, thread, id, parent, fields)``;
``parent`` is the serial of the span that was open on the same thread, which
is what makes a span's self time computable.

A program that has no recorder (the commit before the spans) gives every
function here nothing to read: ``window_records`` returns ``[]`` and each
reduction ``None``, and the metric is left out of the line.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Sequence, Tuple

# the run loop's thread, by what only it records: the leaves of `step`
LOOP_LEAVES = ("data_wait", "dispatch", "host_sync")
LOOP_PREFIX = "loop:"  # the leaves' names among a capture's host events


def recorder():
    """The program's ``RECORDER``, or ``None`` where it has none."""
    try:
        from pytorch_distributed_tpu.obs.trace import RECORDER
    except ImportError:
        return None
    return RECORDER


def window_records(view) -> list:
    """The records that overlap the run's measured window."""
    rec = recorder()
    if rec is None:
        return []
    return rec.records(view.run.window_start, view.run.window_end)


def _clipped(r, t0: float, t1: float) -> float:
    return max(0.0, min(r.end, t1) - max(r.start, t0))


def seconds(records: Sequence, name: str, t0: float, t1: float
            ) -> Optional[float]:
    """Seconds of ``name`` spans inside [t0, t1]; ``None`` if there is no
    such span."""
    found = [r for r in records if r.name == name]
    if not found:
        return None
    return sum(_clipped(r, t0, t1) for r in found)


def self_seconds(records: Sequence, name: str, t0: float, t1: float
                 ) -> Optional[float]:
    """Seconds inside [t0, t1] that ``name`` spans spent outside every span
    entered under them: each one's time less its children's."""
    found = {r.serial: r for r in records if r.name == name}
    if not found:
        return None
    total = sum(_clipped(r, t0, t1) for r in found.values())
    return total - sum(_clipped(r, t0, t1) for r in records
                       if r.parent in found)


def durations(records: Sequence, name: str, t0: float, t1: float
              ) -> List[float]:
    """Durations of the ``name`` spans that lie wholly inside [t0, t1]."""
    return [r.end - r.start for r in records
            if r.name == name and r.start >= t0 and r.end <= t1]


def share_pct(view, name: str, self_time: bool = False) -> Optional[float]:
    """``name``'s seconds (or self seconds) as a share of the window."""
    run = view.run
    total = (self_seconds if self_time else seconds)(
        window_records(view), name, run.window_start, run.window_end)
    return None if total is None else 100.0 * total / run.window_s


def median_ms(view, name: str) -> Optional[float]:
    run = view.run
    spent = durations(window_records(view), name, run.window_start,
                      run.window_end)
    return 1e3 * statistics.median(spent) if spent else None


def field_sums(records: Sequence, name: str, t0: float, t1: float,
               fields: Sequence[str]) -> Optional[Dict[str, float]]:
    """Sums of the counts that the ``name`` spans wholly inside [t0, t1]
    carry; ``None`` if none carries them all."""
    found = [r.fields for r in records
             if r.name == name and r.start >= t0 and r.end <= t1
             and all(f in r.fields for f in fields)]
    if not found:
        return None
    return {f: sum(fs[f] for fs in found) for f in fields}


# ------------------------------------------------- on a capture's clock

def capture_offset(host_events: Sequence[Tuple], window_span: str,
                   window_record_start: float) -> Optional[float]:
    """What to add to a ``perf_counter`` time to get the capture's: the
    start of the ``window_span`` event in the capture less the start of the
    harness's record of the same span."""
    starts = [s for s, _e, n, _line in host_events if n == window_span]
    if len(starts) != 1:
        return None
    return starts[0] - window_record_start


def loop_events(records: Sequence, offset: float) -> List[Tuple]:
    """The run loop's leaf records as host events of a loaded capture,
    named ``loop:<name>``.  The producer thread's spans stay out on
    purpose, and so do ``step`` and ``produce``, which cover every instant:
    the question is what the loop's thread was doing while the chip
    waited."""
    return [(r.start + offset, r.end + offset, LOOP_PREFIX + r.name,
             "program")
            for r in records if r.name in LOOP_LEAVES]


def unattributed_share(idle_gaps: Sequence[Sequence[Any]]
                       ) -> Optional[float]:
    """Of the idle seconds outside programs in a ``reduce()`` table, the
    share under ``none``; ``None`` where there are no such seconds."""
    outside = {name: s for name, s in idle_gaps if name != "within_program"}
    total = sum(outside.values())
    return outside.get("none", 0.0) / total if total > 0 else None
