"""Tracing: host spans kept in the process, in-graph names, profiler windows.

``span(name)`` times a piece of **host** code: one record in the
process-wide ring ``RECORDER`` on ``time.perf_counter``, and a
``TraceAnnotation`` named ``ptd:<name>`` so that inside any profiler capture
the same span lies on the capture's clock beside the device's operations.
It is always on and costs a tuple and a ``deque.append``; the run loop
(``train/trainer.py``), the feeder and the loader (``data/loader.py``) are
on it, and ``benchmark/program_spans.py`` reads it.

``scope(name)`` composes ``jax.profiler.TraceAnnotation`` (a host-side
XPlane span around whatever runs inside the ``with``) with
``jax.named_scope`` (HLO op-name metadata attached to every op *traced*
inside it).  Used around the driver's step call it marks the host
timeline; used inside a jitted function (train/steps.py forward/optimizer
phases, the pipeline schedules' per-stage tick regions) it makes XPlane
self-time attribute to named regions — ``pp_stage_fwd`` instead of
``fusion.1234`` — which is what turns ``scripts/profile_trace.py`` output
into per-stage evidence.

``ProfileWindow`` drives ``jax.profiler.start_trace``/``stop_trace`` from
epoch/step windows so a trace can capture steady state, not just the
warm-up epoch the seed hard-coded.  ``capture(dir)`` is its one-shot
contextmanager form for scripts that just want "trace this block" —
the ``scripts/profile_*.py`` family all funnel through it so there is
exactly one start/stop_trace call site outside the trainers.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax

SPAN_PREFIX = "ptd:"  # a span's name inside a profiler capture


class Span(NamedTuple):
    """One closed ``span()``; ``start``/``end`` are ``time.perf_counter``."""

    serial: int             # process-wide, in order of entry
    name: str
    start: float
    end: float
    thread: int             # threading.get_ident() of the thread it ran on
    id: Optional[int]       # the batch's ordinal since its iterator was made
    parent: Optional[int]   # serial of the span open on this thread at entry
    fields: Dict[str, Any]  # counts the span carries (``fetch``)


class SpanRecorder:
    """A bounded ring of closed spans.  Appending and snapshotting are each
    one C call under the GIL: no lock, no I/O, no device sync.

    ``enabled = False`` makes ``span()`` hand back one shared no-op (for
    tests and A/B runs; there is no flag and no environment variable)."""

    def __init__(self, maxlen: int = 32768):
        self.enabled = True
        self._ring: "collections.deque[Span]" = collections.deque(
            maxlen=maxlen)
        self._serial = itertools.count(1)
        self._open = threading.local()  # .stack: [(serial, id)] per thread

    def _stack(self) -> list:
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def records(self, t0: Optional[float] = None,
                t1: Optional[float] = None) -> List[Span]:
        """The closed spans that overlap ``[t0, t1]``, oldest first."""
        return [r for r in tuple(self._ring)
                if (t1 is None or r.start <= t1)
                and (t0 is None or r.end >= t0)]

    def clear(self) -> None:
        self._ring.clear()

    def dump(self, path: str) -> int:
        """Write every record as one JSON object a line; returns the count."""
        records = self.records()
        with open(path, "w") as f:
            for r in records:
                f.write(json.dumps(r._asdict(), default=float) + "\n")
        return len(records)


RECORDER = SpanRecorder()


class _NoSpan:
    """What ``span()`` hands back while the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **fields) -> None:
        pass


_NO_SPAN = _NoSpan()


class _OpenSpan:
    __slots__ = ("_name", "_id", "_fields", "_serial", "_parent", "_note",
                 "_t0")

    def __init__(self, name: str, id: Optional[int], fields: Dict[str, Any]):
        self._name, self._id, self._fields = name, id, fields

    def set(self, **fields) -> None:
        """Counts known only once the work is done."""
        self._fields.update(fields)

    def __enter__(self):
        stack = RECORDER._stack()
        self._parent = None
        if stack:
            self._parent, inherited = stack[-1]
            if self._id is None:
                self._id = inherited
        self._serial = next(RECORDER._serial)
        stack.append((self._serial, self._id))
        self._note = jax.profiler.TraceAnnotation(SPAN_PREFIX + self._name)
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        RECORDER._stack().pop()
        RECORDER._ring.append(Span(
            self._serial, self._name, self._t0, t1, threading.get_ident(),
            self._id, self._parent, self._fields))
        return False


def span(name: str, id: Optional[int] = None, **fields):
    """Time a piece of host code: ``with span("put"): ...``.

    ``id`` says which batch the work is for; left out, it is the enclosing
    span's.  ``parent`` comes from a per-thread stack, so a span must close
    before a generator that opened it yields."""
    if not RECORDER.enabled:
        return _NO_SPAN
    return _OpenSpan(name, id, fields)


@contextlib.contextmanager
def scope(name: str):
    """Host TraceAnnotation + in-graph named_scope under one name."""
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


@contextlib.contextmanager
def capture(trace_dir: str):
    """One-shot profiler capture: trace everything inside the ``with``.

    The single-segment form of ``ProfileWindow`` — same start/stop pairing,
    no epoch/step bookkeeping.  XPlane files land under ``trace_dir`` and
    can be decoded with ``obs.timeline.find_xplane_files``/``parse_xspace``
    or ``scripts/obs_timeline.py``.
    """
    jax.profiler.start_trace(trace_dir)
    try:
        yield trace_dir
    finally:
        jax.profiler.stop_trace()


def parse_span(spec: Optional[str]) -> Optional[Tuple[int, int]]:
    """``"5"`` → (5, 6); ``"10:20"`` → (10, 20) — python half-open ranges."""
    if spec is None or spec == "":
        return None
    parts = str(spec).split(":")
    try:
        if len(parts) == 1:
            lo = int(parts[0])
            return (lo, lo + 1)
        if len(parts) == 2:
            lo, hi = int(parts[0]), int(parts[1])
            if hi <= lo:
                raise ValueError
            return (lo, hi)
    except ValueError:
        pass
    raise ValueError(
        f"bad span {spec!r}: expected 'N' or 'LO:HI' with HI > LO")


class ProfileWindow:
    """Epoch/step-windowed profiler control.

    - no windows: trace the first trained epoch (the seed behavior);
    - ``epochs='A'`` / ``'A:B'``: trace those epochs (one trace segment per
      epoch — ``stop_trace`` runs at each epoch end);
    - ``steps='I'`` / ``'I:J'``: within an active epoch, trace only that
      in-epoch step range (steady-state capture past compilation and
      cache warm-up).
    """

    def __init__(self, profile_dir: Optional[str], epochs: Optional[str] = None,
                 steps: Optional[str] = None, start_epoch: int = 0):
        self.dir = profile_dir
        self.epochs = parse_span(epochs)
        self.steps = parse_span(steps)
        self.start_epoch = start_epoch
        self._tracing = False

    def _epoch_active(self, epoch: int) -> bool:
        if not self.dir:
            return False
        if self.epochs is None:
            return epoch == self.start_epoch
        return self.epochs[0] <= epoch < self.epochs[1]

    def epoch_begin(self, epoch: int) -> None:
        if self.steps is None and self._epoch_active(epoch):
            self._start()

    def step_begin(self, epoch: int, step: int) -> None:
        """Call at the top of every train step (cheap when inactive)."""
        if self.steps is None:
            return
        if self._epoch_active(epoch) and self.steps[0] <= step < self.steps[1]:
            self._start()
        else:
            self._stop()

    def epoch_end(self) -> bool:
        """Stop an open trace segment; True when one was written."""
        return self._stop()

    def _start(self) -> None:
        if not self._tracing:
            jax.profiler.start_trace(self.dir)
            self._tracing = True

    def _stop(self) -> bool:
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
            return True
        return False
