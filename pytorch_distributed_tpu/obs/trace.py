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

``compiled_scopes(program)`` is the other end of ``scope()``: the names go
into the HLO's ``op_name`` metadata, the optimized module keeps them, and a
capture's ``XLA Ops`` event is named by that module's instruction
(``%fusion.2066 = ...``), so the two join on the instruction's name.  A
step builder keeps a ``StepProgram`` (its jitted step, and the shapes the
step's body noted while it was traced); ``compiled_scopes`` compiles the
step from those shapes once more (the persistent cache answers) and turns
the text into ``{instruction: ScopeOf(scopes, phase)}`` with
``analysis/hlo.py``'s parsers.  ``benchmark/scope_times.py`` joins that to
a capture; ``Trainer.fit`` and ``LMTrainer.fit`` write it as
``scopes.json`` beside ``spans.jsonl``.

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
import os
import re
import threading
import time
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Set, Tuple

import jax

from pytorch_distributed_tpu.analysis import hlo
from pytorch_distributed_tpu.obs.comms import phase_of_op_name

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


SCOPE_NAMES: Set[str] = set()  # every name a scope() was entered under


@contextlib.contextmanager
def scope(name: str):
    """Host TraceAnnotation + in-graph named_scope under one name, which
    is noted in ``SCOPE_NAMES``: ``compiled_scopes`` looks for these names
    in an instruction's ``op_name`` path."""
    SCOPE_NAMES.add(name)
    with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
        yield


# ------------------------------------------------ the compiled step's scopes

class ScopeOf(NamedTuple):
    """Where one instruction of a compiled step belongs."""

    scopes: Tuple[str, ...]  # the scope() names in its op_name, outermost first
    phase: str               # forward | backward | recompute | optimizer |
    #                          unknown (or a pipeline tick's own name)


_PATH_SPLIT = re.compile(r"[/()]")  # jvp(lm_head), .../checkpoint/blockB/mul
_LOC_NAME = re.compile(r'loc\("([^"]*)"')  # a lowering's locations


def _names_in(paths: Iterable[str], names: Set[str]) -> Set[str]:
    found: Set[str] = set()
    for path in set(paths):
        found.update(p for p in _PATH_SPLIT.split(path) if p in names)
    return found


def scope_of_op_name(op_name: str, names: Set[str]) -> ScopeOf:
    """``jit(step)/transpose(jvp(lm_forward))/DecoderLM/.../attn/mul`` ->
    ``(("lm_forward", "attn"), "backward")``.  ``rematted_computation`` in
    the path is the forward pass run again inside the backward one (its
    path holds ``transpose(`` too, so it is looked for first); ``grad_clip``
    and ``grad_sync`` count with the optimizer."""
    scopes = tuple(p for p in _PATH_SPLIT.split(op_name) if p in names)
    if "rematted_computation" in op_name:
        return ScopeOf(scopes, "recompute")
    phase = phase_of_op_name(op_name)
    return ScopeOf(scopes, "optimizer" if phase in ("grad_clip", "grad_sync")
                   else phase)


def scope_map(hlo_text: str, names: Set[str]) -> Dict[str, ScopeOf]:
    """Every instruction the chip executes by itself (those inside fused
    computations have no event in a capture and are left out) with the
    scopes and the phase its ``op_name`` gives.

    An instruction whose ``op_name`` holds none of ``names`` (or that has
    none) takes scopes and phase from the instruction that calls its
    computation (a ``while``'s body and condition, a ``call``, a
    conditional's branches), transitively.  The phase comes with the scopes
    because the compiler's own names would read as forward: the TPU
    compiler renames a ``ragged_dot`` Mosaic call's ``op_name`` to
    ``ragged-dot-none``, in the backward loops too.  Pure text, no jax."""
    instrs = hlo.parse_instructions(hlo_text)
    called_by: Dict[str, hlo.Instruction] = {}
    for ins in instrs:
        for _, computation in hlo.called_computations(ins):
            called_by.setdefault(computation, ins)

    alone: Dict[str, bool] = {}  # computation -> run instruction by instruction

    def runs_alone(computation: str) -> bool:
        if computation not in alone:
            caller = called_by.get(computation)
            alone[computation] = caller is None or (
                caller.opcode != "fusion" and runs_alone(caller.computation))
        return alone[computation]

    own = {ins.name: (ins, scope_of_op_name(
               hlo.parse_op_metadata(ins.line)[0], names))
           for ins in instrs if runs_alone(ins.computation)}
    out: Dict[str, ScopeOf] = {}

    def resolve(name: str) -> ScopeOf:
        if name not in out:
            ins, mine = own[name]
            caller = called_by.get(ins.computation)
            out[name] = (resolve(caller.name)
                         if not mine.scopes and caller is not None else mine)
        return out[name]

    for name in own:
        resolve(name)
    return out


class StepProgram:
    """What a step builder keeps so that its compiled step can name its own
    instructions: the jitted step, under the name of its module
    (``jit_global_step``, ``jit_step``), and the shapes and dtypes of its
    arguments, which the step function's body notes while jit traces it.
    Nothing is wrapped and nothing runs per call."""

    def __init__(self, name: str):
        self.jitted = None        # set by the builder, once jitted
        self.in_shardings = None  # as the builder gave them to jax.jit
        self.args = None          # ShapeDtypeStructs, once traced
        self.scopes: Optional[Dict[str, ScopeOf]] = None
        self.recompiled = False   # the cached executable had other names
        STEP_PROGRAMS[name] = self  # a process's latest step of this name

    def note(self, *args) -> None:
        """Called from the step function's body: the arguments are
        tracers, which have a shape and a dtype.  The jit's
        ``in_shardings`` go with them: lowered from bare shapes the module
        numbers its private functions otherwise, the compile cache misses,
        and the new executable's instructions need not be numbered like
        those of the one that ran (PERF.md 6, PR 34)."""
        shardings = (jax.tree.broadcast(self.in_shardings, args)
                     if self.in_shardings is not None
                     else jax.tree.map(lambda _: None, args))
        self.args = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=s, weak_type=x.weak_type),
            args, shardings)

    def jit(self, fn, **kwargs):
        self.in_shardings = kwargs.get("in_shardings")
        self.jitted = jax.jit(fn, **kwargs)
        return self.jitted


STEP_PROGRAMS: Dict[str, StepProgram] = {}


@contextlib.contextmanager
def _no_persistent_cache():
    """One compile that neither reads nor writes the persistent cache (the
    cache latches its on/off at first use: reset on both sides)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def compiled_scopes(program: str) -> Dict[str, ScopeOf]:
    """``{instruction: ScopeOf}`` of this process's step ``program`` (an
    ``XLA Modules`` name without its fingerprint: ``jit_step``).  On first
    use the step is lowered from the shapes and shardings its body noted
    and compiled, which the compile cache answers with the executable that
    ran (the same module: the same key), and the text is kept.

    The map has to be of *this process's* names, and the persistent cache
    does not promise that: JAX leaves metadata out of the cache key, so a
    program that differs from a cached one only in its scope names is
    served the cached executable, whose text carries the old names.  So
    every scope name in this process's lowering has to occur in the
    compiled text; where one does not, the step is compiled once more with
    the persistent cache out of the way (same input, same compiler: the
    same instruction names) and ``StepProgram.recompiled`` says so.  Names
    are looked for in the whole text, fused instructions included: a scope
    whose operations were all fused under another scope's root is in the
    text and rightly not in the map.

    ``LookupError``: no step of that name was built, or it never ran."""
    step = STEP_PROGRAMS.get(program)
    if step is None or step.args is None or step.jitted is None:
        raise LookupError(f"no traced step program named {program!r} in this "
                          f"process (built: {sorted(STEP_PROGRAMS)})")
    if step.scopes is None:
        names = set(SCOPE_NAMES)
        lowered = step.jitted.lower(*step.args)
        traced = _names_in(
            _LOC_NAME.findall(lowered.as_text(debug_info=True)), names)
        text = lowered.compile().as_text()
        compiled = _names_in(
            (hlo.parse_op_metadata(ins.line)[0]
             for ins in hlo.parse_instructions(text)), names)
        if not traced <= compiled:
            # the process memoises a lowering's executable: dropped first
            jax.clear_caches()
            with _no_persistent_cache():
                text = step.jitted.lower(*step.args).compile().as_text()
            step.recompiled = True
        step.scopes = scope_map(text, names)
    return step.scopes


def dump_scopes(step, path: str) -> int:
    """Write the scope map of a builder's jitted ``step`` (found under its
    module's name, ``jit_<function>``) as one JSON object, ``{instruction:
    {"scopes": [...], "phase": ...}}``; returns the count (0 and no file
    where the step never ran or is no builder's)."""
    try:
        scopes = compiled_scopes("jit_" + getattr(step, "__name__", ""))
    except LookupError:
        return 0
    with open(path, "w") as f:
        json.dump({name: s._asdict() for name, s in scopes.items()}, f)
    return len(scopes)


def dump_beside_capture(profile_dir: str, step) -> None:
    """What a trainer leaves in its ``--profile-dir`` beside the capture:
    ``spans.jsonl``, the run loop's, the feeder's and the loader's host
    spans, and ``scopes.json``, which scope and phase each instruction of
    the compiled ``step`` belongs to (a capture names its device events by
    the instruction)."""
    os.makedirs(profile_dir, exist_ok=True)
    RECORDER.dump(os.path.join(profile_dir, "spans.jsonl"))
    dump_scopes(step, os.path.join(profile_dir, "scopes.json"))


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
