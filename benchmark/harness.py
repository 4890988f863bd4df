"""What every runner shares: spans, the compile log, state from the seed,
the comparison with the plain reference, device memory, the traced window.

The program is touched only through what a user of it touches
(``models.create_model``, ``train/steps.py``, ``Trainer``); everything that
measures lives here, under ``benchmark/``, where later PRs cannot change it.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(HERE, "cache")  # git-ignored: JPEG set, traces, logs
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
REFERENCE_SAMPLE = 16  # images in the comparison with the plain reference


def load_module(path: str):
    """A Python file found by name (``runners/x.py``, ``reference/y.py``)."""
    if not os.path.exists(path):
        raise FileNotFoundError(f"{os.path.relpath(path, ROOT)} is missing")
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def say(what: str, **fields) -> None:
    """An earlier line of the output: a tag and one JSON object."""
    print(f"[bench] {what} {json.dumps(fields, default=float)}", flush=True)


# ------------------------------------------------------------------- the cell

@dataclasses.dataclass
class Cell:
    """One run of one workload, as the runner receives it."""

    name: str
    config: Dict[str, Any]      # benchmark/configs/<config>.json
    traffic: Dict[str, Any]     # benchmark/traffic/<traffic>.json
    chips: int
    seed: int
    seconds: float
    trace: bool
    devices: list               # the first `chips` devices JAX reports
    spans: "Spans"
    compiles: "CompileLog"


@dataclasses.dataclass
class Run:
    """What a runner measured; ``run.py`` and the metric readers read it."""

    items: int                  # items completed inside the window
    window_start: float         # perf_counter at the drain before the window
    window_end: float           # perf_counter at the last block
    attempted: int              # steps dispatched inside the window
    failed: int                 # steps that raised or gave a non-finite loss
    checks: Dict[str, bool]     # every one must hold for `correct`
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace_file: Optional[str] = None     # the .xplane.pb of the traced part
    compiler_bytes: Optional[float] = None   # per step, the compiler's count
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start


# ---------------------------------------------------------------------- spans

class Spans:
    """The harness's own spans, kept in memory: (name, start, end) on
    ``time.perf_counter``.  Each is also a ``TraceAnnotation`` named
    ``bench:<name>``, so that in a traced run it sits on the trace's clock
    beside the device's operations."""

    def __init__(self):
        self.records: List[Tuple[str, float, float]] = []

    def __call__(self, name: str):
        return _Span(self, name)

    def total(self, name: str, t0: float, t1: float) -> float:
        """Seconds of ``name`` spans that fall inside [t0, t1]."""
        return sum(max(0.0, min(e, t1) - max(s, t0))
                   for n, s, e in self.records if n == name)

    def durations(self, name: str, t0: float, t1: float) -> List[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= t0 and e <= t1]


class _Span:
    def __init__(self, spans: Spans, name: str):
        import jax

        self._spans, self._name = spans, name
        self._note = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def __enter__(self):
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._note.__exit__(*exc)
        self._spans.records.append((self._name, self._t0, t1))
        return False


# ---------------------------------------------------------------- compilation

class CompileLog:
    """JAX's own compile events, each with the time it was reported.

    A backend-compile event is reported for a real compilation and for a
    load from the persistent cache alike (its duration is then the load);
    a cache miss is what a real compilation adds."""

    def __init__(self):
        self.backend: List[Tuple[float, float]] = []   # (when, seconds)
        self.misses: List[float] = []
        self.hits: List[float] = []

    def install(self) -> "CompileLog":
        import jax.monitoring

        def on_duration(event: str, duration_secs: float, **_kw) -> None:
            if event == BACKEND_COMPILE_EVENT:
                self.backend.append((time.perf_counter(), duration_secs))

        def on_event(event: str, **_kw) -> None:
            if event == CACHE_MISS_EVENT:
                self.misses.append(time.perf_counter())
            elif event == CACHE_HIT_EVENT:
                self.hits.append(time.perf_counter())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def seconds(self) -> float:
        return sum(d for _, d in self.backend)

    def inside(self, t0: float, t1: float) -> int:
        """Programs compiled or loaded between t0 and t1."""
        return sum(1 for when, _ in self.backend if t0 <= when <= t1)


# ------------------------------------------------------------ the system side

def build_model(cfg: Dict[str, Any]):
    """The configuration's model, from the program's own registry."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu import models

    dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[cfg["precision"]]
    return models.create_model(cfg["arch"], num_classes=cfg["num_classes"],
                               dtype=dtype, **cfg.get("model_kwargs", {}))


def make_state(model, cfg: Dict[str, Any], mesh, seed: int):
    """The train state, made on the device from the seed in one jitted
    call and committed to the layout the step keeps it in."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import state_shardings

    size, chans = cfg["image_size"], cfg["num_channels"]

    def init_state(seed):
        variables = model.init(jax.random.PRNGKey(seed),
                               jnp.zeros((1, size, size, chans), jnp.float32),
                               train=False)
        return TrainState.create(variables, sgd_init(variables["params"]))

    return jax.jit(init_state, out_shardings=state_shardings(mesh))(
        jnp.uint32(seed))


def reference_check(model, cfg: Dict[str, Any], params, batch_stats,
                    seed: int) -> Dict[str, Any]:
    """The program under its precision policy against the plain float32
    reference, on ``REFERENCE_SAMPLE`` seeded images with the weights the
    run starts from: one jitted program, cached like the step.

    The reference file owns the tolerance and the reason for it
    (``reference/<name>.py: TOLERANCE``); a reference may also say with
    which weights to compare (``check_params``), where the start weights
    would switch part of the model off."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops import cross_entropy

    ref = load_module(os.path.join(HERE, "reference",
                                   cfg["reference"] + ".py"))
    size, chans = cfg["image_size"], cfg["num_channels"]

    def check(params, batch_stats, seed):
        k_img, k_lab = jax.random.split(jax.random.PRNGKey(seed))
        images = jax.random.normal(
            k_img, (REFERENCE_SAMPLE, size, size, chans), jnp.float32)
        labels = jax.random.randint(
            k_lab, (REFERENCE_SAMPLE,), 0, cfg["num_classes"], jnp.int32)
        if hasattr(ref, "check_params"):
            params = ref.check_params(params)
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logits = logits.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = ref.forward(cfg, params, images)
            want_loss = ref.loss(want, labels)
        return {"logits_err": jnp.max(jnp.abs(logits - want)),
                "logits_max": jnp.max(jnp.abs(want)),
                "loss": cross_entropy(logits, labels),
                "ref_loss": want_loss}

    # + 1: not the images of the resident batch
    out = {k: float(v) for k, v in jax.jit(check)(
        params, batch_stats, jnp.uint32(seed + 1)).items()}
    tol = ref.TOLERANCE
    out["logits_rel"] = out["logits_err"] / out["logits_max"]
    out["loss_abs"] = abs(out["loss"] - out["ref_loss"])
    out["tolerance"] = tol
    out["ok"] = bool(out["logits_rel"] <= tol["logits_rel"]
                     and out["loss_abs"] <= tol["loss_abs"])
    return out


def placed_everywhere(tree, devices: list) -> bool:
    """Every array of ``tree`` has an addressable shard on each device."""
    import jax

    want = {d.id for d in devices}
    return all({s.device.id for s in leaf.addressable_shards} == want
               for leaf in jax.tree_util.tree_leaves(tree))


def replicas_identical(tree) -> bool:
    """Every replicated array of ``tree`` holds the same bytes on each of
    its devices (exact: the shards are fetched and compared)."""
    import jax
    import numpy as np

    for leaf in jax.tree_util.tree_leaves(tree):
        shards = leaf.addressable_shards
        first = np.asarray(shards[0].data).tobytes()
        if any(np.asarray(s.data).tobytes() != first for s in shards[1:]):
            return False
    return True


# --------------------------------------------------------------------- device

def device_report(devices: list) -> Dict[str, Any]:
    """The ``device`` object of the result line, as JAX reports it.

    ``memory_peak_bytes``: the fullest chip's ``peak_bytes_in_use`` (live
    buffers: state, batches) plus ``peak_bytes_reserved`` (what the running
    program took for its temporaries).  libtpu books the two apart: for
    ResNet-50 at 256 the runtime shows 0.46 GB in use and 9.04 GB reserved,
    and the compiler's own analysis 0.36 GB of arguments and 9.07 GB of
    temporaries (my chip run, PR 22), so only the sum says what a step
    needs.  A backend that reports no memory gives ``None``."""
    import jax

    every = jax.devices()
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return {"platform": every[0].platform, "kind": every[0].device_kind,
            "count": len(every),
            "memory_peak_bytes": max(peaks) if peaks else None}


# ------------------------------------------------------------------- tracing

class TraceWindow:
    """A few seconds of profiler trace in a traced run, taken right after
    the measured window, with the run going on as before: starting and
    stopping the profiler takes seconds and recording slows the host, and
    neither may reach the window's own numbers.

    The runner calls ``start`` at the step boundary that ends the window,
    keeps stepping while ``open()``, then calls ``stop``; the harness's
    ``bench:window`` span marks the part the reduction reads.  The Python
    tracer is off (it hooks every call in every thread, and the loader's
    threads are Python) and the host tracer is at level 1, which keeps a
    ``TraceAnnotation``.  PJRT's host-side re-layout of a batch is recorded
    even so, one 'Transpose' event per row: 3.3 million events in three
    seconds of the fed cell, whose loader then runs at a fifth of its rate
    (my chip run, PR 22), so that cell's device trace overstates idleness."""

    def __init__(self, cell: Cell, seconds: float = 3.0):
        self.dir = os.path.join(CACHE, "traces", cell.name)
        self.seconds = seconds
        self.file: Optional[str] = None
        self._cell = cell
        self._span = None
        self.started_at: Optional[float] = None

    @property
    def running(self) -> bool:
        return self._span is not None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._span = self._cell.spans("window")
        self._span.__enter__()
        self.started_at = time.perf_counter()

    def open(self) -> bool:
        """Started, and not yet ``seconds`` long."""
        return (self.running
                and time.perf_counter() - self.started_at < self.seconds)

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {self.dir}, "
                               f"found {files}")
        self.file = files[0]
