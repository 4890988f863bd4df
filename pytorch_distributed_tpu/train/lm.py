"""Language-model pretraining harness: next-token objective over dp×tp or
dp×sp meshes — the long-context counterpart of the image harness.

Shares the framework's core pieces (SGD with torch semantics, TrainState,
meters, msgpack checkpoints) and adds:

- a deterministic synthetic token stream with *learnable* structure (affine
  next-token process) so smoke runs have a convergence oracle;
- ``make_lm_train_step``: the jitted step with parameter shardings taken
  from ``parallel/tp.py`` (replicated = DP; Megatron specs = TP) — XLA
  inserts the gradient psum over ``data`` and the two per-block activation
  all-reduces over ``model``;
- an epochless step-driven ``LMTrainer`` (LM convention), with meters and
  rank-0 checkpoints.
"""

from __future__ import annotations

import functools
from contextlib import nullcontext
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.models.transformer import bind_mesh
from pytorch_distributed_tpu.obs.trace import StepProgram, scope
from pytorch_distributed_tpu.ops import cross_entropy, qcomm
from pytorch_distributed_tpu.train.meters import StepMeters
from pytorch_distributed_tpu.train.optim import sgd_init, sgd_update
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import (
    gate_update,
    nonfinite_flag,
    tree_l2_norm,
)


class SyntheticTokenDataset:
    """Affine token process: ``x[t+1] = (a·x[t] + c) mod vocab`` with
    per-sample random (a, c, x0).  A 1-layer transformer can learn it, so
    loss visibly drops — the LM smoke oracle."""

    def __init__(self, length: int, seq_len: int, vocab: int, seed: int = 0):
        self.length = length
        self.seq_len = seq_len
        self.vocab = vocab
        self.seed = seed
        self._cache: Dict[int, np.ndarray] = {}

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, index: int) -> np.ndarray:
        # Cached: sequences are deterministic, and at long seq_len the
        # per-token recurrence is real host work that must not sit in the
        # training hot loop more than once per sample.
        cached = self._cache.get(index)
        if cached is not None:
            return cached
        rng = np.random.default_rng((self.seed, index))
        a = int(rng.integers(1, 8))
        c = int(rng.integers(0, self.vocab))
        x = np.empty(self.seq_len, np.int32)
        x[0] = int(rng.integers(0, self.vocab))
        for t in range(1, self.seq_len):
            x[t] = (a * x[t - 1] + c) % self.vocab
        self._cache[index] = x
        return x

    def batch(self, step: int, batch_size: int) -> np.ndarray:
        return _wraparound_batch(self, step, batch_size)


def _wraparound_batch(ds, step: int, batch_size: int,
                      rows: Optional[Tuple[int, int]] = None) -> np.ndarray:
    """Sequential wrap-around batching shared by the LM datasets.
    ``rows=(lo, hi)``: assemble only that row range of the logical global
    batch (multi-process: each host builds just its own shard)."""
    base = (step * batch_size) % max(1, len(ds))
    lo, hi = rows if rows is not None else (0, batch_size)
    return np.stack([ds[(base + i) % len(ds)] for i in range(lo, hi)])


class TextFileDataset:
    """Byte-level LM dataset over real files — vocab 256, sequences are
    strided windows of the concatenated bytes.  The real-data counterpart
    of ``SyntheticTokenDataset`` (zero tokenizer dependencies: bytes ARE the
    tokens, the GPT-style fallback that works on any corpus)."""

    vocab = 256

    def __init__(self, paths, seq_len: int, stride: Optional[int] = None,
                 span=(0.0, 1.0)):
        """``span``: (start, end) fractions of the corpus — carve held-out
        eval windows from the tail, e.g. train (0, .9) / eval (.9, 1)."""
        import glob as _glob

        if isinstance(paths, (str, bytes)):
            paths = sorted(_glob.glob(paths, recursive=True))
        blobs = []
        for p in paths:
            with open(p, "rb") as f:
                blobs.append(f.read())
        data = np.frombuffer(b"\n".join(blobs), dtype=np.uint8)
        # .copy(): a bare view would keep the whole joined corpus resident
        # just to serve a 10% eval tail.
        self.data = data[int(len(data) * span[0]):int(len(data) * span[1])].copy()
        if len(self.data) < seq_len + 1:
            raise ValueError(
                f"corpus has {len(self.data)} bytes < seq_len+1 "
                f"({seq_len + 1}); add files"
            )
        self.seq_len = seq_len
        self.stride = stride or seq_len
        self.length = 1 + (len(self.data) - seq_len - 1) // self.stride

    def __len__(self) -> int:
        return self.length

    def __getitem__(self, index: int) -> np.ndarray:
        lo = index * self.stride
        return self.data[lo:lo + self.seq_len].astype(np.int32)

    def batch(self, step: int, batch_size: int) -> np.ndarray:
        return _wraparound_batch(self, step, batch_size)


def warmup_cosine_lr(base_lr: float, warmup_steps: int, total_steps: int,
                     min_frac: float = 0.1):
    """Standard LM-pretraining schedule: linear warmup then cosine decay to
    ``min_frac·base_lr``.  Returns ``step -> lr`` for ``LMTrainer``'s
    ``lr_schedule`` (computed host-side; the step takes lr as a live scalar
    operand, so no retrace)."""

    def schedule(step: int) -> float:
        if warmup_steps > 0 and step < warmup_steps:
            return base_lr * (step + 1) / warmup_steps
        span = max(1, total_steps - warmup_steps)
        t = min(1.0, (step - warmup_steps) / span)
        cos = 0.5 * (1.0 + np.cos(np.pi * t))
        return base_lr * (min_frac + (1.0 - min_frac) * cos)

    return schedule


def head_matrix(model, tree):
    """The ``[V, d]`` matrix the hidden rows are projected against, out of
    a tree shaped like the params: the model's own (``head_matrix``, an
    untied head) or the tied embedding."""
    own = getattr(model, "head_matrix", None)
    return own(tree) if own is not None else tree["embed"]["embedding"]


def _model_state_collection(model) -> Optional[str]:
    """The flax collection of a model's non-gradient state (the configured
    decoder's selection bias), kept in ``TrainState.batch_stats``."""
    return getattr(model, "state_collection", None)


def lm_state_specs(param_specs, *, residual: bool = False,
                   momentum_specs=None, tx=None, params=None,
                   model_state=None):
    """``parallel/tp.state_specs`` for the LM steps, plus what a
    configured decoder and an optax ``tx`` add: the optimizer state laid
    out like the params it mirrors (counts replicated), and the model's
    non-gradient state replicated (``model_state``: its tree, or ``True``
    for a one-leaf prefix, which ``jit`` takes and ``device_put`` does
    not)."""
    from pytorch_distributed_tpu.parallel.tp import replicated_like, state_specs

    specs = state_specs(param_specs, residual=residual,
                        momentum_specs=momentum_specs)
    if tx is not None:
        import optax

        if params is None:
            raise ValueError("an optax tx needs params= to lay out its state")
        specs = specs.replace(momentum=optax.tree_map_params(
            tx, lambda _, spec: spec, jax.eval_shape(tx.init, params),
            param_specs, transform_non_params=lambda _: P()))
    if model_state is True:
        specs = specs.replace(batch_stats=P())
    elif model_state:
        specs = specs.replace(batch_stats=replicated_like(model_state))
    return specs


def resolve_fused_ce_mode(
    mode: str,
    param_specs,
    mesh: Mesh,
    vocab_size: Optional[int],
    data_axis: str = "data",
    model=None,
) -> Tuple[str, Optional[str]]:
    """Pick the fused-CE sharding variant (ops/fused_ce.py) for this
    mesh/spec combination → ``(mode, model_axis)``.

    - ``'tp'`` when the tied embedding's PartitionSpec shards the vocab dim
      over a live mesh axis other than ``data_axis`` (the parallel/tp.py
      ``P('model', None)`` layout): the shard_map variant consumes the
      shard directly — no replication of ``e`` or ``dE``.
    - ``'dp'`` when the embedding is effectively replicated but the mesh
      data axis is >1 and divides the vocab: the dE accumulator is kept as
      a vocab-row shard per device.
    - ``'replicated'`` otherwise (single device, or an indivisible vocab) —
      the original GSPMD path.

    Explicit ``mode`` values are validated against the same constraints so
    a mis-paired flag fails loudly at step-build time, not at trace time.
    """
    if mode not in ("auto", "replicated", "dp", "tp"):
        raise ValueError(
            f"fused_ce_mode must be auto|replicated|dp|tp, got {mode!r}")
    try:
        embed_spec = head_matrix(model, param_specs)
    except (KeyError, TypeError):
        embed_spec = P()
    mesh_shape = dict(mesh.shape)
    vocab_axis = embed_spec[0] if len(embed_spec) >= 1 else None
    tp_ok = (vocab_axis is not None and vocab_axis != data_axis
             and mesh_shape.get(vocab_axis, 1) > 1
             and vocab_size is not None
             and vocab_size % mesh_shape[vocab_axis] == 0)
    dp = mesh_shape.get(data_axis, 1)
    dp_ok = (dp > 1 and vocab_size is not None and vocab_size % dp == 0
             and (vocab_axis is None or mesh_shape.get(vocab_axis, 1) == 1))
    if mode == "auto":
        mode = "tp" if tp_ok else ("dp" if dp_ok else "replicated")
    elif mode == "tp" and not tp_ok:
        raise ValueError(
            "fused_ce_mode='tp' needs the tied embedding vocab-sharded "
            f"over a non-data mesh axis dividing the vocab; got spec "
            f"{embed_spec} on mesh {mesh_shape} (vocab {vocab_size})")
    elif mode == "dp" and not dp_ok:
        raise ValueError(
            "fused_ce_mode='dp' needs a replicated embedding, a data axis "
            f"> 1, and vocab divisible by it; got spec {embed_spec} on "
            f"mesh {mesh_shape} (vocab {vocab_size})")
    return mode, (vocab_axis if mode == "tp" else None)


def step_compiler_options(mesh: Mesh) -> Optional[Dict[str, Any]]:
    """Options the TPU compiler gets with the train step: its ``list``
    memory scheduler, whatever its estimates say.

    Left to its default the compiler schedules the step three ways (list,
    depth first, post order) and keeps the one whose *estimated* peak is
    smallest, an estimate taken before its later passes.  Depth first
    applies every optimizer update after the whole backward pass, all
    gradients live at once, and is estimated a quarter of a GiB under
    ``list`` (which applies a leaf's update as its gradient arrives) for
    the two routed cells' steps, where it then compiles to 2.4 and 3.8 GB
    more (PERF.md 6, PR 33).  A mesh of CPU devices gets no option: its
    compiler has none of that name.  ``tests/test_tpu_aot.py`` compiles a
    tied step for a described v5e both ways and holds the pin to the gain."""
    if mesh.devices.flat[0].platform != "tpu":
        return None
    return {"xla_memory_scheduler": "list"}


def make_lm_train_step(
    model,
    mesh: Mesh,
    param_specs,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    data_axis: str = "data",
    clip_grad_norm: float = 0.0,
    accum_steps: int = 1,
    fused_ce_chunks: int = 0,
    fused_ce_mode: str = "auto",
    log_norms: bool = False,
    guard_nonfinite: bool = False,
    grad_compress: Optional[str] = None,
    zero: str = "none",
    params=None,
    overlap: str = "none",
    bucket_mb: float = 4.0,
    explicit_collectives: bool = False,
    tx=None,
):
    """Jitted LM step; ``param_specs`` is a PartitionSpec pytree from
    parallel/tp.py (``replicated_like`` for pure DP, ``tp_specs`` for TP).
    ``clip_grad_norm > 0`` rescales gradients to that global L2 norm
    (in-graph, before the update — the torch ``clip_grad_norm_`` analogue).
    ``accum_steps > 1`` accumulates gradients over that many strided
    microbatches inside the one compiled step (same semantics as the image
    path, train/steps.py).  For dense models the update equals the
    unaccumulated step up to fp reassociation (tested); for MoE models the
    router's load-balancing aux loss is computed from *microbatch-local*
    routing fractions, so accumulated and unaccumulated runs differ
    slightly — the standard per-microbatch aux-loss semantics, not a bug.

    ``fused_ce_mode`` selects the sharded fused-CE variant (see
    ``resolve_fused_ce_mode``); the default ``'auto'`` picks from the
    mesh + param specs, so ``fused_ce_chunks=N`` alone does the right
    thing on DP, TP, and single-device meshes alike.

    ``log_norms`` adds in-graph global ``grad_norm``/``param_norm`` metrics
    (per-leaf reductions stay sharding-local; the scalars replicate).  Off
    by default — the extra reduce ops lengthen compiles, so the cost is
    only paid when a metrics sink is on (``LMTrainer`` enables it with
    ``metrics_jsonl``).

    ``guard_nonfinite``: gate the whole update on an in-graph
    loss/grad-norm finiteness check and emit the ``nonfinite`` flag as a
    lazy metric — the divergence guard's detection half (train/steps.py
    ``nonfinite_flag``/``gate_update``; policy in ft/divergence.py).

    ``grad_compress``: gradient-sync compression mode (ops/qcomm.py,
    ``none | bf16 | int8 | fp8``).  Under the default GSPMD step XLA owns
    the gradient psum, so quantized modes run as a *numerics emulation*
    (fake-quantize + error feedback applied to the already-synced global
    gradient; wire bytes unchanged).  ``explicit_collectives=True`` (or
    ``overlap='bucketed'``, which implies it) switches pure-DP meshes onto
    the explicit ``shard_map`` step where the hand-written
    ``psum``/``compressed_psum`` carries the *real* int8/bf16 wire —
    the LM counterpart of the image path's wire transformation.

    ``overlap``: ``none | bucketed`` — the comm-overlap scheduler
    (parallel/overlap.py).  ``bucketed`` partitions the grad pytree into
    ~``bucket_mb``-MiB buckets in reverse-autodiff order and issues one
    collective per bucket under nested ``grad_sync``/``b<k>`` scopes, so
    early-bucket sync can run concurrently with the remaining backward;
    per-leaf math is identical, so results are bit-equal to monolithic
    sync.  Requires a pure data-parallel mesh with replicated params
    (no TP / pipeline / fused-CE / accum / wus — those stay on their
    existing paths).

    ``zero='wus'`` (parallel/zero.py): momentum leaves take data-axis
    ``fsdp_specs`` shardings (``zero_momentum_specs``, composed over
    ``param_specs`` so TP layouts keep their model-axis dims) while the
    update math is untouched — XLA derives the weight-update sharding
    from the layout alone.  Per-device optimizer bytes drop to ~1/N;
    ``params`` (the concrete param tree) is required to size the specs.

    ``tx``: an optional optax ``GradientTransformation``, as
    ``train/steps.make_train_step`` takes one: its state lives in
    ``state.momentum`` (laid out like the params; ``params`` is required),
    and the ``lr`` argument, ``momentum`` and ``weight_decay`` are then
    inactive: schedule and regularisation live inside ``tx``.  ``None``
    keeps the built-in SGD and the program it lowers to.

    A model with a ``state_collection`` (models/decoder.py: the experts'
    selection bias) has that state read from ``state.batch_stats``, updated
    after the gradients by its own ``update_state`` from the counters the
    forward pass sowed (no gradient, no optimizer).  A model with
    ``counter_names`` has its ``step_counters`` added to the metrics.

    A model with ``n_exits`` > 1 (models/decoder.py: a looped decoder)
    returns every exit's hidden rows ``[T, B, L, d]`` and sows a float32
    weight for each (``exits/weight`` [T, B, L], summing to 1 over T): the
    loss is the mean over positions of the weighted sum of the exits'
    cross-entropies, one fused call over all ``T*B*(L-1)`` rows, plus
    whatever the model sowed under ``losses``; the gradient reaches the
    model through the weights too."""
    from pytorch_distributed_tpu.parallel import overlap as overlap_lib
    from pytorch_distributed_tpu.parallel import zero as zero_lib

    model = bind_mesh(model, mesh)
    # the step under its module's name, for obs/trace.py compiled_scopes
    program = StepProgram("jit_step")
    zero_mode = zero_lib.resolve_zero(zero)
    overlap_mode = overlap_lib.resolve_overlap(overlap)
    state_col = _model_state_collection(model)
    if tx is not None:
        import warnings

        warnings.warn(
            "make_lm_train_step: tx provided — the lr argument (and "
            "LMTrainer's schedule) plus momentum/weight_decay are "
            "INACTIVE; configure them inside the optax transformation.",
            stacklevel=2)
    if state_col or tx is not None:
        manual = getattr(model, "has_manual_grads", lambda: False)()
        bad = [what for what, cond in [
            ("explicit collectives / overlap",
             explicit_collectives or overlap_mode == "bucketed"),
            ("the 1F1B pipeline", manual),
            (f"accum_steps={accum_steps}", state_col and accum_steps > 1),
            (f"zero={zero_mode!r}", zero_mode != "none"),
        ] if cond]
        if bad:
            raise ValueError(
                "an optax tx / a model with non-gradient state run on the "
                "plain GSPMD LM step only; got " + "; ".join(bad))
    if explicit_collectives or overlap_mode == "bucketed":
        manual = getattr(model, "has_manual_grads", lambda: False)()
        unsupported = [
            ("the 1F1B pipeline's manual-gradient schedule", manual),
            (f"accum_steps={accum_steps}", accum_steps > 1),
            (f"fused_ce_chunks={fused_ce_chunks}", bool(fused_ce_chunks)),
            (f"zero={zero_mode!r} (use the image trainer's explicit wus "
             "path)", zero_mode != "none"),
        ]
        bad = [what for what, cond in unsupported if cond]
        if bad:
            raise ValueError(
                "the explicit-collectives LM step (overlap/"
                "explicit_collectives) supports the plain pure-DP step "
                "only; got " + "; ".join(bad))
        gc_mode, gc_cast = qcomm.resolve_mode(grad_compress, None)
        return _make_lm_train_step_explicit(
            model, mesh, param_specs, momentum=momentum,
            weight_decay=weight_decay, data_axis=data_axis,
            clip_grad_norm=clip_grad_norm, log_norms=log_norms,
            guard_nonfinite=guard_nonfinite, gc_mode=gc_mode,
            gc_cast=gc_cast, overlap_mode=overlap_mode,
            bucket_mb=bucket_mb)
    mom_specs = None
    if zero_mode == "wus":
        if params is None:
            raise ValueError(
                "make_lm_train_step(zero='wus') needs the concrete params "
                "tree to size the momentum fsdp_specs")
        mom_specs = zero_lib.zero_momentum_specs(
            params, mesh, data_axis, base_specs=param_specs)
    manual = getattr(model, "has_manual_grads", lambda: False)()
    gc_mode, gc_cast = qcomm.resolve_mode(grad_compress, None)
    if gc_mode != "none":
        import warnings

        warnings.warn(
            f"make_lm_train_step: grad_compress={gc_mode!r} under GSPMD is "
            "a NUMERICS emulation only — the gradient psum stays f32 on the "
            "wire (XLA owns the collective). Use the explicit-collectives "
            "image path for true wire compression.",
            UserWarning, stacklevel=2)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if accum_steps > 1 and manual:
        raise ValueError(
            "accum_steps > 1 with the 1F1B pipeline is redundant — the "
            "schedule already splits the batch into pipeline microbatches; "
            "raise n_microbatches instead"
        )
    if fused_ce_chunks and manual:
        raise ValueError(
            "fused_ce_chunks composes with autodiff loss_fn models only, "
            "not the 1F1B pipeline's manual-gradient schedule")
    ce_mode, ce_model_axis = ("replicated", None)
    if fused_ce_chunks:
        ce_mode, ce_model_axis = resolve_fused_ce_mode(
            fused_ce_mode, param_specs, mesh,
            getattr(model, "vocab_size", None), data_axis, model=model)
    # what the forward pass may write besides the sown losses
    counted = bool(getattr(model, "counter_names", ()))
    mutable = ["losses", "counters", "exits"] if counted else ["losses"]
    n_exits = getattr(model, "n_exits", 1)
    if n_exits > 1 and not (fused_ce_chunks and accum_steps == 1):
        raise ValueError(
            "a model with several exits trains on the fused loss, "
            "unaccumulated: pass fused_ce_chunks > 0, accum_steps = 1")

    def step(state: TrainState, tokens: jnp.ndarray, lr: jnp.ndarray):
        program.note(state, tokens, lr)

        def variables(params):
            if state_col:
                return {"params": params, state_col: state.batch_stats}
            return {"params": params}

        def loss_fn(params, toks, probe=None):
            # scope(): forward ops carry the phase name into the compiled
            # module's metadata (autodiff derives the backward names from
            # it): per-phase self-time instead of anonymous fusions.
            with scope("lm_forward"):
                return loss_impl(params, toks, probe)

        def loss_impl(params, toks, probe):
            if fused_ce_chunks:
                # Fused tied-head + CE (ops/fused_ce.py): the [B, L, V]
                # logits tensor never materializes — hidden rows project
                # against the tied embedding per chunk inside a custom VJP.
                # The sharded variants keep the backward's dE accumulator
                # sharded too (vocab rows over data, or the tp.py vocab
                # shard), instead of the replicated [V, D] f32 carry that
                # erased the memory win on data-sharded meshes.
                from pytorch_distributed_tpu.ops.fused_ce import (
                    fused_ce_sums,
                    fused_ce_sums_dp,
                    fused_ce_sums_tp,
                )

                hidden, sown = model.apply(
                    variables(params), toks, mutable=mutable,
                    return_hidden=True,
                )
                d = hidden.shape[-1]
                cdt = getattr(model, "dtype", jnp.float32)
                # one exit: [B, L, d]; several: every exit's rows
                # [T, B, L, d] in one call, so that the head's gradient
                # accumulates once
                h = hidden[..., :-1, :].reshape(-1, d).astype(cdt)
                t = toks[:, 1:].reshape(-1)
                # The rows' weights hold the mean's 1 / ntok, so the sums
                # that come back are the means and the loss's cotangent is
                # exactly 1: the fused loss took its gradients in the pass
                # that had the logits and only scales them by that
                # cotangent, and a scaling by 1 folds away (no second
                # rounding of the bf16 dh, no scaled copy of dE).
                ntok = t.shape[0]
                w = jnp.full(t.shape, 1.0 / ntok, jnp.float32)
                if n_exits > 1:
                    # a row's weight is the model's exit distribution, and
                    # the loss's cotangent on it is what teaches the gate.
                    # ``probe`` [T] is zero: its own cotangent is each
                    # exit's mean cross-entropy
                    t = jnp.tile(t, n_exits)
                    with scope("exit_loss"):
                        w = ((sown["exits"]["weight"][0][..., :-1]
                              + probe[:, None, None]) / ntok).reshape(-1)
                e = head_matrix(model, params).astype(cdt)
                if ce_mode == "tp":
                    loss, acc = fused_ce_sums_tp(
                        h, e, t, w, fused_ce_chunks, mesh,
                        data_axis=data_axis, model_axis=ce_model_axis)
                elif ce_mode == "dp":
                    loss, acc = fused_ce_sums_dp(
                        h, e, t, w, fused_ce_chunks, mesh,
                        data_axis=data_axis)
                else:
                    loss, acc = fused_ce_sums(
                        h, e, t, w, fused_ce_chunks)
                for leaf in jax.tree_util.tree_leaves(
                        sown.get("losses", {})):
                    loss = loss + leaf
                return loss, (acc, sown.get("counters", {}))
            # mutable=["losses"] collects sown auxiliary objectives (the MoE
            # router's load-balancing loss); {} for dense models.
            logits, sown = model.apply(
                variables(params), toks, mutable=mutable
            )
            vocab = logits.shape[-1]
            loss = cross_entropy(
                logits[:, :-1].reshape(-1, vocab),
                toks[:, 1:].reshape(-1),
            )
            for leaf in jax.tree_util.tree_leaves(sown.get("losses", {})):
                loss = loss + leaf
            acc = jnp.mean(
                (jnp.argmax(logits[:, :-1], axis=-1) == toks[:, 1:]).astype(
                    jnp.float32
                )
            )
            return loss, (acc, sown.get("counters", {}))

        seen = {}
        if manual:
            # 1F1B pipeline: gradients come from the schedule's own
            # interleaved scan, not autodiff over the whole step
            # (models/pipeline_lm.py loss_and_grads).
            (loss, acc), grads = model.loss_and_grads(state.params, tokens)
        elif n_exits > 1:
            (loss, (acc, seen)), (grads, exit_losses) = jax.value_and_grad(
                loss_fn, argnums=(0, 2), has_aux=True)(
                    state.params, tokens, jnp.zeros((n_exits,), jnp.float32))
            seen = {**seen, "exit_losses": exit_losses}
        elif accum_steps == 1:
            (loss, (acc, seen)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, tokens)
        else:
            B = tokens.shape[0]
            if B % accum_steps:
                raise ValueError(
                    f"batch {B} not divisible by accum_steps {accum_steps}"
                )
            # Strided split keeps every microbatch evenly spread over the
            # data-sharded rows (a contiguous split would concentrate each
            # microbatch on a device subset — train/steps.py note).
            micro = tokens.reshape(
                B // accum_steps, accum_steps, -1).swapaxes(0, 1)

            def body(carry, mb):
                g_acc, loss_acc, acc_acc = carry
                (l, (a, counters)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, loss_acc + l, acc_acc + a), counters

            init = (
                jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), state.params),
                jnp.float32(0.0),
                jnp.float32(0.0),
            )
            (grads, loss, acc), seen = jax.lax.scan(body, init, micro)
            # a model's counters: the last microbatch's
            seen = jax.tree_util.tree_map(lambda c: c[-1], seen)
            inv = 1.0 / accum_steps  # means-of-equal-size-microbatch-means
            grads = jax.tree_util.tree_map(
                lambda g, p: (g * inv).astype(p.dtype), grads, state.params)
            loss, acc = loss * inv, acc * inv
        # Pre-clip global grad norm: computed in-graph when clipping needs
        # it, when the obs layer asked for it, or when the divergence guard
        # watches it (an on-device scalar — converted lazily, never a host
        # sync).
        gnorm = (tree_l2_norm(grads)
                 if (log_norms or clip_grad_norm > 0.0 or guard_nonfinite)
                 else None)
        if clip_grad_norm > 0.0:
            with scope("grad_clip"):
                scale = jnp.minimum(
                    1.0, clip_grad_norm / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                    grads,
                )
        new_residual = state.residual
        if gc_mode in qcomm.QUANTIZED_MODES:
            # GSPMD numerics emulation: fake-quantize the (already synced)
            # global gradient with error feedback — see module warning.
            with scope("grad_sync"):
                grads, new_residual = qcomm.compress_emulated(
                    grads, state.residual, gc_mode)
        elif gc_cast is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(gc_cast).astype(jnp.float32), grads)
        with scope("optimizer"):
            if tx is None:
                new_params, new_momentum = sgd_update(
                    grads, state.momentum, state.params, lr,
                    momentum=momentum, weight_decay=weight_decay,
                )
            else:
                import optax

                updates, new_momentum = tx.update(
                    grads, state.momentum, state.params)
                new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, "acc": acc * 100.0}
        new_model_state = state.batch_stats
        if state_col:
            new_model_state = model.update_state(state.batch_stats, seen)
        if counted:
            metrics.update(model.step_counters(new_model_state, seen))
        if guard_nonfinite:
            bad = nonfinite_flag(loss, gnorm)
            new_params = gate_update(bad, state.params, new_params)
            new_momentum = gate_update(bad, state.momentum, new_momentum)
            new_residual = gate_update(bad, state.residual, new_residual)
            if state_col:
                new_model_state = gate_update(bad, state.batch_stats,
                                              new_model_state)
            metrics["nonfinite"] = bad
        new_state = TrainState(state.step + 1, new_params, new_model_state,
                               new_momentum, new_residual)
        if log_norms:
            metrics["grad_norm"] = gnorm
            metrics["param_norm"] = tree_l2_norm(new_params)
        return new_state, metrics

    state_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        lm_state_specs(param_specs,
                       residual=gc_mode in qcomm.QUANTIZED_MODES,
                       momentum_specs=mom_specs, tx=tx, params=params,
                       model_state=bool(state_col)),
    )
    token_sharding = NamedSharding(mesh, P(data_axis, None))
    return program.jit(
        step,
        in_shardings=(state_shardings, token_sharding,
                      NamedSharding(mesh, P())),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
        compiler_options=step_compiler_options(mesh),
    )


def _make_lm_train_step_explicit(
    model,
    mesh: Mesh,
    param_specs,
    *,
    momentum: float,
    weight_decay: float,
    data_axis: str,
    clip_grad_norm: float,
    log_norms: bool,
    guard_nonfinite: bool,
    gc_mode: str,
    gc_cast,
    overlap_mode: str,
    bucket_mb: float,
):
    """Explicit ``shard_map`` DP LM step — the wire-transformation half of
    the overlap scheduler (parallel/overlap.py).

    Pure data parallelism with replicated params: each shard computes its
    local mean loss and grads, the hand-written ``psum`` /
    ``compressed_psum`` syncs them (so ``grad_compress`` compresses the
    *actual* wire, unlike the GSPMD emulation), and
    ``overlap='bucketed'`` splits the sync into reverse-autodiff-ordered
    buckets under ``grad_sync``/``b<k>`` scopes so each bucket's
    collective is free to run concurrently with the remaining backward.
    Per-leaf math is unchanged, so monolithic and bucketed steps are
    bit-equal.  Quantized error-feedback residuals ride in
    ``TrainState.residual`` in the stacked ``(n_data, *shape)`` layout
    sharded over ``data_axis`` (ops/qcomm.py ``init_residual``
    ``explicit=True``)."""
    from jax import shard_map

    from pytorch_distributed_tpu.parallel import overlap as overlap_lib

    mesh_shape = dict(mesh.shape)
    off_axes = {a: s for a, s in mesh_shape.items()
                if a != data_axis and s > 1}
    if off_axes:
        raise ValueError(
            "the explicit-collectives LM step needs a pure data-parallel "
            f"mesh; axes {off_axes} are > 1 besides {data_axis!r}")
    nontrivial = [
        s for s in jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))
        if isinstance(s, P) and any(ax is not None for ax in s)
    ]
    if nontrivial:
        raise ValueError(
            "the explicit-collectives LM step keeps params replicated; "
            f"got sharded param_specs {nontrivial[:3]}...")
    n = mesh_shape.get(data_axis, 1)
    quantized = gc_mode in qcomm.QUANTIZED_MODES

    def local_step(state: TrainState, tokens: jnp.ndarray, lr: jnp.ndarray):
        def loss_fn(p, toks):
            with scope("lm_forward"):
                logits, sown = model.apply({"params": p}, toks,
                                           mutable=["losses"])
                vocab = logits.shape[-1]
                loss = cross_entropy(
                    logits[:, :-1].reshape(-1, vocab),
                    toks[:, 1:].reshape(-1),
                )
                for leaf in jax.tree_util.tree_leaves(
                        sown.get("losses", {})):
                    loss = loss + leaf
                acc = jnp.mean(
                    (jnp.argmax(logits[:, :-1], axis=-1)
                     == toks[:, 1:]).astype(jnp.float32))
                return loss, acc

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, tokens)
        new_residual = state.residual
        # Equal-size shards: mean-of-shard-means == global mean, so the
        # synced gradient is psum/n of the local d(mean loss)/dp.
        with scope("grad_sync"):
            if overlap_mode == "bucketed":
                grads, new_residual = overlap_lib.bucketed_psum(
                    grads, state.residual, data_axis, mode=gc_mode,
                    cast_dtype=gc_cast, bucket_mb=bucket_mb)
            elif quantized:
                grads, new_residual = qcomm.compressed_psum(
                    grads, state.residual, data_axis, mode=gc_mode)
            else:
                if gc_cast is not None:
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(gc_cast), grads)
                grads = jax.lax.psum(grads, data_axis)
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / n, grads)
        loss = jax.lax.psum(loss, data_axis) / n
        acc = jax.lax.psum(acc, data_axis) / n
        # Synced grads are identical on every shard, so the per-shard norm
        # IS the global norm — no extra collective.
        gnorm = (tree_l2_norm(grads)
                 if (log_norms or clip_grad_norm > 0.0 or guard_nonfinite)
                 else None)
        if clip_grad_norm > 0.0:
            with scope("grad_clip"):
                scale = jnp.minimum(
                    1.0, clip_grad_norm / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(
                    lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                    grads,
                )
        with scope("optimizer"):
            new_params, new_momentum = sgd_update(
                grads, state.momentum, state.params, lr,
                momentum=momentum, weight_decay=weight_decay,
            )
        metrics = {"loss": loss, "acc": acc * 100.0}
        if guard_nonfinite:
            bad = nonfinite_flag(loss, gnorm)
            new_params = gate_update(bad, state.params, new_params)
            new_momentum = gate_update(bad, state.momentum, new_momentum)
            new_residual = gate_update(bad, state.residual, new_residual)
            metrics["nonfinite"] = bad
        new_state = TrainState(state.step + 1, new_params, state.batch_stats,
                               new_momentum, new_residual)
        if log_norms:
            metrics["grad_norm"] = gnorm
            metrics["param_norm"] = tree_l2_norm(new_params)
        return new_state, metrics

    replicated = NamedSharding(mesh, P())
    state_spec = TrainState(
        step=P(), params=P(), batch_stats=P(), momentum=P(),
        residual=P(data_axis) if quantized else P())
    state_sharding = TrainState(
        step=replicated, params=replicated, batch_stats=replicated,
        momentum=replicated,
        residual=(NamedSharding(mesh, P(data_axis)) if quantized
                  else replicated))
    sharded_step = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, P(data_axis, None), P()),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    # the step under its module's name, for obs/trace.py compiled_scopes
    program = StepProgram("jit_local_step")

    @functools.wraps(local_step)  # the module keeps its name
    def stepped(state, tokens, lr):
        program.note(state, tokens, lr)  # the global shapes, not a shard's
        return sharded_step(state, tokens, lr)

    return program.jit(
        stepped,
        in_shardings=(state_sharding, NamedSharding(mesh, P(data_axis, None)),
                      replicated),
        out_shardings=(state_sharding, replicated),
        donate_argnums=(0,),
    )


def make_lm_eval_step(model, mesh: Mesh, param_specs, data_axis: str = "data",
                      has_residual: bool = False, momentum_specs=None,
                      residual_specs=None, tx=None, params=None):
    """Jitted held-out eval step returning exact token-weighted *sums*
    (loss·count, correct, count) — the LM counterpart of the image harness's
    ``make_eval_step`` (reference validate() pattern,
    reference distributed.py:279-324): aggregation is exact on the host,
    reductions live inside the compiled program.  ``has_residual``: the
    caller's TrainState carries error-feedback residuals (quantized
    ``grad_compress``), so in_shardings must cover that subtree too.
    ``momentum_specs``: the ``--zero wus`` momentum layout
    (``zero_momentum_specs``) — in_shardings must match or XLA gathers
    the sharded optimizer state on every eval call.  ``residual_specs``
    overrides the residual layout: the bucketed-overlap explicit step
    stores residuals stacked per rank and sharded ``P(data_axis)``, not
    param-shaped.  ``tx`` / ``params``: as ``make_lm_train_step`` takes
    them, for the layout of the optimizer state."""
    model = bind_mesh(model, mesh)
    state_col = _model_state_collection(model)

    def step(state: TrainState, tokens: jnp.ndarray):
        # mutable=["losses"]: MoE models sow the router aux loss even in
        # inference; collected and dropped (eval reports data loss only).
        variables = {"params": state.params}
        mutable = ["losses"]
        if state_col:
            variables[state_col] = state.batch_stats
            mutable = ["losses", "counters"]
        logits, _ = model.apply(variables, tokens, mutable=mutable)
        vocab = logits.shape[-1]
        flat_logits = logits[:, :-1].reshape(-1, vocab)
        flat_targets = tokens[:, 1:].reshape(-1)
        count = jnp.float32(flat_targets.shape[0])
        loss = cross_entropy(flat_logits, flat_targets)
        correct = jnp.sum(
            (jnp.argmax(flat_logits, axis=-1) == flat_targets).astype(jnp.float32)
        )
        return {"loss_sum": loss * count, "correct": correct, "count": count}

    specs = lm_state_specs(param_specs, residual=has_residual,
                           momentum_specs=momentum_specs, tx=tx,
                           params=params, model_state=bool(state_col))
    if residual_specs is not None:
        specs = specs.replace(residual=residual_specs)
    state_shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    token_sharding = NamedSharding(mesh, P(data_axis, None))
    return jax.jit(
        step,
        in_shardings=(state_shardings, token_sharding),
        out_shardings=NamedSharding(mesh, P()),
    )


class LMTrainer:
    """Step-driven driver: meters, periodic display, rank-0 checkpoints,
    and a held-out eval loop (loss / perplexity / next-token accuracy) with
    best tracking — mirroring the image harness's validate/best-acc flow
    (reference distributed.py:212-225)."""

    def __init__(
        self,
        model,
        mesh: Mesh,
        dataset: SyntheticTokenDataset,
        batch_size: int,
        lr: float = 1e-2,
        param_specs=None,
        seed: int = 0,
        is_primary: bool = True,
        checkpoint_dir: Optional[str] = None,
        eval_dataset: Optional[SyntheticTokenDataset] = None,
        eval_every: int = 0,
        eval_batches: int = 8,
        lr_schedule=None,
        clip_grad_norm: float = 0.0,
        preempt=None,
        prefetch: int = 2,
        accum_steps: int = 1,
        fused_ce_chunks: int = 0,
        fused_ce_mode: str = "auto",
        metrics_jsonl: Optional[str] = None,
        hb_dir: Optional[str] = None,
        hb_interval_s: float = 5.0,
        mfu: bool = False,
        goodput: bool = False,
        watch_recompiles: bool = False,
        comm_ledger: Optional[str] = None,
        mem_ledger: Optional[str] = None,
        lowering_cache: Optional[str] = None,
        save_steps: int = 0,
        resume: Optional[str] = None,
        nan_guard: bool = False,
        ft_rollback_k: int = 3,
        ft_check_every: int = 10,
        ft_lr_backoff: float = 0.5,
        chaos=None,
        grad_compress: Optional[str] = None,
        zero: Optional[str] = None,
        overlap: str = "none",
        bucket_mb: float = 4.0,
        elastic=None,
        rescale_lr: str = "none",
        flight_rec: Optional[str] = None,
        hang_timeout: float = 30.0,
        metrics_port: int = 0,
        alerts: Optional[str] = None,
        step_attr: bool = False,
        tx=None,
        profile_dir: Optional[str] = None,
        profile_steps: Optional[str] = None,
    ):
        """``tx``: an optional optax ``GradientTransformation`` in place of
        the built-in SGD (``make_lm_train_step``): ``lr`` and
        ``lr_schedule`` are then inactive.

        ``lr_schedule``: optional ``step -> lr`` callable (e.g.
        ``warmup_cosine_lr``) overriding the fixed ``lr``;
        ``clip_grad_norm``: in-graph global-norm gradient clipping;
        ``accum_steps``: gradient accumulation inside the compiled step;
        ``preempt``: optional installed ``utils.preempt.PreemptionGuard`` —
        when it triggers, ``fit`` stops at the next step boundary and the
        end-of-fit checkpoint captures the state.
        ``prefetch``: token batches kept in flight by the background feeder
        (0 = synchronous host assembly + transfer in the step loop — the
        before/after axis measured in experiments/lm_feeder_bench.py);
        ``fused_ce_mode``: sharding variant of the fused loss head
        (auto | replicated | dp | tp — see ``resolve_fused_ce_mode``);
        ``metrics_jsonl``/``hb_dir``: unified observability (obs/) — one
        structured record per step, and per-process heartbeats for the
        cross-process straggler monitor.

        Efficiency accounting (obs/flops.py, goodput.py, watchdog.py):
        ``mfu`` adds per-step MFU/HFU fields from the analytic LM FLOPs
        model (fused-CE / remat / pipeline-aware) over the chips' peak;
        ``goodput`` tracks the live goodput/badput ledger and prints it at
        end of fit; ``watch_recompiles`` installs the jax.monitoring
        recompile watchdog around the step/eval functions.

        Fault tolerance (ft/): ``save_steps`` checkpoints every N steps
        (ft record carries the step, so SIGKILL loses at most N steps);
        ``resume`` restores state AND the exact step from a checkpoint —
        the run continues as if never interrupted (the step-indexed
        wraparound batching regenerates the identical token stream);
        ``nan_guard`` turns on the in-graph non-finite skip plus the
        K-consecutive rollback policy with LR backoff (``ft_rollback_k``,
        ``ft_check_every``, ``ft_lr_backoff`` — see
        ``ft.divergence.DivergenceGuard``); ``chaos``: an optional
        ``ft.chaos`` injector schedule driven once per loop step (tests
        and drills only); ``grad_compress``: gradient-sync compression
        mode (``none | bf16 | int8 | fp8`` — numerics emulation under the
        LM GSPMD step, see ``make_lm_train_step``); ``zero``: ``none|wus``
        weight-update sharding (parallel/zero.py) — momentum leaves take
        ``fsdp_specs`` data-axis shardings over the param specs, 1/N
        optimizer bytes per device, identical numerics and checkpoints;
        ``overlap``/``bucket_mb``: the comm-overlap scheduler
        (parallel/overlap.py) — ``'bucketed'`` switches pure-DP meshes
        onto the explicit shard_map step with ~``bucket_mb``-MiB
        reverse-autodiff grad-sync buckets (real compressed wire under
        ``grad_compress``; bit-equal numerics).

        Elastic training (ft/elastic.py): ``elastic`` is a membership
        controller (``ElasticSim`` in-process, or any object with
        ``poll(step) -> MembershipChange | None``); on a change ``fit``
        tears down and rebuilds the mesh/shardings/feeder/jitted steps
        from the survivor set and re-shards the last-good ``StateKeeper``
        snapshot onto the new topology.  ``rescale_lr`` is the rescale
        rule across a world change: ``none`` holds the *global* batch
        constant (LR untouched — the parity-fence default), ``linear`` /
        ``sqrt`` hold the *per-rank* batch constant and scale the LR by
        (new/old) or sqrt(new/old).

        Crash forensics (obs/flightrec.py): ``flight_rec`` is a directory
        receiving this rank's ``flightrec_rank<k>.json`` ring dump on any
        death path (signal / rollback / checkpoint corruption / unhandled
        exception / hang watchdog); ``hang_timeout`` is the watchdog's
        floor — a step exceeding ``max(hang_timeout, 4×p95)`` emits a
        ``hang`` ft_event and dumps the ring pre-mortem."""
        from pytorch_distributed_tpu.parallel import zero as zero_lib
        from pytorch_distributed_tpu.parallel.tp import replicated_like

        self.model = model
        self.mesh = mesh
        self.dataset = dataset
        self.batch_size = batch_size
        self.lr = lr
        # ``fit`` traces into it (the whole run, or ``profile_steps``
        # 'I:J') and leaves ``spans.jsonl`` and ``scopes.json`` there
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        self.is_primary = is_primary
        self.checkpoint_dir = checkpoint_dir
        self.preempt = preempt

        # Init batch must divide the data axis (ring attention shard_maps the
        # batch dim during init tracing too).
        init_b = dict(mesh.shape).get("data", 1)
        tokens0 = jnp.zeros((init_b, dataset.seq_len), jnp.int32)
        variables = model.init(jax.random.PRNGKey(seed), tokens0)
        params = variables["params"]
        # non-gradient model state (the configured decoder's selection
        # bias): TrainState.batch_stats carries it
        model_state = variables.get(_model_state_collection(model), {})
        self.tx = tx
        self.param_specs = (
            param_specs if param_specs is not None else replicated_like(params)
        )
        self.grad_compress, _ = qcomm.resolve_mode(grad_compress, None)
        self.zero = zero_lib.resolve_zero(zero)
        from pytorch_distributed_tpu.parallel import overlap as overlap_lib

        self.overlap = overlap_lib.resolve_overlap(overlap)
        self.bucket_mb = float(bucket_mb)
        if self.overlap == "bucketed" and elastic is not None:
            raise ValueError(
                "overlap='bucketed' carries stacked per-rank residual "
                "state the elastic re-mesh does not re-grid on the LM "
                "path; run elastic with overlap='none'")
        self.lr_schedule = lr_schedule
        self.eval_dataset = eval_dataset
        self.eval_every = eval_every
        self.eval_batches = eval_batches
        self.best_ppl = float("inf")
        self.eval_history: list = []  # (loss, ppl, acc%) per evaluate() call
        self.prefetch = prefetch
        # ---- elastic membership (ft/elastic.py) ----
        from pytorch_distributed_tpu.ft import elastic as elastic_lib

        if rescale_lr not in elastic_lib.RESCALE_RULES:
            raise ValueError(f"rescale_lr must be one of "
                             f"{elastic_lib.RESCALE_RULES}, got {rescale_lr!r}")
        self.elastic = elastic
        self.rescale_lr_rule = rescale_lr
        self._elastic_lr_scale = 1.0
        self._membership_epoch = 0
        # Everything mesh-shape-dependent lives in _build_for_mesh so a
        # membership change can rebuild it against the survivor set.
        self._step_kwargs = dict(
            clip_grad_norm=clip_grad_norm, accum_steps=accum_steps,
            fused_ce_chunks=fused_ce_chunks, fused_ce_mode=fused_ce_mode,
            overlap=self.overlap, bucket_mb=self.bucket_mb, tx=tx,
            # in-graph norms only when a metrics sink will consume them
            log_norms=bool(metrics_jsonl), guard_nonfinite=nan_guard)
        self._build_for_mesh(mesh, params)
        # Bucketed overlap runs the explicit shard_map step: quantized
        # error-feedback residuals take the stacked per-rank layout
        # sharded over the data axis (one slot per rank).
        explicit = self.overlap == "bucketed"
        residual = qcomm.init_residual(
            params, self.grad_compress, explicit=explicit,
            n_data=dict(mesh.shape).get("data", 1))
        state = TrainState.create(
            {"params": params, "batch_stats": model_state},
            sgd_init(params) if tx is None else tx.init(params),
            residual=residual)
        self.state = self._place_state(state, mesh)
        if explicit and self.grad_compress in qcomm.QUANTIZED_MODES:
            self.state = self.state.replace(residual=jax.device_put(
                self.state.residual, NamedSharding(mesh, P("data"))))
        from pytorch_distributed_tpu.obs import HeartbeatWriter, MetricsLogger

        self.obs = MetricsLogger(metrics_jsonl,
                                 process_index=jax.process_index())
        self.hb = (HeartbeatWriter(hb_dir, jax.process_index(),
                                   interval_s=hb_interval_s,
                                   world=dict(mesh.shape).get("data", 1),
                                   epoch=self._membership_epoch)
                   if hb_dir else None)

        # ---- efficiency accounting (obs/) ----
        self._mfu = None
        self._mfu_on = mfu
        if mfu:
            self._build_mfu()
        self._goodput = None
        if goodput:
            from pytorch_distributed_tpu.obs.goodput import GoodputTracker

            self._goodput = self.obs.register(GoodputTracker())
        self.watchdog = None
        if watch_recompiles:
            from pytorch_distributed_tpu.obs.watchdog import (
                RecompileWatchdog,
            )

            self.watchdog = RecompileWatchdog(obs=self.obs).install()
        # Exact step attribution (obs/stepattr.py, --step-attr): see the
        # image Trainer's twin block — three wall windows + one explicit
        # block per step, identity closed against the meters' seconds.
        self.stepattr = None
        self._stepattr_phases_booked = False
        if step_attr:
            from pytorch_distributed_tpu.obs.flops import chip_link_bytes
            from pytorch_distributed_tpu.obs.stepattr import StepAttr

            kind = getattr(mesh.devices.flat[0], "device_kind", "")
            self.stepattr = StepAttr(link_bytes_per_s=chip_link_bytes(kind))
        # Communication + memory ledgers (obs/comms.py, obs/memory.py):
        # emitted lazily on the first fit() batch; opt-in — the AOT
        # lowering does not share the jit call cache in jax 0.4.x, so the
        # pair costs one extra step compile, shared between them.
        self._comm_ledger_path = comm_ledger
        self._mem_ledger_path = mem_ledger
        self._lowering_cache = lowering_cache
        self._comm_fields: Optional[dict] = None
        # Dominant ledger collective labelling the flight ring's
        # coll_enter events; None until a ledger lowering runs.
        self._flight_coll: Optional[dict] = None

        # ---- crash forensics (obs/flightrec.py) ----
        self.flight = None
        self._hang_wd = None
        if flight_rec:
            from pytorch_distributed_tpu.obs.flightrec import (
                FlightRecorder,
                HangWatchdog,
                attach_to_metrics,
            )

            self.flight = FlightRecorder(flight_rec,
                                         rank=jax.process_index())
            self._hang_wd = HangWatchdog(self.flight, obs=self.obs,
                                         timeout=float(hang_timeout))
            attach_to_metrics(self.flight, self.obs)
            self.flight.set_membership(dict(mesh.shape).get("data", 1),
                                       self._membership_epoch)

        # ---- live telemetry plane (obs/export.py + obs/alerts.py) ----
        # Both are flush-time sinks on the same logger — zero additions
        # to the hot loop.  Rank k serves metrics_port + k; the exporter
        # is an owned sink (started here, stopped at obs.close()).
        self._exporter = None
        if int(metrics_port or 0) > 0:
            from pytorch_distributed_tpu.obs.export import MetricsExporter

            self._exporter = MetricsExporter(
                int(metrics_port) + jax.process_index(),
                rank=jax.process_index())
            self.obs.register(self._exporter)        # lifecycle
            self.obs.register(self._exporter.update)  # per-record sink
        self.alerts = None
        if alerts:
            from pytorch_distributed_tpu.obs.alerts import (
                AlertEngine,
                default_rules,
                load_rules,
            )

            rules = (default_rules() if alerts == "default"
                     else load_rules(alerts))
            self.alerts = AlertEngine(rules, emit=self._emit_alert,
                                      process_index=jax.process_index())
            self.obs.register(self.alerts)
            if self._exporter is not None:
                self._exporter.engine = self.alerts  # ptd_alert_firing

        # ---- fault tolerance (ft/) ----
        self.save_steps = int(save_steps)
        self.chaos = chaos
        self.ft_guard = None
        self._keeper = None
        if nan_guard:
            from pytorch_distributed_tpu.ft import DivergenceGuard

            self.ft_guard = DivergenceGuard(
                rollback_k=ft_rollback_k, check_every=ft_check_every,
                lr_backoff=ft_lr_backoff, obs=self.obs)
        if nan_guard or self.elastic is not None:
            # Elastic re-meshing re-shards from the same last-good host
            # snapshot the divergence guard rolls back to.
            from pytorch_distributed_tpu.ft import StateKeeper

            self._keeper = StateKeeper()
        self._start_step = 0
        if resume:
            from pytorch_distributed_tpu.train.checkpoint import load_checkpoint

            loaded, meta = load_checkpoint(resume, self.state)
            # Host-numpy leaves → re-shard to this trainer's specs (any
            # mesh shape can resume any mesh shape's checkpoint; the
            # momentum re-shards to the wus layout when zero is on).
            self.state = self._place_state(loaded, mesh)
            ft = meta["ft"]
            self._start_step = max(int(ft["global_step"]), int(ft["step"]))
            if self.ft_guard is not None:
                self.ft_guard.lr_scale = float(ft["lr_scale"])
            if self._eval_fn is not None and float(meta["best_acc1"]) > 0:
                self.best_ppl = float(meta["best_acc1"])
            print(f"=> resumed {meta['arch']} from '{resume}' at step "
                  f"{self._start_step}", flush=True)

    def _place_state(self, state: TrainState, mesh: Mesh) -> TrainState:
        """``state`` on ``mesh`` in the layout the jitted steps keep it."""
        from pytorch_distributed_tpu.parallel.tp import shard_pytree

        specs = lm_state_specs(
            self.param_specs,
            residual=bool(jax.tree_util.tree_leaves(state.residual)),
            momentum_specs=self._mom_specs, tx=self.tx, params=state.params,
            model_state=state.batch_stats)
        return shard_pytree(state, specs, mesh)

    def _build_for_mesh(self, mesh: Mesh, params) -> None:
        """Build (or rebuild) every mesh-shape-dependent piece against
        ``mesh``: momentum shardings, the jitted train/eval steps, the
        token sharding, and the caches keyed to the old topology (row
        span, preemption agreement, comm-ledger fields).  Called once
        from ``__init__`` and again on every elastic ``remesh`` — this is
        the mesh-shape-agnostic seam the ISSUE's refactor names."""
        from pytorch_distributed_tpu.parallel import zero as zero_lib

        self.mesh = mesh
        self._mom_specs = (
            zero_lib.zero_momentum_specs(params, mesh,
                                         base_specs=self.param_specs)
            if self.zero == "wus" else None)
        self.step_fn = make_lm_train_step(self.model, mesh, self.param_specs,
                                          grad_compress=self.grad_compress,
                                          zero=self.zero, params=params,
                                          **self._step_kwargs)
        self.token_sharding = NamedSharding(mesh, P("data", None))
        quantized = self.grad_compress in qcomm.QUANTIZED_MODES
        self._eval_fn = (
            make_lm_eval_step(
                self.model, mesh, self.param_specs,
                has_residual=quantized,
                momentum_specs=self._mom_specs, tx=self.tx, params=params,
                # bucketed overlap trains the explicit step: residuals are
                # stacked per rank and sharded over data (_build_for_mesh)
                residual_specs=(
                    jax.tree_util.tree_map(lambda _: P("data"),
                                           self.param_specs)
                    if quantized and self.overlap == "bucketed" else None))
            if self.eval_dataset is not None else None)
        self._span = None   # per-process row range: topology-keyed
        self._agree = None  # lazy PreemptionAgreement holds the old mesh
        self._comm_fields = None  # ledger re-emits against the new mesh

    def _emit_alert(self, **fields) -> None:
        """AlertEngine emit hook: book a firing as an ``alert`` ft_event
        in the same JSONL, so goodput/postmortem/obs_report fold it (and
        the flight ring records it via attach_to_metrics)."""
        self.obs.log_event("alert", **fields)

    def _build_mfu(self) -> None:
        from pytorch_distributed_tpu.obs.flops import (
            MFUReporter,
            device_peak_flops,
            lm_step_cost_for,
        )

        cost = lm_step_cost_for(
            self.model, self.batch_size, self.dataset.seq_len,
            fused_ce_chunks=self._step_kwargs["fused_ce_chunks"])
        dev = self.mesh.devices.flat[0]
        self._mfu = MFUReporter(cost, n_devices=self.mesh.devices.size,
                                peak_per_chip=device_peak_flops(dev))

    def remesh(self, new_world: int, completed: int,
               refresh_snapshot: bool = True) -> int:
        """Re-mesh to ``new_world`` data-parallel devices: rebuild mesh /
        shardings / jitted steps from the survivor set and re-shard the
        last-good ``StateKeeper`` snapshot onto the new topology.  Returns
        the resume step (the snapshot's step — a shrink rewinds to the
        last state the dead rank could not have tainted; a grow refreshes
        the snapshot first, so it resumes where it left off).

        LM state re-shards without layout surgery: params, GSPMD momentum
        (param-shaped, ``zero_momentum_specs``-sharded under ``wus``), and
        the quantized-emulation residual are all param-shaped host leaves,
        and ``shard_state`` places them under any mesh — the same "any
        shape resumes any shape" property the checkpoints already prove.
        (The explicit stacked layouts live in the image ``Trainer``, which
        re-grids them via ft/elastic.py.)"""
        axes = tuple(self.mesh.axis_names)
        if axes != ("data",):
            raise ValueError(
                f"elastic re-mesh supports pure data-parallel meshes; "
                f"this trainer's mesh has axes {axes}")
        devs = jax.devices()
        if not 1 <= new_world <= len(devs):
            raise ValueError(
                f"new world {new_world} outside [1, {len(devs)}] devices")
        old_world = dict(self.mesh.shape)["data"]
        if self._keeper is None:
            from pytorch_distributed_tpu.ft import StateKeeper

            self._keeper = StateKeeper()
        if refresh_snapshot or not self._keeper.has_snapshot:
            self._keeper.update(self.state, completed)
        host = self._keeper.restore()
        resume = int(self._keeper.step)
        from pytorch_distributed_tpu.ft import elastic as elastic_lib

        if self.rescale_lr_rule != "none":
            self.batch_size = elastic_lib.rescale_batch(
                self.batch_size, old_world, new_world, self.rescale_lr_rule)
            self._elastic_lr_scale *= elastic_lib.rescale_lr(
                1.0, old_world, new_world, self.rescale_lr_rule)
        if self.batch_size % new_world:
            raise ValueError(
                f"global batch {self.batch_size} does not divide the new "
                f"data axis ({new_world} devices); pick --min-ranks / batch "
                "so every admissible world divides it")
        from pytorch_distributed_tpu.parallel.mesh import MeshSpec, build_mesh
        from pytorch_distributed_tpu.parallel.tp import shard_state

        new_mesh = build_mesh(MeshSpec(("data",), (new_world,)),
                              devices=devs[:new_world])
        self._build_for_mesh(new_mesh, host.params)
        self.state = shard_state(host, self.param_specs, new_mesh,
                                 momentum_specs=self._mom_specs)
        if self._mfu_on:
            self._build_mfu()  # n_devices (and maybe batch) changed
        self._membership_epoch += 1
        if self.hb is not None:
            self.hb.set_membership(new_world, self._membership_epoch)
        if self.flight is not None:
            self.flight.set_membership(new_world, self._membership_epoch)
        return resume

    def _apply_remesh(self, chg, at_step: int) -> int:
        """Act on a committed ``MembershipChange`` inside ``fit``: log the
        ``remesh`` ft_event (goodput books the gap to the first step on
        the new mesh as ``remesh`` badput) and rebuild.  Returns the
        resume step."""
        kind = chg.kind
        old_world = dict(self.mesh.shape)["data"]
        self.obs.log_event("remesh", step=at_step, change=kind,
                           old_world=chg.old.world, new_world=chg.new.world,
                           epoch=chg.new.epoch, reason=chg.reason,
                           rescale=self.rescale_lr_rule)
        resume = self.remesh(chg.new.world, completed=at_step,
                             refresh_snapshot=(kind == "grow"))
        print(f"=> remesh ({kind}) at step {at_step}: world "
              f"{old_world}->{chg.new.world}, epoch {chg.new.epoch}, "
              f"resuming at step {resume} ({chg.reason})", flush=True)
        return resume

    def _row_span(self) -> Tuple[int, int]:
        """This process's row range of the global batch under the token
        sharding — the LM counterpart of DistributedSampler's per-rank
        shard (reference distributed.py:174-175).  Replicated axes (e.g.
        a cross-process TP mesh with data=1) span the full batch; a
        cross-process data axis yields a contiguous slice.  Static for
        fixed shapes, so computed once."""
        if self._span is None:
            B = self.batch_size
            if jax.process_count() == 1:
                self._span = (0, B)
            else:
                gm = self.token_sharding.devices_indices_map(
                    (B, self.dataset.seq_len))
                me = jax.process_index()
                spans = [
                    (s[0].start or 0, B if s[0].stop is None else s[0].stop)
                    for d, s in gm.items() if d.process_index == me
                ]
                lo = min(s[0] for s in spans)
                hi = max(s[1] for s in spans)
                # (min, max) assumes this process's row slices tile a
                # contiguous range; a future hybrid/multi-slice device
                # order could interleave processes, and an over-wide span
                # would surface as a confusing shape error deep inside
                # make_array_from_process_local_data (advisor r3).
                rows = sum(b - a for a, b in set(spans))
                if hi - lo != rows:
                    raise ValueError(
                        f"process {me} holds a non-contiguous row shard "
                        f"{sorted(set(spans))} of the global batch; "
                        "contiguous per-process rows are required for the "
                        "local-assembly feed path"
                    )
                self._span = (lo, hi)
        return self._span

    def _local_rows(self, global_batch: np.ndarray) -> np.ndarray:
        """Slice an already-assembled global batch down to this process's
        rows (prefer ``_local_batch``, which never assembles foreign rows)."""
        lo, hi = self._row_span()
        return global_batch[lo:hi]

    def _local_batch(self, ds, step: int) -> np.ndarray:
        """Assemble ONLY this process's rows of logical global batch
        ``step`` — no cross-host redundant window stacking."""
        return _wraparound_batch(ds, step, self.batch_size,
                                 rows=self._row_span())

    def _put_tokens(self, local_tokens: np.ndarray) -> jax.Array:
        """This process's host rows → sharded global device array (the LM
        counterpart of DeviceFeeder._put)."""
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                self.token_sharding, local_tokens
            )
        return jax.device_put(local_tokens, self.token_sharding)

    def _wd_watch(self, label: str, step: Optional[int] = None):
        """Watchdog attribution context for a jitted call (inert when
        ``watch_recompiles`` is off)."""
        if self.watchdog is not None:
            return self.watchdog.watch(label, step=step)
        import contextlib

        return contextlib.nullcontext()

    def _preempt_agreed(self) -> bool:
        """Cross-process 'any rank flagged?' — every rank calls this at the
        same step (it runs a collective on multi-process meshes)."""
        if self._agree is None:
            from pytorch_distributed_tpu.utils.preempt import (
                PreemptionAgreement,
            )

            self._agree = PreemptionAgreement(self.mesh)
        return self._agree(self.preempt.triggered)

    def evaluate(self) -> Tuple[float, float, float]:
        """Held-out ``(loss, perplexity, next-token acc%)`` over
        ``eval_batches`` batches; prints the summary line (the LM analogue of
        the reference's ``* Acc@1 …``, distributed.py:321-322)."""
        if self._eval_fn is None:
            raise ValueError("LMTrainer built without eval_dataset")
        totals = {"loss_sum": 0.0, "correct": 0.0, "count": 0.0}
        for i in range(self.eval_batches):
            tokens = self._put_tokens(self._local_batch(self.eval_dataset, i))
            with self._wd_watch("lm_eval_step"):
                sums = self._eval_fn(self.state, tokens)
            for k in totals:
                totals[k] += float(sums[k])
        count = max(totals["count"], 1.0)
        loss = totals["loss_sum"] / count
        ppl = float(np.exp(min(loss, 30.0)))
        acc = totals["correct"] * 100.0 / count
        print(f" * Eval loss {loss:.4f} ppl {ppl:.2f} Acc@1 {acc:.2f}",
              flush=True)
        self.eval_history.append((loss, ppl, acc))
        return loss, ppl, acc

    def _ft_record(self, completed: int) -> dict:
        """The step-granular resume record for a checkpoint at
        ``completed`` finished steps (LM is epochless: step == global
        step; the wraparound batching is purely step-indexed, so these
        two integers restore the exact token stream)."""
        return {
            "step": int(completed),
            "global_step": int(completed),
            "lr_scale": (self.ft_guard.lr_scale
                         if self.ft_guard is not None else 1.0),
        }

    def _save_checkpoint(self, completed: int, is_best: bool = False) -> None:
        """ALL ranks call: save_checkpoint gathers sharded leaves with a
        cross-process collective before its primary guard — gating the
        call itself on is_primary would deadlock multi-host TP/SP runs.
        best_acc1 slot carries the best perplexity for the LM family."""
        from pytorch_distributed_tpu.train.checkpoint import save_checkpoint

        save_checkpoint(
            self.checkpoint_dir, self.state, 0, "transformer_lm",
            self.best_ppl if self._eval_fn is not None else 0.0,
            is_best=is_best, is_primary=self.is_primary,
            ft=self._ft_record(completed),
        )
        if self.flight is not None:
            self.flight.event("checkpoint", completed)

    def _rollback(self, step: int) -> None:
        """Divergence recovery: restore the last-good snapshot and back
        off the LR scale (ft/divergence.py policy).  The jitted step's
        ``in_shardings`` re-shard the host-numpy snapshot on the next
        call, exactly like a ``--resume`` load."""
        restored_step = None
        if self._keeper is not None and self._keeper.has_snapshot:
            self.state = self._keeper.restore()
            restored_step = self._keeper.step
        scale = self.ft_guard.note_rollback(step, restored_step)
        print(f"=> divergence rollback at step {step}: restored state from "
              f"step {restored_step}, lr scale now {scale:g}", flush=True)
        if self.flight is not None:
            # The rollback itself is forensic: snapshot the ring (the
            # `rollback` ft_event is already in it via attach_to_metrics).
            self.flight.dump("rollback")

    def _emit_ledgers(self, tokens, lr) -> None:
        """AOT-compile the live LM step once against the first batch's
        real shardings and itemize both opt-in receipts off that single
        lowering (``analysis.lowering.aot_ledgers`` — counted against
        the process-wide compile budget and, with ``lowering_cache``
        set, persisted in the service's artifact layout): the collective
        ledger and the static HBM memory ledger.  The cached metrics
        fields ride every subsequent record."""
        from pytorch_distributed_tpu.analysis import lowering
        from pytorch_distributed_tpu.obs import comms

        args = (self.state, tokens, lr)
        ledger, mled = lowering.aot_ledgers(
            self.step_fn, args, step="lm_step",
            mesh_shape=dict(self.mesh.shape),
            want_comm=self._comm_ledger_path is not None,
            want_mem=self._mem_ledger_path is not None,
            cache_dir=self._lowering_cache)
        self._comm_fields = {}
        if ledger is not None:
            self._comm_fields.update(ledger.metrics_fields())
            if ledger.entries:
                top = max(ledger.entries, key=lambda e: e.wire_bytes)
                self._flight_coll = {"kind": top.kind, "bytes": top.bytes,
                                     "name": top.name}
            if self.is_primary:
                comms.write_ledgers(self._comm_ledger_path, [ledger])
                print(f"=> wrote comm ledger ({ledger.count} collectives, "
                      f"{ledger.total_bytes} B/step payload) to "
                      f"{self._comm_ledger_path}", flush=True)
        if mled is not None:
            from pytorch_distributed_tpu.obs import memory

            self._comm_fields.update(mled.metrics_fields())
            if self.is_primary:
                memory.write_ledgers(self._mem_ledger_path, [mled])
                print(f"=> wrote mem ledger (peak {mled.peak_bytes} B at "
                      f"instr {mled.peak_index}/{mled.n_instructions}) to "
                      f"{self._mem_ledger_path}", flush=True)

    def _book_stepattr_phases(self) -> None:
        """Image-Trainer twin: hand the attribution recorder the comm
        ledger's wire bytes (when one ran) and book the static per-phase
        roofline ledger once as a ``stepattr_phases`` ft_event."""
        if self.stepattr is None or self._stepattr_phases_booked:
            return
        self._stepattr_phases_booked = True
        from pytorch_distributed_tpu.obs import flops, stepattr

        wire = float((self._comm_fields or {}).get("comm_wire_bytes", 0.0))
        if wire > 0:
            self.stepattr.set_comm_bytes(wire)
        try:
            cost = flops.lm_step_cost_for(
                self.model, self.batch_size, self.dataset.seq_len,
                fused_ce_chunks=self._step_kwargs["fused_ce_chunks"])
        except (AttributeError, KeyError, ValueError):
            return  # exotic model: attribution still runs, no roofline
        kind = getattr(self.mesh.devices.flat[0], "device_kind", "")
        prof = stepattr.phase_profile(
            cost.breakdown,
            stepattr.split_step_bytes(cost.bytes, cost.params),
            comm_bytes=wire,
            peak_flops=flops.chip_peak_flops(kind),
            hbm_bw=flops.chip_hbm_bw(kind),
            link_bw=flops.chip_link_bytes(kind),
            n_devices=self.mesh.devices.size)
        self.obs.log_event("stepattr_phases",
                           **stepattr.phase_event_fields(prof))

    def _token_iter(self, start: int, steps: int):
        """Token stream for logical steps ``[start, steps)`` — prefetched
        via AsyncFeeder or synchronous.  Factored out so an elastic
        re-mesh can rebuild it mid-fit: the generators bind ``self``
        lazily, so a fresh iterator picks up the new batch size, row span,
        and token sharding."""
        from pytorch_distributed_tpu.data.loader import AsyncFeeder

        host_iter = (
            self._local_batch(self.dataset, i) for i in range(start, steps)
        )
        if self.prefetch > 0:
            return AsyncFeeder(self._put_tokens,
                               prefetch=self.prefetch)(host_iter)
        # synchronous baseline (measured in lm_feeder_bench)
        return (self._put_tokens(b) for b in host_iter)

    def fit(self, steps: int, print_freq: int = 10) -> float:
        from pytorch_distributed_tpu.obs.trace import (
            ProfileWindow,
            dump_beside_capture,
            span,
        )

        profiler = ProfileWindow(self.profile_dir, steps=self.profile_steps)
        if self.watchdog is not None:
            self.watchdog.install()  # idempotent (re-fit after a fit)
        if self._exporter is not None and not self._exporter.running:
            # A prior fit's obs.close() stopped the owned exporter;
            # re-register so this fit serves (and tears down) again.
            self.obs.register(self._exporter)

        meters = StepMeters(
            steps,
            [("loss", "Loss", ":.4e"), ("acc", "Acc@1", ":6.2f")],
            prefix="Step: ",
        )
        start = min(self._start_step, steps)
        # Tokens per optimizer step — the LM throughput unit (tokens/s).
        tokens_per_step = self.batch_size * self.dataset.seq_len
        final_ppl = None  # ppl from an interval eval on the very last step
        preempted = False
        completed = start  # steps finished (preemption/ft checkpoints)
        # Prefetch ≥2: batch assembly (real host work for TextFileDataset
        # windows) + async transfer dispatch run on a producer thread, off
        # the step hot path — the LM counterpart of the image DeviceFeeder
        # (reference apex data_prefetcher, apex_distributed.py:115-169).
        # Each process assembles ONLY its own rows (wraparound batching,
        # the convention both LM datasets implement); a resumed run starts
        # the stream at the checkpointed step — no epoch rerun.
        token_iter = self._token_iter(start, steps)
        if self._keeper is not None and not self._keeper.has_snapshot:
            # Initial last-good snapshot (all ranks — see StateKeeper).
            self._keeper.update(self.state, start)
        lr_val = None  # cached: jnp.float32() only when the value changes
        lr = jnp.float32(self.lr)
        # Flight recorder death paths: signal-dump chain (chains to the
        # caller's PreemptionGuard handler when both hold the same
        # signals) + the collective-hang watchdog daemon.
        flight_sig = None
        if self.flight is not None:
            import signal as _signal
            import threading as _threading

            if _threading.current_thread() is _threading.main_thread():
                from pytorch_distributed_tpu.obs.flightrec import (
                    FlightSignalDump,
                )

                sigs = (getattr(self.preempt, "_signals", None)
                        or (_signal.SIGTERM,))
                flight_sig = FlightSignalDump(self.flight,
                                              signals=sigs).install()
            if self._hang_wd is not None:
                self._hang_wd.start()
        try:
            meters.restart_clock()
            profiler.epoch_begin(0)
            i = start
            while i < steps:
                profiler.step_begin(0, i)
                # One `step` span an iteration (obs/trace.py), as in
                # Trainer.train_epoch: its children are the feeder's
                # `data_wait`, `dispatch` and the `host_sync` drains.
                with span("step", id=i):
                    # print_freq cadence: the cross-process agreement collective
                    # (see utils/preempt.py) must run at the same step on every
                    # rank, and stays off the per-step hot path.
                    if (self.preempt is not None and i % print_freq == 0
                            and self._preempt_agreed()):
                        print(f"=> preemption signal: stopping at step {i}",
                              flush=True)
                        self.obs.log_event("preempt", step=i)
                        preempted = True
                        break
                    if self.chaos is not None:
                        self.chaos.on_step(self, i)
                    if self.elastic is not None:
                        # Membership epochs are coordinator-committed and read
                        # by every rank at the same step — an agreed value,
                        # not a local liveness probe (synclint would otherwise
                        # flag the re-mesh below as a divergent collective).
                        chg = self.elastic.poll(i)  # synclint: agreement
                        if chg is not None:
                            # Membership changed: rebuild against the survivor
                            # set and restart the token stream at the resume
                            # step (a shrink rewinds to the last-good snapshot;
                            # the step-indexed batching regenerates the same
                            # tokens, so retrained steps replay, not drift).
                            token_iter.close()
                            completed = i = self._apply_remesh(chg, at_step=i)
                            token_iter = self._token_iter(i, steps)
                            tokens_per_step = (self.batch_size
                                               * self.dataset.seq_len)
                            lr_val = None  # re-push the LR to the new mesh
                            meters.restart_clock()
                            continue
                    # Attribution windows (--step-attr): data_wait wraps
                    # batch acquisition *and* the chaos on_batch hook, so
                    # injected loader delay lands in the measured component.
                    sa = self.stepattr
                    _dw = sa.data_wait if sa is not None else nullcontext
                    with _dw():
                        tokens = next(token_iter)
                    if self.chaos is not None:
                        with _dw():
                            tokens = self.chaos.on_batch(i, tokens)
                    val = (self.lr_schedule(i)
                           if self.lr_schedule is not None else self.lr)
                    if self.ft_guard is not None:
                        val = val * self.ft_guard.lr_scale
                    val = val * self._elastic_lr_scale
                    if val != lr_val:
                        lr_val, lr = val, jnp.float32(val)
                    if ((self._comm_ledger_path is not None
                            or self._mem_ledger_path is not None)
                            and self._comm_fields is None):
                        self._emit_ledgers(tokens, lr)
                    if self.flight is not None:
                        # Ring: step window + collective region (labelled with
                        # the ledger's dominant entry when the AOT lowering
                        # ran) — two deque appends, no sync/I/O.
                        self.flight.step_begin(i)
                        fc = self._flight_coll or {}
                        self.flight.coll_enter(i, kind=fc.get("kind"),
                                               bytes=fc.get("bytes"),
                                               name=fc.get("name"))
                    if self.chaos is not None:
                        self.chaos.on_collective(self, i)
                    _dev = sa.device if sa is not None else nullcontext
                    _hs = sa.host_sync if sa is not None else nullcontext
                    with span("dispatch") as booked, scope("lm_step"), \
                            self._wd_watch("lm_step", i), _dev():
                        self.state, metrics = self.step_fn(
                            self.state, tokens, lr)
                        # a model's own counters (``counter_names``: a
                        # configured decoder's routing) ride on the record
                        # as unready device scalars: whoever reads the
                        # record converts them, the loop does not
                        booked.set(**{k: metrics[k] for k in getattr(
                            self.model, "counter_names", ())})
                        if sa is not None:
                            # The step's blocking transfer: without it, async
                            # dispatch smears step N's device time into N+1's
                            # windows.  Only when --step-attr opted in;
                            # overhead fenced <2% p50 in RESULTS_stepattr.json.
                            jax.block_until_ready(metrics)  # shardlint: allow-sync
                    if self.flight is not None:
                        self.flight.coll_exit(i)
                        self.flight.step_end(i)
                    completed = i + 1
                    with span("host_sync"), _hs():
                        dt = meters.update(metrics, self.batch_size)
                    extra = (dict(self._mfu.fields(dt))
                             if self._mfu is not None else {})
                    if self._comm_fields:
                        extra.update(self._comm_fields)
                    if sa is not None:
                        extra.update(sa.fields(dt))
                    # log_step's lazy-flush scalar drain accrues to the *next*
                    # step's host_sync window (its dt covers this wall time).
                    with span("host_sync"), _hs():
                        self.obs.log_step(
                            i, step_time=dt, n_items=tokens_per_step, lr=lr,
                            scalars=dict(metrics),  # incl. norms when log_norms on
                            extra=extra or None,
                        )
                    # booked after the first step's record so the event's
                    # timestamp cannot widen the post-hoc goodput wall span
                    # back across the step-0 compile
                    if sa is not None and not self._stepattr_phases_booked:
                        self._book_stepattr_phases()
                    if self.hb is not None:
                        from pytorch_distributed_tpu.obs import (
                            sample_process_memory,
                        )
                        self.hb.beat(i, step_time_ema=self.obs.ema,
                                     last_ft=self.obs.last_event_kind,
                                     mem_bytes=sample_process_memory(),
                                     data_wait_ms=(sa.data_wait_ema_ms
                                                   if sa is not None else None))
                        if self.flight is not None:
                            self.flight.heartbeat(
                                {"step": i,
                                 "last_ft": self.obs.last_event_kind})
                    with span("host_sync"):
                        meters.maybe_display(i, print_freq)
                    at_save = (self.save_steps > 0
                               and completed % self.save_steps == 0)
                    if self.ft_guard is not None:
                        # Lazy-sync policy: flags buffer unconverted and drain
                        # every check_every steps — forced at a save boundary so
                        # a snapshot never races an undetected divergence.
                        rollback = self.ft_guard.observe(
                            i, metrics.get("nonfinite"))
                        if at_save:
                            # Agreed: the drained flag is the in-step
                            # all-reduced nonfinite count — every rank reads
                            # the identical verdict at the same boundary.
                            rollback = self.ft_guard.drain() or rollback  # synclint: agreement
                        if rollback:
                            self._rollback(i)
                        # A flagged streak means the current state is suspect —
                        # don't refresh the last-good snapshot from it.
                        at_save = at_save and self.ft_guard.consecutive == 0
                    if at_save:
                        if self._keeper is not None:
                            self._keeper.update(self.state, completed)
                        if self.checkpoint_dir:
                            self._save_checkpoint(completed)
                            meters.restart_clock()  # exclude ckpt I/O from meter
                    if (
                        self._eval_fn is not None
                        and self.eval_every > 0
                        and (i + 1) % self.eval_every == 0
                    ):
                        _, final_ppl, _ = self.evaluate()
                        self.best_ppl = min(self.best_ppl, final_ppl)
                        meters.restart_clock()  # eval must not pollute the meter
                    else:
                        final_ppl = None
                    i += 1
            if self.ft_guard is not None and self.ft_guard.drain():  # synclint: agreement
                # Trailing flags buffered past the last cadence point must
                # resolve before the end-of-fit checkpoint can capture a
                # diverged state.  Agreed: the flag drains an in-step
                # all-reduced scalar.
                self._rollback(completed)
        except BaseException as e:
            if self.flight is not None:
                from pytorch_distributed_tpu.ft.integrity import (
                    CheckpointCorruptError,
                )

                self.flight.record("exception", completed,
                                   error=type(e).__name__)
                self.flight.dump("checkpoint_corrupt"
                                 if isinstance(e, CheckpointCorruptError)
                                 else f"exception:{type(e).__name__}")
            raise
        finally:
            token_iter.close()  # unblocks the producer on early exit
            profiler.epoch_end()
            if self.profile_dir:
                dump_beside_capture(self.profile_dir, self.step_fn)
            if self._hang_wd is not None:
                self._hang_wd.stop()
            if flight_sig is not None:
                flight_sig.uninstall()
            if self.watchdog is not None:
                self.watchdog.uninstall()
            if self.hb is not None:
                from pytorch_distributed_tpu.obs import sample_process_memory
                self.hb.close(int(self.state.step) - 1,
                              step_time_ema=self.obs.ema,
                              last_ft=self.obs.last_event_kind,
                              mem_bytes=sample_process_memory(),
                              data_wait_ms=(self.stepattr.data_wait_ema_ms
                                            if self.stepattr is not None
                                            else None))
            self.obs.flush()
            if self._goodput is not None:
                print(f"=> {self._goodput.format_summary()}", flush=True)
            self.obs.close()
        is_best = False
        if self._eval_fn is not None and not preempted:
            # Preempted runs skip the final eval: the SIGTERM grace window
            # belongs to the checkpoint, and a partial-state eval must not
            # contend for the best-checkpoint slot.
            if final_ppl is None:  # last step didn't land on an eval boundary
                _, final_ppl, _ = self.evaluate()
            # <= so the final state is marked best when it ties the best seen
            # (the common case: the just-run interval eval set best_ppl).
            is_best = final_ppl <= self.best_ppl
            self.best_ppl = min(self.best_ppl, final_ppl)
        last_loss = meters["loss"].val  # end-of-training loss, not run avg
        if self.checkpoint_dir:
            # End-of-fit checkpoint; its ft record carries the exact
            # completed-step count, so a preempted run resumes mid-stream.
            self._save_checkpoint(completed, is_best=is_best)
        return last_loss
