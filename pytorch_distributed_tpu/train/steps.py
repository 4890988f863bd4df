"""Jitted SPMD train/eval steps — the heart of the framework.

Replaces the reference's hot loop (reference distributed.py:242-276), which
performs 4 synchronous collectives + 3 ``.item()`` host syncs per batch
*before* backward even starts (SURVEY.md §3.1a note), with one compiled XLA
program per step:

- forward, loss, backward, gradient sync, SGD update, and the global metric
  means are all **inside** the jitted function;
- gradient all-reduce is not a backward hook (DDP, distributed.py:147) but a
  collective XLA fuses into the step — under GSPMD it is inserted
  automatically from the shardings; in the explicit variant we write the
  ``psum`` ourselves inside ``shard_map`` (Horovod-recipe analogue, with
  bf16 wire compression ≙ horovod_distributed.py:159-164);
- the reference's ``barrier()`` has no equivalent: XLA programs are
  bulk-synchronous by construction (SURVEY.md §5.8).

Metrics are returned as unready device scalars; meters read them lazily, so
the host never blocks inside the loop.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from pytorch_distributed_tpu.models.transformer import bind_mesh
from pytorch_distributed_tpu.obs.trace import StepProgram, scope
from pytorch_distributed_tpu.ops import cross_entropy, qcomm, topk_correct
from pytorch_distributed_tpu.parallel import overlap as overlap_lib
from pytorch_distributed_tpu.parallel import zero as zero_lib
from pytorch_distributed_tpu.train.optim import sgd_update
from pytorch_distributed_tpu.train.state import TrainState

Batch = Dict[str, jnp.ndarray]
Metrics = Dict[str, jnp.ndarray]


def tree_l2_norm(tree) -> jnp.ndarray:
    """Global L2 norm of a pytree, f32 accumulation — computed in-graph so
    the host never syncs for it (meters / MetricsLogger convert lazily)."""
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        for leaf in jax.tree_util.tree_leaves(tree)
    ))


def nonfinite_flag(loss: jnp.ndarray, grad_norm: jnp.ndarray) -> jnp.ndarray:
    """1.0 when loss or the global grad norm is NaN/inf, else 0.0 — the
    divergence-guard observable (ft/divergence.py).  The grad norm covers
    gradient overflow the loss alone misses (f32 loss can stay finite while
    a bf16 backward has already produced infs)."""
    ok = jnp.logical_and(jnp.isfinite(loss), jnp.isfinite(grad_norm))
    return jnp.logical_not(ok).astype(jnp.float32)


def gate_update(bad: jnp.ndarray, old_tree, new_tree):
    """Select ``old_tree`` leaf-wise when ``bad`` (a 0/1 scalar) is set —
    the in-graph skip that keeps a non-finite batch's update out of the
    weights entirely, with no host round-trip.  ``jnp.where`` on a
    replicated scalar predicate compiles to a select XLA fuses into the
    optimizer; sharded leaves keep their layout."""
    pred = bad > 0
    return jax.tree_util.tree_map(
        lambda old, new: jnp.where(pred, old, new), old_tree, new_tree
    )


def _forward_and_sums(model, params, batch_stats, batch: Batch, train: bool,
                      dropout_rng=None):
    """Weighted-sum loss/metric numerators + weight count (exact over padding)."""
    variables = {"params": params, "batch_stats": batch_stats}
    # scope(): forward ops carry this name into the compiled module's
    # metadata (autodiff derives the backward op names from it), so a
    # capture's time reads by phase (obs/trace.py compiled_scopes) instead
    # of by anonymous fusions.
    with scope("forward"):
        if train:
            rngs = {"dropout": dropout_rng} if dropout_rng is not None else None
            logits, mutated = model.apply(
                variables, batch["images"], train=True,
                mutable=["batch_stats"], rngs=rngs,
            )
            new_stats = mutated.get("batch_stats", batch_stats)
        else:
            logits = model.apply(variables, batch["images"], train=False)
            new_stats = batch_stats
    with scope("loss_and_metrics"):
        w = batch["weights"].astype(jnp.float32)
        count = jnp.sum(w)
        loss_sum = cross_entropy(logits, batch["labels"], weights=w) * count
        c1 = jnp.sum(topk_correct(logits, batch["labels"], 1) * w)
        c5 = jnp.sum(topk_correct(logits, batch["labels"], 5) * w)
    return loss_sum, (logits, new_stats, c1, c5, count)


def make_train_step(
    model,
    mesh: Mesh,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    data_axis: str = "data",
    wire_dtype: Optional[jnp.dtype] = None,
    grad_compress: Optional[str] = None,
    explicit_collectives: bool = False,
    seed: int = 0,
    tx=None,
    accum_steps: int = 1,
    log_norms: bool = False,
    guard_nonfinite: bool = False,
    zero: str = "none",
    params: Optional[Any] = None,
    overlap: str = "none",
    bucket_mb: float = overlap_lib.DEFAULT_BUCKET_MB,
    wus_gather: str = "eager",
) -> Callable[[TrainState, Batch, jnp.ndarray], Tuple[TrainState, Metrics]]:
    """Build the jitted train step for ``mesh``.

    Two interchangeable gradient-sync expressions (the recipe difference
    matrix, SURVEY.md §2.3):

    - GSPMD (default): shardings in, XLA inserts the gradient all-reduce.
      ≙ DDP's fused bucketed allreduce (reference distributed.py:147-148).
    - ``explicit_collectives=True``: ``shard_map`` over the data axis with a
      hand-written ``psum`` — the Horovod-analogue; ``grad_compress="bf16"``
      reproduces fp16 gradient wire compression
      (horovod_distributed.py:159-164) as bf16-compressed collectives, and
      ``grad_compress="int8"``/``"fp8"`` goes further: a per-block
      quantized all-reduce (ops/qcomm.py, the EQuARX decomposition) with
      DynamiQ-style error feedback — the residual rides in
      ``TrainState.residual``, stacked over the data axis.

    ``grad_compress``: ``none | bf16 | int8 | fp8`` — the gradient wire
    format for the DP sync.  Under GSPMD every non-``none`` mode is a
    NUMERICS emulation only (XLA owns the collective; see the warning);
    real wire compression requires ``explicit_collectives=True``.  The
    legacy ``wire_dtype`` argument is a deprecated alias for the ``bf16``
    mode.

    ``accum_steps``: gradient accumulation — the batch is split into that
    many microbatches (strided, so each microbatch stays evenly spread over
    the data-sharded devices with no resharding), gradients/metrics are
    summed across a ``lax.scan`` inside the compiled step, and one optimizer
    update is applied.  Lets the reference's global-batch-3200 default
    (distributed.py:43-48) run on any chip count within HBM limits.  For
    BN-free, dropout-free models the numerics exactly equal the
    unaccumulated step (sum-form loss normalized once); with BatchNorm the
    batch statistics are per-microbatch (like training at the smaller batch)
    and dropout draws per-microbatch keys — standard accumulation semantics,
    same as torch.

    ``tx``: an optional optax ``GradientTransformation``.  Default (None) is
    the torch-parity SGD (train/optim.py), with ``lr`` as a live scalar
    operand; with optax the schedule lives inside ``tx`` and the ``lr``
    argument is ignored (state.momentum carries the optax opt_state).

    ``log_norms``: add in-graph global ``grad_norm``/``param_norm`` scalars
    to the metrics dict (the obs-layer observables, converted lazily by the
    MetricsLogger).  Off by default: the per-leaf reductions measurably
    lengthen XLA compiles, so the cost is only paid when a metrics sink is
    actually attached (Trainer enables it with ``--metrics-jsonl``).

    ``zero``: ``none | wus`` — ZeRO-style weight-update sharding
    (parallel/zero.py, arXiv:2004.13336).  Under ``wus`` the explicit
    path replaces the gradient all-reduce with a reduce-scatter, keeps
    the momentum buffer sharded ``P(data_axis)`` in stacked-chunk layout,
    applies the torch-parity SGD update on the 1/N shard, and all-gathers
    the parameter delta once per step; ``grad_compress`` composes — both
    wire hops ride the quantized qcomm path with error feedback
    (``compressed_reduce_scatter`` / ``compressed_all_gather``).  Under
    GSPMD the same semantics are a sharding-spec change: momentum takes
    ``fsdp_specs`` shardings (pass ``params`` so the layout can be
    derived) and XLA inserts the reduce-scatter/all-gather pair.  The
    momentum pytree under explicit wus is ``{"buf": chunks[, "agerr":
    chunks]}`` — build it with ``zero_lib.init_wus_momentum``; checkpoints
    still store the param-shaped layout (train/checkpoint.py gathers on
    save and re-chunks on restore).  Requires the default torch-parity
    SGD (``tx`` must be None: the chunked update re-implements
    ``optim._upd`` on flat shards).

    ``guard_nonfinite``: compute a ``nonfinite`` flag from loss + global
    grad norm and gate the whole update (params, momentum, BN stats) on it
    inside the compiled step — a NaN/inf batch is structurally skipped
    (state passes through unchanged except the step counter) and the flag
    lands in the metrics as a lazily-converted device scalar for the host
    ``DivergenceGuard`` policy (ft/divergence.py).  ``--nan-guard``.

    ``overlap``: ``none | bucketed`` — the comm-overlap scheduler
    (parallel/overlap.py).  ``bucketed`` partitions the gradient pytree
    into ~``bucket_mb``-MiB buckets in reverse-autodiff order and issues
    each bucket's sync (``psum`` / ``compressed_psum`` / reduce-scatter)
    as its own collective under a nested ``grad_sync``/``b<k>`` scope, so
    the sync of early-produced gradients can run concurrently with the
    remaining backward instead of as one tail-end collective; the per-leaf
    math is identical, so results are bit-equal to ``overlap="none"``.
    Requires ``explicit_collectives=True`` (under GSPMD, XLA owns the
    collective placement).  The ``--zero wus`` delta all-gather buckets
    too (``ag_b<k>`` scopes, forward order).

    ``wus_gather``: ``eager | deferred`` — with ``zero='wus'`` +
    ``overlap='bucketed'``, ``deferred`` double-buffers the param state:
    the step *stages* its delta chunks in ``momentum["pending"]`` and
    drains the previous step's at its head under a ``param_gather`` scope
    (parallel/overlap.py), so the gather overlaps the next forward.
    ``state.params`` then lag one staged delta; drain with
    ``overlap_lib.materialize_params`` before eval/checkpoint.  Build the
    momentum with an extra ``pending`` slot (``init_pending``).  Only the
    f32/bf16 delta wire supports deferral (quantized error feedback is
    step-order-dependent).

    BatchNorm semantics differ deliberately, matching each formulation's GPU
    ancestor: GSPMD BN normalizes over the *global* batch (SyncBN — XLA
    inserts the cross-replica mean), while the shard_map variant normalizes
    per shard, exactly like torch DDP's unsynced BN (the reference's
    behavior).  Running stats are pmean'd in both so replicas stay consistent.
    """

    model = bind_mesh(model, mesh)  # a ViT's kernels wrap themselves for it
    # the step under its module's name, for obs/trace.py compiled_scopes
    program = StepProgram("jit_local_step" if explicit_collectives
                          else "jit_global_step")
    mode, cast_dtype = qcomm.resolve_mode(grad_compress, wire_dtype)
    zero_mode = zero_lib.resolve_zero(zero)
    overlap_mode = overlap_lib.resolve_overlap(overlap)
    if overlap_mode == "bucketed" and not explicit_collectives:
        raise ValueError(
            "overlap='bucketed' schedules hand-written collectives and "
            "requires explicit_collectives=True (under GSPMD, XLA owns "
            "collective placement — there is nothing to bucket)")
    if wus_gather not in ("eager", "deferred"):
        raise ValueError(
            f"wus_gather must be 'eager' or 'deferred', got {wus_gather!r}")
    if wus_gather == "deferred":
        if zero_mode != "wus" or overlap_mode != "bucketed":
            raise ValueError(
                "wus_gather='deferred' is the double-buffered ZeRO-WUS "
                "delta gather — it requires zero='wus' and "
                "overlap='bucketed'")
        if mode in qcomm.QUANTIZED_MODES:
            raise ValueError(
                "wus_gather='deferred' supports the f32/bf16 delta wire "
                "only: the quantized gather's error feedback is step-order"
                "-dependent and cannot be staged across steps")
    if zero_mode == "wus":
        if tx is not None:
            raise ValueError(
                "zero='wus' implements the torch-parity SGD update on 1/N "
                "shards; an optax tx cannot be chunked — drop one of them")
        if not explicit_collectives and params is None:
            raise ValueError(
                "zero='wus' under GSPMD derives the momentum shardings "
                "from the params tree — pass params=state.params")

    def sync_grads(grads, count, residual):
        # grads arrive as *local weighted sums*; sync then normalize.
        with scope("grad_sync"):
            if overlap_mode == "bucketed":
                grads, residual = overlap_lib.bucketed_psum(
                    grads, residual, data_axis, mode=mode,
                    cast_dtype=cast_dtype, bucket_mb=bucket_mb)
            elif mode in qcomm.QUANTIZED_MODES:
                grads, residual = qcomm.compressed_psum(
                    grads, residual, data_axis, mode=mode)
            else:
                if cast_dtype is not None:
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(cast_dtype), grads)
                grads = jax.lax.psum(grads, data_axis)
            gcount = jax.lax.psum(count, data_axis)
            return jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) / gcount, grads
            ), gcount, residual

    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    base_key = jax.random.PRNGKey(seed)
    if tx is not None:
        import warnings

        warnings.warn(
            "make_train_step: tx provided — the lr argument (and the "
            "harness's step-decay schedule) plus the momentum/weight_decay "
            "settings are INACTIVE; configure schedule and regularization "
            "inside the optax transformation.",
            stacklevel=2,
        )
    if mode != "none" and not explicit_collectives:
        import warnings

        warnings.warn(
            f"make_train_step: grad_compress={mode!r} under GSPMD is a "
            "NUMERICS emulation only — XLA places the gradient all-reduce "
            "from the shardings, so the quantize/cast rounds already-synced "
            "values and does not compress the collective wire format. Use "
            "explicit_collectives=True for true compressed-wire gradient "
            "sync (the Horovod-compression analogue).",
            stacklevel=2,
        )

    def apply_updates(state: TrainState, grads, lr):
        with scope("optimizer"):
            if tx is None:
                return sgd_update(
                    grads, state.momentum, state.params, lr,
                    momentum=momentum, weight_decay=weight_decay,
                )
            import optax

            updates, new_opt = tx.update(grads, state.momentum, state.params)
            return optax.apply_updates(state.params, updates), new_opt

    def micro_grads(params, stats, mbatch, mrng):
        """Unnormalized (sum-form) grads + metric sums for one microbatch."""

        def loss_fn(params):
            loss_sum, aux = _forward_and_sums(
                model, params, stats, mbatch, train=True, dropout_rng=mrng
            )
            return loss_sum, aux

        (loss_sum, (_, new_stats, c1, c5, count)), grads = (
            jax.value_and_grad(loss_fn, has_aux=True)(params)
        )
        return grads, new_stats, (loss_sum, c1, c5, count)

    def accumulated_grads(params, stats, batch: Batch, rng):
        """Sum-form grads/metric-sums over ``accum_steps`` strided microbatches.

        Shared by both formulations: under GSPMD the batch is the global
        batch; under shard_map it is the per-shard slice (the strided split
        is then shard-local, and the single psum still happens *after* the
        scan — one collective per optimizer step, not per microbatch, which
        is the whole point of accumulating)."""
        if accum_steps == 1:
            return micro_grads(params, stats, batch, rng)
        b = batch["images"].shape[0]
        if b % accum_steps:
            raise ValueError(
                f"batch dimension {b} (per-shard under explicit collectives, "
                f"global under GSPMD) is not divisible by accum_steps "
                f"{accum_steps}"
            )
        # Strided split: microbatch i = samples [i::accum_steps].  A
        # contiguous split would concentrate each microbatch on a subset
        # of the data-sharded devices and force an all-to-all of the
        # whole input every step; the strided layout keeps every
        # microbatch evenly distributed shard-locally.
        micro = jax.tree_util.tree_map(
            lambda v: v.reshape(
                (v.shape[0] // accum_steps, accum_steps) + v.shape[1:]
            ).swapaxes(0, 1),
            batch,
        )

        def body(carry, xs):
            g_acc, stats, sums = carry
            mb, i = xs
            g, stats, s = micro_grads(params, stats, mb, jax.random.fold_in(rng, i))
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            sums = tuple(a + b for a, b in zip(sums, s))
            return (g_acc, stats, sums), None

        init = (
            jax.tree_util.tree_map(jnp.zeros_like, params),
            stats,
            (jnp.float32(0), jnp.float32(0), jnp.float32(0), jnp.float32(0)),
        )
        (grads, new_stats, sums), _ = jax.lax.scan(
            body, init, (micro, jnp.arange(accum_steps))
        )
        return grads, new_stats, sums

    def local_step(state: TrainState, batch: Batch, lr: jnp.ndarray):
        """Runs per-shard under shard_map; all reductions explicit."""
        # Per-step, per-shard dropout stream (shards see different data).
        rng = jax.random.fold_in(
            jax.random.fold_in(base_key, state.step),
            jax.lax.axis_index(data_axis),
        )
        params = state.params
        if wus_gather == "deferred":
            # Double-buffered WUS: drain the PREVIOUS step's staged delta
            # chunks at the head of this step — in dataflow terms layer
            # k's gather only blocks layer k's forward, so the gather
            # overlaps this step's earlier-layer compute.
            params = overlap_lib.drain_pending(
                params, state.momentum["pending"], data_axis,
                cast_dtype=cast_dtype)
        grads, new_stats, (loss_sum, c1, c5, count) = accumulated_grads(
            params, state.batch_stats, batch, rng
        )
        if zero_mode == "wus":
            # Weight-update sharding: reduce-scatter the gradient sums so
            # this rank owns the exact f32 sum of its 1/N chunk, update on
            # the shard (momentum stays chunked), all-gather the delta.
            n = jax.lax.axis_size(data_axis)
            idx = jax.lax.axis_index(data_axis)
            with scope("grad_sync"):
                if overlap_mode == "bucketed":
                    gchunks, new_residual = overlap_lib.bucketed_reduce_scatter(
                        grads, state.residual, data_axis, n, mode=mode,
                        cast_dtype=cast_dtype, bucket_mb=bucket_mb)
                elif mode in qcomm.QUANTIZED_MODES:
                    gchunks, new_residual = qcomm.compressed_reduce_scatter(
                        grads, state.residual, data_axis, mode=mode)
                else:
                    gchunks = zero_lib.reduce_scatter_grads(
                        grads, data_axis, n, cast_dtype=cast_dtype)
                    new_residual = state.residual
                gcount = jax.lax.psum(count, data_axis)
                gchunks = jax.tree_util.tree_map(
                    lambda g: g / gcount, gchunks)
            with scope("optimizer"):
                if wus_gather == "deferred":
                    # Stage this step's deltas; the next step drains them.
                    deltas, new_buf = zero_lib.wus_update_chunks(
                        params, state.momentum, gchunks, lr, idx, n,
                        momentum_coef=momentum, weight_decay=weight_decay)
                    new_params = params
                    new_momentum = {
                        "buf": new_buf,
                        "pending": jax.tree_util.tree_map(
                            lambda d: d.reshape((1,) + d.shape), deltas),
                    }
                else:
                    new_params, new_momentum = zero_lib.wus_apply_updates(
                        params, state.momentum, gchunks, lr, idx, n,
                        data_axis, momentum_coef=momentum,
                        weight_decay=weight_decay, mode=mode,
                        cast_dtype=cast_dtype,
                        bucket_mb=(bucket_mb if overlap_mode == "bucketed"
                                   else None))
        else:
            grads, gcount, new_residual = sync_grads(
                grads, count, state.residual)
            new_params, new_momentum = apply_updates(state, grads, lr)
        # BN running stats: average local EMAs across shards so replicas agree.
        new_stats = jax.lax.pmean(new_stats, data_axis)
        metrics = {
            "loss": jax.lax.psum(loss_sum, data_axis) / gcount,
            "acc1": jax.lax.psum(c1, data_axis) * 100.0 / gcount,
            "acc5": jax.lax.psum(c5, data_axis) * 100.0 / gcount,
        }
        gnorm = None
        if log_norms or guard_nonfinite:
            if zero_mode == "wus":
                # Reduce-scattered chunks are disjoint across ranks, so the
                # replicated-path shortcut (per-shard norm == global norm)
                # does not hold — one extra scalar psum of per-chunk square
                # sums recovers the exact global norm (padding is zeros).
                gnorm = jnp.sqrt(jax.lax.psum(
                    zero_lib.chunk_sq_sum(gchunks), data_axis))
            else:
                # Synced grads are identical on every shard, so the
                # per-shard norm IS the global norm — no extra collective.
                gnorm = tree_l2_norm(grads)
        if guard_nonfinite:
            bad = nonfinite_flag(metrics["loss"], gnorm)
            new_params = gate_update(bad, state.params, new_params)
            new_momentum = gate_update(bad, state.momentum, new_momentum)
            new_stats = gate_update(bad, state.batch_stats, new_stats)
            new_residual = gate_update(bad, state.residual, new_residual)
            metrics["nonfinite"] = bad
        if log_norms:
            metrics["grad_norm"] = gnorm
            metrics["param_norm"] = tree_l2_norm(new_params)
        return (
            TrainState(state.step + 1, new_params, new_stats, new_momentum,
                       new_residual),
            metrics,
        )

    def global_step(state: TrainState, batch: Batch, lr: jnp.ndarray):
        """GSPMD formulation: global-semantics math, XLA infers collectives."""
        program.note(state, batch, lr)
        rng = jax.random.fold_in(base_key, state.step)
        grads, new_stats, (loss_sum, c1, c5, count) = accumulated_grads(
            state.params, state.batch_stats, batch, rng
        )
        count = jnp.maximum(count, 1.0)
        grads = jax.tree_util.tree_map(lambda g: g / count, grads)
        new_residual = state.residual
        if mode in qcomm.QUANTIZED_MODES:
            with scope("grad_sync"):
                grads, new_residual = qcomm.compress_emulated(
                    grads, state.residual, mode)
        elif cast_dtype is not None:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(cast_dtype).astype(jnp.float32), grads
            )
        new_params, new_momentum = apply_updates(state, grads, lr)
        metrics = {
            "loss": loss_sum / count,
            "acc1": c1 * 100.0 / count,
            "acc5": c5 * 100.0 / count,
        }
        gnorm = (tree_l2_norm(grads)
                 if (log_norms or guard_nonfinite) else None)
        if guard_nonfinite:
            bad = nonfinite_flag(metrics["loss"], gnorm)
            new_params = gate_update(bad, state.params, new_params)
            new_momentum = gate_update(bad, state.momentum, new_momentum)
            new_stats = gate_update(bad, state.batch_stats, new_stats)
            new_residual = gate_update(bad, state.residual, new_residual)
            metrics["nonfinite"] = bad
        if log_norms:
            metrics["grad_norm"] = gnorm
            metrics["param_norm"] = tree_l2_norm(new_params)
        return (
            TrainState(state.step + 1, new_params, new_stats, new_momentum,
                       new_residual),
            metrics,
        )

    replicated = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(data_axis))
    batch_shardings = {"images": sharded, "labels": sharded, "weights": sharded}
    # The error-feedback residual of the explicit quantized path is per-rank
    # state: stacked (n_data, *shape) leaves sharded over the data axis so
    # each rank owns exactly its slot (a TrainState-shaped prefix tree; the
    # other fields stay replicated).
    state_sharding = replicated
    state_spec = P()
    quantized = mode in qcomm.QUANTIZED_MODES
    if explicit_collectives and (quantized or zero_mode == "wus"):
        # Weight-update sharding adds a second sharded-state subtree: the
        # stacked-chunk momentum {"buf"[, "agerr"]} rides P(data_axis) with
        # the same one-slot-per-rank discipline as the residual.
        res_sh = (NamedSharding(mesh, P(data_axis)) if quantized
                  else replicated)
        mom_sh = (NamedSharding(mesh, P(data_axis)) if zero_mode == "wus"
                  else replicated)
        state_sharding = TrainState(
            step=replicated, params=replicated, batch_stats=replicated,
            momentum=mom_sh, residual=res_sh)
        state_spec = TrainState(
            step=P(), params=P(), batch_stats=P(),
            momentum=P(data_axis) if zero_mode == "wus" else P(),
            residual=P(data_axis) if quantized else P())
    elif zero_mode == "wus":
        # GSPMD WUS is a layout statement: momentum leaves take their
        # fsdp_specs sharding while params stay replicated; XLA's SPMD
        # partitioner inserts the gradient reduce-scatter (into the
        # sharded buffer) and the parameter-delta all-gather on its own.
        mom_sharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            zero_lib.zero_momentum_specs(params, mesh, data_axis=data_axis))
        state_sharding = TrainState(
            step=replicated, params=replicated, batch_stats=replicated,
            momentum=mom_sharding, residual=replicated)

    if explicit_collectives:
        batch_specs = {k: P(data_axis) for k in ("images", "labels", "weights")}
        sharded_step = shard_map(
            local_step,
            mesh=mesh,
            in_specs=(state_spec, batch_specs, P()),
            out_specs=(state_spec, P()),
            check_vma=False,
        )

        @functools.wraps(local_step)  # the module keeps its name
        def stepped(state, batch, lr):
            program.note(state, batch, lr)  # the global shapes, not a shard's
            return sharded_step(state, batch, lr)
    else:
        stepped = global_step

    return program.jit(
        stepped,
        in_shardings=(state_sharding, batch_shardings, replicated),
        out_shardings=(state_sharding, replicated),
        donate_argnums=(0,),
    )


def state_shardings(mesh: Mesh, data_axis: str = "data",
                    residual_sharded: bool = False,
                    momentum_sharding=None) -> TrainState:
    """Where a ``TrainState`` lives between steps, as a ``TrainState`` of
    shardings: everything replicated, except the explicit quantized
    path's stacked residuals (``residual_sharded``: ``P(data_axis)``) and
    ``--zero wus`` momentum (``momentum_sharding``: a NamedSharding prefix
    or a momentum-shaped tree of them)."""
    replicated = NamedSharding(mesh, P())
    return TrainState(
        step=replicated,
        params=replicated,
        batch_stats=replicated,
        momentum=(replicated if momentum_sharding is None
                  else momentum_sharding),
        residual=(NamedSharding(mesh, P(data_axis)) if residual_sharded
                  else replicated),
    )


def make_eval_step(
    model,
    mesh: Mesh,
    data_axis: str = "data",
    residual_sharded: bool = False,
    momentum_sharding=None,
) -> Callable[[TrainState, Batch], Metrics]:
    """Distributed evaluation step (reference validate(),
    distributed.py:279-324 + the README's distributed-eval chapter).

    Returns weighted *sums* (loss·w, correct@1, correct@5, count) so the host
    can aggregate exactly over an epoch — the all-reduce lives inside the
    compiled program; no ``barrier()`` + 3 ``all_reduce`` calls per batch.

    ``residual_sharded``: the explicit quantized grad-sync path
    (``grad_compress=int8|fp8``) carries stacked error-feedback residuals
    sharded over ``data_axis`` in ``TrainState.residual``; eval never reads
    them, but the in_shardings must still describe them or pjit rejects the
    state.

    ``momentum_sharding``: same story for ``--zero wus`` optimizer state —
    pass the momentum sharding (a NamedSharding prefix or a momentum-shaped
    tree of them) the train step uses; ``None`` keeps the replicated-DP
    default.
    """

    model = bind_mesh(model, mesh)
    def step(state: TrainState, batch: Batch) -> Metrics:
        loss_sum, (_, _, c1, c5, count) = _forward_and_sums(
            model, state.params, state.batch_stats, batch, train=False
        )
        return {"loss_sum": loss_sum, "correct1": c1, "correct5": c5, "count": count}

    sharded = NamedSharding(mesh, P(data_axis))
    batch_shardings = {"images": sharded, "labels": sharded, "weights": sharded}
    return jax.jit(
        step,
        in_shardings=(
            state_shardings(mesh, data_axis, residual_sharded,
                            momentum_sharding),
            batch_shardings),
        out_shardings=NamedSharding(mesh, P()),
    )
