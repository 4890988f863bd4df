"""Fused tied-head + cross-entropy: the LM loss without the [N, V] logits
tensor.

The LM step's last matmul projects hidden states onto the vocabulary and
feeds softmax cross-entropy (models/transformer.py:251-253 → ops/loss.py).
Materializing those logits costs N·V f32 in HBM *twice over* (forward write
+ backward read) plus the softmax intermediates — at b8·L1024·V32k that is
>2 GB of pure loss-head traffic per step.  This op computes the SAME loss in
row chunks with a custom VJP whose gradient is taken in the pass that has
the logits:

- **under differentiation** (the VJP's forward rule): ONE ``lax.scan`` over
  N/num_chunks row blocks.  A block: logits ``h_b·Eᵀ`` ([chunk, V], f32
  accumulation), ``logz``, the true logit, the weighted loss and
  ``correct`` sums, then ``(softmax − onehot)·w``, ``dh_b`` (cast to
  ``h.dtype``, stacked) and ``dE += dlogitᵀ·h_b`` in the f32 carry: three
  head products a chunk (``GRAD_HEAD_PRODUCTS``), nothing recomputed.  The
  loss is the last thing the forward pass does and the first the backward
  pass undoes, and the gradient is linear in the loss's cotangent, the one
  thing that arrives later.  Residuals: ``dh``, ``dE`` and the per-row
  cross-entropy ``logz − true_logit`` (the ``weights`` cotangent) — not
  ``h``, not ``e``, nothing O(N·V).
- **the backward rule** multiplies the three by the loss's cotangent and
  returns them (``correct_sum`` stays non-differentiable; ``dE`` leaves in
  ``e.dtype``).  A caller
  that hands rows whose weights already hold its mean's ``1 / N``
  (train/lm.py) has a cotangent of exactly 1, and the scaling folds away.
- **undifferentiated** (an eval step, a check's forward value): the
  loss-only loop, one product a chunk, no ``[V, D]`` accumulator.

Until PR 33 the backward rule ran a second loop that recomputed every
block's logits ("FLOPs are free here, bytes are not": true of a 32k head
on a step bound by memory, not of a 49k-131k head on a step bound by
compute, where the fourth product was 3.7% of the step).

**Sharded composition** — three variants, selected by the sharding context
(train/lm.py ``fused_ce_mode``), all three with the one loop above:

- ``fused_ce_sums`` (replicated): the GSPMD baseline.  Under pure data
  sharding its loop carries a fully *replicated* ``[V, D]`` f32 ``dE``
  accumulator (125 MiB/device at V32k·D1024) while the logits it eliminates
  were already batch-sharded — measured net-neutral at 8-way
  (RESULTS_fused_ce_memory.json round 5).
- ``fused_ce_sums_dp`` (DP mode): explicit ``shard_map`` over the data
  axis.  The scan's ``dE`` carry is a *vocab-row shard* ``[V/k, D]`` f32
  per device; each block's ``dlogit`` is exchanged with one
  ``all_to_all`` (batch-sharded → vocab-sharded — the cross-replica
  partial-sum reduction of arXiv 2004.13336, the traffic EQuARX/2506.17615
  compresses) and the cotangent is returned still vocab-sharded, so the
  one gather back to the replicated parameter rides the existing GSPMD
  gradient reduction outside the scan.  Restores the full fused-head
  memory win on data-sharded meshes.
- ``fused_ce_sums_tp`` (TP mode): accepts the *vocab-sharded* tied
  embedding from parallel/tp.py (``P('model', None)``) directly inside
  ``shard_map`` — block-local logsumexp / true-logit partials are combined
  with ``psum``/``pmax`` over the model axis, ``dE`` accumulates as the
  local ``[V/tp, D]`` shard (one deferred psum over data at scan end), and
  the cotangent comes back ``P(model, None)``: neither ``e`` nor ``dE`` is
  ever replicated.

Numerics: logits and ``dlogit`` are f32 (``preferred_element_type``) from
bf16/f32 operands, ``dE`` is summed in f32 — at least as accurate as the
unfused head (which casts the f32 hidden back through the embed dtype).
Equality to the unfused ``cross_entropy(model(tokens))`` path, and the
count of head products in each variant's jaxpr, are pinned in
tests/test_fused_ce.py for all three variants.

Reference anchor: the loss of every reference recipe is
``nn.CrossEntropyLoss`` on the model head (reference distributed.py:151);
this is that capability, restructured for the TPU memory hierarchy.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp

from pytorch_distributed_tpu.obs.trace import scope


# The head products a chunk of the differentiated loss runs: its logits,
# dh and dE (the undifferentiated loss runs the first alone).
# tests/test_fused_ce.py holds every variant's jaxpr to it.
GRAD_HEAD_PRODUCTS = 3


def _logits(h_blk, e):
    """``h_blk . e^T``: [chunk, V] f32 from operands in their own dtype."""
    return jax.lax.dot_general(
        h_blk, e, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _block_sums(logits, t_blk, w_blk):
    """What the loss reads of a row block's logits: the per-row
    cross-entropy ``ce = logz - true_logit``, ``logz``, and the weighted
    (loss_sum, correct_sum), all f32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, t_blk[:, None], axis=-1)[:, 0]
    ce = logz - true_logit
    correct = jnp.sum(
        (jnp.argmax(logits, axis=-1) == t_blk).astype(jnp.float32) * w_blk)
    return ce, logz, jnp.sum(ce * w_blk), correct


def _dlogit(logits, logz, t_blk, w_blk):
    """``(softmax - onehot) * w`` per row, [chunk, V] f32: the gradient of
    the weighted loss sum in the logits, at a cotangent of 1.  ``t_blk``
    indexes the logits' own columns; one_hot of an index outside them is
    the zero row (a vocabulary shard's restriction)."""
    p = jnp.exp(logits - logz[:, None])
    onehot = jax.nn.one_hot(t_blk, logits.shape[1], dtype=jnp.float32)
    return (p - onehot) * w_blk[:, None]


def _dh(dlogit, e):
    """``dlogit . e``: [chunk, D] f32."""
    return jax.lax.dot_general(
        dlogit, e, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _de(dlogit, h_blk):
    """``dlogit^T . h_blk``: [V, D] f32, a block's share of the head's
    gradient."""
    return jax.lax.dot_general(
        dlogit, h_blk, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _pad_rows(h, targets, weights, multiple: int):
    """Pad N up to a multiple with weight-0 rows (zero loss and zero
    gradient contribution — the same masking the image eval path uses for
    partial batches)."""
    pad = (-h.shape[0]) % multiple
    if pad:
        h = jnp.concatenate(
            [h, jnp.zeros((pad, h.shape[1]), h.dtype)], axis=0)
        targets = jnp.concatenate(
            [targets, jnp.zeros((pad,), targets.dtype)], axis=0)
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad,), weights.dtype)], axis=0)
    return h, targets, weights


def _chunks(num_chunks: int, *xs):
    """Each ``[N, ...]`` as ``[num_chunks, N / num_chunks, ...]``."""
    return tuple(
        x.reshape((num_chunks, x.shape[0] // num_chunks) + x.shape[1:])
        for x in xs)


def _loss_loop(num_chunks: int, h, t, w, sums):
    """The loss-only loop over the row blocks: ``sums(hb, tb, wb)`` is a
    block's ``_block_sums``; → ``(loss_sum, correct_sum)``."""
    def body(carry, blk):
        _, _, dl, dc = sums(*blk)
        return (carry[0] + dl, carry[1] + dc), None

    with scope("fused_ce"):
        out, _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)),
            _chunks(num_chunks, h, t, w))
    return out


def _grad_loop(num_chunks: int, h, t, w, de_shape, grads):
    """The one loop under differentiation: ``grads(hb, tb, wb)`` → a
    block's ``(ce, loss_sum, correct_sum, dh_b, de_b)``, ``dh_b`` in the
    rows' dtype and ``de_b`` its f32 share of the ``de_shape`` accumulator
    the loop carries; → ``(loss_sum, correct_sum, dh, de, ce)``."""
    def body(carry, blk):
        loss, correct, de_acc = carry
        ce, dl, dc, dh_b, de_b = grads(*blk)
        return (loss + dl, correct + dc, de_acc + de_b), (dh_b, ce)

    with scope("fused_ce"):
        (loss, correct, de), (dh, ce) = jax.lax.scan(
            body,
            (jnp.float32(0.0), jnp.float32(0.0),
             jnp.zeros(de_shape, jnp.float32)),
            _chunks(num_chunks, h, t, w))
        return (loss, correct, dh.reshape((-1,) + h.shape[1:]), de,
                ce.reshape(-1))


def _scale(res, cts):
    """The backward rule of all three variants: the forward rule's
    gradients, taken at a cotangent of 1, times the loss's cotangent (the
    gradient is linear in it).  ``correct_sum``'s cotangent (``cts[1]``)
    is ignored and ``targets`` gets none; ``dE`` and the weights'
    cotangent leave in their inputs' dtypes."""
    dh, de, ce, e_like, w_like = res
    g = cts[0]
    with scope("fused_ce"):
        return ((dh * g).astype(dh.dtype), (de * g).astype(e_like.dtype),
                None, (ce * g).astype(w_like.dtype))


def fused_ce_sums(h, e, targets, weights, num_chunks: int):
    """``h [N, D]`` hidden rows, ``e [V, D]`` tied embedding, ``targets
    [N]`` int32, ``weights [N]`` f32 → ``(loss_sum, correct_sum)`` f32
    scalars (weighted sums; divide by ``weights.sum()`` for means).

    N is padded up to a multiple of ``num_chunks`` (see ``_pad_rows``).
    ``correct_sum`` is non-differentiable (its cotangent is ignored);
    ``weights`` carries the true loss-path cotangent
    ``(logz − true_logit)·ḡ`` per row."""
    h, targets, weights = _pad_rows(h, targets, weights, num_chunks)
    return _fused_ce_sums(h, e, targets, weights, num_chunks)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused_ce_sums(h, e, targets, weights, num_chunks: int):
    return _loss_loop(
        num_chunks, h, targets, weights,
        lambda hb, tb, wb: _block_sums(_logits(hb, e), tb, wb))


def _fwd(h, e, targets, weights, num_chunks: int):
    def grads(hb, tb, wb):
        logits = _logits(hb, e)
        ce, logz, dl, dc = _block_sums(logits, tb, wb)
        dlogit = _dlogit(logits, logz, tb, wb)
        dh_b = _dh(dlogit, e).astype(h.dtype)
        return ce, dl, dc, dh_b, _de(dlogit, hb)

    loss, correct, dh, de, ce = _grad_loop(
        num_chunks, h, targets, weights, e.shape, grads)
    return (loss, correct), (dh, de, ce, e[:0], weights[:0])


def _bwd(num_chunks: int, res, cts):
    return _scale(res, cts)


_fused_ce_sums.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# DP mode: vocab-row-sharded dE accumulator over the data axis.
# ---------------------------------------------------------------------------


def fused_ce_sums_dp(h, e, targets, weights, num_chunks: int, mesh,
                     data_axis: str = "data"):
    """Data-sharded fused CE: same contract as ``fused_ce_sums`` but the
    gradient loop's ``dE`` scan carry is a vocab-row shard ``[V/k, D]`` f32
    per device instead of the replicated ``[V, D]``.

    Rows (``h``/``targets``/``weights``) enter batch-sharded over
    ``data_axis``; ``e`` is the replicated tied embedding.  Under
    differentiation each
    block exchanges its ``[chunk/k, V]`` dlogit with one ``all_to_all``
    (batch-sharded → vocab-sharded) so every device accumulates only its
    vocab slice; the cotangent is returned still ``P(data, None)``-sharded
    and the single gather back to the replicated parameter is left to the
    existing GSPMD gradient reduction, outside the scan.

    Requires ``V % k == 0`` for the vocab all_to_all split (k = data-axis
    size).  ``train/lm.py`` ``fused_ce_mode='auto'`` falls back to the
    replicated variant otherwise."""
    k = dict(mesh.shape).get(data_axis, 1)
    if k <= 1:
        return fused_ce_sums(h, e, targets, weights, num_chunks)
    if e.shape[0] % k:
        raise ValueError(
            f"fused_ce_sums_dp: vocab {e.shape[0]} not divisible by the "
            f"'{data_axis}' axis size {k} (needed for the vocab-sharded "
            f"dE accumulator); use the replicated variant")
    h, targets, weights = _pad_rows(h, targets, weights, num_chunks * k)
    fn = _make_dp_fn(num_chunks, mesh, data_axis)
    return fn(h, e, targets, weights)


@functools.lru_cache(maxsize=None)
def _make_dp_fn(num_chunks: int, mesh, data_axis: str):
    from jax.sharding import PartitionSpec as P

    row = P(data_axis)
    rows2d = P(data_axis, None)
    rep = P()

    def _psum(x):
        return jax.lax.psum(x, data_axis)

    def fwd_local(h, e, t, w):
        loss, correct = _loss_loop(
            num_chunks, h, t, w,
            lambda hb, tb, wb: _block_sums(_logits(hb, e), tb, wb))
        return _psum(loss), _psum(correct)

    k_dp = dict(mesh.shape)[data_axis]

    def grad_local(h, e, t, w):
        def grads(hb, tb, wb):  # this shard's rows of the block
            logits = _logits(hb, e)  # [chunk/k, V] f32
            ce, logz, dl, dc = _block_sums(logits, tb, wb)
            dlogit = _dlogit(logits, logz, tb, wb)
            dh_b = _dh(dlogit, e).astype(h.dtype)
            # Batch-sharded → vocab-sharded: this device receives ALL the
            # block's rows restricted to its vocab slice — the per-block
            # cross-replica partial-sum exchange (arXiv 2004.13336).
            dl_v = jax.lax.all_to_all(
                dlogit, data_axis, split_axis=1, concat_axis=0, tiled=True
            )  # [chunk, V/k]
            h_full = jax.lax.all_gather(
                hb, data_axis, axis=0, tiled=True)  # [chunk, D]
            # [V/k, D] — complete sum for this vocab slice
            return ce, dl, dc, dh_b, _de(dl_v, h_full)

        loss, correct, dh, de, ce = _grad_loop(
            num_chunks, h, t, w, (e.shape[0] // k_dp, e.shape[1]), grads)
        return _psum(loss), _psum(correct), dh, de, ce

    fwd_sm = jax.shard_map(
        fwd_local, mesh=mesh, in_specs=(rows2d, rep, row, row),
        out_specs=(rep, rep), check_vma=False,
    )
    grad_sm = jax.shard_map(
        grad_local, mesh=mesh, in_specs=(rows2d, rep, row, row),
        out_specs=(rep, rep, rows2d, rows2d, row), check_vma=False,
    )

    @jax.custom_vjp
    def f(h, e, t, w):
        return fwd_sm(h, e, t, w)

    def f_fwd(h, e, t, w):
        loss, correct, dh, de, ce = grad_sm(h, e, t, w)
        return (loss, correct), (dh, de, ce, e[:0], w[:0])

    f.defvjp(f_fwd, _scale)
    return f


# ---------------------------------------------------------------------------
# TP mode: vocab-sharded tied embedding (parallel/tp.py P('model', None)).
# ---------------------------------------------------------------------------


def fused_ce_sums_tp(h, e, targets, weights, num_chunks: int, mesh,
                     data_axis: str = "data", model_axis: str = "model"):
    """Tensor-parallel fused CE: ``e`` enters *vocab-sharded* over
    ``model_axis`` (the parallel/tp.py ``P('model', None)`` layout) and is
    never replicated — each device's scan sees only its ``[V/tp, D]``
    shard.

    Per block, each model shard computes its local ``[chunk, V/tp]``
    logits and the global softmax statistics are combined with one
    ``pmax`` + two ``psum`` over the model axis (logsumexp / true logit;
    argmax for ``correct_sum`` keeps jnp.argmax's first-occurrence
    tie-break via a pmin over candidate indices).  Under differentiation
    the same loop forms ``dlogit`` from the ``logz`` it has (no second run
    of the model-axis collectives), ``dE`` accumulates as the local
    ``[V/tp, D]`` shard with the cross-replica (data-axis) sum deferred to
    one psum at scan end, and the cotangent returns ``P(model,
    None)``-sharded.

    Requires ``V % tp == 0`` (the tp.py layout already does) and
    ``model_axis != data_axis``."""
    tp = dict(mesh.shape).get(model_axis, 1)
    if tp <= 1:
        return fused_ce_sums(h, e, targets, weights, num_chunks)
    if model_axis == data_axis:
        raise ValueError(
            "fused_ce_sums_tp: model_axis must differ from data_axis "
            f"(both {model_axis!r}); a same-axis vocab shard would mix "
            "row shards into the softmax reductions")
    if e.shape[0] % tp:
        raise ValueError(
            f"fused_ce_sums_tp: vocab {e.shape[0]} not divisible by the "
            f"'{model_axis}' axis size {tp}")
    dp = dict(mesh.shape).get(data_axis, 1)
    h, targets, weights = _pad_rows(h, targets, weights, num_chunks * dp)
    fn = _make_tp_fn(num_chunks, mesh, data_axis, model_axis)
    return fn(h, e, targets, weights)


@functools.lru_cache(maxsize=None)
def _make_tp_fn(num_chunks: int, mesh, data_axis: str, model_axis: str):
    from jax.sharding import PartitionSpec as P

    has_dp = dict(mesh.shape).get(data_axis, 1) > 1
    row_axis = data_axis if has_dp else None
    row = P(row_axis)
    rows2d = P(row_axis, None)
    vocab2d = P(model_axis, None)
    rep = P()

    def _psum_dp(x):
        return jax.lax.psum(x, data_axis) if has_dp else x

    tp_size = dict(mesh.shape)[model_axis]

    def block_sums(logits, lo, tb, wb):
        """``_block_sums`` over a vocabulary shard's columns ``[lo, lo +
        V/tp)``: the softmax statistics, the true logit and the argmax
        combined over the model axis."""
        vloc = logits.shape[1]
        lmax_loc = jnp.max(logits, axis=-1)
        lmax = jax.lax.pmax(lmax_loc, model_axis)
        ssum = jax.lax.psum(
            jnp.sum(jnp.exp(logits - lmax[:, None]), axis=-1), model_axis)
        logz = lmax + jnp.log(ssum)
        tloc = tb - lo
        in_shard = (tloc >= 0) & (tloc < vloc)
        tl_part = jnp.where(
            in_shard,
            jnp.take_along_axis(
                logits, jnp.clip(tloc, 0, vloc - 1)[:, None], axis=-1)[:, 0],
            0.0)
        ce = logz - jax.lax.psum(tl_part, model_axis)
        # global argmax with jnp.argmax's first-occurrence tie-break:
        # among shards achieving the global max, take the lowest
        # global index.
        amax_loc = lo + jnp.argmax(logits, axis=-1)
        cand = jnp.where(lmax_loc >= lmax, amax_loc, vloc * tp_size)
        gidx = jax.lax.pmin(cand, model_axis)
        correct = jnp.sum((gidx == tb).astype(jnp.float32) * wb)
        return ce, logz, jnp.sum(ce * wb), correct

    def fwd_local(h, e, t, w):
        lo = jax.lax.axis_index(model_axis) * e.shape[0]
        # [chunk, V/tp] f32 logits — this shard's vocab columns only
        loss, correct = _loss_loop(
            num_chunks, h, t, w,
            lambda hb, tb, wb: block_sums(_logits(hb, e), lo, tb, wb))
        return _psum_dp(loss), _psum_dp(correct)

    def grad_local(h, e, t, w):
        lo = jax.lax.axis_index(model_axis) * e.shape[0]

        def grads(hb, tb, wb):
            logits = _logits(hb, e)  # [chunk, V/tp]
            ce, logz, dl, dc = block_sums(logits, lo, tb, wb)
            dlogit = _dlogit(logits, logz, tb - lo, wb)
            dh_b = jax.lax.psum(_dh(dlogit, e), model_axis).astype(h.dtype)
            # [V/tp, D] — this data shard's rows only
            return ce, dl, dc, dh_b, _de(dlogit, hb)

        loss, correct, dh, de, ce = _grad_loop(
            num_chunks, h, t, w, e.shape, grads)
        # dE's cross-replica sum is deferred to here: ONE collective
        return _psum_dp(loss), _psum_dp(correct), dh, _psum_dp(de), ce

    fwd_sm = jax.shard_map(
        fwd_local, mesh=mesh, in_specs=(rows2d, vocab2d, row, row),
        out_specs=(rep, rep), check_vma=False,
    )
    grad_sm = jax.shard_map(
        grad_local, mesh=mesh, in_specs=(rows2d, vocab2d, row, row),
        out_specs=(rep, rep, rows2d, vocab2d, row), check_vma=False,
    )

    @jax.custom_vjp
    def f(h, e, t, w):
        return fwd_sm(h, e, t, w)

    def f_fwd(h, e, t, w):
        loss, correct, dh, de, ce = grad_sm(h, e, t, w)
        return (loss, correct), (dh, de, ce, e[:0], w[:0])

    f.defvjp(f_fwd, _scale)
    return f
