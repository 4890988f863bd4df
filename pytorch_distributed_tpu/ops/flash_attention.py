"""Fused flash attention — Pallas TPU kernel for the framework's hot op.

Replaces the reference's native-kernel layer for attention-bearing models:
where the GPU stack reaches cuDNN/apex fused kernels through torch bindings
(SURVEY.md §2.2), the TPU stack reaches the MXU through this Pallas kernel.
Dense XLA attention materializes the [L, L] score matrix in HBM; this kernel
keeps score blocks in VMEM with online softmax, so HBM traffic stays
O(L·D) and memory O(L·BK) — the single-chip complement of the cross-chip
ring attention in parallel/ring.py (which this kernel's math mirrors).

Every kernel runs the grid (batch·heads, steps of a ``block_schedule``):
the schedule lists, at trace time, the (q-block, kv-block) pairs the mask
leaves, and its int32 tables reach the index maps and the kernel by scalar
prefetch.  A causal call therefore has no grid step (no copy, no wait) for
a block above the diagonal, and masks only the blocks the diagonal crosses;
a call that is not causal visits the rectangle with the same kernels.
Where the blocks are square (``block_q == block_k``) every crossed block
lies on the diagonal, so which of its parts the mask empties is known at
trace time: ``subtile`` cuts its side into four (where each quarter is
whole 8-row tiles), and the kernels work it
in static slices (``_diagonal_parts``: sub-rows in the forward and dq
passes, sub-columns in the dk/dv pass) that compute no product, ``exp``
or sum over the sub-tiles above the diagonal, 6 of 16 a block.  Blocks of
other shapes are crossed at an offset that moves from step to step; they,
and calls that are not causal, run the whole block as before.
``blocks_visited`` counts a forward call's blocks, its masked ones and the
sub-tiles it skips in those (48 a batch-head at L = 8,192 in blocks of
1,024).
Forward: f32 accumulators in VMEM scratch, online softmax over the
kv-blocks of a q-block.  Backward: the flash-attention-2 decomposition —
a dq pass (the forward's order) and a dk/dv pass (the q-blocks of one
kv-block after another), both recomputing P online from the saved
logsumexp with VMEM accumulators; O(L·BK) memory, every matmul on the MXU.
``bwd_impl="xla"`` selects the plain-XLA blockwise recompute (the oracle
the kernels are tested against).

Layout: [B, L, H, D] like parallel/ring.py; blocks default to 256 × 1024
(q × kv).  ``q`` and ``k`` share one head size and ``v`` may have
another (latent attention: 192 | 128); ``scale`` defaults to
``D_qk ** -0.5``.  ``k`` and ``v`` may have fewer heads than ``q``
([B, L, G, D], G dividing H: grouped-query attention): query head ``h``
reads key-value head ``h // (H / G)`` through the blocks' index maps, so no
copy of K or V is made; the dk/dv pass runs a query head at a time and its
float32 results are summed over each group outside the kernel.  The kernels' matrix products take their operands in the
type of ``q``, ``k`` and ``v`` (bf16 in, bf16 on the MXU); accumulation and
the softmax are float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


class BlockSchedule(NamedTuple):
    """The blocks a kernel visits, in visiting order: one int32 entry a
    grid step in each table."""

    q_block: np.ndarray
    kv_block: np.ndarray
    crossed: np.ndarray    # 1: the diagonal crosses the block (a masked pair)
    first: np.ndarray      # 1: the first step of its accumulation row
    last: np.ndarray       # 1: the last step of its accumulation row


def block_schedule(L: int, block_q: int, block_k: int, causal: bool,
                   order: str) -> BlockSchedule:
    """Which ``(q-block, kv-block)`` pairs of an ``L x L`` score matrix a
    kernel visits, and which of them it masks.

    Causal: the blocks holding an unmasked pair (``k <= q``), so none above
    the diagonal; ``crossed`` marks those that also hold a masked pair.
    Not causal: the whole rectangle, none marked.  ``order="q"`` visits the
    live kv-blocks of one q-block after another (the forward and the dq
    pass accumulate over them), ``order="kv"`` the live q-blocks of one
    kv-block after another (the dk/dv pass); ``first`` / ``last`` bracket
    each such row, and every row has a live block."""
    if order not in ("q", "kv"):
        raise ValueError(f"unknown order {order!r}: expected 'q' or 'kv'")
    qi, kj = np.meshgrid(np.arange(L // block_q), np.arange(L // block_k),
                         indexing="ij")
    q_lo, k_lo = qi * block_q, kj * block_k
    if causal:
        live = k_lo <= q_lo + block_q - 1
        crossed = live & (k_lo + block_k - 1 > q_lo)
    else:
        live = np.ones(qi.shape, bool)
        crossed = np.zeros(qi.shape, bool)
    if order == "q":
        row, col = np.nonzero(live)
        q_block, kv_block = row, col
    else:
        row, col = np.nonzero(live.T)
        q_block, kv_block = col, row
    turn = row[1:] != row[:-1]
    return BlockSchedule(*(np.asarray(t, np.int32) for t in (
        q_block, kv_block, crossed[q_block, kv_block],
        np.r_[True, turn], np.r_[turn, True])))


# A crossed block is worked in sub-tiles of a quarter of its side: at
# L = 8,192 in blocks of 1,024 the kernels then compute 33 of the 36
# block-products a whole crossed block costs.  On a TPU v5e at
# [2, 8192, 16, 128], two forwards and one backward took 26.15 ms with the
# whole block, 25.47 in halves, 25.41 in quarters, 25.42 in eighths; the
# backward gains (dq -8%, dk/dv -7%), the forward at this width loses 2%.
SUBTILES = 4


def subtile(block_q: int, block_k: int, causal: bool) -> Optional[int]:
    """The side of the square sub-tiles in which the kernels work a block
    the diagonal crosses, skipping those wholly above it; ``None``: the
    crossed blocks are worked whole.

    Only square blocks are sub-tiled: with ``block_q == block_k`` every
    crossed block lies on the diagonal (its q-block is its kv-block), so
    which sub-tiles are live is known at trace time.  Blocks of other
    shapes are crossed at an offset that changes from step to step.  The
    side must cut into ``SUBTILES`` sub-tiles of whole 8-row tiles; a
    block of ``min(block, L)`` rows (``_blocks``) may not, and is worked
    whole."""
    s = block_q // SUBTILES
    if not causal or block_q != block_k or block_q % (SUBTILES * 8):
        return None
    return s


def _scores(q, k, scale, mask):
    """Float32 scores ``[rows of q, rows of k]``; ``mask`` is ``None``
    below the diagonal, else ``(qi, kj, tile_q, tile_k)``: the tile the
    diagonal crosses is at ``(qi, kj)`` in a grid of ``tile_q x tile_k``
    tiles (a whole block, or a part of a diagonal block:
    ``_diagonal_parts``)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if mask is not None:
        qi, kj, tile_q, tile_k = mask
        qpos = qi * tile_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kj * tile_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    return s


def _diagonal_parts(block: int, sub: int, order: str):
    """``(rows, cols, mask)`` of the parts in which a square block on the
    diagonal is worked, in sub-tiles of ``sub``: ``order="q"`` one sub-row
    of queries at a time against the keys up to its last, ``order="kv"``
    one sub-column of keys at a time against the queries from its first.
    No part holds a sub-tile above the diagonal."""
    for i in range(block // sub):
        lo, hi = i * sub, (i + 1) * sub
        if order == "q":
            yield slice(lo, hi), slice(0, hi), (i, 0, sub, sub)
        else:
            yield slice(lo, block), slice(lo, hi), (i, i, sub, sub)


def _this_step(refs, causal: bool, block_q: int, block_k: int, order: str):
    """Split a kernel's references into the schedule's tables, which come
    first, and the rest; of this grid step's entries return ``first``,
    ``last`` and ``on_block(update)``.  That runs ``update(rows, cols,
    mask)`` (``mask`` as ``_scores`` takes it) on the whole block, unmasked
    below the diagonal; where the diagonal crosses it, masked or, where
    the blocks are sub-tiled (``subtile``), on each of its
    ``_diagonal_parts``."""
    n = len(BlockSchedule._fields)
    sched = BlockSchedule(*refs[:n])
    step = pl.program_id(1)
    at = (sched.q_block[step], sched.kv_block[step])
    crossed = sched.crossed[step]
    sub = subtile(block_q, block_k, causal)

    def on_block(update):
        whole = functools.partial(update, slice(None), slice(None))
        if not causal:
            return whole(None)
        if sub:
            def on_crossed():
                for part in _diagonal_parts(block_q, sub, order):
                    update(*part)
        else:
            on_crossed = functools.partial(whole, (*at, block_q, block_k))
        pl.when(crossed == 1)(on_crossed)
        pl.when(crossed == 0)(functools.partial(whole, None))

    return sched.first[step] == 1, sched.last[step] == 1, on_block, refs[n:]


def _fwd_kernel(*refs, scale: float, causal: bool,
                block_q: int, block_k: int):
    """One (bh, step) grid step: accumulate the schedule's q-block x
    kv-block online, the rows of a part at a time."""
    first, last, on_block, refs = _this_step(refs, causal, block_q, block_k,
                                             "q")
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs

    @pl.when(first)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(rows, cols, mask):
        v = v_ref[0, cols]                           # [BK, Dv]
        s = _scores(q_ref[0, rows], k_ref[0, cols], scale,
                    mask)                            # [BQ, BK]
        m_prev = m_scr[rows, :1]                     # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [BQ, BK]
        corr = jnp.exp(m_prev - m_new)               # [BQ, 1]
        l_new = l_scr[rows, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[rows] = acc_scr[rows] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[rows] = jnp.broadcast_to(m_new, (m_new.shape[0], 128))
        l_scr[rows] = jnp.broadcast_to(l_new, (l_new.shape[0], 128))

    on_block(update)

    @pl.when(last)
    def _final():
        l = l_scr[:, :1]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse is lane-broadcast to 128 (TPU block alignment; caller reads
        # lane 0) — same layout as jax's reference TPU kernel.
        lse_ref[0] = jnp.broadcast_to(
            m_scr[:, :1] + jnp.log(safe_l), lse_ref.shape[1:]
        )


def _blocks(L: int, block_q: int, block_k: int):
    bq = min(block_q, L)
    bk = min(block_k, L)
    assert L % bq == 0 and L % bk == 0, (
        f"sequence length {L} must divide block sizes ({bq}, {bk})"
    )
    return bq, bk


def blocks_visited(L: int, block_q: int, block_k: int,
                   causal: bool = True):
    """``(visited, masked, subtiles_skipped)``: the score blocks one
    forward call visits a batch-head, those of them it masks, and the
    sub-tiles above the diagonal it skips in them (``subtile``)."""
    bq, bk = _blocks(L, block_q, block_k)
    sched = block_schedule(L, bq, bk, causal, "q")
    masked = int(sched.crossed.sum())
    sub = subtile(bq, bk, causal)
    n = bq // sub if sub else 1
    return len(sched.crossed), masked, masked * n * (n - 1) // 2


def _scheduled_call(kernel, sched: BlockSchedule, batch_heads: int,
                    in_specs, out_specs, out_shape, scratch_shapes,
                    interpret: bool):
    """``pallas_call`` over the grid (batch-heads, the schedule's steps),
    the schedule's tables prefetched as scalars: the index maps of
    ``_spec`` and the kernel read them."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched),
            grid=(batch_heads, len(sched.q_block)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        interpret=interpret,
    )


def _spec(side: str, rows: int, width: int, group: int = 1) -> pl.BlockSpec:
    """A ``[1, rows, width]`` block of a ``[B*H, L, width]`` array: the
    step's q-block (``side="q"``) or its kv-block.  ``group`` > 1: the
    array has ``B*H / group`` rows, one for every ``group`` batch-heads of
    the grid (a key-value head shared by a group of query heads)."""
    table = BlockSchedule._fields.index(f"{side}_block")
    if group == 1:
        def index(b, s, *tables):
            return b, tables[table][s], 0
    else:
        def index(b, s, *tables):
            return b // group, tables[table][s], 0
    return pl.BlockSpec((1, rows, width), index, memory_space=pltpu.VMEM)


def _heads_first(x):
    """[B, L, H, D] -> [B*H, L, D]."""
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


def _group(q, k) -> int:
    """Query heads a key-value head: ``H / G``."""
    H, G = q.shape[2], k.shape[2]
    if H % G:
        raise ValueError(f"{G} key-value heads do not divide {H} query heads")
    return H // G


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool, scale: Optional[float] = None):
    B, L, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq, bk = _blocks(L, block_q, block_k)
    group = _group(q, k)
    qr, kr, vr = _heads_first(q), _heads_first(k), _heads_first(v)

    sched = block_schedule(L, bq, bk, causal, "q")
    out, lse = _scheduled_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        sched, B * H,
        in_specs=[_spec("q", bq, D), _spec("kv", bk, D, group),
                  _spec("kv", bk, Dv, group)],
        out_specs=[_spec("q", bq, Dv), _spec("q", bq, 128)],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, L, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((bq, Dv), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(*sched, qr, kr, vr)
    # Residual lse is [B*H, L] (lane 0 of the kernel's lane-broadcast
    # output) — saving the full 128-lane layout would hold 128x the bytes
    # across the fwd->bwd interval; the backward re-broadcasts cheaply.
    return out.reshape(B, H, L, Dv).transpose(0, 2, 1, 3), lse[:, :, 0]


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                    scale, rows, cols, mask):
    """Shared backward block math: online-recomputed (p, ds) plus the
    block views — the single source for both the dq and dk/dv kernels (and
    the same masking the forward kernel applies); ``rows`` of the q-block
    against ``cols`` of the kv-block."""
    q = q_ref[0, rows]                           # [BQ, D]
    k = k_ref[0, cols]                           # [BK, D]
    v = v_ref[0, cols]                           # [BK, Dv]
    do = do_ref[0, rows]                         # [BQ, Dv]
    lse = lse_ref[0, rows][:, :1]                # [BQ, 1]
    dlt = dlt_ref[0, rows][:, :1]                # [BQ, 1]
    s = _scores(q, k, scale, mask)               # [BQ, BK]
    p = jnp.exp(s - lse)                         # [BQ, BK]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # [BQ, BK]
    ds = p * (dp - dlt) * scale
    return p.astype(do.dtype), ds.astype(q.dtype), q, k, do


def _bwd_dq_kernel(*refs, scale: float, causal: bool,
                   block_q: int, block_k: int):
    """dq pass: grid (bh, step), the schedule in q order: accumulate dq_i
    over its live kv blocks."""
    first, last, on_block, refs = _this_step(refs, causal, block_q, block_k,
                                             "q")
    operands, (dq_ref, dq_scr) = refs[:6], refs[6:]

    @pl.when(first)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def update(rows, cols, mask):
        _, ds, _, k, _ = _recompute_p_ds(*operands, scale, rows, cols, mask)
        dq_scr[rows] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    on_block(update)

    @pl.when(last)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool,
                    block_q: int, block_k: int):
    """dk/dv pass: grid (bh, step), the schedule in kv order: accumulate
    dk_j, dv_j over the q blocks at or below the diagonal."""
    first, last, on_block, refs = _this_step(refs, causal, block_q, block_k,
                                             "kv")
    operands, (dk_ref, dv_ref, dk_scr, dv_scr) = refs[:6], refs[6:]

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def update(rows, cols, mask):
        p, ds, q, _, do = _recompute_p_ds(*operands, scale, rows, cols, mask)
        dv_scr[cols] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # [BK, D]
        dk_scr[cols] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                            # [BK, D]

    on_block(update)

    @pl.when(last)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_pallas(res, g, causal: bool, block_q: int, block_k: int,
                interpret: bool, scale: Optional[float] = None):
    """Fused Pallas backward: dq pass + dk/dv pass, both with online
    recompute from the saved lse — no [L, L] materialization, all matmuls
    on the MXU (flash-attention-2 decomposition)."""
    q, k, v, out, lse = res               # lse: [B*H, L] f32
    B, L, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    bq, bk = _blocks(L, block_q, block_k)
    f32 = jnp.float32
    group = _group(q, k)
    qr, kr, vr, gr, of = (_heads_first(x) for x in (q, k, v, g, out))
    delta = jnp.sum(of.astype(f32) * gr.astype(f32), axis=-1)     # [BH, L]
    # Lane-broadcast for block slicing (transient, not a saved residual).
    lse128 = jnp.broadcast_to(lse[:, :, None], (B * H, L, 128))
    dlt128 = jnp.broadcast_to(delta[:, :, None], (B * H, L, 128))
    operands = (qr, kr, vr, gr, lse128, dlt128)
    in_specs = [_spec("q", bq, D), _spec("kv", bk, D, group),
                _spec("kv", bk, Dv, group),
                _spec("q", bq, Dv), _spec("q", bq, 128), _spec("q", bq, 128)]
    static = dict(scale=scale, causal=causal, block_q=bq, block_k=bk)

    sched = block_schedule(L, bq, bk, causal, "q")
    dq = _scheduled_call(
        functools.partial(_bwd_dq_kernel, **static), sched, B * H,
        in_specs=in_specs,
        out_specs=[_spec("q", bq, D)],
        out_shape=[jax.ShapeDtypeStruct((B * H, L, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, D), f32)],
        interpret=interpret,
    )(*sched, *operands)[0]

    # a group's query heads each give their part of dk and dv, in float32
    # where there are several to sum
    sched = block_schedule(L, bq, bk, causal, "kv")
    dk, dv = _scheduled_call(
        functools.partial(_bwd_dkv_kernel, **static), sched, B * H,
        in_specs=in_specs,
        out_specs=[_spec("kv", bk, D), _spec("kv", bk, Dv)],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, L, D),
                                 k.dtype if group == 1 else f32),
            jax.ShapeDtypeStruct((B * H, L, Dv),
                                 v.dtype if group == 1 else f32)],
        scratch_shapes=[pltpu.VMEM((bk, D), f32),
                        pltpu.VMEM((bk, Dv), f32)],
        interpret=interpret,
    )(*sched, *operands)

    def back(x):
        return x.reshape(B, H, L, x.shape[-1]).transpose(0, 2, 1, 3)

    def back_grouped(x, like):
        if group == 1:
            return back(x)
        x = x.reshape(B, H // group, group, L, x.shape[-1]).sum(2)
        return x.transpose(0, 2, 1, 3).astype(like.dtype)

    return back(dq), back_grouped(dk, k), back_grouped(dv, v)


def _bwd_blockwise(res, g, causal: bool, block_k: int,
                   scale: Optional[float] = None):
    """Memory-efficient backward: recompute P blockwise from saved lse.
    (Plain-XLA reference path, selected via ``bwd_impl="xla"`` — the
    semantics oracle the Pallas backward kernels are tested against.)"""
    q, k, v, out, lse = res  # q,k,v,out: [B,L,H,D]; lse: [B*H, L]
    if _group(q, k) != 1:
        raise ValueError("bwd_impl='xla' has no grouped key-value heads")
    B, L, H, D = q.shape
    Dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    f32 = jnp.float32
    qf = q.astype(f32).transpose(0, 2, 1, 3).reshape(B * H, L, D)
    kf = k.astype(f32).transpose(0, 2, 1, 3).reshape(B * H, L, D)
    vf = v.astype(f32).transpose(0, 2, 1, 3).reshape(B * H, L, Dv)
    of = out.astype(f32).transpose(0, 2, 1, 3).reshape(B * H, L, Dv)
    gf = g.astype(f32).transpose(0, 2, 1, 3).reshape(B * H, L, Dv)

    delta = jnp.sum(of * gf, axis=-1)  # [BH, L] = rowsum(dO ∘ O)
    bk = min(block_k, L)
    nk = L // bk
    pos = jnp.arange(L)

    def kv_block(carry, j):
        dq = carry
        ks = jax.lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)  # [BH,bk,D]
        vs = jax.lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
        s = jnp.einsum("zqd,zkd->zqk", qf, ks) * scale             # [BH,L,bk]
        if causal:
            kpos = j * bk + jnp.arange(bk)
            s = jnp.where(kpos[None, None, :] <= pos[None, :, None], s, NEG_INF)
        p = jnp.exp(s - lse[:, :, None])                           # [BH,L,bk]
        dv = jnp.einsum("zqk,zqd->zkd", p, gf)
        dp = jnp.einsum("zqd,zkd->zqk", gf, vs)
        ds = p * (dp - delta[:, :, None]) * scale
        dq = dq + jnp.einsum("zqk,zkd->zqd", ds, ks)
        dk = jnp.einsum("zqk,zqd->zkd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = jax.lax.scan(kv_block, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B * H, L, D)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B * H, L, Dv)

    def back(x):
        return x.reshape(B, H, L, x.shape[-1]).transpose(0, 2, 1, 3)

    return (back(dq).astype(q.dtype), back(dk).astype(k.dtype),
            back(dv).astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    bwd_impl: str = "pallas",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Fused attention over ``q`` [B, L, H, D], ``k`` [B, L, G, D] and
    ``v`` [B, L, G, Dv], G dividing H (G = H: one key-value head a query
    head).  ``interpret=None`` auto-selects the Pallas interpreter off-TPU (slow,
    exact) and compiled mode on TPU.  ``bwd_impl``: "pallas" = fused
    dq/dk/dv kernels (default); "xla" = the blockwise-recompute reference
    path.  ``scale`` multiplies the scores (default ``D ** -0.5``)."""
    out, _ = _flash_fwd(q, k, v, causal, block_q, block_k,
                        _resolve_interpret(interpret), scale)
    return out


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention_on_mesh(q, k, v, causal: bool,
                            mesh: Optional[Mesh], **kernel_kw) -> jnp.ndarray:
    """``flash_attention`` inside a GSPMD program over ``mesh``;
    ``kernel_kw`` (block sizes, ``scale``) goes to the kernel.

    A Mosaic kernel has no SPMD partitioning rule (the TPU compiler
    refuses it: "Mosaic kernels cannot be automatically partitioned"), so
    on a mesh of more than one device the call is wrapped in a
    ``shard_map`` over the axes that shard batch (``data``) and heads
    (``model``, parallel/mesh.py) — the form ``parallel/ulysses.py`` uses —
    and every device runs the kernel on its own ``[B/data, L, H/model, D]``
    block.  An axis that does not divide
    its dimension is left out (GSPMD then gathers that dimension).  With no
    mesh, one device, or a caller that is already inside a ``shard_map``
    (the explicit-collectives step), this is the bare call."""
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return flash_attention(q, k, v, causal, **kernel_kw)

    def axis(name: str, dim: int) -> Optional[str]:
        fits = name in mesh.axis_names and dim % mesh.shape[name] == 0
        return name if fits else None

    # the key-value heads: they divide the query heads, so an axis that
    # divides them divides both
    spec = P(axis("data", q.shape[0]), None, axis("model", k.shape[2]), None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal, **kernel_kw),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def pick_attention_impl(L: int, attn_impl: str = "auto") -> str:
    """The shared 'auto' policy: the Pallas flash kernel on TPU at long,
    1024-aligned L (where it beats XLA dense ~1.4-2.4×, RESULTS_flash.json);
    dense otherwise.  Used by models/transformer.SelfAttention and the
    Ulysses a2a inner attention (parallel/ulysses.py)."""
    if attn_impl in ("flash", "dense"):
        return attn_impl
    if jax.default_backend() == "tpu" and L >= 4096 and L % 1024 == 0:
        return "flash"
    return "dense"


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret, bwd_impl, scale):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k,
                          _resolve_interpret(interpret), scale)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, interpret, bwd_impl, scale, res, g):
    if bwd_impl == "pallas":
        # The dq pass reuses the forward's q-block size; the dk/dv pass
        # accumulates over q blocks with the same tiling.
        return _bwd_pallas(res, g, causal, block_q, block_k,
                           _resolve_interpret(interpret), scale)
    if bwd_impl != "xla":
        raise ValueError(
            f"unknown bwd_impl {bwd_impl!r}: expected 'pallas' or 'xla'"
        )
    return _bwd_blockwise(res, g, causal, block_k, scale)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
