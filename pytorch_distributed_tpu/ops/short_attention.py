"""One-block fused attention for short, unmasked sequences (ViT shapes).

A sequence whose whole score tile fits VMEM needs none of what
``ops/flash_attention.py`` carries for long ones: no loop over key blocks,
no running max or denominator, no causal skipping, one backward pass
instead of two.  Here one grid step takes one batch row with **all its
heads**, computes scores, a float32 softmax and the weighted values on
VMEM tiles, and the ``[B, H, L, L]`` scores and probabilities never exist
in HBM.  The backward recomputes the probabilities once from the saved
logsumexp and writes ``dq``, ``dk`` and ``dv`` from the same grid step
(five products a head).  The two files share ``NEG_INF`` and nothing else.

Layout.  ``q``, ``k``, ``v`` are ``[B, L, H, D]`` as the projections give
them, seen by the kernels as ``[B, L, H*D]`` (a free reshape): a 128-lane
column group holds ``128 // D`` whole heads, so no operand is ever sliced
inside a lane tile; a head's 64-wide contraction is the group's 128-wide
one with the other head's lanes zeroed (the MXU contracts 128 deep either
way).  Scores are kept **transposed**, keys on sublanes and queries on
lanes: the softmax's max and sum over keys are then elementwise over
vregs, and the logsumexp of a head is a ``[1, L]`` row that is stored in
``[B, H, L]`` as it stands.

Padding happens inside the call and costs no HBM copy: the blocks are
``Lp = round_up(L, 128)`` rows long over arrays of ``L`` rows, so rows
``L..Lp`` of a VMEM block hold whatever was there.  The kernels zero those
rows of every operand whose padding could reach a valid result, mask
padded keys to ``NEG_INF`` before the softmax, and the rows of padded
queries are dropped when a block is written back.

Precision: the products take their operands in the inputs' type (bf16 on
the MXU under the bf16 policy) and accumulate in float32; scores, max,
exponentials, sum and normalisation are float32; the probabilities are
cast to the inputs' type only as the second product's operand, as flax
does with ``force_fp32_for_softmax=True``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.obs.trace import scope
from pytorch_distributed_tpu.ops.flash_attention import NEG_INF

LANES = 128
# Sublane tile of the narrowest operand type (bf16 packs 16 rows a vreg):
# the key axis of the score tile is rounded up to this, not to LANES.
SUBLANES = 16
# A v5e core has 128 MiB of VMEM and gives a kernel 16 MiB of it unless
# told otherwise.  These kernels ask for VMEM_LIMIT and plan for
# VMEM_BUDGET of it; the rest is the compiler's own scratch.
VMEM_LIMIT = 32 * 2 ** 20
VMEM_BUDGET = 24 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))   # contract the lanes of both: a @ b.T
_NN = (((1,), (0,)), ((), ()))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def one_block_bytes(length: int, heads: int, head_dim: int) -> int:
    """VMEM one backward grid step needs, the larger of the two kernels:
    eight ``[Lp, H*D]`` blocks (q, k, v, out, dO in; dq, dk, dv out), each
    double-buffered by the pipeline and counted at four bytes an element,
    and six float32 ``[Lp, Lp]`` tiles of scores and their gradients."""
    lp = _round_up(length, LANES)
    return 8 * 2 * lp * heads * head_dim * 4 + 6 * lp * lp * 4


def fits_one_block(length: int, heads: int, head_dim: int) -> bool:
    """Whether this module's kernels can run the shape: heads fill whole
    128-lane groups, and a grid step's blocks fit ``VMEM_BUDGET``.  At 12
    heads of 64 that is a padded length of up to 384, at 16 heads up to
    256: ViT-B/16, /32 and ViT-L/16 at 224 pixels (197, 50, 197 tokens)
    fit, 577 tokens (384 pixels) do not."""
    whole_groups = (head_dim <= LANES and LANES % head_dim == 0
                    and (heads * head_dim) % LANES == 0)
    return whole_groups and one_block_bytes(
        length, heads, head_dim) <= VMEM_BUDGET


def pick_attention(backend: str, length: int, heads: int, head_dim: int,
                   dropout: bool, masked: bool = False) -> str:
    """``"fused"`` or ``"dense"``, from what the call site can see: the
    fused kernels run on a TPU, without a mask or bias, with no dropout on
    the probabilities, where the padded tile fits one block."""
    if (backend == "tpu" and not masked and not dropout
            and fits_one_block(length, heads, head_dim)):
        return "fused"
    return "dense"


def _head_lanes(rows: int, head_dim: int):
    """For each head of a lane group, the mask of its lanes."""
    lane = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    return [(lane >= a * head_dim) & (lane < (a + 1) * head_dim)
            for a in range(LANES // head_dim)]


def _pad_rows(x, rows: int):
    """``x`` with zero rows appended up to ``rows`` (a matrix product's
    contraction runs over whole lane tiles)."""
    if x.shape[0] == rows:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((rows - x.shape[0], x.shape[1]), x.dtype)], axis=0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, length: int,
                head_dim: int, scale: float):
    """One batch row, every head.  Blocks ``[1, Lp, H*D]``; ``lse_ref``
    ``[1, H, Lp]``."""
    lp = q_ref.shape[1]
    lk = _round_up(length, SUBLANES)
    per = LANES // head_dim
    f32 = jnp.float32
    row_ok = lax.broadcasted_iota(jnp.int32, (lp, LANES), 0) < length
    key_ok = lax.broadcasted_iota(jnp.int32, (lk, lp), 0) < length
    lanes = _head_lanes(lk, head_dim)
    for g in range(q_ref.shape[2] // LANES):
        cols = slice(g * LANES, (g + 1) * LANES)
        # as flax: the query is scaled in its own type before the product
        qs = q_ref[0, :, cols] * scale                   # [Lp, 128]
        k = k_ref[0, :lk, cols]                          # [Lk, 128]
        # 0 * anything must stay 0 in the second product
        vt = jnp.where(row_ok, v_ref[0, :, cols], 0).T   # [128, Lp]
        outs = []
        for a in range(per):
            ka = jnp.where(lanes[a], k, 0) if per > 1 else k
            st = lax.dot_general(ka, qs, _NT,
                                 preferred_element_type=f32)  # [Lk, Lp]
            st = jnp.where(key_ok, st, NEG_INF)
            m = jnp.max(st, axis=0, keepdims=True)       # [1, Lp]
            e = jnp.exp(st - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            # one exact reciprocal a query, not one division a score
            pt = (e * (1.0 / l)).astype(vt.dtype)
            lse_ref[0, g * per + a:g * per + a + 1, :] = m + jnp.log(l)
            outs.append(lax.dot_general(
                vt[a * head_dim:(a + 1) * head_dim], _pad_rows(pt, lp), _NN,
                preferred_element_type=f32))             # [D, Lp]
        ot = outs[0] if per == 1 else jnp.concatenate(outs, axis=0)
        o_ref[0, :, cols] = ot.T.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, *, length: int, head_dim: int,
                scale: float):
    """One batch row, every head: P recomputed once, ``dq``, ``dk`` and
    ``dv`` written from the same tile."""
    lp = q_ref.shape[1]
    lk = _round_up(length, SUBLANES)
    per = LANES // head_dim
    f32 = jnp.float32
    row_ok = lax.broadcasted_iota(jnp.int32, (lp, LANES), 0) < length
    key_ok = lax.broadcasted_iota(jnp.int32, (lk, lp), 0) < length
    query_ok = lax.broadcasted_iota(jnp.int32, (1, lp), 1) < length
    lanes = _head_lanes(lk, head_dim)
    # row a: ones on head a's lanes.  delta[a, q] = sum_d dO[q, d] O[q, d]
    # over that head, as a row, by one small float32 product.
    head_rows = (
        lax.broadcasted_iota(jnp.int32, (8, LANES), 1) // head_dim
        == lax.broadcasted_iota(jnp.int32, (8, LANES), 0)).astype(f32)

    def rows(ref, cols):
        # padded rows zeroed: a padded query or key adds to no gradient
        return jnp.where(row_ok, ref[0, :, cols], 0)

    for g in range(q_ref.shape[2] // LANES):
        cols = slice(g * LANES, (g + 1) * LANES)
        qs = rows(q_ref, cols) * scale                   # [Lp, 128]
        k, v = rows(k_ref, cols), rows(v_ref, cols)
        do = rows(do_ref, cols)
        kt = k.T                                         # [128, Lp]
        delta = lax.dot_general(
            head_rows, do.astype(f32) * rows(o_ref, cols).astype(f32), _NT,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=f32)                  # [8, Lp]
        dk = jnp.zeros((lk, LANES), f32)
        dv = jnp.zeros((lk, LANES), f32)
        dqs = []
        for a in range(per):
            h = g * per + a
            ka = jnp.where(lanes[a], k[:lk], 0) if per > 1 else k[:lk]
            va = jnp.where(lanes[a], v[:lk], 0) if per > 1 else v[:lk]
            st = lax.dot_general(ka, qs, _NT,
                                 preferred_element_type=f32)  # [Lk, Lp]
            st = jnp.where(key_ok, st, NEG_INF)
            lse = jnp.where(query_ok, lse_ref[0, h:h + 1, :], 0.0)
            pt = jnp.exp(st - lse)
            dpt = lax.dot_general(va, do, _NT, preferred_element_type=f32)
            dst = (pt * (dpt - delta[a:a + 1])).astype(qs.dtype)
            dv_a = lax.dot_general(pt.astype(do.dtype), do, _NN,
                                   preferred_element_type=f32)  # [Lk, 128]
            dk_a = lax.dot_general(dst, qs, _NN, preferred_element_type=f32)
            dv = jnp.where(lanes[a], dv_a, dv) if per > 1 else dv_a
            dk = jnp.where(lanes[a], dk_a, dk) if per > 1 else dk_a
            dqs.append(lax.dot_general(
                kt[a * head_dim:(a + 1) * head_dim], _pad_rows(dst, lp), _NN,
                preferred_element_type=f32) * scale)     # [D, Lp]
        dqt = dqs[0] if per == 1 else jnp.concatenate(dqs, axis=0)
        dq_ref[0, :, cols] = dqt.T.astype(dq_ref.dtype)
        dk_ref[0, :lk, cols] = dk.astype(dk_ref.dtype)
        dv_ref[0, :lk, cols] = dv.astype(dv_ref.dtype)


def _specs(lp: int, width: int, heads: int):
    block = pl.BlockSpec((1, lp, width), lambda b: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    lse = pl.BlockSpec((1, heads, lp), lambda b: (b, 0, 0),
                       memory_space=pltpu.VMEM)
    return block, lse


def _bytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


# Jitted: the twelve layers of a model then trace and lower each kernel
# once, not once a layer.  A kernel's body is some thousand operations
# (every head written out), and tracing it 48 times added 14 s to the
# start of a run (my chip run, PR 27).
_once_a_shape = functools.partial(
    jax.jit, static_argnames=("heads", "length", "interpret"))


@_once_a_shape
def _fused_fwd(q, k, v, heads: int, length: int, interpret: bool):
    """``q, k, v`` ``[B, L', H*D]`` whose first ``length`` rows count (the
    public call has ``L' == length``).  Returns ``out`` like ``q`` and the
    float32 logsumexp ``[B, H, L']``."""
    B, rows, width = q.shape
    head_dim = width // heads
    lp = _round_up(length, LANES)
    block, lse_block = _specs(lp, width, heads)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct((B, heads, rows), jnp.float32)]
    pairs = B * heads * length * length
    with scope("vit_attn"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, length=length, head_dim=head_dim,
                              scale=head_dim ** -0.5),
            grid=(B,),
            in_specs=[block, block, block],
            out_specs=[block, lse_block],
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=VMEM_LIMIT),
            cost_estimate=pl.CostEstimate(
                flops=2 * 2 * pairs * head_dim, transcendentals=pairs,
                bytes_accessed=_bytes(q, k, v, *out_shape)),
            interpret=interpret,
            name="vit_attn_fwd",
        )(q, k, v)


@_once_a_shape
def _fused_bwd(q, k, v, out, lse, g, heads: int, length: int,
               interpret: bool):
    B, _, width = q.shape
    head_dim = width // heads
    lp = _round_up(length, LANES)
    block, lse_block = _specs(lp, width, heads)
    pairs = B * heads * length * length
    with scope("vit_attn"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, length=length, head_dim=head_dim,
                              scale=head_dim ** -0.5),
            grid=(B,),
            in_specs=[block] * 5 + [lse_block],
            out_specs=[block] * 3,
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in (q, k, v)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=VMEM_LIMIT),
            cost_estimate=pl.CostEstimate(
                flops=5 * 2 * pairs * head_dim, transcendentals=pairs,
                bytes_accessed=_bytes(q, k, v, out, g, lse, q, k, v)),
            interpret=interpret,
            name="vit_attn_bwd",
        )(q, k, v, out, g, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def short_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Non-causal attention over ``q, k, v`` ``[B, L, H, D]`` with the
    scores scaled by ``D ** -0.5``, for shapes ``fits_one_block`` admits.
    ``interpret=None`` runs the Pallas interpreter off the TPU."""
    return _sa_fwd(q, k, v, interpret)[0]


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _sa_fwd(q, k, v, interpret):
    B, L, H, D = q.shape
    if not fits_one_block(L, H, D):
        raise ValueError(
            f"short_attention: {L} tokens of {H} heads of {D} do not fit "
            f"one block ({one_block_bytes(L, H, D)} bytes of VMEM against "
            f"{VMEM_BUDGET}, heads in whole {LANES}-lane groups)")
    flat = [x.reshape(B, L, H * D) for x in (q, k, v)]
    out, lse = _fused_fwd(*flat, H, L, _resolve_interpret(interpret))
    # residuals: the unpadded operands, the output and [B, H, L] float32
    return out.reshape(q.shape), (*flat, out, lse)


def _sa_bwd(interpret, res, g):
    q, k, v, out, lse = res
    B, L, _ = q.shape
    H = lse.shape[1]
    grads = _fused_bwd(q, k, v, out, lse, g.reshape(q.shape), H, L,
                       _resolve_interpret(interpret))
    return tuple(x.reshape(g.shape) for x in grads)


short_attention.defvjp(_sa_fwd, _sa_bwd)


def short_attention_on_mesh(q, k, v, mesh: Optional[Mesh],
                            interpret: Optional[bool] = None):
    """``short_attention`` inside a program over ``mesh``.  A Mosaic call
    has no partitioning rule, so on a mesh of more than one device it runs
    under a ``shard_map`` over the axes that shard batch (``data``) and
    heads (``model``), every device on its own ``[B/data, L, H/model, D]``;
    an axis that does not divide its dimension, or would split a lane
    group of heads, is left out (GSPMD gathers that dimension).  One
    device, or a caller already inside a ``shard_map``, is the bare call."""
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return short_attention(q, k, v, interpret)
    B, _, H, D = q.shape

    def axis(name: str, dim: int, unit: int = 1) -> Optional[str]:
        fits = (name in mesh.axis_names
                and dim % (mesh.shape[name] * unit) == 0)
        return name if fits else None

    spec = P(axis("data", B), None, axis("model", H, LANES // D), None)
    return jax.shard_map(
        lambda q, k, v: short_attention(q, k, v, interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
