"""Fused conv1x1+BN(+ReLU) backward — the BN-dx fold (ROADMAP item 1).

**Measured outcome (v5e, 2026-07-31): the fold LOSES — keep it off.**  The
full-model fused variant runs the b256 ResNet-50 step at 1,208-1,395 img/s
vs 2,536 unfused (scripts/fused_triage.py); per-shape, the kernels never beat
the XLA backward at any of the 13 distinct conv->BN backward shapes in the
model (0.54-0.96x, scripts/profile_fused_conv_bn.py).  The premise was
traffic: autodiff writes dy to HBM and the dgrad/wgrad convs read it back.
The optimized HLO (scripts/hlo_dy_check.py) shows XLA instead *clones* the
cheap elementwise dy computation into each consumer's input fusion and its
conv emitters stream near HBM peak — so the fold saves less traffic than
theorized and pays for it with hand-scheduled Mosaic matmuls that reach a
fraction of the conv emitters' effective bandwidth, plus custom-call
boundaries that break XLA's surrounding fusions.  The module stays as an
opt-in (``--fused-convbn``), fully parity-tested, as the measured record of
why the obvious kernel-fusion route past the step's memory roofline does
not work on this chip.

The round-2 roofline (scripts/profile_trace.py) showed the ResNet-50 step is
HBM-bound with a ~3,080 img/s ceiling at b256; the only route past it is
removing whole memory passes.  The largest remaining pass *appeared* to be
the BN-backward dx: autodiff materializes ``dy`` (the gradient at the conv
output / BN input) to HBM, then the dgrad and wgrad convolutions each read
it back — for every conv→BN pair, (y, do) are read for the reductions, read
again to form dy, dy is written, then read twice more:

    XLA (theorized): reduce(y,do) + write dy(y,do) + dgrad(dy) + wgrad(dy,a)
                     ≈ 9 tensor-passes per pair
    this kernel:     reduce(y,do) + fused[dy in VMEM → dgrad+wgrad]
                     ≈ 6 tensor-passes — dy never exists in HBM

For the 1×1 stride-1 convolutions the conv is exactly a matmul over
channels, so the fold is a single Pallas kernel: per M-tile (M = N·H·W
rows), recompute the ReLU mask and dy in VMEM from (y, do) and per-channel
vectors, then

    da(tile)  = dy @ Wᵀ                       (MXU)
    dW       += aᵀ @ dy     (f32 accumulator, written at the last grid step)

reading y, do, a from HBM exactly once each.  The 3×3 stride-1 SAME conv
(the bottleneck's middle conv) folds the same way with per-IMAGE tiling —
every ResNet-50 3×3 plane fits VMEM whole, so dgrad/wgrad become 9
shifted matmuls each off the in-VMEM dy with no halo exchange
(``_bwd3_kernel``).  Together that folds every conv of a stride-1
bottleneck whose plane passes the VMEM guard below (under the 96 MiB
``CompilerParams`` cap all four ResNet-50 bf16 stages engage, full-model
compile validated on v5e) plus the 1×1s of strided blocks; strided /
grouped / genuinely oversized slots keep the plain XLA backward
(``models/resnet.py`` selects).

Forward is unchanged XLA (conv + the one-pass BN+ReLU of ops/fused_bn.py) —
forward fusion is something XLA already does well; the backward pass is where
the traffic lives.

Semantics match ``nn.Conv(use_bias=False)`` → ``FusedBatchNormAct`` exactly
(global-batch SyncBN statistics under GSPMD, per-shard statistics under
shard_map — identical to the unfused pair; tests/test_fused_conv_bn.py).

Reference anchor: the conv+BN stacks of every torchvision model the
reference instantiates (reference distributed.py:134-139); the perf target
is the reference's recorded-wall-clock methodology (reference README.md:15-17).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pytorch_distributed_tpu.ops.fused_bn import _bn_act, _bn_act_fwd


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# Mosaic's default scoped-VMEM cap is 16 MiB; the whole-plane 3x3 kernel's
# stack (f32 dy/dof temporaries + padded copies, every channel dim lane-
# padded to 128) measures 21.7 MiB at ResNet-50's 56x56x64 slot on a real
# v5e.  The chip has 128 MiB of VMEM — raise the cap for these kernels and
# let conv3x3_plane_fits_vmem keep genuinely oversized slots on the XLA
# backward.
_VMEM_LIMIT_BYTES = 96 << 20
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _pick_mtile(M: int, Ci: int, Co: int, itemsize: int) -> int:
    """M-tile for ``_bwd_kernel``: as many rows as fit a ~24 MiB stack.

    A v5e measurement (runs of 2026-07-31) showed the original fixed
    128/256-row tiles cost the full-model step 45%: stage 1 becomes a
    3,136-step grid moving 32 KB blocks — far too little work per step to
    amortize DMA issue + grid overhead.  Per-row footprint counts the
    lane-padded (128) channel dims: the y/do/a/da blocks (double-buffered
    by Mosaic's pipeline), the f32 y/do temporaries, and the f32 dgrad
    accumulator before the output cast."""
    ci_p = ((Ci + 127) // 128) * 128
    co_p = ((Co + 127) // 128) * 128
    # Per-row: y/do/a/da blocks (double-buffered), f32 y/do temps, the
    # cast dy tile, and the f32 dgrad accumulator pre-cast.
    row = (2 * (ci_p + co_p) * itemsize * 2 + 2 * co_p * 4
           + co_p * itemsize + ci_p * 4)
    # Grid-constant: the weights tile and the f32 dW accumulator.
    fixed = ci_p * co_p * (itemsize + 4)
    mt = max(0, (24 << 20) - fixed) // row
    mt = max(256, min(8192, (mt // 256) * 256))
    # Never tile far past M itself (small call sites pad to one tile).
    return min(mt, ((M + 255) // 256) * 256)


def _bwd_kernel(y_ref, do_ref, a_ref, w_ref, vec_ref, da_ref, dw_ref,
                *, relu: bool, cdt):
    """One M-tile: dy in VMEM, then dgrad + wgrad off the same registers.

    vec rows: 0=s (γ·inv), 1=t, 2=u  (dy = s∘dof + t∘y + u), 3=v
    (mask pre-activation = s∘y + v); see the wrapper for the algebra.
    """
    i = pl.program_id(0)
    yf = y_ref[:].astype(jnp.float32)                    # [MT, Co]
    dof = do_ref[:].astype(jnp.float32)                  # [MT, Co]
    s = vec_ref[0:1, :]                                  # [1, Co]
    t = vec_ref[1:2, :]
    u = vec_ref[2:3, :]
    if relu:
        v = vec_ref[3:4, :]
        dof = jnp.where(yf * s + v > 0, dof, 0.0)
    dy = (dof * s + yf * t + u).astype(cdt)              # [MT, Co]
    # dgrad: da = dy @ Wᵀ (contract Co)
    da_ref[:] = jax.lax.dot_general(
        dy, w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(da_ref.dtype)
    # wgrad: dW += aᵀ @ dy (contract M), f32 accumulation across the grid —
    # the output block is grid-constant, so it lives in VMEM for the whole
    # kernel and is written back once.
    contrib = jax.lax.dot_general(
        a_ref[:].astype(cdt), dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == 0)
    def _():
        dw_ref[:] = contrib

    @pl.when(i > 0)
    def _():
        dw_ref[:] = dw_ref[:] + contrib


def _fused_dgrad_wgrad(y, do, a, w, s, t, u, v, relu: bool, interpret: bool
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """da, dW for the 1×1 conv whose output fed BN — one pass over (y,do,a).

    Shapes: y/do [..., Co], a [..., Ci] with identical leading dims; w
    [Ci, Co].  Leading dims are flattened to M rows and zero-padded to the
    tile size (padded ``do``/``a`` rows are zero, so they contribute nothing
    to dW and their da rows are dropped; bench shapes divide evenly).
    """
    Ci, Co = w.shape
    M = 1
    for d in y.shape[:-1]:
        M *= d
    y2 = y.reshape(M, Co)
    do2 = do.reshape(M, Co)
    a2 = a.reshape(M, Ci)
    cdt = a.dtype
    mt = _pick_mtile(M, Ci, Co, jnp.dtype(cdt).itemsize)
    mp = ((M + mt - 1) // mt) * mt
    if mp != M:
        pad = ((0, mp - M), (0, 0))
        y2 = jnp.pad(y2, pad)
        do2 = jnp.pad(do2, pad)
        a2 = jnp.pad(a2, pad)
    vec = jnp.stack([s, t, u, v]).astype(jnp.float32)    # [4, Co]
    da2, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, relu=relu, cdt=cdt),
        grid=(mp // mt,),
        in_specs=[
            pl.BlockSpec((mt, Co), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((mt, Co), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((mt, Ci), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((Ci, Co), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((4, Co), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((mt, Ci), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((Ci, Co), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, Ci), cdt),
            jax.ShapeDtypeStruct((Ci, Co), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(y2, do2, a2, w.astype(cdt), vec)
    return da2[:M].reshape(a.shape), dw


def _bwd3_kernel(y_ref, do_ref, a_ref, w_ref, vec_ref, da_ref, dw_ref,
                 *, relu: bool, cdt, H: int, Wd: int):
    """One image (grid over N): dy for the full [H, W, Co] plane in VMEM,
    then the 3x3 dgrad and wgrad as 9 shifted matmuls each — the same
    one-read-per-tensor economics as the 1x1 kernel, with the halo problem
    dissolved by whole-plane tiling (every ResNet-50 3x3 plane fits VMEM;
    56x56x64 bf16 is ~400 KB, 7x7x512 is ~50 KB).
    """
    n = pl.program_id(0)
    Co = y_ref.shape[-1]
    Ci = a_ref.shape[-1]
    yf = y_ref[0].astype(jnp.float32)                    # [H, W, Co]
    dof = do_ref[0].astype(jnp.float32)
    s = vec_ref[0:1, :].reshape(1, 1, Co)
    t = vec_ref[1:2, :].reshape(1, 1, Co)
    u = vec_ref[2:3, :].reshape(1, 1, Co)
    if relu:
        v = vec_ref[3:4, :].reshape(1, 1, Co)
        dof = jnp.where(yf * s + v > 0, dof, 0.0)
    dy = (dof * s + yf * t + u).astype(cdt)              # [H, W, Co]
    af = a_ref[0].astype(cdt)                            # [H, W, Ci]
    # Zero-pad once; every (kh, kw) tap is then a static slice.
    dyp = jnp.pad(dy, ((1, 1), (1, 1), (0, 0)))
    ap = jnp.pad(af, ((1, 1), (1, 1), (0, 0)))
    dx = jnp.zeros((H * Wd, Ci), jnp.float32)
    for kh in range(3):
        for kw in range(3):
            # dgrad: dx[p,q] += dy[p-kh+1, q-kw+1] @ W[kh,kw]^T
            sh = dyp[2 - kh:2 - kh + H, 2 - kw:2 - kw + Wd, :]
            dx = dx + jax.lax.dot_general(
                sh.reshape(H * Wd, Co), w_ref[kh, kw],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # wgrad: dW[kh,kw] += a[h+kh-1, w+kw-1]^T @ dy[h, w]
            sa = ap[kh:kh + H, kw:kw + Wd, :]
            contrib = jax.lax.dot_general(
                sa.reshape(H * Wd, Ci), dy.reshape(H * Wd, Co),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

            @pl.when(n == 0)
            def _():
                dw_ref[kh, kw] = contrib

            @pl.when(n > 0)
            def _():
                dw_ref[kh, kw] = dw_ref[kh, kw] + contrib
    da_ref[0] = dx.reshape(H, Wd, Ci).astype(da_ref.dtype)


def _fused_dgrad_wgrad_3x3(y, do, a, w, s, t, u, v, relu: bool,
                           interpret: bool):
    """da, dW for the 3x3 stride-1 SAME conv whose output fed BN.

    Shapes: y/do [N, H, W, Co], a [N, H, W, Ci], w [3, 3, Ci, Co]."""
    N, H, Wd, Co = y.shape
    Ci = a.shape[-1]
    cdt = a.dtype
    vec = jnp.stack([s, t, u, v]).astype(jnp.float32)
    da, dw = pl.pallas_call(
        functools.partial(_bwd3_kernel, relu=relu, cdt=cdt, H=H, Wd=Wd),
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, H, Wd, Co), lambda n: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, H, Wd, Co), lambda n: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, H, Wd, Ci), lambda n: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, Ci, Co), lambda n: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((4, Co), lambda n: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, H, Wd, Ci), lambda n: (n, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((3, 3, Ci, Co), lambda n: (0, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, Wd, Ci), cdt),
            jax.ShapeDtypeStruct((3, 3, Ci, Co), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(y, do, a, w.astype(cdt), vec)
    return da, dw


def _conv3x3(a, w):
    return jax.lax.conv_general_dilated(
        a, w.astype(a.dtype), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv1x1(a, w):
    return jax.lax.conv_general_dilated(
        a, w.astype(a.dtype), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _make_conv_bn_op(conv_fwd, dgrad_wgrad, doc: str):
    """Build a ``(o, mu, var) = BN+ReLU(conv(a, w))`` custom-VJP op from a
    forward conv primitive and a fused dgrad+wgrad backward — one
    residual-packing / cotangent-unpacking implementation for both kernel
    shapes."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
    def op(a, w, gamma, beta, eps: float, relu: bool,
           interpret: Optional[bool] = None):
        (o, mu, var), _ = fwd(a, w, gamma, beta, eps, relu, interpret)
        return o, mu, var

    def fwd(a, w, gamma, beta, eps, relu, interpret):
        y = conv_fwd(a, w)
        (o, mu, var), (y_res, mu_res, inv, g_res, b_res) = _bn_act_fwd(
            y, gamma, beta, eps, relu
        )
        return (o, mu, var), (a, w, y_res, mu_res, inv, g_res, b_res)

    def bwd(eps, relu, interpret, res, cts):
        a, w, y, mu, inv, gamma, beta = res
        do = cts[0]  # mu/var cotangents are zero (EMA is stop-grad)
        s, t, u, v, dgamma, dbeta = _bn_bwd_vectors(y, do, mu, inv, gamma,
                                                    beta, relu)
        da, dw = dgrad_wgrad(y, do, a, w, s, t, u, v, relu,
                             _resolve_interpret(interpret))
        return (da.astype(a.dtype), dw.reshape(w.shape).astype(w.dtype),
                dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype))

    op.defvjp(fwd, bwd)
    op.__doc__ = doc
    return op


conv1x1_bn_act = _make_conv_bn_op(
    _conv1x1,
    lambda y, do, a, w, *r: _fused_dgrad_wgrad(
        y, do, a, w.reshape(w.shape[-2], w.shape[-1]), *r),
    doc="""``(o, mu, var) = BN+ReLU(conv1x1(a, w))`` with the fused backward.

    ``a``: NHWC activations; ``w``: [1, 1, Ci, Co] (HWIO) f32 params cast to
    ``a.dtype`` for compute, like ``nn.Conv(dtype=...)``.  mu/var are exposed
    for the EMA update (stop-gradiented by the caller, like ops/fused_bn).
    """,
)

conv3x3_bn_act = _make_conv_bn_op(
    _conv3x3,
    _fused_dgrad_wgrad_3x3,
    doc="""``(o, mu, var) = BN+ReLU(conv3x3_s1_SAME(a, w))`` with the fused
    backward — the 3x3 counterpart of ``conv1x1_bn_act`` (the bottleneck's
    middle conv when stride 1 and ungrouped).""",
)


def conv3x3_plane_fits_vmem(h: int, w_: int, ci: int, co: int,
                            itemsize: int, budget: int = 48 << 20) -> bool:
    """Per-grid-step working-set estimate for ``_bwd3_kernel`` (blocks +
    padded copies + f32 accumulators + weights and the f32 dW): whole-plane
    tiling only engages when it fits comfortably under the raised
    ``_VMEM_LIMIT_BYTES``; otherwise the caller keeps the unfused XLA
    backward for that slot.  Under the 96 MiB cap every ResNet-50 bf16
    plane engages (and the wide-resnet f32 stage-1 plane, ~30 MiB
    estimated, now fits too); genuinely oversized working sets — e.g.
    112x112 planes at 256+ f32 channels — still decline.

    Mosaic lays every [..., C] VMEM buffer out in (8, 128) tiles, so channel
    dims are lane-padded to 128 — at ResNet-50's 64-channel stage that
    doubles every plane buffer.  With padded channels this formula estimates
    14.7 MiB for the 56x56x64 slot; a real v5e measures a 21.7 MiB scoped
    allocation (extra Mosaic temporaries for the 9 shifted-slice matmuls),
    so the estimate carries a 1.5x headroom factor."""
    ci_p = ((ci + 127) // 128) * 128
    co_p = ((co + 127) // 128) * 128
    hw = (h + 2) * (w_ + 2)
    # planes (y/do/a/da blocks + f32 dy intermediates + padded copies) +
    # the grid-constant weights and f32 dW accumulator (not
    # double-buffered).
    est = (hw * (12 * co_p + 8 * ci_p + 3 * itemsize * (ci_p + co_p))
           + 9 * ci_p * co_p * (itemsize + 4))
    return (est * 3) // 2 <= budget


def _bn_bwd_vectors(y, do, mu, inv, gamma, beta, relu: bool):
    """Pass 1 (XLA, fused reductions): dβ, dγ and the per-channel vectors
    the fused kernels consume.  Under GSPMD with a sharded batch the
    reductions are global (SyncBN backward); under shard_map per-shard —
    identical to the unfused _bn_act_bwd.

    dy = s·(dof − dβ/n − x̂·dγ/n) rearranged to two per-channel FMAs:
    dy = s∘dof + t∘y + u with t = −s·inv·dγ/n, u = −s·dβ/n − t·μ; the
    ReLU mask pre-activation is s∘y + v with v = β − s·μ."""
    f32 = jnp.float32
    axes = tuple(range(y.ndim - 1))
    n = 1
    for ax in axes:
        n *= y.shape[ax]
    yf = y.astype(f32)
    dof = do.astype(f32)
    s = gamma * inv
    v = beta - s * mu
    if relu:
        dof = jnp.where(yf * s + v > 0, dof, 0.0)
    dbeta = dof.sum(axes)
    xhat = (yf - mu) * inv
    dgamma = (dof * xhat).sum(axes)
    t = -(s * inv) * (dgamma / n)
    u = -s * (dbeta / n) - t * mu
    return s, t, u, v, dgamma, dbeta


def conv1x1_bn(mdl, conv_name: str, bn_name: str, x, features: int, *,
               relu: bool, use_running_average: bool, dtype,
               momentum: float = 0.9, eps: float = 1e-5,
               scale_init=None, fused: bool = True,
               interpret: Optional[bool] = None,
               kernel_size: Tuple[int, int] = (1, 1)):
    """Flax-level combinator: a ``Conv_k``→``FusedBatchNormAct_k`` pair whose
    params live at EXACTLY the unfused pair's paths (declared through child
    scopes), so toggling the fused backward never invalidates a checkpoint —
    asserted by tests/test_fused_conv_bn.py.

    ``mdl`` is the calling (compact) module; names are the explicit child
    names the unfused branch would auto-assign.  ``kernel_size`` selects
    the fused op: (1, 1) or (3, 3) stride-1 SAME (the two bottleneck
    shapes with fused backwards).
    """
    from flax import linen as nn

    if kernel_size not in ((1, 1), (3, 3)):
        raise ValueError(f"no fused backward for kernel {kernel_size}")
    is3 = kernel_size == (3, 3)
    conv_fwd = _conv3x3 if is3 else _conv1x1
    fused_op = conv3x3_bn_act if is3 else conv1x1_bn_act
    if is3 and fused and not conv3x3_plane_fits_vmem(
            x.shape[1], x.shape[2], x.shape[-1], features,
            jnp.dtype(dtype).itemsize):
        fused = False  # unfused XLA backward for this oversized slot
    if scale_init is None:
        scale_init = nn.initializers.ones
    ci = x.shape[-1]
    csc = mdl.scope.push(conv_name)
    kernel = csc.param("kernel", nn.initializers.lecun_normal(),
                       kernel_size + (ci, features), jnp.float32)
    bsc = mdl.scope.push(bn_name)
    gamma = bsc.param("scale", scale_init, (features,), jnp.float32)
    beta = bsc.param("bias", nn.initializers.zeros, (features,), jnp.float32)
    ra_mean = bsc.variable("batch_stats", "mean",
                           lambda: jnp.zeros((features,), jnp.float32))
    ra_var = bsc.variable("batch_stats", "var",
                          lambda: jnp.ones((features,), jnp.float32))

    xd = x.astype(dtype)
    if use_running_average:
        y = conv_fwd(xd, kernel)
        invr = jax.lax.rsqrt(ra_var.value + eps)
        scale = gamma * invr
        shift = beta - ra_mean.value * scale
        o = (y.astype(jnp.float32) * scale + shift).astype(y.dtype)
        return jax.nn.relu(o) if relu else o

    if mdl.is_initializing() or not fused:
        y = conv_fwd(xd, kernel)
        o, mu, var = _bn_act(y, gamma, beta, eps, relu)
    else:
        o, mu, var = fused_op(xd, kernel, gamma, beta, eps, relu,
                              interpret)
    if not mdl.is_initializing():
        m = momentum
        ra_mean.value = m * ra_mean.value + (1 - m) * jax.lax.stop_gradient(mu)
        ra_var.value = m * ra_var.value + (1 - m) * jax.lax.stop_gradient(var)
    return o
