"""Pallas flash attention vs dense oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops.flash_attention import (
    _flash_fwd,
    block_schedule,
    blocks_visited,
    flash_attention,
)
from pytorch_distributed_tpu.parallel.ring import dense_attention


def _qkv(B=2, L=128, H=2, D=64, seed=0, Dv=None):
    rng = np.random.default_rng(seed)
    mk = lambda d: jnp.asarray(rng.normal(size=(B, L, H, d)).astype(np.float32))
    return mk(D), mk(D), mk(Dv or D)


def _dense(q, k, v, causal):
    """Float32 scores, softmax and log-sum-exp, ``v`` of any width:
    ``(out [B, L, H, Dv], lse [B*H, L])``."""
    B, L, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * D ** -0.5
    if causal:
        pos = jnp.arange(L)
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                     v.astype(jnp.float32))
    return out, jax.nn.logsumexp(s, -1).reshape(B * H, L)


# ------------------------------------------------ the schedule (no kernel)

def _unmasked(L, causal):
    q, k = np.meshgrid(np.arange(L), np.arange(L), indexing="ij")
    return (k <= q) if causal else np.ones((L, L), bool)


SCHEDULES = [(L, bq, bk, causal, order)
             for L, bq, bk in [(64, 16, 16), (64, 8, 32), (64, 32, 8),
                               (96, 16, 48), (32, 32, 32), (128, 64, 16)]
             for causal in (True, False) for order in ("q", "kv")]


@pytest.mark.parametrize("L,bq,bk,causal,order", SCHEDULES)
def test_schedule_covers_each_unmasked_pair_once(L, bq, bk, causal, order):
    sched = block_schedule(L, bq, bk, causal, order)
    seen = np.zeros((L, L), int)
    for i, j in zip(sched.q_block, sched.kv_block):
        seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk] += 1
    want = _unmasked(L, causal)
    assert (seen[want] == 1).all()       # each unmasked pair, exactly once
    assert seen.max() == 1               # and no block twice
    for i, j, crossed in zip(sched.q_block, sched.kv_block, sched.crossed):
        block = want[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
        assert block.any()               # no visited block wholly masked
        assert bool(crossed) == (not block.all())
    assert all(t.dtype == np.int32 for t in sched)


@pytest.mark.parametrize("L,bq,bk,causal,order", SCHEDULES)
def test_schedule_rows_are_contiguous(L, bq, bk, causal, order):
    sched = block_schedule(L, bq, bk, causal, order)
    row = sched.q_block if order == "q" else sched.kv_block
    n_rows = L // (bq if order == "q" else bk)
    # every row of the result is visited, its steps in one run ...
    runs = [r for n, r in enumerate(row) if n == 0 or r != row[n - 1]]
    assert runs == list(range(n_rows))
    # ... bracketed by one first and one last
    turn = np.r_[True, row[1:] != row[:-1]]
    assert (sched.first == turn).all()
    assert (sched.last == np.r_[turn[1:], True]).all()
    assert sched.first.sum() == sched.last.sum() == n_rows


@pytest.mark.parametrize("L,bq,bk,visited,masked", [
    (8192, 1024, 1024, 36, 8),      # the configured decoder's blocks
    (8192, 256, 1024, 144, 32),     # the kernel's default blocks
    (8192, 512, 512, 136, 16),
])
def test_schedule_counts(L, bq, bk, visited, masked):
    assert blocks_visited(L, bq, bk) == (visited, masked)
    assert blocks_visited(L, bq, bk, causal=False) == (
        (L // bq) * (L // bk), 0)
    for order in ("q", "kv"):
        sched = block_schedule(L, bq, bk, True, order)
        assert (len(sched.q_block), int(sched.crossed.sum())) == (
            visited, masked)
        dense = block_schedule(L, bq, bk, False, order)
        assert len(dense.q_block) == (L // bq) * (L // bk)
        assert not dense.crossed.any()


def test_schedule_rejects_unknown_order():
    with pytest.raises(ValueError, match="order"):
        block_schedule(64, 16, 16, True, "rows")


# ------------------------------------- the kernels (Pallas interpreter)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, 64, 64, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk,D,Dv", [
    (32, 64, 64, 64),     # bq < bk
    (64, 16, 64, 64),     # bq > bk
    (32, 32, 24, 16),     # D_qk != D_v (a scaled 192 | 128)
    (16, 64, 24, 16),
])
def test_flash_forward_and_lse_match_dense(bq, bk, D, Dv, causal):
    """Output and log-sum-exp over several batch-heads, for blocks the
    diagonal crosses off-centre."""
    q, k, v = _qkv(B=2, L=128, H=2, D=D, Dv=Dv, seed=5)
    want, want_lse = _dense(q, k, v, causal)
    got, lse = _flash_fwd(q, k, v, causal, bq, bk, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)


def test_flash_multiblock_accumulation():
    # L=256 with 64-blocks: 4x4 block grid exercises the online-softmax
    # correction across many steps.
    q, k, v = _qkv(L=256)
    want = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, True, 64, 64, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(L=64, H=1, D=32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, 32, 32, True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_bf16():
    q, k, v = _qkv()
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = flash_attention(qb, kb, vb, True, 64, 64, True)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=5e-2, atol=5e-2)


def test_flash_rejects_indivisible_length():
    q, k, v = _qkv(L=96)
    with pytest.raises(AssertionError, match="must divide"):
        flash_attention(q, k, v, True, 64, 64, True)


@pytest.mark.parametrize("bwd_impl", ["pallas", "xla"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_bwd_impls_match_dense_multiblock(causal, bwd_impl):
    """Both backward implementations, multi-block grid (the Pallas dq and
    dk/dv kernels accumulate across 4x4 blocks here)."""
    q, k, v = _qkv(L=128, H=2, D=32, seed=3)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal, 32, 32, True, bwd_impl) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk,D,Dv", [
    (16, 64, 32, 32),     # bq < bk: a kv block's row starts mid-block
    (64, 16, 32, 32),     # bq > bk: several crossed blocks a q row
    (32, 32, 24, 16),     # D_qk != D_v
    (64, 32, 24, 16),
])
def test_flash_pallas_gradients_match_dense_and_xla(bq, bk, D, Dv, causal):
    """The dq pass (q order) and the dk/dv pass (kv order) under uneven
    blocks and head widths, two batch-heads: against explicit scores and
    against the blockwise ``xla`` backward."""
    q, k, v = _qkv(B=1, L=128, H=2, D=D, Dv=Dv, seed=6)
    w = jnp.asarray(np.random.default_rng(7).normal(size=(1, 128, 2, Dv)),
                    jnp.float32)

    def grads(f):
        return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                        argnums=(0, 1, 2))(q, k, v)

    gp = grads(lambda q, k, v: flash_attention(q, k, v, causal, bq, bk, True))
    gx = grads(lambda q, k, v: flash_attention(q, k, v, causal, bq, bk, True,
                                               "xla"))
    gd = grads(lambda q, k, v: _dense(q, k, v, causal)[0])
    for a, b, c, name in zip(gp, gx, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("bq,bk,D,Dv,causal", [
    (64, 64, 64, 64, True),
    (32, 64, 64, 64, True),
    (64, 32, 24, 16, True),
    (32, 64, 24, 16, False),
])
def test_flash_bwd_pallas_matches_xla_bf16(bq, bk, D, Dv, causal):
    q, k, v = _qkv(L=128, H=1, D=D, Dv=Dv, seed=4)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal, bq, bk, True, impl)
                .astype(jnp.float32) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(qb, kb, vb)

    gp = loss("pallas")
    gx = loss("xla")
    for a, b, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=0.05, atol=0.05, err_msg=name)
