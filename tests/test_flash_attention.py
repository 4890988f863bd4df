"""Pallas flash attention vs dense oracle (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops.flash_attention import (
    _diagonal_parts,
    _flash_fwd,
    block_schedule,
    blocks_visited,
    flash_attention,
    subtile,
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


@pytest.mark.parametrize("L,bq,bk,visited,masked,skipped", [
    (8192, 1024, 1024, 36, 8, 48),  # the configured decoder's blocks
    (8192, 256, 1024, 144, 32, 0),  # the kernel's default blocks
    (8192, 512, 512, 136, 16, 96),
])
def test_schedule_counts(L, bq, bk, visited, masked, skipped):
    assert blocks_visited(L, bq, bk) == (visited, masked, skipped)
    assert blocks_visited(L, bq, bk, causal=False) == (
        (L // bq) * (L // bk), 0, 0)
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


@pytest.mark.parametrize("bq,bk,causal,sub", [
    (1024, 1024, True, 256),   # the configured decoder's blocks
    (64, 64, True, 16),
    (32, 32, True, 8),
    (16, 16, True, None),      # sub-tiles of 4 rows: not a multiple of 8
    (130, 130, True, None),    # four sub-tiles of 32 would leave 2 rows
    (136, 136, True, None),    # sub-tiles of 34 rows: not a multiple of 8
    (160, 160, True, 40),
    (256, 1024, True, None),   # crossed at an offset that moves
    (1024, 256, True, None),
    (1024, 1024, False, None),
])
def test_subtile_engages_on_square_causal_blocks_only(bq, bk, causal, sub):
    assert subtile(bq, bk, causal) == sub


@pytest.mark.parametrize("order", ["q", "kv"])
@pytest.mark.parametrize("block,sub", [(64, 16), (64, 32), (1024, 256)])
def test_diagonal_parts_cover_the_diagonal_blocks_unmasked_pairs_once(
        block, sub, order):
    """The parts of a block on the diagonal hold each of its unmasked pairs
    once, every sub-tile they touch is at or below the diagonal, and the
    sub-tiles they leave are the ones ``blocks_visited`` counts."""
    seen = np.zeros((block, block), int)
    tiles = set()
    for rows, cols, (i, j, tile_q, tile_k) in _diagonal_parts(
            block, sub, order):
        seen[rows, cols] += 1
        # the part's mask starts at its first row and column
        assert (i * tile_q, j * tile_k) == (rows.start, cols.start)
        tiles |= {(r, c) for r in range(rows.start // sub, rows.stop // sub)
                  for c in range(cols.start // sub, cols.stop // sub)}
    assert seen.max() == 1 and (seen[_unmasked(block, True)] == 1).all()
    assert all(c <= r for r, c in tiles)
    n = block // sub
    assert len(tiles) == n * (n + 1) // 2


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


# ------------- the crossed blocks in sub-tiles (bq == bk: 16-row sub-tiles)

@pytest.mark.parametrize("bq,bk,causal", [
    (64, 64, True),       # sub-tiled diagonal blocks
    (32, 64, True),       # bq != bk: the whole crossed block, masked
    (64, 64, False),      # not causal: no mask at all
])
@pytest.mark.parametrize("D,Dv", [(64, 64), (24, 16)])
def test_subtiled_forward_and_lse_match_dense(bq, bk, causal, D, Dv):
    """Output and log-sum-exp at L = 256 over four batch-heads, where the
    sub-tiled body engages and, as controls, where it does not."""
    q, k, v = _qkv(B=2, L=256, H=2, D=D, Dv=Dv, seed=8)
    want, want_lse = _dense(q, k, v, causal)
    got, lse = _flash_fwd(q, k, v, causal, bq, bk, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bq,bk,causal", [
    (64, 64, True),
    (64, 32, True),
    (64, 64, False),
])
@pytest.mark.parametrize("D,Dv", [(64, 64), (24, 16)])
def test_subtiled_pallas_gradients_match_dense_and_xla(bq, bk, causal, D, Dv):
    """The dq pass's sub-rows and the dk/dv pass's sub-columns at L = 256:
    against explicit scores and against the blockwise ``xla`` backward."""
    q, k, v = _qkv(B=1, L=256, H=2, D=D, Dv=Dv, seed=9)
    w = jnp.asarray(np.random.default_rng(10).normal(size=(1, 256, 2, Dv)),
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


@pytest.mark.parametrize("L", [130, 160])
def test_short_sequence_in_the_default_blocks_matches_dense(L):
    """The default 256 x 1,024 blocks shrink to one square block of ``L``
    rows: cut into sub-tiles of 40 at L = 160, worked whole at L = 130
    (four sub-tiles of 32 would leave its last two rows and keys out)."""
    q, k, v = _qkv(B=1, L=L, H=2, D=32, seed=14)
    w = jnp.asarray(np.random.default_rng(15).normal(size=(1, L, 2, 32)),
                    jnp.float32)

    def run(f):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                                  argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: flash_attention(q, k, v, True, interpret=True))
    want = run(lambda q, k, v: _dense(q, k, v, True)[0])
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-4)
    for a, b, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_subtiled_grouped_heads_match_dense():
    """Four query heads over two key-value heads, sub-tiled diagonal
    blocks: output and all three gradients against explicit scores over
    the key-value heads repeated."""
    q, _, _ = _qkv(B=1, L=256, H=4, D=32, seed=11)
    _, k, v = _qkv(B=1, L=256, H=2, D=32, seed=12)
    w = jnp.asarray(np.random.default_rng(13).normal(size=(1, 256, 4, 32)),
                    jnp.float32)

    def run(f):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(f(q, k, v) * w),
                                  argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: flash_attention(q, k, v, True, 64, 64, True))
    want = run(lambda q, k, v: _dense(
        q, *(jnp.repeat(x, 2, axis=2) for x in (k, v)), True)[0])
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-4)
    for a, b, name in zip(got[1], want[1], "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
