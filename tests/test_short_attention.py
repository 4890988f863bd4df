"""The one-block fused attention (ops/short_attention.py): the kernels in
interpret mode against a plain dense float32 attention, what padding may
and may not do, what the backward keeps, and the rule that picks the
path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops import short_attention as sa

# What the kernels may differ by from dense float32 attention, as a share
# of the reference's largest entry.  float32: summation order.  bf16: the
# operands, the probabilities as the second product's operand and the
# results are rounded to 8 bits of mantissa, as flax's dense path rounds
# them under the same policy (readings on these cases: 3e-3 to 7e-3).
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def dense_f32(q, k, v):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _inputs(shape, dtype, seed=0, n=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype)
            for k in keys]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", [
    (2, 197, 12, 64),   # vit_b_16 at 224 pixels
    (2, 50, 12, 64),    # vit_b_32
    (1, 197, 2, 128),   # one head a lane group
], ids=["L197", "L50", "D128"])
def test_forward_and_gradients_match_dense_float32(shape, dtype):
    q, k, v, w = _inputs(shape, dtype)
    w = w.astype(jnp.float32)

    def fused(q, k, v):
        return (sa.short_attention(q, k, v, True).astype(jnp.float32)
                * w).sum()

    def dense(q, k, v):
        return (dense_f32(q, k, v) * w).sum()

    tol = TOLERANCE[dtype]
    out = sa.short_attention(q, k, v, True)
    assert out.shape == shape and out.dtype == dtype
    assert _rel(out, dense_f32(q, k, v)) < tol
    got = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip("qkv", got, want):
        assert g.shape == shape and g.dtype == dtype, name
        assert _rel(g, r) < tol, name


@pytest.mark.parametrize("length", [197, 50])
def test_padding_carries_no_weight(length):
    """Rows past ``length`` of a block hold whatever was in VMEM.  Here
    they are made to hold two different things, one of them not finite:
    no output row and no gradient row before ``length`` may change."""
    B, H, D = 1, 4, 64
    lp = sa._round_up(length, sa.LANES)
    real = _inputs((B, length, H * D), jnp.float32, seed=1, n=5)

    def padded(fill):
        tail = jnp.full((B, lp - length, H * D), fill, jnp.float32)
        return [jnp.concatenate([x, tail], axis=1) for x in real]

    results = []
    for fill in (7.5, jnp.nan):
        q, k, v, g, _ = padded(fill)
        out, lse = sa._fused_fwd(q, k, v, H, length, True)
        grads = sa._fused_bwd(q, k, v, out, lse, g, H, length, True)
        results.append([np.asarray(x[:, :length]) for x in (out, *grads)]
                       + [np.asarray(lse[..., :length])])
    for a, b in zip(*results):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)
    # and the public call on unpadded arrays says the same
    q, k, v, g, _ = real
    out, vjp = jax.vjp(lambda q, k, v: sa.short_attention(
        *(x.reshape(B, length, H, D) for x in (q, k, v)), True), q, k, v)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(B, length, H * D), results[0][0])
    for got, want in zip(vjp(g.reshape(out.shape)), results[0][1:4]):
        assert got.shape == (B, length, H * D)
        np.testing.assert_array_equal(np.asarray(got), want)


def test_backward_keeps_no_scores_and_no_padded_copy():
    B, L, H, D = 2, 197, 12, 64
    q, k, v = _inputs((B, L, H, D), jnp.bfloat16, n=3)
    out, residuals = sa._sa_fwd(q, k, v, True)
    assert out.shape == (B, L, H, D)
    shapes = sorted((tuple(r.shape), r.dtype.name) for r in residuals)
    assert shapes == sorted(
        [((B, L, H * D), "bfloat16")] * 4 + [((B, H, L), "float32")])
    # the same through autodiff: what jax.vjp closes over
    _, vjp = jax.vjp(lambda q, k, v: sa.short_attention(q, k, v, True),
                     q, k, v)
    kept = jax.tree_util.tree_leaves(vjp)
    assert kept and all(x.size <= B * L * H * D for x in kept)


@pytest.mark.parametrize("backend,length,heads,head_dim,dropout,masked,want", [
    ("tpu", 197, 12, 64, False, False, "fused"),   # vit_b_16
    ("tpu", 50, 12, 64, False, False, "fused"),    # vit_b_32
    ("tpu", 197, 16, 64, False, False, "fused"),   # vit_l_16
    ("tpu", 5, 2, 64, False, False, "fused"),
    ("tpu", 197, 6, 128, False, False, "fused"),
    ("cpu", 197, 12, 64, False, False, "dense"),   # tier-1 and the baselines
    ("gpu", 197, 12, 64, False, False, "dense"),
    ("tpu", 197, 12, 64, True, False, "dense"),    # dropout on the weights
    ("tpu", 197, 12, 64, False, True, "dense"),    # a mask or a bias
    ("tpu", 577, 12, 64, False, False, "dense"),   # 384 pixels: two blocks
    ("tpu", 1024, 12, 64, False, False, "dense"),
    ("tpu", 197, 12, 80, False, False, "dense"),   # heads split a lane tile
    ("tpu", 197, 3, 64, False, False, "dense"),    # half a lane group
])
def test_policy_is_a_function_of_what_the_call_site_sees(
        backend, length, heads, head_dim, dropout, masked, want):
    assert sa.pick_attention(backend, length, heads, head_dim,
                             dropout=dropout, masked=masked) == want


def test_one_block_bound_follows_from_vmem():
    """The bound is bytes of VMEM, not a list of models: it moves with
    the budget, and the padded length decides, not the length."""
    assert sa.fits_one_block(256, 12, 64) == sa.fits_one_block(197, 12, 64)
    longest = max(n for n in range(128, 2049, 128)
                  if sa.fits_one_block(n, 12, 64))
    assert sa.one_block_bytes(longest, 12, 64) <= sa.VMEM_BUDGET
    assert sa.one_block_bytes(longest + 128, 12, 64) > sa.VMEM_BUDGET
    assert sa.VMEM_BUDGET < sa.VMEM_LIMIT
    with pytest.raises(ValueError, match="do not fit one block"):
        sa.short_attention(*_inputs((1, 1024, 12, 64), jnp.float32, n=3),
                           True)


def test_on_mesh_shards_batch_and_heads_and_matches_the_bare_call():
    """Eight virtual devices as data x model: every device runs the kernel
    on its own rows and its own lane groups of heads."""
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("data", "model"))
    q, k, v = _inputs((4, 50, 4, 64), jnp.float32, n=3)
    want = sa.short_attention(q, k, v, True)
    got = jax.jit(lambda q, k, v: sa.short_attention_on_mesh(
        q, k, v, mesh, True))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # a batch the data axis does not divide is gathered, not refused
    got = jax.jit(lambda q, k, v: sa.short_attention_on_mesh(
        q, k, v, mesh, True))(q[:3], k[:3], v[:3])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:3]),
                               rtol=1e-6, atol=1e-6)
