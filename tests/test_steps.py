"""DP-semantics tests on the simulated 8-device mesh.

The core correctness contracts (SURVEY.md §4 implication):
- sharded-batch gradient step ≡ single-device large-batch step
- GSPMD and explicit-shard_map steps agree
- bf16 wire compression only perturbs within tolerance
- metrics are global (all shards contribute)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu import models
from pytorch_distributed_tpu.parallel import build_mesh, MeshSpec
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import make_eval_step, make_train_step


def _setup(num_devices=8, image=32, classes=10, batch=16, seed=0):
    # Private compile, deliberately NOT on the shared lowering sweep:
    # resnet18 (BN) at 32x32 on the 8-way mesh has no recipe twin in
    # analysis.core.RECIPES (the matrix is the BN-free TinyMLP at 4-way).
    mesh = build_mesh(MeshSpec(("data",), (num_devices,)), jax.devices()[:num_devices])
    model = models.create_model("resnet18", num_classes=classes)
    rng = jax.random.PRNGKey(seed)
    variables = model.init(rng, jnp.zeros((1, image, image, 3)), train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    np_rng = np.random.default_rng(seed)
    batch_data = {
        "images": np_rng.normal(size=(batch, image, image, 3)).astype(np.float32),
        "labels": np_rng.integers(0, classes, size=batch).astype(np.int32),
        "weights": np.ones(batch, np.float32),
    }
    return mesh, model, state, batch_data


def _leaves_allclose(a, b, rtol, atol=1e-5):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)


def test_sharded_step_matches_single_device():
    mesh8, model, state, batch = _setup()
    mesh1 = build_mesh(MeshSpec(("data",), (1,)), jax.devices()[:1])
    step8 = make_train_step(model, mesh8)
    step1 = make_train_step(model, mesh1)
    s8, m8 = step8(state, batch, jnp.float32(0.1))
    # state was donated; rebuild for the single-device run
    _, _, state2, _ = (None, None, *_setup()[2:3], None)
    s1, m1 = step1(state2, batch, jnp.float32(0.1))
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-4)
    np.testing.assert_allclose(float(m8["acc1"]), float(m1["acc1"]), atol=1e-4)
    _leaves_allclose(s8.params, s1.params, rtol=1e-4)


def _tiny_bneck(**kw):
    """The block the benchmark's ResNet-50 cells run, at a size a CPU steps
    in seconds (the model __graft_entry__.py sends through the dry run)."""
    from pytorch_distributed_tpu.models.resnet import Bottleneck, ResNet

    return ResNet(stage_sizes=[1, 1], block_cls=Bottleneck, num_classes=10,
                  num_filters=16, **kw)


@pytest.mark.parametrize("formulation", ["gspmd", "explicit"])
def test_bottleneck_step_parity(formulation):
    """Two steps of a Bottleneck model, so the second loss has been through
    the blocks' backward.  gspmd: the 4-way mesh against one device.
    explicit: the shard_map step with SyncBN against the GSPMD step (whose
    BN is global-batch by construction; without the axis name the explicit
    step's BN is per shard and equals neither)."""
    mesh4 = build_mesh(MeshSpec(("data",), (4,)), jax.devices()[:4])
    mesh1 = build_mesh(MeshSpec(("data",), (1,)), jax.devices()[:1])
    plain = _tiny_bneck()
    variables = plain.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3)), train=False)
    np_rng = np.random.default_rng(0)
    batch = {
        "images": np_rng.normal(size=(16, 16, 16, 3)).astype(np.float32),
        "labels": np_rng.integers(0, 10, size=16).astype(np.int32),
        "weights": np.ones(16, np.float32),
    }

    def two_steps(step):
        state = TrainState.create(
            jax.tree_util.tree_map(jnp.copy, variables),
            sgd_init(variables["params"]))
        state, _ = step(state, batch, jnp.float32(0.1))
        return step(state, batch, jnp.float32(0.1))

    if formulation == "gspmd":
        got = two_steps(make_train_step(plain, mesh4))
        want = two_steps(make_train_step(plain, mesh1))
    else:
        got = two_steps(make_train_step(
            _tiny_bneck(bn_axis_name="data"), mesh4,
            explicit_collectives=True))
        want = two_steps(make_train_step(plain, mesh4))
    (s_got, m_got), (s_want, m_want) = got, want
    np.testing.assert_allclose(
        float(m_got["loss"]), float(m_want["loss"]), rtol=1e-4)
    np.testing.assert_allclose(
        float(m_got["acc1"]), float(m_want["acc1"]), atol=1e-4)
    _leaves_allclose(s_got.params, s_want.params, rtol=1e-4)


class _MLP(__import__("flax").linen.Module):
    """BN-free model: isolates collective plumbing from BN-semantics deltas."""

    classes: int = 10

    @__import__("flax").linen.compact
    def __call__(self, x, train: bool = True):
        import flax.linen as nn

        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(32)(x)
        x = nn.relu(x)
        return nn.Dense(self.classes)(x)


def _setup_mlp(num_devices=8, image=8, classes=10, batch=16, seed=0):
    # Still needed where the assertion depends on a shape the recipe
    # matrix doesn't carry (the padded-batch test re-steps at batch 8,
    # which would force a second compile of the shared twin anyway).
    mesh = build_mesh(MeshSpec(("data",), (num_devices,)), jax.devices()[:num_devices])
    model = _MLP(classes=classes)
    variables = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, image, image, 3)))
    state = TrainState.create(variables, sgd_init(variables["params"]))
    np_rng = np.random.default_rng(seed)
    batch_data = {
        "images": np_rng.normal(size=(batch, image, image, 3)).astype(np.float32),
        "labels": np_rng.integers(0, classes, size=batch).astype(np.int32),
        "weights": np.ones(batch, np.float32),
    }
    return mesh, model, state, batch_data


def test_explicit_shard_map_matches_gspmd_without_bn(get_lowering):
    """With no BatchNorm the two gradient-sync formulations must agree.

    Rides the session-shared lowering sweep (ISSUE 13 S3): the BN-free
    recipe twins ``train_image_gspmd`` / ``train_image_explicit`` are
    already compiled once per session for the shardlint/ledger fences,
    so the semantics check re-executes those compiled steps on fresh
    (undonated) states instead of paying two private compiles.  The
    resnet18/BN tests below keep their private ``_setup`` compiles —
    their model and 8-way mesh are not in the recipe matrix."""
    from pytorch_distributed_tpu.analysis import core

    low_g = get_lowering("train_image_gspmd")
    low_e = get_lowering("train_image_explicit")
    before = get_lowering.compile_count()
    batch = core._image_batch()
    sg, mg = low_g.jitted(core._image_state(core._tiny_image_model()),
                          batch, jnp.float32(0.1))
    se, me = low_e.jitted(
        core._image_state(core._tiny_image_model(), explicit=True),
        batch, jnp.float32(0.1))
    np.testing.assert_allclose(float(mg["loss"]), float(me["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(mg["acc1"]), float(me["acc1"]), atol=1e-5)
    _leaves_allclose(sg.params, se.params, rtol=1e-5)
    # re-executing cached twins is free: zero new AOT compiles, and the
    # process-wide sweep stays inside its budget
    assert get_lowering.compile_count() == before
    assert get_lowering.compile_count() <= get_lowering.compile_budget()


def test_shard_map_bn_is_local_like_torch_ddp():
    """Documented delta: shard_map BN normalizes per shard (torch DDP parity),
    GSPMD BN is global (SyncBN).  Losses must *differ* on small shards."""
    mesh, model, state, batch = _setup()
    step_g = make_train_step(model, mesh)
    step_e = make_train_step(model, mesh, explicit_collectives=True)
    _, mg = step_g(state, batch, jnp.float32(0.1))
    _, _, state2, _ = _setup()
    _, me = step_e(state2, batch, jnp.float32(0.1))
    assert abs(float(mg["loss"]) - float(me["loss"])) > 1e-3


def test_bf16_wire_compression_close_to_f32():
    mesh, model, state, batch = _setup()
    step_f = make_train_step(model, mesh, explicit_collectives=True)
    step_w = make_train_step(model, mesh, explicit_collectives=True,
                             wire_dtype=jnp.bfloat16)
    sf, _ = step_f(state, batch, jnp.float32(0.1))
    _, _, state2, _ = _setup()
    sw, _ = step_w(state2, batch, jnp.float32(0.1))
    # bf16 has ~3 decimal digits; updates are lr-scaled so params stay close.
    _leaves_allclose(sf.params, sw.params, rtol=5e-2, atol=5e-3)


def test_padded_batch_excluded_from_loss_and_grads():
    """On a BN-free model, a zero-weighted pad half must leave loss, metrics,
    AND the parameter update identical to the unpadded half-batch.  (BN models
    avoid train-time padding entirely: the trainer drops the partial final
    train batch; eval uses running stats, so padding is exact there.)"""
    mesh, model, state, batch = _setup_mlp(batch=16)
    step = make_train_step(model, mesh)
    batch_padded = {
        "images": np.concatenate([batch["images"][:8],
                                  np.zeros_like(batch["images"][:8])]),
        "labels": np.concatenate([batch["labels"][:8], np.zeros(8, np.int32)]),
        "weights": np.concatenate([np.ones(8, np.float32), np.zeros(8, np.float32)]),
    }
    s_pad, m_pad = step(state, batch_padded, jnp.float32(0.1))

    mesh_b, model_b, state_b, _ = _setup_mlp(batch=16)
    batch_half = {
        "images": batch["images"][:8],
        "labels": batch["labels"][:8],
        "weights": np.ones(8, np.float32),
    }
    step_half = make_train_step(model_b, mesh_b)
    s_half, m_half = step_half(state_b, batch_half, jnp.float32(0.1))
    np.testing.assert_allclose(float(m_pad["loss"]), float(m_half["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(m_pad["acc1"]), float(m_half["acc1"]), atol=1e-5)
    _leaves_allclose(s_pad.params, s_half.params, rtol=1e-5)


def test_eval_step_returns_exact_sums():
    mesh, model, state, batch = _setup()
    ev = make_eval_step(model, mesh)
    batch["weights"][-3:] = 0.0
    sums = ev(state, batch)
    assert float(sums["count"]) == 13.0
    assert 0.0 <= float(sums["correct1"]) <= 13.0
    assert float(sums["correct1"]) <= float(sums["correct5"])


def test_train_step_increments_step_counter():
    mesh, model, state, batch = _setup()
    step = make_train_step(model, mesh)
    s1, _ = step(state, batch, jnp.float32(0.1))
    assert int(s1.step) == 1
    s2, _ = step(s1, batch, jnp.float32(0.1))
    assert int(s2.step) == 2
