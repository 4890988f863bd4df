"""SyncBN (--sync-bn): cross-replica BN moments in the explicit-collectives
step must reproduce GSPMD's global-batch BN semantics exactly.

The round-4 hard-oracle matrix measured the per-shard-BN explicit leg
converging 18 points under the GSPMD legs at batch 4/device
(RESULTS_convergence_hard.json); this is the framework-level fix — the
torch capability analogue is ``nn.SyncBatchNorm`` (reference recipes train
unsynced BN under DDP, distributed.py:147-148, which is the default here).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_distributed_tpu.models import create_model
from pytorch_distributed_tpu.ops.fused_bn import FusedBatchNormAct, _bn_act
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import make_train_step

N_DEV = 8


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("data",))


def test_bn_act_syncbn_matches_full_batch():
    """shard_map'd _bn_act(axis_name='data') on 8 shards == single-call
    _bn_act on the concatenated batch — forward AND backward."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(1.5, 2.0, size=(16, 4, 4, 3)), jnp.float32)
    gamma = jnp.asarray(rng.normal(1, 0.1, size=(3,)), jnp.float32)
    beta = jnp.asarray(rng.normal(0, 0.1, size=(3,)), jnp.float32)

    def full_loss(x, g, b):
        o, _, _ = _bn_act(x, g, b, 1e-5, True)
        return jnp.sum(o * o)

    def sharded_loss(x, g, b):
        def local(xs, g, b):
            o, _, _ = _bn_act(xs, g, b, 1e-5, True, "data")
            # per-shard partial loss; psum -> global scalar
            return jax.lax.psum(jnp.sum(o * o), "data")

        return shard_map(
            local, mesh=_mesh(), in_specs=(P("data"), P(), P()),
            out_specs=P(), check_vma=False,
        )(x, g, b)

    want, wg = jax.value_and_grad(full_loss, argnums=(0, 1, 2))(x, gamma, beta)
    got, gg = jax.value_and_grad(sharded_loss, argnums=(0, 1, 2))(
        x, gamma, beta)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b_ in zip(gg, wg):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_explicit_syncbn_step_matches_gspmd():
    """One optimizer step: explicit-collectives + sync_bn == GSPMD (whose
    BN is global-batch by construction) — params, stats, and metrics."""
    mesh = _mesh()
    kw = dict(num_classes=10, dtype=jnp.float32)
    model_sync = create_model("resnet18", bn_axis_name="data", **kw)
    model_plain = create_model("resnet18", **kw)

    sample = jnp.zeros((1, 32, 32, 3), jnp.float32)
    variables = model_plain.init(jax.random.PRNGKey(0), sample, train=False)
    state0 = lambda: TrainState.create(  # noqa: E731
        jax.tree_util.tree_map(jnp.copy, variables),
        sgd_init(variables["params"]))

    rng = np.random.default_rng(1)
    batch = {
        "images": jnp.asarray(
            rng.normal(0, 1, size=(16, 32, 32, 3)), jnp.float32),
        "labels": jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32),
        "weights": jnp.ones((16,), jnp.float32),
    }
    lr = jnp.float32(0.1)

    step_sync = make_train_step(model_sync, mesh, explicit_collectives=True)
    step_gspmd = make_train_step(model_plain, mesh)
    s1, m1 = step_sync(state0(), batch, lr)
    s2, m2 = step_gspmd(state0(), batch, lr)

    for k in m1:
        np.testing.assert_allclose(
            float(m1[k]), float(m2[k]), rtol=1e-4, atol=1e-4)
    flat1 = jax.tree_util.tree_leaves_with_path(s1.params)
    flat2 = dict(jax.tree_util.tree_leaves_with_path(s2.params))
    for path, v in flat1:
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(flat2[path]), rtol=5e-3, atol=5e-3,
            err_msg=jax.tree_util.keystr(path))
    stats1 = jax.tree_util.tree_leaves_with_path(s1.batch_stats)
    stats2 = dict(jax.tree_util.tree_leaves_with_path(s2.batch_stats))
    for path, v in stats1:
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(stats2[path]), rtol=1e-3, atol=1e-3,
            err_msg=jax.tree_util.keystr(path))


def test_pershard_bn_differs_from_syncbn():
    """Sanity: WITHOUT sync_bn the explicit step's BN statistics are
    per-shard, so its first-step metrics differ from GSPMD's on a batch
    with shard-skewed distribution (the round-4 convergence-gap mechanism
    in miniature)."""
    mesh = _mesh()
    kw = dict(num_classes=10, dtype=jnp.float32)
    model_plain = create_model("resnet18", **kw)

    sample = jnp.zeros((1, 32, 32, 3), jnp.float32)
    variables = model_plain.init(jax.random.PRNGKey(0), sample, train=False)
    mk_state = lambda: TrainState.create(  # noqa: E731
        jax.tree_util.tree_map(jnp.copy, variables),
        sgd_init(variables["params"]))

    rng = np.random.default_rng(2)
    # shard-skewed inputs: shard i centered at i (BN per-shard mean removes
    # the skew; global BN does not)
    imgs = np.stack([
        rng.normal(i % N_DEV, 1, size=(32, 32, 3)) for i in range(16)
    ]).astype(np.float32)
    batch = {
        "images": jnp.asarray(imgs),
        "labels": jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32),
        "weights": jnp.ones((16,), jnp.float32),
    }
    lr = jnp.float32(0.1)
    step_nosync = make_train_step(
        model_plain, mesh, explicit_collectives=True)
    step_gspmd = make_train_step(model_plain, mesh)
    _, m_no = step_nosync(mk_state(), batch, lr)
    _, m_gs = step_gspmd(mk_state(), batch, lr)
    assert abs(float(m_no["loss"]) - float(m_gs["loss"])) > 1e-4


def test_sync_bn_trainer_gates():
    """--sync-bn config gates: rejected for BN-free archs; accepted
    quietly under GSPMD (documented no-op)."""
    from pytorch_distributed_tpu.train.config import Config
    from pytorch_distributed_tpu.train.trainer import Trainer

    def cfg(**kw):
        kw.setdefault("arch", "resnet18")
        return Config(synthetic=True, synthetic_length=16, batch_size=16,
                      image_size=32, num_classes=4, epochs=1, **kw)

    with pytest.raises(ValueError, match="no BatchNorm"):
        Trainer(cfg(sync_bn=True, arch="alexnet"),
                explicit_collectives=True)
    # plain VGG carries the field (the *_bn variants share the class) but
    # has no BN layers — must refuse rather than silently no-op
    with pytest.raises(ValueError, match="no BatchNorm"):
        Trainer(cfg(sync_bn=True, arch="vgg11"),
                explicit_collectives=True)


def test_explicit_syncbn_step_matches_gspmd_flax_bn_model():
    """The flax-BatchNorm(axis_name) path (zoo-wide --sync-bn, torch
    SyncBatchNorm is model-agnostic): one explicit+sync step on
    shufflenet_v2 (dropout-free, so the two formulations' rng streams
    cannot diverge the comparison) == one GSPMD step (global-batch BN)."""
    mesh = _mesh()
    kw = dict(num_classes=10, dtype=jnp.float32)
    model_sync = create_model("shufflenet_v2_x0_5", bn_axis_name="data",
                              **kw)
    model_plain = create_model("shufflenet_v2_x0_5", **kw)

    sample = jnp.zeros((1, 32, 32, 3), jnp.float32)
    variables = model_plain.init(jax.random.PRNGKey(0), sample, train=False)
    mk_state = lambda: TrainState.create(  # noqa: E731
        jax.tree_util.tree_map(jnp.copy, variables),
        sgd_init(variables["params"]))

    rng = np.random.default_rng(4)
    batch = {
        "images": jnp.asarray(
            rng.normal(0, 1, size=(16, 32, 32, 3)), jnp.float32),
        "labels": jnp.asarray(rng.integers(0, 10, size=(16,)), jnp.int32),
        "weights": jnp.ones((16,), jnp.float32),
    }
    lr = jnp.float32(0.1)
    s1, m1 = make_train_step(model_sync, mesh, explicit_collectives=True)(
        mk_state(), batch, lr)
    s2, m2 = make_train_step(model_plain, mesh)(mk_state(), batch, lr)
    for k in m1:
        np.testing.assert_allclose(
            float(m1[k]), float(m2[k]), rtol=1e-4, atol=1e-4)
    got = jax.tree_util.tree_leaves_with_path(s1.params)
    want = dict(jax.tree_util.tree_leaves_with_path(s2.params))
    for path, v in got:
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(want[path]), rtol=5e-3, atol=5e-3,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("arch", ["resnet18", "resnet50"])
def test_sync_bn_axis_name_keeps_variable_tree(arch):
    """A ResNet built with bn_axis_name="data" declares the variable tree
    of one built without (same paths, shapes, dtypes; BasicBlock and
    Bottleneck), so checkpoints pass between sync and per-shard runs."""
    kw = dict(num_classes=10, dtype=jnp.float32)
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)

    def tree(model):
        return jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), sample, train=False))

    assert (tree(create_model(arch, bn_axis_name="data", **kw))
            == tree(create_model(arch, **kw)))
