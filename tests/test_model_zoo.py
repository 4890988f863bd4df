"""Every registered image arch initializes, runs forward, and (for a sample
incl. a dropout model) takes a train step on the simulated mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu import models
from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import make_train_step

EXPECTED = {
    "alexnet", "vgg11", "vgg13", "vgg16", "vgg19",
    "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn",
    "densenet121", "densenet161", "densenet169", "densenet201",
    "mobilenet_v2",
    "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
    "wide_resnet50_2", "wide_resnet101_2",
    "resnext50_32x4d", "resnext101_32x8d",
    "squeezenet1_0", "squeezenet1_1",
    "shufflenet_v2_x0_5", "shufflenet_v2_x1_0",
    "shufflenet_v2_x1_5", "shufflenet_v2_x2_0",
    "mnasnet0_5", "mnasnet0_75", "mnasnet1_0", "mnasnet1_3",
    "googlenet", "inception_v3",
}


def test_registry_contains_expected_families():
    assert EXPECTED <= set(models.model_names())


# Keep per-arch cost low: one light representative per family at tiny size.
FWD_ARCHS = ["alexnet", "vgg11_bn", "densenet121", "mobilenet_v2",
             "resnet34", "squeezenet1_1", "shufflenet_v2_x0_5",
             "mnasnet0_5"]


@pytest.mark.parametrize("arch", FWD_ARCHS)
def test_forward_shapes(arch):
    model = models.create_model(arch, num_classes=7)
    size = 64 if arch == "alexnet" else 32  # alexnet's 11x11/s4 stem needs room
    x = jnp.zeros((2, size, size, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 7)
    assert out.dtype == jnp.float32


def test_googlenet_forward_and_aux():
    """96px keeps the test cheap (aux adaptive-pool keeps param shapes
    size-independent); aux logits are returned only under capture_aux."""
    model = models.create_model("googlenet", num_classes=5)
    x = jnp.zeros((2, 96, 96, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 5)

    aux_model = models.create_model("googlenet", num_classes=5, aux_logits=True)
    variables = aux_model.init(jax.random.PRNGKey(0), x, train=False)
    logits, (a1, a2) = aux_model.apply(
        variables, x, train=False, capture_aux=True,
        rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert logits.shape == a1.shape == a2.shape == (2, 5)


def test_inception_v3_forward():
    model = models.create_model("inception_v3", num_classes=5)
    x = jnp.zeros((1, 96, 96, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (1, 5)


def test_inception_v3_aux_small_input_and_stats_tree():
    """Aux head must init at sub-299 sizes (clamped pool window) and the
    gated-out aux compute must not change the batch_stats tree structure
    across a mutable train-mode apply."""
    model = models.create_model("inception_v3", num_classes=5, aux_logits=True)
    x = jnp.zeros((1, 96, 96, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits, aux = model.apply(
        variables, x, train=False, capture_aux=True,
        rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert logits.shape == aux.shape == (1, 5)
    _, mutated = model.apply(
        variables, x, train=True, mutable=["batch_stats"],
        rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert (
        jax.tree_util.tree_structure(mutated["batch_stats"])
        == jax.tree_util.tree_structure(variables["batch_stats"])
    )


def test_dropout_arch_trains():
    """AlexNet has dropout: the train step must thread a dropout rng."""
    mesh = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
    model = models.create_model("alexnet", num_classes=4)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)),
                           train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    step = make_train_step(model, mesh, seed=3)
    rng = np.random.default_rng(0)
    batch = {
        "images": rng.normal(size=(16, 64, 64, 3)).astype(np.float32),
        "labels": rng.integers(0, 4, size=16).astype(np.int32),
        "weights": np.ones(16, np.float32),
    }
    s1, m1 = step(state, batch, jnp.float32(0.01))
    assert np.isfinite(float(m1["loss"]))
    s2, m2 = step(s1, batch, jnp.float32(0.01))
    assert np.isfinite(float(m2["loss"]))


def test_vgg_trains_through_explicit_collectives():
    mesh = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
    model = models.create_model("vgg11", num_classes=4)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    step = make_train_step(model, mesh, explicit_collectives=True, seed=1)
    rng = np.random.default_rng(1)
    batch = {
        "images": rng.normal(size=(16, 32, 32, 3)).astype(np.float32),
        "labels": rng.integers(0, 4, size=16).astype(np.int32),
        "weights": np.ones(16, np.float32),
    }
    _, m = step(state, batch, jnp.float32(0.01))
    assert np.isfinite(float(m["loss"]))


def test_space_to_depth_stem_equivalence():
    """The packed stem must be numerically identical to the conv7 stem on
    the SAME parameters (both read conv_init/kernel (7,7,3,64))."""
    m_std = models.create_model("resnet50", num_classes=6)
    m_s2d = models.create_model("resnet50", num_classes=6,
                                stem="space_to_depth")
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 64, 64, 3)).astype(np.float32)
    )
    variables = m_std.init(jax.random.PRNGKey(0), x, train=False)
    v2 = m_s2d.init(jax.random.PRNGKey(0), x, train=False)
    assert (
        jax.tree_util.tree_structure(v2) ==
        jax.tree_util.tree_structure(variables)
    )
    out_std = m_std.apply(variables, x, train=False)
    out_s2d = m_s2d.apply(variables, x, train=False)
    np.testing.assert_allclose(
        np.asarray(out_s2d), np.asarray(out_std), rtol=2e-4, atol=2e-5
    )


def _bn(i):
    return [f"FusedBatchNormAct_{i}/bias", f"FusedBatchNormAct_{i}/scale"]


def _stats(i):
    return [f"FusedBatchNormAct_{i}/mean", f"FusedBatchNormAct_{i}/var"]


# On 64 input channels: filters=64 (Basic) / 16 (Bottleneck, x4) at stride 1
# keep the identity shortcut; stride 2 forces the projection.
RESNET_PATH_CASES = {
    "basic": (
        lambda: models.resnet.BasicBlock(filters=64),
        ["Conv_0/kernel", "Conv_1/kernel", *_bn(0), *_bn(1)],
        [*_stats(0), *_stats(1)]),
    "basic_projection": (
        lambda: models.resnet.BasicBlock(filters=32, strides=2),
        ["Conv_0/kernel", "Conv_1/kernel", "Conv_2/kernel",
         *_bn(0), *_bn(1), *_bn(2)],
        [*_stats(0), *_stats(1), *_stats(2)]),
    "bottleneck": (
        lambda: models.resnet.Bottleneck(filters=16),
        ["Conv_0/kernel", "Conv_1/kernel", "Conv_2/kernel",
         *_bn(0), *_bn(1), *_bn(2)],
        [*_stats(0), *_stats(1), *_stats(2)]),
    "bottleneck_projection": (
        lambda: models.resnet.Bottleneck(filters=32, strides=2),
        ["Conv_0/kernel", "Conv_1/kernel", "Conv_2/kernel", "Conv_3/kernel",
         *_bn(0), *_bn(1), *_bn(2), *_bn(3)],
        [*_stats(0), *_stats(1), *_stats(2), *_stats(3)]),
    # first path component only: a block's inside is the cases above
    "resnet18_top": (
        lambda: models.create_model("resnet18", num_classes=10),
        [*(f"BasicBlock_{i}" for i in range(8)), "bn_init", "conv_init",
         "fc"],
        [*(f"BasicBlock_{i}" for i in range(8)), "bn_init"]),
}


@pytest.mark.parametrize("case", list(RESNET_PATH_CASES))
def test_resnet_param_paths(case):
    """Every ResNet checkpoint on disk is keyed by these names, and only
    flax's per-class counter produces them: a reordered or renamed call in
    a block silently orphans them."""
    from flax import traverse_util

    build, want_params, want_stats = RESNET_PATH_CASES[case]
    top = case == "resnet18_top"
    x = jnp.zeros((2, 32, 32, 3) if top else (2, 8, 8, 64))
    variables = jax.eval_shape(
        lambda: build().init(jax.random.PRNGKey(0), x))
    got = {col: sorted({k[0] if top else "/".join(k)
                        for k in traverse_util.flatten_dict(tree)})
           for col, tree in variables.items()}
    assert got == {"params": want_params, "batch_stats": want_stats}


def test_adaptive_avg_pool_matches_torch():
    """Non-divisible sizes must follow torch AdaptiveAvgPool2d bin edges
    (regression: earlier fallback collapsed to a global mean)."""
    torch = pytest.importorskip("torch")
    from pytorch_distributed_tpu.models.simple import _adaptive_avg_pool

    rng = np.random.default_rng(0)
    for H, out in ((8, 7), (5, 7), (13, 6), (1, 7), (14, 7)):
        x = rng.normal(size=(2, H, H, 3)).astype(np.float32)
        want = (
            torch.nn.AdaptiveAvgPool2d(out)(
                torch.from_numpy(x.transpose(0, 3, 1, 2))
            ).numpy().transpose(0, 2, 3, 1)
        )
        got = np.asarray(_adaptive_avg_pool(jnp.asarray(x), out))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
