"""Vision Transformer family: registry surface, forward contract, and an
end-to-end Trainer epoch (patch embed / class token / position embeddings
all exercised under the image-harness path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu import models
from pytorch_distributed_tpu.train.config import Config
from pytorch_distributed_tpu.train.trainer import Trainer


def _tiny(**kw):
    base = dict(num_classes=7, d_model=64, n_layers=2, n_heads=4, mlp_dim=128)
    base.update(kw)
    return models.create_model("vit_b_16", **base)


def test_registry_and_forward():
    assert {"vit_b_16", "vit_b_32", "vit_l_16"} <= set(models.model_names())
    model = _tiny()
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 7)
    assert out.dtype == jnp.float32
    # No BatchNorm: a ViT carries no mutable batch_stats collection.
    assert set(variables) == {"params"}
    # Position embeddings are grid-shaped from the init input (32/16 = 2x2).
    assert variables["params"]["pos_embedding"].shape == (1, 2, 2, 64)


def test_wrong_resolution_fails_loudly():
    model = _tiny()
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False)
    # Different resolution: grid 4x4 vs stored 2x2 → param shape mismatch.
    with pytest.raises(Exception, match="[Ss]hape"):
        model.apply(variables, jnp.zeros((1, 64, 64, 3)), train=False)
    # Same token count, different aspect (1x4 vs 2x2): must ALSO fail — the
    # grid-shaped pos-embedding param is what catches this silent case.
    with pytest.raises(Exception, match="[Ss]hape"):
        model.apply(variables, jnp.zeros((1, 16, 64, 3)), train=False)


def test_trainer_epoch_with_vit(tmp_path):
    import functools

    # A tiny ViT registered through the public hook: the Trainer resolves it
    # like any zoo arch; position embeddings size themselves from
    # --image-size via the init sample.
    models.register(
        "vit_tiny_test",
        functools.partial(
            models.VisionTransformer, patch_size=16, d_model=32,
            n_layers=2, n_heads=2, mlp_dim=64,
        ),
    )
    cfg = Config(
        arch="vit_tiny_test", batch_size=16, epochs=1, lr=0.01, print_freq=4,
        synthetic=True, synthetic_length=32, image_size=32, num_classes=4,
        seed=0, checkpoint_dir=str(tmp_path), workers=2,
    )
    t = Trainer(cfg)
    p0 = np.asarray(
        jax.tree_util.tree_leaves(t.state.params)[0]).copy()
    best = t.fit()
    p1 = np.asarray(jax.tree_util.tree_leaves(t.state.params)[0])
    assert not np.array_equal(p0, p1), "params must move"
    assert 0.0 <= best <= 100.0
    assert (tmp_path / "checkpoint.msgpack").exists()


def test_remat_parity():
    """remat=True must change NOTHING but memory: same param tree, same
    forward, same grads (guards the static_argnums=(2,) convention in
    models/vit.py against EncoderBlock signature drift)."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    kw = dict(patch_size=16, d_model=32, n_layers=2, n_heads=2, mlp_dim=64,
              num_classes=5)
    m0 = models.VisionTransformer(**kw)
    m1 = models.VisionTransformer(**kw, remat=True)
    v0 = m0.init(jax.random.PRNGKey(0), x, train=False)
    v1 = m1.init(jax.random.PRNGKey(0), x, train=False)
    assert (jax.tree_util.tree_structure(v0)
            == jax.tree_util.tree_structure(v1)), "param tree changed"
    y0 = m0.apply(v0, x, train=False)
    y1 = m1.apply(v1, x, train=False)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), rtol=1e-6)
    g0 = jax.grad(lambda v: m0.apply(v, x, train=False).sum())(v0)
    g1 = jax.grad(lambda v: m1.apply(v, x, train=False).sum())(v1)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g0, g1)


# --- the attention call site (ops/short_attention.py behind attention_fn) ---

def test_parameter_tree_is_what_it_was():
    """Names, shapes and dtypes as ``nn.MultiHeadDotProductAttention`` lays
    them out: the benchmark's reference and saved checkpoints read them."""
    model = _tiny(d_model=128, n_heads=2, dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                        train=False)["params"]
    assert set(params) == {
        "patch_embed", "cls_token", "cls_pos_embedding", "pos_embedding",
        "encoder_0", "encoder_1", "ln_f", "head"}
    block = params["encoder_0"]
    assert set(block) == {"ln_1", "self_attention", "ln_2", "mlp_fc1",
                          "mlp_fc2"}
    got = {"/".join(k.key for k in path): (leaf.shape, leaf.dtype.name)
           for path, leaf in jax.tree_util.tree_leaves_with_path(
               block["self_attention"])}
    assert got == {
        "query/kernel": ((128, 2, 64), "float32"),
        "query/bias": ((2, 64), "float32"),
        "key/kernel": ((128, 2, 64), "float32"),
        "key/bias": ((2, 64), "float32"),
        "value/kernel": ((128, 2, 64), "float32"),
        "value/bias": ((2, 64), "float32"),
        "out/kernel": ((2, 64, 128), "float32"),
        "out/bias": ((128,), "float32"),
    }


def _force_fused(monkeypatch):
    """The policy sees a TPU; off the TPU the kernels then run in the
    Pallas interpreter (``interpret=None``)."""
    from pytorch_distributed_tpu.models import vit
    from pytorch_distributed_tpu.ops import short_attention as sa

    monkeypatch.setattr(
        vit, "pick_attention",
        lambda backend, *a, **kw: sa.pick_attention("tpu", *a, **kw))


def _one_step(dtype, layers, mesh, explicit=False):
    """Loss, gradients (the momentum after one step from zero, no weight
    decay) and the lowered text of one ``make_train_step`` on 32x32."""
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step

    model = _tiny(d_model=128, n_heads=2, n_layers=layers, dtype=dtype)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                           train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    rng = np.random.default_rng(0)
    batch = {"images": rng.normal(size=(8, 32, 32, 3)).astype(np.float32),
             "labels": rng.integers(0, 7, size=8).astype(np.int32),
             "weights": np.ones(8, np.float32)}
    step = make_train_step(model, mesh, weight_decay=0.0,
                           explicit_collectives=explicit)
    text = step.lower(state, batch, jnp.float32(0.1)).as_text()
    new_state, metrics = step(state, batch, jnp.float32(0.1))
    return float(metrics["loss"]), new_state.momentum, text


@pytest.mark.parametrize("dtype,tol,layers", [(jnp.float32, 1e-4, 2),
                                              (jnp.bfloat16, 2e-2, 1)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("devices,explicit",
                         [(1, False), (4, False), (4, True)],
                         ids=["one", "gspmd4", "shard_map4"])
def test_fused_step_agrees_with_dense_step(monkeypatch, dtype, tol, layers,
                                           devices, explicit):
    """One train step with the fused path forced (interpret mode) against
    the same step on flax's dense path: loss and every gradient, on one
    device, under the GSPMD step on four (where the model learns the
    step's mesh and wraps the kernels in a shard_map) and under the
    explicit-collectives step on four (already inside a shard_map: the
    bare call on a device's own two images).  bf16: the
    tolerance of tests/test_short_attention.py for that type, on one
    block (every further bf16 layer between a gradient and the attention
    rounds the two paths apart again: 2.6e-2 on two blocks)."""
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(("data",), (devices,)),
                      jax.devices()[:devices])
    loss_d, grads_d, text_d = _one_step(dtype, layers, mesh, explicit)
    _force_fused(monkeypatch)
    loss_f, grads_f, text_f = _one_step(dtype, layers, mesh, explicit)
    # the dense step holds the [B, H, L, L] scores (8 images, or a
    # device's 2 inside the explicit step's shard_map; 2 heads, 5 tokens),
    # the fused step no operation over them
    scores = f"{8 // devices if explicit else 8}x2x5x5x"
    assert scores in text_d
    assert scores not in text_f
    assert loss_f == pytest.approx(loss_d, rel=tol)
    flat_d = jax.tree_util.tree_leaves_with_path(grads_d)
    flat_f = jax.tree_util.tree_leaves(grads_f)
    assert len(flat_d) == len(flat_f)
    for (path, d), f in zip(flat_d, flat_f):
        d, f = np.asarray(d), np.asarray(f)
        scale = np.abs(d).max()
        if path[-2:] == (jax.tree_util.DictKey("key"),
                         jax.tree_util.DictKey("bias")):
            # zero in exact arithmetic (a softmax does not see a constant
            # added to every key): both sides hold rounding only, so the
            # query bias's gradient is the yardstick
            scale = np.abs(np.asarray(grads_d[path[0].key]["self_attention"]
                                      ["query"]["bias"])).max()
        assert np.abs(f - d).max() <= tol * scale + 1e-7, path


def test_attention_takes_dense_on_cpu_with_dropout_and_when_blind(
        monkeypatch):
    """What the call site itself decides: on the CPU nothing changes;
    forced onto the kernels' backend, dropout on the probabilities in
    training still takes flax's path (and differs from the deterministic
    result), and so does a model on several devices that was given no
    mesh to wrap the kernels with."""
    from pytorch_distributed_tpu.models import vit
    from pytorch_distributed_tpu.ops import short_attention as sa

    calls = []
    real = sa.short_attention_on_mesh
    monkeypatch.setattr(
        vit, "short_attention_on_mesh",
        lambda *a, **kw: calls.append(a[3]) or real(*a, **kw))
    q, k, v = (jax.random.normal(key, (2, 5, 2, 64))
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    dense = vit.attention(q, k, v)
    assert not calls                                   # CPU
    _force_fused(monkeypatch)
    assert jax.device_count() > 1
    vit.attention(q, k, v)
    assert not calls                                   # no mesh to wrap with
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    fused = vit.attention(q, k, v, mesh=one)
    assert calls == [one]
    np.testing.assert_allclose(np.asarray(fused), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    dropped = vit.attention(q, k, v, dropout_rng=jax.random.PRNGKey(1),
                            dropout_rate=0.5, deterministic=False, mesh=one)
    assert calls == [one]                              # dropout: dense
    assert not np.allclose(np.asarray(dropped), np.asarray(dense))
    vit.attention(q, k, v, dropout_rate=0.5, deterministic=True, mesh=one)
    assert len(calls) == 2                             # off in evaluation
    vit.attention(q, k, v, mask=jnp.ones((2, 1, 5, 5), bool), mesh=one)
    assert len(calls) == 2                             # a mask: dense


def test_init_traces_no_kernel(monkeypatch):
    """``model.init`` wants shapes and throws its output away: even where
    the policy picks the kernels, it runs flax's own attention (the same
    parameters either way), and ``apply`` then runs one fused attention a
    block."""
    from pytorch_distributed_tpu.models import vit

    calls = []
    real = vit.short_attention_on_mesh
    monkeypatch.setattr(
        vit, "short_attention_on_mesh",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    _force_fused(monkeypatch)
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    model = _tiny(d_model=128, n_heads=2).clone(mesh=one)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert not calls
    model.apply(variables, x, train=False)
    assert len(calls) == 2
