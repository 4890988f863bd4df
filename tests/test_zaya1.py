"""The configured decoder's ZAYA1 block (models/decoder.py: attention in a
compressed latent with convolutions, grouped key-value heads, an MLP router
that picks one expert a token, a scaled residual, the tied head; one chip's
share) against the plain reference (tests/reference_zaya1.py), at a preset
with every width divided down and every ratio kept."""

import hashlib
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import reference_zaya1 as ref  # noqa: E402

from pytorch_distributed_tpu.models.decoder import (  # noqa: E402
    DecoderBlock,
    DecoderConfig,
    DecoderLM,
    dense_attention,
)
from pytorch_distributed_tpu.models.moe import RoutedExperts  # noqa: E402
from pytorch_distributed_tpu.parallel import data_parallel_mesh  # noqa: E402
from pytorch_distributed_tpu.parallel.tp import replicated_like  # noqa: E402
from pytorch_distributed_tpu.train.lm import (  # noqa: E402
    head_matrix,
    make_lm_train_step,
)
from pytorch_distributed_tpu.train.state import TrainState  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = (8, 8)
LAYERS = 3
# d 64, 4 query heads over 2 key-value heads of 16 (latent 64 | 32), expert
# width 64, router width 16, 16 experts of which 8 are held, one a token,
# three layers, V 512 of 1,024
PRESET = dict(
    vocab_size=512, hidden_size=64, moe_intermediate_size=64,
    num_hidden_layers=LAYERS, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=HELD[1], num_experts_per_tok=1,
    router_hidden_size=16, cca_time0=2, cca_time1=2, rms_norm_eps=1e-5,
    partial_rotary_factor=0.5, tie_word_embeddings=True,
    layer_types=["hybrid"] * LAYERS, sliding_window=None,
    lm_head_bias=False, attention_bias=False, hidden_act="silu",
    rope_parameters={
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    deployment={"num_experts": 16, "first_expert": HELD[0]},
    training={"remat": True})
B, L = 2, 64


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """32 pairs a pass of the grouped products, so that a layer's pairs run
    the loops several passes deep."""
    from pytorch_distributed_tpu.models import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "GMM_CHUNK_ROWS", 32)
        yield


def _tokens(seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0,
                              PRESET["vocab_size"])


def _shaken(tree, seed, by=0.05):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + by * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])


def _init(model, seed=1):
    """Seeded weights, every leaf shaken (norm scales, residual scales and
    temperatures off 1; shifts, gamma and biases off 0), the selection bias
    drawn non-zero so that selection (p + b) and gate (p) differ."""
    variables = model.init(jax.random.PRNGKey(seed), _tokens())
    bias = jax.tree_util.tree_map(
        lambda b: 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                           b.shape), variables["router"])
    return _shaken(variables["params"], seed + 1), bias


def _ref_bias(bias):
    return {k: v["moe"]["e_score_correction_bias"] for k, v in bias.items()}


@pytest.fixture(scope="module")
def f32():
    model = DecoderLM(DecoderConfig.from_dict(PRESET), dtype=jnp.float32)
    params, bias = _init(model)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        want_logits, _, counts, _ = ref.forward(
            PRESET, params, _ref_bias(bias), tokens, experts_held=HELD)
        want_loss, want_grads = jax.value_and_grad(
            lambda p: ref.objective(PRESET, p, _ref_bias(bias), tokens,
                                    row_block=32, experts_held=HELD))(params)
    return dict(model=model, params=params, bias=bias, tokens=tokens,
                logits=want_logits, loss=want_loss, grads=want_grads,
                counts=counts)


def _logits(f32, params=None, tokens=None):
    return f32["model"].apply(
        {"params": f32["params"] if params is None else params,
         "router": f32["bias"]},
        f32["tokens"] if tokens is None else tokens,
        mutable=["losses", "counters"])


def test_float32_logits_equal_reference(f32):
    with jax.default_matmul_precision("highest"):
        logits, sown = _logits(f32)
    assert logits.shape == (B, L, PRESET["vocab_size"])
    np.testing.assert_allclose(logits, f32["logits"], atol=2e-5)
    assert "losses" not in sown        # no auxiliary loss in this block
    # the blockwise loss is the plain one
    assert float(f32["loss"]) == pytest.approx(
        float(ref.loss(f32["logits"], f32["tokens"])), abs=2e-6)


@pytest.fixture(scope="module")
def f32_step(f32):
    """One step of the real train step with plain SGD at rate 1: the
    gradient of every leaf is ``old - new``."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mesh = data_parallel_mesh(jax.devices()[:1])
        tx = optax.sgd(1.0)
        state = TrainState.create(
            {"params": f32["params"], "batch_stats": f32["bias"]},
            tx.init(f32["params"]))
        step = make_lm_train_step(
            f32["model"], mesh, replicated_like(f32["params"]), tx=tx,
            params=f32["params"], fused_ce_chunks=2)
        with jax.default_matmul_precision("highest"):
            new_state, metrics = step(
                jax.tree_util.tree_map(jnp.copy, state), f32["tokens"],
                jnp.float32(0.0))
    grads = jax.tree_util.tree_map(lambda a, b: a - b, f32["params"],
                                   new_state.params)
    return new_state, metrics, grads


def test_float32_loss_equals_reference(f32, f32_step):
    _, metrics, _ = f32_step
    assert float(metrics["loss"]) == pytest.approx(float(f32["loss"]),
                                                   abs=2e-5)


def _leaf_paths():
    model = DecoderLM(DecoderConfig.from_dict(PRESET))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), _tokens())
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(
                shapes["params"])[0]]


@pytest.mark.parametrize("path", _leaf_paths())
def test_float32_gradient_of_every_leaf_equals_reference(f32, f32_step, path):
    _, _, grads = f32_step
    got, want = grads, f32["grads"]
    for key in path.split("/"):
        got, want = got[key], want[key]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf no gradient reaches tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-3 * scale + 2e-7)


def test_the_reference_names_leaves_the_model_has():
    paths = set(_leaf_paths())
    for path in ref.GRAD_LEAVES:
        assert "/".join(path).replace(
            "layer_last", f"layer_{LAYERS - 1}") in paths


def test_step_counts_every_pair_and_moves_the_bias(f32, f32_step):
    new_state, metrics, _ = f32_step
    assert int(metrics["rows_grouped"]) == int(metrics["routed_here"]) > 0
    held = [int(c[HELD[0]:sum(HELD)].sum()) for c in f32["counts"].values()]
    assert int(metrics["routed_here"]) == sum(held)
    shares = [100.0 * h / (B * L) for h in held]
    assert float(metrics["held_share_pct"]) == pytest.approx(
        sum(shares) / LAYERS, rel=1e-5)
    assert float(metrics["held_share_min_pct"]) == pytest.approx(
        min(shares), rel=1e-5)
    assert float(metrics["held_share_max_pct"]) == pytest.approx(
        max(shares), rel=1e-5)
    # a sixteenth is what a uniform router gives its pick
    assert 1 / 16 < float(metrics["gate_mean"]) < 1
    assert 0 < float(metrics["router_entropy"]) < np.log(16)
    for name, counts in f32["counts"].items():
        want = ref.bias_update(_ref_bias(f32["bias"])[name], counts)
        got = new_state.batch_stats[name]["moe"]["e_score_correction_bias"]
        np.testing.assert_allclose(got, want, atol=1e-7)
    assert set(f32["model"].counter_names) <= set(metrics)
    assert set(DecoderLM.TOP1_COUNTERS) <= set(f32["model"].counter_names)


def test_changing_a_token_changes_no_logit_before_it(f32):
    """Two convolutions and a value shift reach back, none forward."""
    t = 37
    other = f32["tokens"].at[:, t].set(
        (f32["tokens"][:, t] + 1) % PRESET["vocab_size"])
    with jax.default_matmul_precision("highest"):
        base, _ = _logits(f32)
        moved, _ = _logits(f32, tokens=other)
        want, _, _, _ = ref.forward(PRESET, f32["params"],
                                    _ref_bias(f32["bias"]), other,
                                    experts_held=HELD)
    np.testing.assert_array_equal(moved[:, :t], base[:, :t])
    assert float(jnp.max(jnp.abs(moved[:, t] - base[:, t]))) > 1e-3
    # and the position after it sees it through the taps and the shift
    assert float(jnp.max(jnp.abs(moved[:, t + 1] - base[:, t + 1]))) > 1e-3
    np.testing.assert_array_equal(want[:, :t], f32["logits"][:, :t])


# ---------------------------------------------------------------- the kernels

@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 1), (4, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_flash_kernels_equal_explicit_scores(heads, kv_heads, dtype):
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    b, l, d = 1, 256, 32
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(kq, (b, l, heads, d)).astype(dtype)
    k = jax.random.normal(kk, (b, l, kv_heads, d)).astype(dtype)
    v = jax.random.normal(kv, (b, l, kv_heads, d)).astype(dtype)
    g = jax.random.normal(kg, (b, l, heads, d)).astype(dtype)
    scale = 0.41 * d ** -0.5

    def run(fn, *args):
        out, vjp = jax.vjp(fn, *args[:3])
        return (out,) + vjp(args[3].astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v: dense_attention(q, k, v, scale),
                   *(x.astype(jnp.float32) for x in (q, k, v, g)))
    got = run(lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, True, "pallas", scale), q, k, v, g)
    assert got[0].shape == (b, l, heads, d) and got[0].dtype == dtype
    assert got[2].shape == (b, l, kv_heads, d) and got[2].dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for a, w in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32), w,
                                   atol=tol * float(jnp.max(jnp.abs(w))))


def test_as_many_key_value_heads_as_query_heads_lowers_as_before():
    """``G = H`` is the parent's program: the digest of
    ``tests/test_decoder.py::test_flash_kernel_on_float32_lowers_as_before``
    (square causal blocks) from a call that says its heads twice."""
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    kv = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 128, 128, True) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "9b5e38bfbbc5c33528c118eb692c5828ed6672e69ee819c6ae513cfa77d249e1")
    grouped = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv[:, :, :1], kv[:, :, :1]).as_text()
    assert grouped != text


def test_key_value_heads_must_divide_query_heads():
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 3, 32))
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], True, 128, 128, True)


# --------------------------------------------------------- the expert layer

def _layer(held, dtype=jnp.float32):
    return RoutedExperts(
        n_routed=16, top_k=1, width=64, held=held, norm_topk_prob=False,
        router_hidden=16, dtype=dtype)


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut expert layer's weights (all 16 experts), shaken, an input
    and the state of a router before it."""
    x = jax.random.normal(jax.random.PRNGKey(5), (B, L, 64))
    before = jax.random.normal(jax.random.PRNGKey(8), (B * L, 16))
    variables = _layer((0, 16)).init(jax.random.PRNGKey(6), x, before)
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    return x, before, _shaken(variables["params"], 9), bias


def _share(params, first, count):
    experts = {k: v[first:first + count]
               for k, v in params["experts"].items()}
    return {**params, "experts": experts}


def _apply(layer, params, bias, x, before):
    (out, state), sown = layer.apply(
        {"params": params,
         "router": {"e_score_correction_bias": bias}}, x, before,
        mutable=["losses", "counters"])
    return out, state, sown


def _ref_layer(params, bias, x, before, held=None):
    return ref.expert_layer(PRESET, params, bias, x,
                            before.reshape(B, L, -1), held)


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    """Experts 0-7's part plus experts 8-15's part is the whole layer's
    result: no shared expert to count once."""
    x, before, params, bias = whole_layer
    with jax.default_matmul_precision("highest"):
        want, want_state, counts, _, _ = _ref_layer(params, bias, x, before)
        total, rows = 0.0, 0
        for first in (0, 8):
            part, state, sown = _apply(
                _layer((first, 8)), _share(params, first, 8), bias, x,
                before)
            total = total + part
            rows += int(sown["counters"]["routed_here"][0])
            np.testing.assert_allclose(
                state, want_state.reshape(B * L, -1), atol=1e-5)
    assert rows == B * L == int(counts.sum())
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_the_gate_is_the_picks_probability_and_teaches_the_router(
        whole_layer):
    x, before, params, bias = whole_layer
    with jax.default_matmul_precision("highest"):
        prob, _ = ref.router(PRESET, params["router"], x,
                             before.reshape(B, L, -1))
        _, _, _, gate, _ = _ref_layer(params, bias, x, before)
        _, _, sown = _apply(_layer((0, 16)), params, bias, x, before)

        def loss(p):
            return jnp.sum(jnp.sin(_apply(_layer((0, 16)), p, bias, x,
                                          before)[0]))

        grads = jax.grad(loss)(params)
    pick = jnp.argmax(prob + bias, -1)
    np.testing.assert_allclose(
        gate, jnp.take_along_axis(prob, pick[..., None], -1)[..., 0])
    assert float(jnp.max(gate)) < 1.0       # not normalised to 1
    assert float(sown["counters"]["gate_mean"][0]) == pytest.approx(
        float(jnp.mean(gate)), rel=1e-5)
    entropy = -jnp.mean(jnp.sum(prob * jnp.log(prob), -1))
    assert float(sown["counters"]["router_entropy"][0]) == pytest.approx(
        float(entropy), rel=1e-4)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            grads["router"])[0]:
        assert float(jnp.max(jnp.abs(leaf))) > 0, path


def test_a_bias_moves_the_pick_and_leaves_the_gate(whole_layer):
    x, before, params, bias = whole_layer
    moved = bias.at[11].add(1.0)
    with jax.default_matmul_precision("highest"):
        prob, _ = ref.router(PRESET, params["router"], x,
                             before.reshape(B, L, -1))
        out, _, sown = _apply(_layer((0, 16)), params, moved, x, before)
        want, _, counts, gate, _ = _ref_layer(params, moved, x, before)
    assert int(sown["counters"]["expert_counts"][0][11]) == B * L
    assert int(counts[11]) == B * L
    np.testing.assert_allclose(gate, prob[..., 11])    # p, not p + 1
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_gamma_zero_is_a_router_without_a_state(whole_layer):
    x, before, params, bias = whole_layer
    zero = {**params, "router": {**params["router"],
                                 "gamma": jnp.zeros_like(
                                     params["router"]["gamma"])}}
    alone = {**params, "router": {k: v for k, v in params["router"].items()
                                  if k != "gamma"}}
    with jax.default_matmul_precision("highest"):
        with_zero, _, _ = _apply(_layer((0, 16)), zero, bias, x, before)
        handed_none, _, _ = _apply(_layer((0, 16)), alone, bias, x, None)
        with_state, _, _ = _apply(_layer((0, 16)), params, bias, x, before)
    np.testing.assert_array_equal(with_zero, handed_none)
    assert float(jnp.max(jnp.abs(with_state - handed_none))) > 1e-3


def test_a_block_hands_its_routers_state_on(f32):
    """Layer 1's logits depend on layer 0's router through ``gamma``."""
    params = f32["params"]
    block = DecoderBlock(f32["model"].config, expert_layer=True)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, L, 64))
    (y0, state0), _ = block.apply(
        {"params": params["layer_0"], "router": f32["bias"]["layer_0"]}, x,
        None, mutable=["counters"])
    assert state0.shape == (B * L, PRESET["router_hidden_size"])
    assert "gamma" not in params["layer_0"]["moe"]["router"]
    (y1, _), _ = block.apply(
        {"params": params["layer_1"], "router": f32["bias"]["layer_1"]}, y0,
        state0, mutable=["counters"])
    (y1_other, _), _ = block.apply(
        {"params": params["layer_1"], "router": f32["bias"]["layer_1"]}, y0,
        2.0 * state0, mutable=["counters"])
    assert float(jnp.max(jnp.abs(y1 - y1_other))) > 0


@pytest.mark.parametrize("target", [10, 3], ids=["held", "absent"])
def test_dropless_under_total_imbalance(whole_layer, target):
    """Every token forced onto one expert.  Held: all B*L rows land here,
    several chunks deep, none lost.  Absent: the layer runs, adds nothing,
    ``routed_here`` is 0 and the held experts' gradients are exactly 0."""
    x, before, params, bias = whole_layer
    held = HELD
    bias = bias.at[target].add(100.0)
    here = held[0] <= target < sum(held)
    with jax.default_matmul_precision("highest"):
        want, _, counts, _, _ = _ref_layer(params, bias, x, before, held)
        got, _, sown = _apply(_layer(held), _share(params, *held), bias, x,
                              before)
    seen = sown["counters"]
    assert int(counts[target]) == B * L
    assert int(seen["rows_grouped"][0]) == int(seen["routed_here"][0])
    assert int(seen["routed_here"][0]) == (B * L if here else 0)
    assert int(seen["rows_max"][0]) == (B * L if here else 0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if not here:
        assert float(jnp.max(jnp.abs(got))) == 0.0

    def loss(p, layer_fn):
        return jnp.sum(jnp.sin(layer_fn(p) + x))

    with jax.default_matmul_precision("highest"):
        g_ref = jax.grad(loss)(
            _share(params, *held),
            lambda p: _ref_layer(p, bias, x, before, held)[0])
        g_got = jax.grad(loss)(
            _share(params, *held),
            lambda p: _apply(_layer(held), p, bias, x, before)[0])
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=2e-3 * float(
            jnp.max(jnp.abs(b))) + 1e-6)
    if not here:
        for leaf in jax.tree_util.tree_leaves(g_got["experts"]):
            assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_a_step_runs_when_every_token_goes_elsewhere(f32):
    """The whole step with every layer's tokens on an absent expert:
    finite loss, ``routed_here`` 0, the held experts unmoved by SGD."""
    bias = jax.tree_util.tree_map(lambda b: b.at[3].add(100.0), f32["bias"])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mesh = data_parallel_mesh(jax.devices()[:1])
        tx = optax.sgd(1.0)
        state = TrainState.create(
            {"params": f32["params"], "batch_stats": bias},
            tx.init(f32["params"]))
        step = make_lm_train_step(
            f32["model"], mesh, replicated_like(f32["params"]), tx=tx,
            params=f32["params"], fused_ce_chunks=2)
        new_state, metrics = step(
            jax.tree_util.tree_map(jnp.copy, state), f32["tokens"],
            jnp.float32(0.0))
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["routed_here"]) == int(metrics["rows_grouped"]) == 0
    assert float(metrics["held_share_max_pct"]) == 0.0
    for i in range(LAYERS):
        for name in ("gate_proj", "up_proj", "down_proj"):
            np.testing.assert_array_equal(
                new_state.params[f"layer_{i}"]["moe"]["experts"][name],
                f32["params"][f"layer_{i}"]["moe"]["experts"][name])


# ------------------------------------------------------------- the tied head

def test_the_tied_head_is_the_embedding(f32):
    model, params = f32["model"], f32["params"]
    assert "head" not in params
    assert head_matrix(model, params) is params["embed"]["embedding"]
    untied = DecoderLM(DecoderConfig.from_dict(
        {**PRESET, "tie_word_embeddings": False}))
    shapes = jax.eval_shape(untied.init, jax.random.PRNGKey(0), _tokens())
    assert shapes["params"]["head"]["weight"].shape == (512, 64)


def test_fused_tied_loss_equals_the_unfused_one(f32, f32_step):
    """The step's fused loss against the embedding, and the embedding's
    whole gradient (the lookup's part plus the head's), against plain
    cross-entropy over the model's own logits."""
    _, metrics, grads = f32_step

    def unfused(p):
        logits, _ = _logits(f32, params=p)
        return ref.loss(logits, f32["tokens"])

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(unfused)(f32["params"])
    assert float(metrics["loss"]) == pytest.approx(float(want), abs=2e-5)
    got, want = grads["embed"]["embedding"], want_grads["embed"]["embedding"]
    np.testing.assert_allclose(
        got, want, atol=2e-3 * float(jnp.max(jnp.abs(want))))
    # both parts are in it: rows of ids the batch never holds get a
    # gradient from the head alone, rows it holds from the lookup too
    absent = np.setdiff1d(np.arange(512), np.asarray(f32["tokens"]))
    assert float(jnp.max(jnp.abs(got[absent]))) > 0


def test_the_lookups_gradient_is_summed_in_float32(f32):
    """Under the bf16 policy the tied model gathers the table's float32
    rows and rounds them after (``nn.Embed`` rounds the table first, and
    the lookup's gradient would be a scatter-add in bf16: a frequent id's
    thousand rows summed at 8 bits of mantissa).  The step's lowered text
    scatters float32 into [V, d]; ``tests/test_tpu_aot.py`` compiles the
    step for the chip and finds that scatter landing in the head's gradient
    buffer, no second one."""
    from pytorch_distributed_tpu.train.optim import adamw

    model = DecoderLM(DecoderConfig.from_dict(PRESET), dtype=jnp.bfloat16)
    tokens = jnp.zeros((B, L), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    tx = adamw({"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                "weight_decay": 0.1})
    state = jax.eval_shape(lambda v: TrainState.create(
        {"params": v["params"], "batch_stats": v["router"]},
        tx.init(v["params"])), variables)
    mesh = data_parallel_mesh(jax.devices()[:1])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        step = make_lm_train_step(
            model, mesh, replicated_like(state.params), tx=tx,
            params=state.params, fused_ce_chunks=2)
    text = step.lower(state, tokens, jnp.float32(0.0)).as_text()
    # the scatter of the [B, L] tokens' rows into the table's shape
    assert text.count("(tensor<512x64xf32>, tensor<2x64x1xi32>, "
                      "tensor<2x64x64xf32>) -> tensor<512x64xf32>") == 1
    assert "(tensor<512x64xbf16>, tensor<2x64x1xi32>" not in text


# ------------------------------------------------------------ precision

def _against_reference(model, params, bias, tokens, want):
    """``ref.agreement`` for ``model``'s policy with ``params``, against
    the reference on the fixture's float32 weights: logits, losses and
    gradients over the positions the reference finds clear of ties."""

    def reference(p):
        rows, _, margin = ref.hidden(PRESET, p, _ref_bias(bias), tokens,
                                     experts_held=HELD)
        clear = ref.clear_of_ties(margin)
        loss = ref.loss_rows(rows, ref.embedding_of(p), tokens, clear, 32)
        return loss, (rows, loss, clear)

    with jax.default_matmul_precision("highest"):
        (_, (want_rows, want_loss, clear)), want_grads = (
            jax.value_and_grad(reference, has_aux=True)(want["params"]))

    def program(p):
        rows, _ = model.apply({"params": p, "router": bias}, tokens,
                              mutable=["losses", "counters"],
                              return_hidden=True)
        rows = rows.astype(model.dtype)
        loss = ref.loss_rows(
            rows.astype(jnp.float32),
            head_matrix(model, p).astype(model.dtype).astype(jnp.float32),
            tokens, clear, 32)
        return loss, (rows, loss)

    (_, (rows, loss)), grads = jax.value_and_grad(
        program, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        worst, top = ref.logits_error(
            rows.astype(jnp.float32),
            head_matrix(model, params).astype(model.dtype).astype(
                jnp.float32),
            want_rows, ref.embedding_of(want["params"]), 32)
    out = ref.agreement(
        worst, top, loss, want_loss, ref.grad_leaves(grads, LAYERS),
        ref.grad_leaves(want_grads, LAYERS), clear)
    out = {k: float(v) for k, v in out.items()}
    out["ok"] = ref.within_tolerance(out, slack=2.0)  # the preset's
    return out


def test_bf16_policy_is_inside_and_8bit_weights_outside_the_tolerance(f32):
    model = DecoderLM(DecoderConfig.from_dict(PRESET), dtype=jnp.bfloat16)
    good = _against_reference(model, f32["params"], f32["bias"],
                              f32["tokens"], f32)
    assert good["ok"], good
    assert good["tied_share"] < 0.8
    coarse = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), f32["params"])
    bad = _against_reference(model, coarse, f32["bias"], f32["tokens"], f32)
    assert not bad["ok"], bad


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(ROOT, "tests", "reference_zaya1.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "zaya1.py"),
              "rb") as f:
        assert f.read() == mine


# ----------------------------------------------------------- the file's keys

def test_from_dict_reads_the_catalogs_keys():
    c = DecoderConfig.from_dict(PRESET)
    assert (c.num_attention_heads, c.num_key_value_heads, c.head_dim) == (
        4, 2, 16)
    assert (c.cca_time0, c.cca_time1) == (2, 2)
    assert c.rope_theta == 5000000 and c.partial_rotary_factor == 0.5
    assert c.n_routed_experts == 16 and c.experts_held == HELD
    assert c.num_experts_per_tok == 1 and c.router_hidden_size == 16
    assert c.tie_word_embeddings and c.seq_aux_alpha == 0.0
    assert c.first_k_dense_replace == 0 and c.expert_layers == LAYERS


def test_the_configuration_file_reads_and_rehearses():
    import json

    from pytorch_distributed_tpu.models.decoder import overlay

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "zaya1-8b-ep2.json")) as f:
        cfg = json.load(f)
    c = DecoderConfig.from_dict(cfg)
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.moe_intermediate_size, c.router_hidden_size) == (
                2048, 8, 2, 128, 2048, 256)
    assert c.n_routed_experts == 16 and c.experts_held == (0, 8)
    assert c.vocab_size == 131136 and c.num_hidden_layers == 4
    small = DecoderConfig.from_dict(overlay(cfg, cfg["rehearse"]))
    assert small.hidden_size == 64 and small.experts_held == (0, 8)
    assert small.n_routed_experts == 16 and small.num_hidden_layers == 3


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["hybrid", "hybrid_sliding", "hybrid"]),
    ("kv_lora_rank", 32), ("sliding_window", 4096), ("lm_head_bias", True),
    ("num_experts_per_tok", 2), ("scoring_func", "sigmoid"),
    ("rope_parameters", {"hybrid": {"rope_theta": 1e6, "rope_type": "yarn"}}),
])
def test_from_dict_refuses_by_name_what_it_lacks(key, value):
    with pytest.raises(ValueError, match=key):
        DecoderConfig.from_dict({**PRESET, key: value})


def test_plain_heads_still_refuse_grouped_ones():
    plain = dict(vocab_size=512, hidden_size=64, intermediate_size=176,
                 num_hidden_layers=2, num_attention_heads=4, head_dim=16,
                 rms_norm_eps=1e-6, rope_theta=1e6)
    DecoderConfig.from_dict({**plain, "num_key_value_heads": 4})
    with pytest.raises(ValueError, match="num_key_value_heads"):
        DecoderConfig.from_dict({**plain, "num_key_value_heads": 2})


# ------------------------------------------------------------ the MFU line

def test_the_trainers_cost_counts_the_block_as_the_benchmarks_file():
    """``obs/flops.lm_step_cost_for`` (``LMTrainer``'s MFU line) against
    ``benchmark/flops_zaya1.py`` at the preset, and its parameters against
    the model's own: grouped heads at half the square, the second
    convolution, the router's MLP, one expert at the uniform held share,
    the tied head once."""
    import importlib.util

    from pytorch_distributed_tpu.obs.flops import lm_step_cost_for

    spec = importlib.util.spec_from_file_location(
        "flops_zaya1", os.path.join(ROOT, "benchmark", "flops_zaya1.py"))
    counts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(counts)
    cfg = {**PRESET, "training": {**PRESET["training"], "seq_len": L}}
    model = DecoderLM(DecoderConfig.from_dict(PRESET))
    cost = lm_step_cost_for(model, B, L, fused_ce_chunks=2)
    assert cost.breakdown["forward"] + cost.breakdown["backward"] == (
        pytest.approx(counts.train_flops_per_item(cfg) * B * L, rel=1e-12))
    d, v, hd, r, w = 64, 512, 16, 16, 64
    attention = (2.0 * (d * 4 * hd + 2 * d * 2 * hd + 4 * hd * d)
                 + 2.0 * 2 * 6 * hd * hd + 2.0 * 4 * (hd + hd) * L / 2)
    router = 2.0 * (d * r + 2 * r * r + r * 16)
    assert counts.forward_flops_per_token(cfg) == LAYERS * (
        attention + router + 0.5 * 2.0 * 3 * d * w) + 2.0 * d * v
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), _tokens())
    assert cost.params == sum(
        int(np.prod(x.shape))
        for x in jax.tree_util.tree_leaves(shapes["params"]))
    # what runs twice: every block (remat), and not the head (the fused
    # loss takes its gradient in the pass that has the logits)
    assert cost.breakdown["recompute"] == pytest.approx(
        cost.breakdown["forward"] - B * L * 2.0 * d * v)
