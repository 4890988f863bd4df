"""The configured decoder (models/decoder.py: latent attention, sigmoid
top-k routing over shared and routed experts, one chip's share) against the
plain reference (tests/reference_decoder.py), at a preset with every width
divided down and every ratio kept."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import reference_decoder as ref  # noqa: E402

from pytorch_distributed_tpu.models.decoder import DecoderConfig, DecoderLM  # noqa: E402
from pytorch_distributed_tpu.models.moe import RoutedExperts  # noqa: E402
from pytorch_distributed_tpu.parallel import data_parallel_mesh  # noqa: E402
from pytorch_distributed_tpu.parallel.tp import replicated_like  # noqa: E402
from pytorch_distributed_tpu.train.lm import make_lm_train_step  # noqa: E402
from pytorch_distributed_tpu.train.optim import sgd_init  # noqa: E402
from pytorch_distributed_tpu.train.state import TrainState  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = (4, 4)
# d 64, 4 heads of 16+8 | 16, latent 32, 16 experts of which 4 are held,
# 3 a token, two shared, one dense and two expert layers, V 512
PRESET = dict(
    vocab_size=512, hidden_size=64, intermediate_size=352,
    moe_intermediate_size=44, num_hidden_layers=3, num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=HELD[1],
    num_experts_per_tok=3, n_shared_experts=2, first_k_dense_replace=1,
    routed_scaling_factor=2.446, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_theta=800000, scoring_func="sigmoid", topk_method="noaux_tc",
    seq_aux=True,
    deployment={"n_routed_experts": 16, "first_expert": HELD[0]},
    training={"remat": True})
B, L = 2, 64


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """32 pairs a pass of the grouped products, so that the preset's 384
    pairs a layer run the loops several passes deep."""
    from pytorch_distributed_tpu.models import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "GMM_CHUNK_ROWS", 32)
        yield


def _tokens(seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0,
                              PRESET["vocab_size"])


def _init(model, seed=1):
    """Seeded weights, norms shaken off 1, the selection bias drawn
    non-zero so that selection (s + b) and gate (s) differ."""
    variables = model.init(jax.random.PRNGKey(seed), _tokens())
    leaves, tree = jax.tree_util.tree_flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])
    bias = jax.tree_util.tree_map(
        lambda b: 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 2),
                                          b.shape), variables["router"])
    return params, bias


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
                                    experts_held=HELD))(params)
    return dict(model=model, params=params, bias=bias, tokens=tokens,
                logits=want_logits, loss=want_loss, grads=want_grads,
                counts=counts)


def test_float32_logits_equal_reference(f32):
    logits, _ = f32["model"].apply(
        {"params": f32["params"], "router": f32["bias"]}, f32["tokens"],
        mutable=["losses", "counters"])
    assert logits.shape == (B, L, PRESET["vocab_size"])
    np.testing.assert_allclose(logits, f32["logits"], atol=2e-5)


@pytest.fixture(scope="module")
def f32_step(f32):
    """One step of the real train step with plain SGD at rate 1: the
    gradient of every leaf is ``old - new``."""
    import warnings

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


def test_step_counts_every_pair_and_moves_the_bias(f32, f32_step):
    new_state, metrics, _ = f32_step
    assert int(metrics["rows_grouped"]) == int(metrics["routed_here"]) > 0
    # the reference's counts, all 16 experts, both layers
    here = sum(int(c[HELD[0]:sum(HELD)].sum())
               for c in f32["counts"].values())
    assert int(metrics["routed_here"]) == here
    assert float(metrics["expert_rows_mean"]) == pytest.approx(
        here / HELD[1])
    assert float(metrics["expert_rows_max"]) >= float(
        metrics["expert_rows_mean"])
    for name, counts in f32["counts"].items():
        want = ref.bias_update(_ref_bias(f32["bias"])[name], counts)
        got = new_state.batch_stats[name]["moe"]["e_score_correction_bias"]
        np.testing.assert_allclose(got, want, atol=1e-7)
    # the names the loop books and the benchmark reads are the step's own
    assert set(f32["model"].counter_names) <= set(metrics)
    assert float(metrics["bias_abs_max"]) == pytest.approx(max(
        float(jnp.max(jnp.abs(b))) for b in jax.tree_util.tree_leaves(
            new_state.batch_stats)))


# ------------------------------------------------------------ precision

def _against_reference(model, params, bias, tokens, want):
    """``ref.agreement`` for ``model``'s policy with ``params``, against
    the reference on the fixture's float32 weights: logits, losses and
    gradients over the positions the reference finds clear of ties."""
    n = PRESET["num_hidden_layers"]

    def reference(p):
        logits, aux, _, margin = ref.forward(
            PRESET, p, _ref_bias(bias), tokens, experts_held=HELD)
        clear = ref.clear_of_ties(margin)
        loss = ref.loss(logits, tokens, clear)
        return loss + aux, (logits, loss, clear)

    with jax.default_matmul_precision("highest"):
        (_, (want_logits, want_loss, clear)), want_grads = (
            jax.value_and_grad(reference, has_aux=True)(want["params"]))

    def program(p):
        logits, sown = model.apply({"params": p, "router": bias}, tokens,
                                   mutable=["losses", "counters"])
        loss = ref.loss(logits.astype(jnp.float32), tokens, clear)
        return (loss + sum(jax.tree_util.tree_leaves(sown["losses"])),
                (logits, loss))

    (_, (logits, loss)), grads = jax.value_and_grad(
        program, has_aux=True)(params)
    out = ref.agreement(
        logits, want_logits, loss, want_loss, ref.grad_leaves(grads, n),
        ref.grad_leaves(want_grads, n), clear)
    out = {k: float(v) for k, v in out.items()}
    out["ok"] = ref.within_tolerance(out, slack=2.0)  # the preset's
    return out


def test_bf16_policy_is_inside_and_8bit_weights_outside_the_tolerance(f32):
    model = DecoderLM(DecoderConfig.from_dict(PRESET), dtype=jnp.bfloat16)
    good = _against_reference(model, f32["params"], f32["bias"],
                              f32["tokens"], f32)
    assert good["ok"], good
    coarse = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), f32["params"])
    bad = _against_reference(model, coarse, f32["bias"], f32["tokens"], f32)
    assert not bad["ok"], bad


# --------------------------------------------------------- the expert layer

def _layer(held, dtype=jnp.float32):
    return RoutedExperts(
        n_routed=16, top_k=3, width=44, held=held, n_shared=2,
        scaling=2.446, seq_aux_alpha=1e-4, dtype=dtype)


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut expert layer's weights (all 16 experts) and an input."""
    x = jax.random.normal(jax.random.PRNGKey(5), (B, L, 64))
    variables = _layer((0, 16)).init(jax.random.PRNGKey(6), x)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    return x, variables["params"], bias


def _share(params, first, count):
    experts = {k: v[first:first + count]
               for k, v in params["experts"].items()}
    return {**params, "experts": experts}


def _apply(layer, params, bias, x):
    return layer.apply(
        {"params": params,
         "router": {"e_score_correction_bias": bias}}, x,
        mutable=["losses", "counters"])


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    x, params, bias = whole_layer
    with jax.default_matmul_precision("highest"):
        want, _, _, _ = ref.expert_layer(PRESET, params, bias, x)
        shared = ref.swiglu(x, params["shared"])
        total = shared
        for first in range(0, 16, 4):
            part, _ = _apply(_layer((first, 4)), _share(params, first, 4),
                             bias, x)
            total = total + (part - shared)
    np.testing.assert_allclose(total, want, atol=2e-5)


@pytest.mark.parametrize("forced", [1, 3], ids=["one_expert", "every_pair"])
def test_dropless_under_total_imbalance(whole_layer, forced):
    """Every token forced onto the same held expert(s): with ``forced`` = 3
    all B*L*3 pairs land here, several chunks deep; none is lost."""
    x, params, bias = whole_layer
    held = (4, 4)
    bias = bias.at[held[0]:held[0] + forced].add(100.0)
    with jax.default_matmul_precision("highest"):
        want, _, counts, _ = ref.expert_layer(PRESET, params, bias, x, held)
        got, sown = _apply(_layer(held), _share(params, *held), bias, x)
    seen = sown["counters"]
    assert int(counts[held[0]]) == B * L
    assert int(seen["rows_grouped"][0]) == int(seen["routed_here"][0])
    assert int(seen["routed_here"][0]) == int(counts[4:8].sum())
    assert int(seen["routed_here"][0]) >= B * L * forced
    assert int(seen["rows_max"][0]) == B * L
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and its gradients: the backward pass is the same loop
    def loss(p, layer_fn):
        return jnp.sum(jnp.sin(layer_fn(p)))

    with jax.default_matmul_precision("highest"):
        g_ref = jax.grad(loss)(
            _share(params, *held),
            lambda p: ref.expert_layer(PRESET, p, bias, x, held)[0])
        g_got = jax.grad(loss)(
            _share(params, *held),
            lambda p: _apply(_layer(held), p, bias, x)[0])
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, atol=2e-3 * float(
            jnp.max(jnp.abs(b))) + 1e-6)


def test_bias_update_and_sequence_loss_against_numpy(whole_layer):
    x, params, bias = whole_layer
    _, sown = _apply(_layer((0, 16)), params, bias, x)
    xs = np.asarray(x, np.float64)
    s = 1 / (1 + np.exp(-xs @ np.asarray(params["router"]["kernel"],
                                         np.float64)))       # [B, L, 16]
    idx = np.argsort(-(s + np.asarray(bias)), -1)[..., :3]
    chose = np.zeros_like(s)
    np.put_along_axis(chose, idx, 1.0, -1)
    f = chose.sum(1) * 16 / (3 * L)
    p = (s / s.sum(-1, keepdims=True)).mean(1)
    aux = 1e-4 * (f * p).sum(-1).mean()
    counts = chose.sum((0, 1))
    assert float(sown["losses"]["moe_seq_aux"][0]) == pytest.approx(
        aux, rel=1e-5)
    np.testing.assert_array_equal(sown["counters"]["expert_counts"][0],
                                  counts)
    want = np.asarray(bias) + 0.001 * np.sign(counts.mean() - counts)
    np.testing.assert_allclose(ref.bias_update(bias, jnp.asarray(counts)),
                               want, atol=1e-7)


# ---------------------------------------------------------------- the kernel

@pytest.mark.parametrize("d_qk,d_v,dtype", [
    (192, 128, jnp.float32), (24, 16, jnp.float32),
    (192, 128, jnp.bfloat16)], ids=["192-128", "24-16", "192-128-bf16"])
def test_flash_kernel_with_two_head_sizes(d_qk, d_v, dtype):
    from pytorch_distributed_tpu.models.decoder import dense_attention
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    b, l, h = 1, 256, 2
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(kq, (b, l, h, d_qk)).astype(dtype)
    k = jax.random.normal(kk, (b, l, h, d_qk)).astype(dtype)
    v = jax.random.normal(kv, (b, l, h, d_v)).astype(dtype)
    g = jax.random.normal(kg, (b, l, h, d_v)).astype(dtype)
    scale = 0.37 * d_qk ** -0.5  # not the default

    def run(fn, *args):
        out, vjp = jax.vjp(fn, *args[:3])
        return (out,) + vjp(args[3].astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        want = run(lambda q, k, v: dense_attention(q, k, v, scale),
                   *(x.astype(jnp.float32) for x in (q, k, v, g)))
    # the kernels' products take their operands as they come: bf16 in,
    # bf16 on the MXU
    got = run(lambda q, k, v: flash_attention(
        q, k, v, True, 128, 128, True, "pallas", scale), q, k, v, g)
    assert got[0].shape == (b, l, h, d_v) and got[0].dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for a, w in zip(got, want):
        np.testing.assert_allclose(a.astype(jnp.float32), w,
                                   atol=tol * float(jnp.max(jnp.abs(w))))


# ------------------------------------------------- what must not have moved

def test_tx_none_lowers_the_transformer_lm_step_as_before():
    """``make_lm_train_step(tx=None)`` on a ``TransformerLM``: the lowered
    text's digest as the commit before PR 26 gave it."""
    from pytorch_distributed_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=128, d_model=32, n_heads=2, n_layers=2)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    state = TrainState.create({"params": params}, sgd_init(params))
    mesh = data_parallel_mesh(jax.devices()[:1])
    step = make_lm_train_step(model, mesh, replicated_like(params))
    text = step.lower(state, tokens, jnp.float32(0.1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "7bd903cd57943631a56ce0dfee441838ca4e0e6292002cb87691ae3318d69735")


@pytest.mark.parametrize("blocks,causal,digest", [
    # square causal blocks: the diagonal blocks in sub-tiles
    ((128, 128), True,
     "9b5e38bfbbc5c33528c118eb692c5828ed6672e69ee819c6ae513cfa77d249e1"),
    # no sub-tiles: the text of the block schedule by scalar prefetch
    ((64, 128), True,
     "33137d3bea5f49e71c0aaecd09b5f2cccd8de43c5e7278c4f80c323cde04796d"),
    ((128, 64), True,
     "98c85c05ad052ddabb1bdc6ab07d7076c0016f77c6679ccf9abf744e845e5521"),
    ((128, 128), False,
     "b155860271f6903ea02571975edef3449f9591074793fd6769df3a9dee88dbc0"),
])
def test_flash_kernel_on_float32_lowers_as_before(blocks, causal, digest):
    """One head size, the default scale, float32 in: forward and the two
    backward kernels lower to a fixed text.  Where the crossed blocks are
    not sub-tiled (blocks that are not square, a call that is not causal)
    it is the text of the block schedule before the sub-tiles; square
    causal blocks take the sub-tiled text."""
    from pytorch_distributed_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, *blocks, True) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_step_reports_the_attention_counters(f32, f32_step):
    """The three attention counters lead the step's counters: 0 on the
    CPU's dense path; under flash at L = 8,192 the schedule's counts."""
    from pytorch_distributed_tpu.models.decoder import attention_blocks

    _, metrics, _ = f32_step
    names = ("attn_blocks_visited", "attn_blocks_masked",
             "attn_subtiles_skipped")
    assert f32["model"].counter_names[:3] == names
    assert [int(metrics[name]) for name in names] == [0, 0, 0]
    assert attention_blocks(8192, "flash") == (36, 8, 48)
    assert attention_blocks(8192, "dense") == (0, 0, 0)


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(ROOT, "tests", "reference_decoder.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "kimi_vl_a3b.py"), "rb") as f:
        assert f.read() == mine
