"""The looped, multi-exit decoder (models/decoder.py with
``total_ut_steps`` > 1: plain heads, four norms a block, the stack run four
times over one set of weights, an exit gate) and the step's loss over its
exits, against the plain reference (tests/reference_ouro.py), at a preset
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
import reference_ouro as ref  # noqa: E402

from pytorch_distributed_tpu.models.decoder import (  # noqa: E402
    DecoderBlock,
    DecoderConfig,
    DecoderLM,
    RMSNorm,
    exit_distribution,
)
from pytorch_distributed_tpu.parallel import data_parallel_mesh  # noqa: E402
from pytorch_distributed_tpu.parallel.tp import replicated_like  # noqa: E402
from pytorch_distributed_tpu.train.lm import make_lm_train_step  # noqa: E402
from pytorch_distributed_tpu.train.optim import adamw, sgd_init  # noqa: E402
from pytorch_distributed_tpu.train.state import TrainState  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# d 64, 4 heads of 16, width 176 (2.75 d), 3 layers run 4 times, V 512
LAYERS, PASSES = 3, 4
PRESET = dict(
    vocab_size=512, hidden_size=64, intermediate_size=176,
    num_hidden_layers=LAYERS, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, hidden_act="silu", rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None, tie_word_embeddings=False, total_ut_steps=PASSES,
    early_exit_threshold=1, use_sliding_window=False, sliding_window=None,
    layer_types=["full_attention"] * LAYERS, norm_placement="sandwich",
    training={"remat": True, "exit_entropy_beta": ref.BETA})
B, L = 2, 64
CHUNKS = 4


def _tokens(seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0,
                              PRESET["vocab_size"])


def _model(dtype=jnp.float32, **over):
    return DecoderLM(DecoderConfig.from_dict({**PRESET, **over}),
                     dtype=dtype)


def _init(model, seed=1):
    """Seeded weights, every leaf shaken (norm scales off 1, the gate's
    bias off 0), so that no gradient is zero by symmetry."""
    params = model.init(jax.random.PRNGKey(seed), _tokens())["params"]
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])


def _leaf_paths(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@pytest.fixture(scope="module")
def f32():
    model = _model()
    params, tokens = _init(model), _tokens()
    with jax.default_matmul_precision("highest"):
        (loss, (s, p, exit_ce)), grads = jax.value_and_grad(
            lambda q: ref.objective(PRESET, q, tokens), has_aux=True)(params)
        logits = ref.logits(params, s)
    return dict(model=model, params=params, tokens=tokens, logits=logits,
                p=p, loss=loss, grads=grads, exit_ce=exit_ce)


@pytest.fixture(scope="module")
def f32_forward(f32):
    hidden, sown = f32["model"].apply(
        {"params": f32["params"]}, f32["tokens"], return_hidden=True,
        mutable=["losses", "counters", "exits"])
    return hidden, sown


@pytest.fixture(scope="module")
def sgd_step(f32):
    """``run(params)``: one step of the real train step with plain SGD at
    rate 1, compiled once: the metrics, and the gradient of every leaf as
    ``old - new``."""
    model, tx = f32["model"], optax.sgd(1.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        step = make_lm_train_step(
            model, data_parallel_mesh(jax.devices()[:1]),
            replicated_like(f32["params"]), tx=tx, params=f32["params"],
            fused_ce_chunks=CHUNKS)

    def run(params):
        state = TrainState.create({"params": params}, tx.init(params))
        with jax.default_matmul_precision("highest"):
            new_state, metrics = step(
                jax.tree_util.tree_map(jnp.copy, state), f32["tokens"],
                jnp.float32(0.0))
        return metrics, jax.tree_util.tree_map(lambda a, b: a - b, params,
                                               new_state.params)

    return run


@pytest.fixture(scope="module")
def f32_step(f32, sgd_step):
    return sgd_step(f32["params"])


# ----------------------------------------------------- against the reference

@pytest.mark.parametrize("exit_", range(PASSES))
def test_float32_logits_of_each_exit_equal_reference(f32, f32_forward, exit_):
    hidden, _ = f32_forward
    assert hidden.shape == (PASSES, B, L, PRESET["hidden_size"])
    logits = jnp.einsum("bld,vd->blv", hidden[exit_],
                        f32["params"]["head"]["weight"])
    np.testing.assert_allclose(logits, f32["logits"][exit_], atol=2e-5)


def test_the_models_logits_are_the_last_exits(f32):
    logits, _ = f32["model"].apply(
        {"params": f32["params"]}, f32["tokens"],
        mutable=["losses", "counters", "exits"])
    np.testing.assert_allclose(logits, f32["logits"][-1], atol=2e-5)


def test_float32_exit_distribution_equals_reference(f32, f32_forward):
    _, sown = f32_forward
    p = sown["exits"]["weight"][0]
    np.testing.assert_allclose(p, f32["p"], atol=2e-6)
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    assert float(jnp.min(p)) > 0.0


def test_float32_loss_and_each_exits_own_equal_reference(f32, f32_step):
    metrics, _ = f32_step
    assert float(metrics["loss"]) == pytest.approx(float(f32["loss"]),
                                                   abs=2e-5)
    for t in range(PASSES):
        assert float(metrics[f"loss_exit_{t + 1}"]) == pytest.approx(
            float(f32["exit_ce"][t]), abs=2e-5)
        assert float(metrics[f"exit_p_{t + 1}"]) == pytest.approx(
            float(jnp.mean(f32["p"][t])), abs=2e-6)


@pytest.mark.parametrize("path", _leaf_paths(jax.eval_shape(
    _model().init, jax.random.PRNGKey(0), _tokens())["params"]))
def test_float32_gradient_of_every_leaf_equals_reference(f32, f32_step, path):
    """A block's leaves get four passes' sum; the gate's come through ``p``
    alone, ``norm_f``'s through every pass and every exit."""
    _, grads = f32_step
    got, want = grads, f32["grads"]
    for key in path.split("/"):
        got, want = got[key], want[key]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, "a leaf no gradient reaches tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-3 * scale + 2e-7)


# --------------------------------------------------------------- the loop

def test_four_passes_are_the_one_pass_stack_applied_four_times(f32,
                                                               f32_forward):
    hidden, _ = f32_forward
    params, config = f32["params"], f32["model"].config
    x = params["embed"]["embedding"][f32["tokens"]]
    for t in range(PASSES):
        for i in range(LAYERS):
            x = DecoderBlock(config).apply(
                {"params": params[f"layer_{i}"]}, x)
        x = RMSNorm(config.rms_norm_eps).apply(
            {"params": params["norm_f"]}, x)
        np.testing.assert_allclose(hidden[t], x, atol=2e-5)
    # the one-pass model is that stack, once, and has no gate
    once = _model(total_ut_steps=1)
    single = {k: v for k, v in params.items() if k != "exit_gate"}
    np.testing.assert_allclose(
        once.apply({"params": single}, f32["tokens"], return_hidden=True),
        hidden[0], atol=2e-5)


def test_parameter_count_does_not_depend_on_the_passes():
    def count(passes):
        model = _model(total_ut_steps=passes)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), _tokens())
        # no non-gradient state: what else ``init`` returns was sown
        assert model.state_collection is None and "router" not in shapes
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(shapes["params"]))

    d, w, v = (PRESET[k] for k in ("hidden_size", "intermediate_size",
                                   "vocab_size"))
    stack = LAYERS * (4 * d * d + 3 * d * w + 4 * d) + 2 * v * d + d
    assert count(1) == stack
    assert count(2) == count(4) == count(8) == stack + d + 1


def test_step_counts_passes_times_layers_block_applications(f32, f32_step):
    metrics, _ = f32_step
    assert int(metrics["block_applications"]) == PASSES * LAYERS
    assert set(f32["model"].counter_names) <= set(metrics)
    assert "routed_here" not in metrics


def test_step_reports_no_attention_blocks_on_the_dense_path(f32, f32_step):
    """The CPU's ``auto`` policy takes explicit scores: no block schedule."""
    metrics, _ = f32_step
    assert f32["model"].counter_names[:2] == (
        "attn_blocks_visited", "attn_blocks_masked")
    assert int(metrics["attn_blocks_visited"]) == 0
    assert int(metrics["attn_blocks_masked"]) == 0


@pytest.mark.parametrize("passes,blocks,counts", [
    (1, (16, 32), (6, 4, 0)),
    (2, (16, 32), (6, 4, 0)),
    (1, (32, 32), (3, 2, 12)),   # square: diagonal blocks in 8-row sub-tiles
])
def test_step_reports_the_schedules_blocks_under_flash(monkeypatch, passes,
                                                       blocks, counts):
    """``attn_impl="flash"`` (the Pallas interpreter here) at L = 64: the
    step's counters are the schedule's counts, for a looped decoder and for
    a plain one, and its loss is the dense path's."""
    from pytorch_distributed_tpu.models import decoder
    from pytorch_distributed_tpu.ops.flash_attention import blocks_visited

    monkeypatch.setattr(decoder, "FLASH_BLOCKS", blocks)
    assert blocks_visited(L, *blocks) == counts
    over = dict(total_ut_steps=passes, num_hidden_layers=1,
                layer_types=["full_attention"])
    tokens, tx = _tokens(), optax.sgd(1.0)
    params = _init(_model(**over))
    metrics = {}
    for impl in ("flash", "dense"):
        model = DecoderLM(DecoderConfig.from_dict({**PRESET, **over}),
                          attn_impl=impl)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            step = make_lm_train_step(
                model, data_parallel_mesh(jax.devices()[:1]),
                replicated_like(params), tx=tx, params=params,
                fused_ce_chunks=CHUNKS)
        state = TrainState.create({"params": params}, tx.init(params))
        _, metrics[impl] = step(jax.tree_util.tree_map(jnp.copy, state),
                                tokens, jnp.float32(0.0))
    names = ("attn_blocks_visited", "attn_blocks_masked",
             "attn_subtiles_skipped")
    assert tuple(int(metrics["flash"][name]) for name in names) == counts
    assert int(metrics["dense"]["attn_blocks_visited"]) == 0
    np.testing.assert_allclose(metrics["flash"]["loss"],
                               metrics["dense"]["loss"], atol=1e-4)


# ------------------------------------------------------ the loss over exits

def test_fused_weighted_loss_equals_the_unfused_sum_over_exits(f32, f32_step):
    """Value and gradient of the step's one fused call over all exits'
    rows against four plain cross-entropies weighted by ``p`` (which is
    nowhere 1), the model's own forward on both sides."""
    model, tokens = f32["model"], f32["tokens"]

    def unfused(params):
        hidden, sown = model.apply(
            {"params": params}, tokens, return_hidden=True,
            mutable=["losses", "exits"])
        p = sown["exits"]["weight"][0]
        logits = jnp.einsum("tbld,vd->tblv", hidden,
                            params["head"]["weight"])
        logp = jax.nn.log_softmax(logits[:, :, :-1], -1)
        ce = -jnp.take_along_axis(
            logp, jnp.broadcast_to(tokens[:, 1:], (PASSES, B, L - 1))[
                ..., None], -1)[..., 0]
        return (jnp.mean(jnp.sum(p[:, :, :-1] * ce, 0))
                + sown["losses"]["exit_entropy"][0])

    want, want_grads = jax.value_and_grad(unfused)(f32["params"])
    metrics, grads = f32_step
    assert float(metrics["loss"]) == pytest.approx(float(want), abs=2e-5)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, atol=2e-3 * float(jnp.max(jnp.abs(b))) + 2e-7)


@pytest.mark.parametrize("bias,exit_", [(20.0, 0), (-20.0, PASSES - 1)],
                         ids=["leaves_at_once", "stays_to_the_end"])
def test_a_saturated_gate_gives_one_exits_cross_entropy(f32, sgd_step, bias,
                                                        exit_):
    params = jax.tree_util.tree_map(lambda x: x, f32["params"])
    params["exit_gate"] = {"kernel": jnp.zeros_like(
        params["exit_gate"]["kernel"]), "bias": jnp.full((1,), bias)}
    metrics, grads = sgd_step(params)
    assert float(metrics["loss"]) == pytest.approx(
        float(metrics[f"loss_exit_{exit_ + 1}"]), abs=1e-5)
    assert float(metrics[f"exit_p_{exit_ + 1}"]) == pytest.approx(1.0,
                                                                  abs=1e-6)
    assert float(metrics["exit_entropy"]) == pytest.approx(0.0, abs=1e-5)
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))


def test_exit_distribution_and_entropy_against_numpy():
    z = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (PASSES, 5, 7)),
                   np.float64) * 3.0
    lam = 1.0 / (1.0 + np.exp(-z))
    want = np.empty_like(lam)
    stay = np.ones_like(lam[0])
    for t in range(PASSES - 1):
        want[t] = lam[t] * stay
        stay = stay * (1.0 - lam[t])
    want[-1] = stay
    p, entropy = exit_distribution(jnp.asarray(z, jnp.float32))
    np.testing.assert_allclose(p, want, atol=1e-6)
    np.testing.assert_allclose(want.sum(0), 1.0, atol=1e-12)
    np.testing.assert_allclose(entropy, -(want * np.log(want)).sum(0),
                               atol=1e-5)
    ref_p, ref_entropy = ref.exit_distribution(jnp.asarray(z, jnp.float32))
    np.testing.assert_allclose(ref_p, want, atol=1e-6)
    np.testing.assert_allclose(ref_entropy, entropy, atol=1e-5)


# ------------------------------------------------------------ precision

def _against_reference(model, params, f32):
    """``ref.agreement`` for ``model``'s policy on ``params``, against the
    reference on the fixture's float32 weights."""
    tokens = f32["tokens"]

    def reference(p):
        loss, (s, prob, _) = ref.objective(PRESET, p, tokens)
        return loss, (s, prob)

    with jax.default_matmul_precision("highest"):
        (want_loss, (want_s, want_p)), want_grads = jax.value_and_grad(
            reference, has_aux=True)(f32["params"])

    def program(p):
        hidden, sown = model.apply(
            {"params": p}, tokens, return_hidden=True,
            mutable=["losses", "exits"])
        prob = sown["exits"]["weight"][0]
        rows = hidden.astype(model.dtype)
        head = p["head"]["weight"].astype(model.dtype)
        logits = jnp.einsum("tbld,vd->tblv", rows, head,
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits[:, :, :-1], -1)
        ce = -jnp.take_along_axis(
            logp, jnp.broadcast_to(tokens[:, 1:], (PASSES, B, L - 1))[
                ..., None], -1)[..., 0]
        loss = (jnp.mean(jnp.sum(prob[:, :, :-1] * ce, 0))
                + sown["losses"]["exit_entropy"][0])
        return loss, (rows, head, prob)

    (loss, (rows, head, prob)), grads = jax.value_and_grad(
        program, has_aux=True)(params)
    worst, top = ref.logits_error(rows, head, want_s,
                                  f32["params"]["head"]["weight"],
                                  row_block=16)
    out = ref.agreement(worst, top, prob, want_p, loss, want_loss,
                        ref.grad_leaves(grads, LAYERS),
                        ref.grad_leaves(want_grads, LAYERS))
    out = {k: float(v) for k, v in out.items()}
    out["ok"] = ref.within_tolerance(out, slack=2.0)  # the preset's
    return out


def test_bf16_policy_is_inside_and_8bit_weights_outside_the_tolerance(f32):
    model = _model(jnp.bfloat16)
    good = _against_reference(model, f32["params"], f32)
    assert good["ok"], good
    coarse = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), f32["params"])
    bad = _against_reference(model, coarse, f32)
    assert not bad["ok"], bad


def test_blocked_reference_equals_the_unblocked(f32):
    """``q_block`` and ``row_block`` change memory, not numbers."""
    with jax.default_matmul_precision("highest"):
        loss, (s, p, _) = ref.objective(PRESET, f32["params"], f32["tokens"],
                                        q_block=16, row_block=20)
    assert float(loss) == pytest.approx(float(f32["loss"]), abs=1e-5)
    np.testing.assert_allclose(p, f32["p"], atol=1e-6)


# ------------------------------------------------------------ the MFU line

def test_the_trainers_cost_counts_applications_as_the_benchmarks_file():
    """``obs/flops.lm_step_cost_for`` (``LMTrainer``'s MFU line) against
    ``benchmark/flops_ouro.py`` at the preset: passes x layers block
    applications and a head after every pass, one set of parameters."""
    import importlib.util

    from pytorch_distributed_tpu.obs.flops import lm_step_cost_for

    spec = importlib.util.spec_from_file_location(
        "flops_ouro", os.path.join(ROOT, "benchmark", "flops_ouro.py"))
    counts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(counts)
    cfg = {**PRESET, "training": {**PRESET["training"], "seq_len": L}}
    cost = lm_step_cost_for(_model(), B, L, fused_ce_chunks=CHUNKS)
    assert cost.breakdown["forward"] + cost.breakdown["backward"] == (
        pytest.approx(counts.train_flops_per_item(cfg) * B * L, rel=1e-12))
    d, w, v = (PRESET[k] for k in ("hidden_size", "intermediate_size",
                                   "vocab_size"))
    block = 2.0 * (4 * d * d + 3 * d * w) + 2.0 * 4 * (16 + 16) * L / 2
    assert counts.forward_flops_per_token(cfg) == PASSES * (
        LAYERS * block + 2.0 * d * v)
    # what runs twice: every block application (remat), and no head (the
    # fused loss takes its gradient in the pass that has the logits)
    assert cost.breakdown["recompute"] == pytest.approx(
        cost.breakdown["forward"] - B * L * PASSES * 2.0 * d * v)
    assert cost.breakdown["recompute"] == pytest.approx(
        B * L * PASSES * LAYERS * block)
    once = lm_step_cost_for(_model(total_ut_steps=1), B, L)
    assert cost.params == once.params + d + 1
    assert cost.breakdown["forward"] == pytest.approx(
        PASSES * once.breakdown["forward"])


# ----------------------------------------------------------- what is refused

@pytest.mark.parametrize("key,value", [
    ("num_key_value_heads", 2), ("use_sliding_window", True),
    ("sliding_window", 4096), ("attention_bias", True),
    ("lm_head_bias", True), ("norm_placement", "post"),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"]),
    # the scan over the passes carries no routing state
    ("n_routed_experts", 8)])
def test_from_dict_refuses_by_name_what_it_lacks(key, value):
    with pytest.raises(ValueError, match=key):
        DecoderConfig.from_dict({**PRESET, key: value})


def test_several_exits_need_the_fused_loss():
    model = _model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            _tokens())["params"]
    mesh = data_parallel_mesh(jax.devices()[:1])
    with pytest.raises(ValueError, match="several exits"):
        make_lm_train_step(model, mesh, replicated_like(params))


# ------------------------------------------------- what must not have moved

def _digest(step, state, tokens):
    text = step.lower(state, tokens, jnp.float32(0.0)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_the_kimi_presets_step_lowers_as_before():
    """The configured decoder with latent attention and experts, bf16,
    AdamW, the fused loss: the lowered text's digest.  It held through PR
    30 and through PR 31's kernels (``66cff231...``: the CPU takes the
    dense path); PR 31's two counters, ``attn_blocks_visited`` and
    ``attn_blocks_masked`` among the step's metrics, then moved it, and PR
    33's fused loss (one loop under differentiation, the mean's 1 / ntok
    in the rows' weights; the returned hidden rows behind a barrier) again,
    and PR 34 by taking ``loss_head_products`` out of the step's metrics
    (one scalar result fewer; the scope names PR 34 adds are metadata and
    not in this text), and the third attention counter,
    ``attn_subtiles_skipped``."""
    from test_decoder import PRESET as KIMI

    model = DecoderLM(DecoderConfig.from_dict(KIMI), dtype=jnp.bfloat16)
    tokens = jnp.zeros((2, 64), jnp.int32)
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
    assert _digest(step, state, tokens) == (
        "20fa27088359a5b56305d79e632ef4c0024076b2ac117fd6b1856a246ecc8d23")


def test_the_transformer_lms_fused_step_lowers_as_before():
    """``TransformerLM`` through the fused loss (the path the exits
    share): the digest the commit before PR 30 gave it held until PR 33's
    fused loss moved it, and PR 34's one result fewer (both as above)."""
    from pytorch_distributed_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=128, d_model=32, n_heads=2, n_layers=2)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            tokens)["params"]
    state = jax.eval_shape(
        lambda p: TrainState.create({"params": p}, sgd_init(p)), params)
    mesh = data_parallel_mesh(jax.devices()[:1])
    step = make_lm_train_step(model, mesh, replicated_like(params),
                              fused_ce_chunks=2)
    assert _digest(step, state, tokens) == "9ba4b43584e083d1f425e0b046c2b8ee893e9fd47aee2c9fca9391e7e4af122e"


def test_the_two_copies_of_the_reference_are_identical():
    with open(os.path.join(ROOT, "tests", "reference_ouro.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(ROOT, "benchmark", "reference", "ouro.py"),
              "rb") as f:
        assert f.read() == mine
