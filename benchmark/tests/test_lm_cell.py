"""The LM cell's own files: the runner's step against the recipe's, the
FLOPs count against the compiler's, the comparison with the reference, and
the train state's reader."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import harness
from conftest import BENCH

CONFIG = os.path.join(BENCH, "configs", "kimi-vl-a3b-ep8.json")
TRAFFIC = os.path.join(BENCH, "traffic", "resident-lm-4x8192-zipf.json")


def _overlaid(path):
    from pytorch_distributed_tpu.models.decoder import overlay

    base = harness.load_json(path)
    return overlay(base, base.pop("rehearse"))


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """64 pairs a pass of the grouped products, so that the preset's 1,536
    pairs a layer run the loops many passes deep (and the CPU compiler,
    which counts a grouped product as every group over every row of a
    pass, and a loop's body once, counts about the rows there are)."""
    from pytorch_distributed_tpu.models import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "GMM_CHUNK_ROWS", 64)
        yield


@pytest.fixture(scope="module")
def preset():
    runner = harness.load_module(BENCH + "/runners/lm_resident_step.py")
    return runner, _overlaid(CONFIG), _overlaid(TRAFFIC)


@pytest.fixture(scope="module")
def built(preset):
    from pytorch_distributed_tpu.parallel import data_parallel_mesh

    runner, cfg, traffic = preset
    mesh = data_parallel_mesh(jax.devices()[:1])
    with pytest.warns(UserWarning, match="tx provided"):
        model, tx = runner.build_model(cfg)
        state = runner.make_state(model, tx, mesh, 7)
        step = runner.make_step(model, mesh, cfg, tx, state.params)
    batch = runner.make_batch(cfg, traffic, mesh, 8, 7)
    return model, tx, mesh, state, step, batch


def test_same_lowered_program_as_the_recipe(preset, built, tmp_path):
    """``lm_pretrain --model-config <file> --rehearse`` builds, through
    ``LMTrainer``, the step the runner times."""
    from pytorch_distributed_tpu.recipes import lm_pretrain
    from pytorch_distributed_tpu.train.lm import LMTrainer

    runner, cfg, traffic = preset
    model, tx = built[0], built[1]
    held = {}

    def keep_instead_of_fit(self, steps, print_freq=10):
        held["trainer"] = self
        return 0.0

    fit, LMTrainer.fit = LMTrainer.fit, keep_instead_of_fit
    try:
        lm_pretrain.main([
            "--model-config", CONFIG, "--rehearse", "--seq-len",
            str(traffic["seq_len"]), "-b", "8", "--seed", "7", "--no-eval",
            "--dataset-length", "16"])
    finally:
        LMTrainer.fit = fit
    trainer = held["trainer"]
    mesh = trainer.mesh  # the recipe spans every device it finds
    state = runner.make_state(model, tx, mesh, 7)
    with pytest.warns(UserWarning, match="tx provided"):
        step = runner.make_step(model, mesh, cfg, tx, state.params)
    batch = runner.make_batch(cfg, traffic, mesh, 8, 7)
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(trainer.state))
    lr = jnp.float32(cfg["optimizer"]["lr"])
    mine = step.lower(state, batch, lr).as_text()
    theirs = trainer.step_fn.lower(trainer.state, batch, lr).as_text()
    assert mine == theirs


def test_flops_count_equals_the_compilers_at_the_preset(preset, built):
    """``flops_kimi_vl_a3b.train_flops_per_item`` against the compiled
    step's own ``cost_analysis``, the routed experts at the rows the step
    measured instead of the uniform expectation.  The compiler counts what
    the chip executes: the rematerialised forward a second time, the fused
    loss's recomputed logits, AdamW, softmax and norms; the preset's dense
    attention computes the whole square.  Each is added here from the
    file's sizes, and what is left (elementwise work) is a few percent."""
    runner, cfg, traffic = preset
    _, _, _, state, step, batch = built
    counts = harness.load_module(BENCH + "/flops_kimi_vl_a3b.py")
    tokens = batch.size
    compiled = step.lower(state, batch,
                          jnp.float32(1e-3)).compile().cost_analysis()
    _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch,
                      jnp.float32(1e-3))
    rows = float(metrics["rows_grouped"])
    d, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    expert_layers = layers - cfg["first_k_dense_replace"]
    uniform = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
               / cfg["deployment"]["n_routed_experts"])
    forward = counts.forward_flops_per_token(cfg) * tokens
    # measured rows in place of the uniform expectation
    forward += 2.0 * 3 * d * width * (rows - uniform * tokens * expert_layers)
    # dense attention computes the other half of the square too
    forward += 2.0 * h * (qk + cfg["v_head_dim"]) * (
        traffic["seq_len"] / 2) * tokens * layers
    head = 2.0 * d * cfg["vocab_size"] * tokens
    executed = 3.0 * forward + (forward - head) + head  # remat, fused loss
    assert compiled["flops"] == pytest.approx(executed, rel=0.12)
    assert counts.train_flops_per_item(cfg) == pytest.approx(
        3.0 * counts.forward_flops_per_token(cfg))


def test_forward_count():
    cfg = harness.load_json(CONFIG)
    counts = harness.load_module(BENCH + "/flops_kimi_vl_a3b.py")
    assert counts.forward_flops_per_token(cfg) == pytest.approx(
        878.3e6, rel=1e-3)


def test_bf16_agrees_with_the_reference_and_8bit_weights_do_not(preset,
                                                                 built):
    runner, cfg, traffic = preset
    model, _, _, state, _, batch = built
    good = runner.reference_check(model, cfg, state.params, batch[:1], 7,
                                  traffic["reference_q_block"], slack=2.0)
    assert good["ok"], good
    assert good["tied_share"] < 0.5
    bad = runner.reference_check(
        model, cfg, state.params, batch[:1], 7,
        traffic["reference_q_block"], slack=2.0,
        program_params=jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype),
            state.params))
    assert not bad["ok"], bad


def test_one_held_expert_lost_fails_the_comparison(preset, built):
    """A fault that reaches a minority of the positions: the last layer's
    first held expert returns nothing.  The medians hardly move; the
    largest error over the positions clear of ties does."""
    runner, cfg, traffic = preset
    model, _, _, state, _, batch = built
    last = f"layer_{cfg['num_hidden_layers'] - 1}"
    faulty = jax.tree_util.tree_map(lambda x: x, state.params)
    stack = faulty[last]["moe"]["experts"]
    stack["down_proj"] = stack["down_proj"].at[0].set(0.0)
    bad = runner.reference_check(
        model, cfg, state.params, batch[:1], 7,
        traffic["reference_q_block"], slack=2.0, program_params=faulty)
    assert not bad["ok"], bad
    assert bad["logits_max"] > 2.0 * bad["tolerance"]["logits_max"], bad


def test_zipf_ids_are_skewed_and_inside_the_vocabulary(preset, built):
    _, cfg, _ = preset
    batch = built[5]
    ids, counts = jnp.unique(batch, return_counts=True)
    assert int(ids.min()) >= 0 and int(ids.max()) < cfg["vocab_size"]
    # rank 1 of a Zipf(1) over 512 ids takes 1 / H_512 = 14.6% of the draws
    assert 0.08 < float(counts.max()) / batch.size < 0.25


# ---------------------------------------------------------------- reader

def test_train_state_reader():
    reader = harness.load_module(f"{BENCH}/layer_metrics/train_state_gb.py")
    run = types.SimpleNamespace(notes={"batch": 32768,
                                       "state_bytes": 8026682632})
    assert reader.read(types.SimpleNamespace(run=run)) == pytest.approx(
        8.026682632)
    # the parent's program, or an image cell: no such note, no metric
    run = types.SimpleNamespace(notes={"batch": 256})
    assert reader.read(types.SimpleNamespace(run=run)) is None
