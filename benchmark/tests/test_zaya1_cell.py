"""The ZAYA1 cell's own files: the runner's step against the recipe's, the
FLOPs count against the compiler's, the grouped kernels' cost against hand
counts, and the comparison with the reference (which must refuse 8-bit
weights and a lost expert)."""

import os

import jax
import jax.numpy as jnp
import pytest

import harness
from conftest import BENCH

CONFIG = os.path.join(BENCH, "configs", "zaya1-8b-ep2.json")
TRAFFIC = os.path.join(BENCH, "traffic",
                       "resident-lm-2x8192-zipf-routed.json")


def _overlaid(path):
    from pytorch_distributed_tpu.models.decoder import overlay

    base = harness.load_json(path)
    return overlay(base, base.pop("rehearse"))


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """64 pairs a pass of the grouped products, so that the preset's 512
    pairs a layer run the loops several passes deep (and the CPU compiler,
    which counts a grouped product as every group over every row of a
    pass, and a loop's body once, counts about the rows there are)."""
    from pytorch_distributed_tpu.models import moe

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "GMM_CHUNK_ROWS", 64)
        yield


@pytest.fixture(scope="module")
def preset():
    runner = harness.load_module(BENCH + "/runners/lm_top1_resident_step.py")
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


def test_flops_count_against_the_compilers_at_the_preset(preset, built):
    """``flops_zaya1.train_flops_per_item`` against the compiled step's own
    ``cost_analysis``.  The compiler counts what the chip executes: the
    rematerialised forward a second time, the fused loss's recomputed
    logits, AdamW, softmaxes, norms, the depthwise taps; the preset's dense
    attention computes the whole square; and the CPU compiler counts a
    loop's body once and a grouped product as every group over every row
    of a pass, which at 8 groups and 64 rows a pass is every token of the
    layer: a held share of 1.  Each is added here from the file's sizes;
    what is left is elementwise work, a fifth at 64 channels."""
    runner, cfg, traffic = preset
    _, _, _, state, step, batch = built
    counts = harness.load_module(BENCH + "/flops_zaya1.py")
    tokens = batch.size
    compiled = step.lower(state, batch,
                          jnp.float32(1e-3)).compile().cost_analysis()
    _, metrics = step(jax.tree_util.tree_map(jnp.copy, state), batch,
                      jnp.float32(1e-3))
    # the step's counters and the count's parameter speak of one share
    assert float(metrics["rows_grouped"]) == pytest.approx(
        float(metrics["held_share_pct"]) / 100.0 * tokens
        * cfg["num_hidden_layers"])
    d, h, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["head_dim"])
    forward = counts.forward_flops_per_token(cfg, held_share=1.0) * tokens
    # dense attention computes the other half of the square too
    forward += 2.0 * h * (hd + hd) * (
        traffic["seq_len"] / 2) * tokens * cfg["num_hidden_layers"]
    head = 2.0 * d * cfg["vocab_size"] * tokens
    executed = 3.0 * forward + (forward - head) + head  # remat, fused loss
    assert executed < compiled["flops"] < 1.3 * executed
    assert counts.train_flops_per_item(cfg) == pytest.approx(
        3.0 * counts.forward_flops_per_token(cfg))


def test_forward_count_at_the_published_widths():
    """0.704 GFLOP a token forward, 0.537 of them the tied head's."""
    cfg = harness.load_json(CONFIG)
    counts = harness.load_module(BENCH + "/flops_zaya1.py")
    assert counts.forward_flops_per_token(cfg) == pytest.approx(
        704.4e6, rel=1e-3)
    assert 2.0 * 2048 * 131136 / counts.forward_flops_per_token(
        cfg) == pytest.approx(0.7625, rel=1e-3)
    # every token on a held expert, and none
    assert counts.forward_flops_per_token(cfg, held_share=1.0) - (
        counts.forward_flops_per_token(cfg, held_share=0.0)) == (
            4 * 2.0 * 3 * 2048 * 2048)


def test_the_programs_count_is_the_benchmarks():
    from pytorch_distributed_tpu.models.decoder import DecoderConfig
    from pytorch_distributed_tpu.obs.flops import decoder_step_cost

    cfg = harness.load_json(CONFIG)
    counts = harness.load_module(BENCH + "/flops_zaya1.py")
    cost = decoder_step_cost(DecoderConfig.from_dict(cfg), 2, 8192,
                             fused_ce=True)
    assert cost.breakdown["forward"] + cost.breakdown["backward"] == (
        pytest.approx(counts.train_flops_per_item(cfg) * 2 * 8192,
                      rel=1e-12))
    assert cost.params == 696248072


# ------------------------------------------------------ the grouped kernels

def test_grouped_cost_against_hand_counts():
    """The cell's call: 2 sequences of 8,192, 8 query heads over 2
    key-value heads of 128, bf16."""
    cost = harness.load_module(BENCH + "/attention_cost_gqa.py")
    cfg = harness.load_json(CONFIG)
    assert cost.head_counts(cfg) == (8, 2, 128)
    square = 2 * 8 * 8192 * 8192 / 2          # causal: half of it
    q_rows, kv_rows = 2 * 8 * 8192, 2 * 2 * 8192
    flops, moved = cost.call_cost("fwd", 2, 8, 2, 8192, 128)
    assert flops == 2.0 * square * 2 * 128    # S = Q K^T and O = P V
    assert moved == (q_rows * 128 * 2 + 2 * kv_rows * 128 * 2   # Q, K, V
                     + q_rows * 128 * 2 + q_rows * 4)           # O, lse
    flops, moved = cost.call_cost("dq", 2, 8, 2, 8192, 128)
    assert flops == 2.0 * square * 3 * 128    # S, dP, dQ
    assert moved == (q_rows * 128 * 2 + 2 * kv_rows * 128 * 2
                     + q_rows * 128 * 2 + 2 * q_rows * 4
                     + q_rows * 128 * 2)                         # dQ out
    flops, moved = cost.call_cost("dkv", 2, 8, 2, 8192, 128)
    assert flops == 2.0 * square * 4 * 128    # S, dP, dV, dK
    assert moved == (q_rows * 128 * 2 + 2 * kv_rows * 128 * 2
                     + q_rows * 128 * 2 + 2 * q_rows * 4
                     + 2 * kv_rows * 128 * 2)            # dK, dV: G heads
    # as many key-value heads as query heads: attention_cost.py's counts
    plain = harness.load_module(BENCH + "/attention_cost.py")
    for kind in ("fwd", "dq", "dkv"):
        assert cost.call_cost(kind, 2, 16, 16, 8192, 128) == (
            plain.call_cost(kind, 32, 8192, 128, 128))
    peaks = harness.load_json(BENCH + "/peaks.json")["TPU v5 lite"]
    # compute-bound: 2 x 2^32 x 256 operations at the bf16 peak
    assert cost.floor_seconds("fwd", 2, 8, 2, 8192, 128, peaks) == (
        pytest.approx(2.0 * square * 256 / peaks["flops_per_s_bf16"]))


def test_grouped_call_kinds_from_a_captures_texts():
    cost = harness.load_module(BENCH + "/attention_cost_gqa.py")
    fwd = ("%attn.16 = (bf16[16,8192,128]{2,1,0:T(8,128)(2,1)}, "
           "f32[16,8192,128]{2,1,0:T(8,128)}) custom-call(s32[36]{0} %c)")
    dq = "%attn.21 = bf16[16,8192,128]{2,1,0:T(8,128)(2,1)} custom-call()"
    dkv = ("%attn.22 = (f32[16,8192,128]{2,1,0:T(8,128)}, "
           "f32[16,8192,128]{2,1,0:T(8,128)}) custom-call(s32[36]{0} %c)")
    assert [cost.call_kind(t) for t in (fwd, dq, dkv)] == [
        "fwd", "dq", "dkv"]
    assert cost.call_kind("%fusion.3 = bf16[16,8192,128]{2,1,0} fusion()"
                          ) is None


# --------------------------------------------------------- the comparison

def _check(preset, built, **kw):
    runner, cfg, traffic = preset
    model, _, _, state, _, batch = built
    return runner.reference_check(
        model, cfg, state.params, batch[:1], 7, traffic["reference_q_block"],
        traffic["reference_row_block"], slack=2.0, **kw)


def test_bf16_agrees_with_the_reference_and_8bit_weights_do_not(preset,
                                                                 built):
    good = _check(preset, built)
    assert good["ok"], good
    # the lean: the last layer routes its rows here, the one before none
    assert good["held_rows_last"] == good["held_rows_max"] > 0
    assert good["held_rows_min"] == 0
    bad = _check(preset, built, program_params=jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), built[3].params))
    assert not bad["ok"], bad


def test_one_held_expert_lost_fails_the_comparison(preset, built):
    """A fault that reaches the positions one expert serves: a held expert
    of the last layer returns nothing.  Whichever expert it is, if rows
    reach it the comparison refuses the program."""
    runner, cfg, traffic = preset
    state = built[3]
    last = f"layer_{cfg['num_hidden_layers'] - 1}"
    good = _check(preset, built)
    refused = 0
    for lost in range(cfg["num_experts"]):
        faulty = jax.tree_util.tree_map(lambda x: x, state.params)
        stack = faulty[last]["moe"]["experts"]
        stack["down_proj"] = stack["down_proj"].at[lost].set(0.0)
        bad = _check(preset, built, program_params=faulty)
        if bad["logits_max"] > 2.0 * good["logits_max"]:   # rows reached it
            refused += 1
            assert not bad["ok"], (lost, bad)
    assert refused >= 2


def test_zipf_ids_are_skewed_and_inside_the_vocabulary(preset, built):
    _, cfg, _ = preset
    batch = built[5]
    ids, counts = jnp.unique(batch, return_counts=True)
    assert int(ids.min()) >= 0 and int(ids.max()) < cfg["vocab_size"]
    # rank 1 of a Zipf(1) over 512 ids takes 1 / H_512 = 14.6% of the draws
    assert 0.08 < float(counts.max()) / batch.size < 0.25
