"""The looped LM cell's own files: the runner's step against the recipe's,
the FLOPs count against the program's and the compiler's, the comparison
with the reference, the attention kernels' counts and their reader."""

import os
import types

import jax
import jax.numpy as jnp
import pytest

import attention_cost
import harness
from conftest import BENCH

CONFIG = os.path.join(BENCH, "configs", "ouro-2.6b.json")
TRAFFIC = os.path.join(BENCH, "traffic", "resident-lm-2x8192-zipf.json")
PEAKS = harness.load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]


def _overlaid(path):
    from pytorch_distributed_tpu.models.decoder import overlay

    base = harness.load_json(path)
    return overlay(base, base.pop("rehearse"))


@pytest.fixture(scope="module")
def preset():
    runner = harness.load_module(BENCH + "/runners/lm_exits_resident_step.py")
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
    batch = runner.make_batch(cfg, traffic, mesh, 2, 7)
    return model, tx, mesh, state, step, batch


def test_same_lowered_program_as_the_recipe(preset, built):
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


def test_flops_count_equals_the_programs_and_the_published_arithmetic(preset):
    from pytorch_distributed_tpu.models.decoder import DecoderConfig, DecoderLM
    from pytorch_distributed_tpu.obs.flops import lm_step_cost_for

    counts = harness.load_module(BENCH + "/flops_ouro.py")
    full = harness.load_json(CONFIG)
    assert counts.train_flops_per_item(full) == 12_230_590_464
    for cfg in (full, preset[1]):
        seq = cfg["training"]["seq_len"]
        cost = lm_step_cost_for(
            DecoderLM(DecoderConfig.from_dict(cfg)), 2, seq,
            cfg["training"]["fused_ce_chunks"])
        assert (cost.breakdown["forward"] + cost.breakdown["backward"]
                == pytest.approx(counts.train_flops_per_item(cfg) * 2 * seq,
                                 rel=1e-12))
    assert cost.params == 216_961
    assert lm_step_cost_for(DecoderLM(DecoderConfig.from_dict(full)), 2,
                            8192).params == 509_661_185


def test_flops_count_equals_the_compilers_for_the_unrolled_reference(preset):
    """``flops_ouro.train_flops_per_item`` against the compiler's count of
    the plain reference's value and gradient at the preset, its passes
    laid out one after another (``unroll``: the compiler counts a loop's
    body once), nothing looped in the program.  The compiler
    counts what runs: each checkpointed block application a second time,
    the whole square of the dense attention; both are added here from the
    file's sizes, and what is left (norms, softmax, gate) is a few
    percent."""
    _, cfg, traffic = preset
    counts = harness.load_module(BENCH + "/flops_ouro.py")
    ref = harness.load_module(BENCH + "/reference/ouro.py")
    seq = traffic["seq_len"]
    tokens = jnp.zeros((2, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: harness.load_module(
            BENCH + "/runners/lm_exits_resident_step.py").build_model(cfg)[
                0].init(jax.random.PRNGKey(0), tokens)["params"])
    compiled = jax.jit(jax.value_and_grad(
        lambda p, t: ref.objective(cfg, p, t, unroll=True)[0])).lower(
            params, tokens).compile().cost_analysis()
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    forward = counts.forward_flops_per_token(cfg) * tokens.size
    # dense attention computes the other half of the square too
    square = 2.0 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (
        seq / 2) * tokens.size * applications
    forward += square
    head = cfg["total_ut_steps"] * 2.0 * cfg["hidden_size"] * cfg[
        "vocab_size"] * tokens.size
    executed = 3.0 * forward + (forward - head)  # + the checkpointed blocks
    assert compiled["flops"] == pytest.approx(executed, rel=0.12)


def _eight_bit(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype), params)


def _check(preset, built, step=None, **kw):
    """The runner's comparison of ``step`` (default: the cell's) on a state
    of its own (the step donates it), at the preset's slack."""
    runner, cfg, traffic = preset
    model, tx, mesh, _, real, batch = built
    _, out = runner.reference_check(
        model, cfg, runner.make_state(model, tx, mesh, 7), batch,
        step or real, traffic["reference_q_block"],
        traffic["reference_row_block"], slack=2.0, **kw)
    return out


def _outside(out, slack=2.0):
    return {k for k, limit in out["tolerance"].items()
            if out[k] > slack * limit}


def test_the_timed_step_agrees_with_the_reference(preset, built):
    good = _check(preset, built)
    assert good["ok"] and not _outside(good), good


def test_8bit_weights_do_not_agree(preset, built):
    bad = _check(preset, built, coarse=_eight_bit)
    assert not bad["ok"], bad
    # float32 on both sides: the weights' precision does not reach it
    assert "update_rel" not in _outside(bad), bad


def _half_the_batch(step, cfg):
    """The step trains on the first sequence alone."""
    return lambda state, batch, lr: step(
        state, jnp.concatenate([batch[:1], batch[:1]]), lr)


def _weights_left_unchanged(step, cfg):
    """The step reports its loss and keeps its moments, and hands back the
    weights it was given."""
    def faulty(state, batch, lr):
        kept = jax.tree_util.tree_map(jnp.copy, state.params)
        state, metrics = step(state, batch, lr)
        return state.replace(params=kept), metrics
    return faulty


def _ten_times_the_decay(step, cfg):
    """AdamW's decay at ten times the file's, added after the step."""
    def faulty(state, batch, lr):
        kept = jax.tree_util.tree_map(jnp.copy, state.params)
        state, metrics = step(state, batch, lr)
        decay = 9.0 * lr * cfg["optimizer"]["weight_decay"]
        return state.replace(params=jax.tree_util.tree_map(
            lambda new, old: new - decay * old if old.ndim >= 2 else new,
            state.params, kept)), metrics
    return faulty


@pytest.mark.parametrize("fault,caught_by", [
    (_half_the_batch, "grad_rel/layer_0/attn/q_proj/kernel"),
    (_weights_left_unchanged, "update_rel"),
    (_ten_times_the_decay, "update_rel"),
])
def test_a_faulty_step_fails_the_comparison(preset, built, fault, caught_by):
    """Faults of the step and not of the model: the model's forward pass
    agrees in each, so only what the timed step returns can tell."""
    bad = _check(preset, built, fault(built[4], preset[1]))
    assert not bad["ok"], bad
    outside = _outside(bad)
    assert caught_by in outside, bad
    assert not {"logits_max", "p_max"} & outside, bad


def test_one_pass_skipped_fails_the_comparison(preset, built):
    """A fault only a looped model can have: the step runs three passes
    where the file says four (and reports the third exit's loss twice)."""
    import dataclasses

    runner, cfg, _ = preset
    model, tx, mesh, state = built[:4]
    short = model.clone(config=dataclasses.replace(
        model.config, total_ut_steps=3))
    with pytest.warns(UserWarning, match="tx provided"):
        step = runner.make_step(short, mesh, cfg, tx, state.params)

    def faulty(state, batch, lr):
        state, metrics = step(state, batch, lr)
        return state, {**metrics, "loss_exit_4": metrics["loss_exit_3"]}

    bad = _check(preset, built, faulty)
    assert not bad["ok"], bad
    assert "loss_abs" in _outside(bad), bad


# ------------------------------------------------------ attention's counts

def test_attention_call_costs_against_hand_counts():
    """The cell's calls: 32 batch-heads, 8,192 positions, heads of 128."""
    bh, seq, d = 32, 8192, 128
    half = bh * seq * seq // 2
    rows = bh * seq
    assert attention_cost.call_cost("fwd", bh, seq, d, d) == (
        2.0 * half * 2 * d, rows * (4 * d * 2 + 4))
    assert attention_cost.call_cost("dq", bh, seq, d, d) == (
        2.0 * half * 3 * d, rows * (5 * d * 2 + 8))
    assert attention_cost.call_cost("dkv", bh, seq, d, d) == (
        2.0 * half * 4 * d, rows * (6 * d * 2 + 8))
    # 549.8 GFLOP a forward call: 2.79 ms at the chip's peak, and bound by
    # compute (its 268 MB take 0.33 ms)
    assert attention_cost.floor_seconds(
        "fwd", bh, seq, d, d, PEAKS) == pytest.approx(2.791e-3, rel=1e-3)
    # the other LM's heads: 192 wide for scores, 128 for values
    kimi = harness.load_json(os.path.join(BENCH, "configs",
                                          "kimi-vl-a3b-ep8.json"))
    assert attention_cost.head_sizes(kimi) == (192, 128)
    assert attention_cost.head_sizes(harness.load_json(CONFIG)) == (128, 128)
    assert attention_cost.call_cost("fwd", 64, seq, 192, 128)[0] == (
        2.0 * 64 * seq * seq / 2 * 320)


FWD = ("%attn.7 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, "
       "f32[32,8192,128]{2,1,0:T(8,128)}) custom-call(%a, %b, %c), "
       "custom_call_target=\"tpu_custom_call\"")
DQ = ("%attn.9 = bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} custom-call(%a)")
DKV = ("%attn.11 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, "
       "bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}) custom-call(%a)")


def test_call_kinds_from_instruction_texts():
    assert attention_cost.call_kind(FWD) == "fwd"
    assert attention_cost.call_kind(DQ) == "dq"
    assert attention_cost.call_kind(DKV) == "dkv"
    assert attention_cost.results(FWD)[0] == ("bf16", 32, 8192, 128)
    for other in ("%fusion.51 = bf16[32,8192,128]{2,1,0} fusion(%x)",
                  "%vit_attn_fwd.3 = bf16[32,8192,128]{2,1,0} custom-call()",
                  "%attn_norm.2 = f32[2,8192,2048]{2,1,0} fusion(%x)"):
        assert attention_cost.call_kind(other) is None


def _view(ops, tmp_path, monkeypatch, steps=((1.0, 4.0),)):
    """A view whose capture holds ``ops`` on one device."""
    import trace_reduce

    trace = {"devices": {0: {
        "modules": [(s, e, "jit_step(123)") for s, e in steps],
        "ops": ops, "async": []}},
        "host": [(0.5, 9.0, harness.WINDOW_SPAN, "python")]}
    monkeypatch.setattr(trace_reduce, "load", lambda path: trace)
    path = tmp_path / "capture.xplane.pb"
    path.write_bytes(b"")
    run = types.SimpleNamespace(trace_file=str(path),
                                notes={"step_program": "jit_step"})
    cell = types.SimpleNamespace(config=harness.load_json(CONFIG))
    return types.SimpleNamespace(run=run, peaks=PEAKS, cell=cell)


def test_roofline_reader_on_a_recorded_step(tmp_path, monkeypatch):
    reader = harness.load_module(
        f"{BENCH}/layer_metrics/flash_attn_roofline.py")
    floor = {k: attention_cost.floor_seconds(k, 32, 8192, 128, 128, PEAKS)
             for k in ("fwd", "dq", "dkv")}
    # one forward at twice its floor, one dq and one dk/dv at four times;
    # a fusion, and a forward outside any whole step, are not counted
    ops = [(1.0, 1.0 + 2 * floor["fwd"], FWD),
           (2.0, 2.0 + 4 * floor["dq"], DQ),
           (3.0, 3.0 + 4 * floor["dkv"], DKV),
           (3.5, 3.9, "%fusion.1 = bf16[32,8192,128]{2,1,0} fusion(%x)"),
           (5.0, 5.5, FWD)]
    view = _view(ops, tmp_path, monkeypatch)
    spent = 2 * floor["fwd"] + 4 * floor["dq"] + 4 * floor["dkv"]
    assert reader.read(view) == pytest.approx(
        100.0 * sum(floor.values()) / spent)


def test_roofline_reader_finds_nothing_without_the_kernels(tmp_path,
                                                           monkeypatch):
    reader = harness.load_module(
        f"{BENCH}/layer_metrics/flash_attn_roofline.py")
    view = _view([(1.0, 2.0, "%fusion.1 = bf16[4]{0} fusion(%x)")],
                 tmp_path, monkeypatch)
    assert reader.read(view) is None
    view.run.trace_file = None   # an untraced run, or the CPU's
    assert reader.read(view) is None
