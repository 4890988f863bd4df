"""Quantized gradient collectives (ops/qcomm.py) + their fences.

Covers the ISSUE-8 contracts end to end on the simulated CPU mesh:

- per-block symmetric quantize/dequantize round-trip error bounds;
- error-feedback residual exactness: the residuals carried in TrainState
  telescope to exactly (true sum - wire sum), both for the emulated
  (GSPMD) path and the explicit shard_map two-hop decomposition;
- step parity: int8+EF training tracks the f32 step at loose tolerance;
- wire fence: the compiled int8 step's measured grad_sync wire bytes
  (comm ledger) shrink >= 3.5x vs the f32 explicit step, and the
  analytic model (obs/flops.py image_comm_bytes_compressed) lands within
  the +-15% residual window;
- shardlint fence: the pinned train_image_int8 collective baseline makes
  an f32 fallback (all-reduce bytes at grad size) a hard error;
- mode plumbing: resolve_mode's wire_dtype deprecation shim, the GSPMD
  numerics-emulation warning, and checkpoint round-trip of residuals.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.ops import qcomm
from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState
from pytorch_distributed_tpu.train.steps import make_train_step

from tests.test_steps import _MLP, _leaves_allclose, _setup_mlp


# ------------------------------------------------------------- quant kernels

def test_int8_roundtrip_error_bound():
    """|x - dq(q(x))| <= scale/2 per element (symmetric round-to-nearest)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32) * 3.0)
    q, scale = qcomm.quantize_blockwise(x, "int8")
    assert q.dtype == jnp.int8
    dq = qcomm.dequantize_blockwise(q, scale, x.shape)
    nb = scale.size
    per_block = np.repeat(np.asarray(scale), qcomm.DEFAULT_BLOCK)[: x.size]
    np.testing.assert_array_less(
        np.abs(np.asarray(x - dq)), per_block / 2 + 1e-12)
    assert nb == int(np.ceil(x.size / qcomm.DEFAULT_BLOCK))


def test_quantize_zero_block_is_exact():
    x = jnp.zeros((512,), jnp.float32)
    q, scale = qcomm.quantize_blockwise(x, "int8")
    np.testing.assert_array_equal(np.asarray(q), 0)
    np.testing.assert_array_equal(np.asarray(scale), 0.0)
    np.testing.assert_array_equal(
        np.asarray(qcomm.dequantize_blockwise(q, scale, x.shape)), 0.0)


@pytest.mark.skipif(not qcomm.fp8_supported(), reason="no fp8 dtype")
def test_fp8_roundtrip_loose():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(512,)).astype(np.float32))
    dq = qcomm.fake_quantize(x, "fp8")
    # e4m3 carries ~3 mantissa bits; block scaling keeps it relative.
    np.testing.assert_allclose(np.asarray(dq), np.asarray(x),
                               rtol=0.13, atol=1e-3)


def test_chunk_layout_small_leaf_shrinks_block():
    # 10-element leaf, 4 ranks: chunk 3 -> block 3, padded 12 (not 1024).
    padded, nb = qcomm.chunk_layout(10, 4, 256)
    assert padded == 12 and nb == 1
    # Exact multiples pad nothing.
    padded, nb = qcomm.chunk_layout(49152, 4, 256)
    assert padded == 49152 and nb == 48


# ------------------------------------------------------------- mode plumbing

def test_resolve_mode_wire_dtype_shim():
    with pytest.deprecated_call():
        mode, cast = qcomm.resolve_mode(None, jnp.bfloat16)
    assert mode == "bf16" and cast == jnp.bfloat16
    assert qcomm.resolve_mode(None, None) == ("none", None)
    assert qcomm.resolve_mode("none", None) == ("none", None)
    mode, cast = qcomm.resolve_mode("bf16", None)
    assert mode == "bf16" and cast == jnp.bfloat16
    assert qcomm.resolve_mode("int8", None) == ("int8", None)
    with pytest.raises(ValueError):
        qcomm.resolve_mode("int4", None)
    with pytest.raises(ValueError):
        qcomm.resolve_mode("int8", jnp.bfloat16)


def test_gspmd_int8_warns_numerics_emulation():
    mesh, model, state, batch = _setup_mlp(num_devices=4)
    with pytest.warns(UserWarning, match="NUMERICS emulation"):
        make_train_step(model, mesh, grad_compress="int8")


# -------------------------------------------------------- error feedback

def test_emulated_error_feedback_telescopes():
    """compress_emulated: residual == (input - fake-quantized output)."""
    rng = np.random.default_rng(2)
    grads = {"w": jnp.asarray(rng.normal(size=(300,)).astype(np.float32))}
    residual = qcomm.init_residual(grads, "int8")
    out, res = qcomm.compress_emulated(grads, residual, "int8")
    np.testing.assert_allclose(
        np.asarray(res["w"]), np.asarray(grads["w"] - out["w"]),
        rtol=0, atol=1e-6)
    # Second step folds the carried residual into the quantizer input.
    out2, res2 = qcomm.compress_emulated(grads, res, "int8")
    np.testing.assert_allclose(
        np.asarray(res2["w"]),
        np.asarray(grads["w"] + res["w"] - out2["w"]), rtol=0, atol=1e-6)


def test_compressed_psum_exact_telescoping():
    """Explicit two-hop decomposition: summed residual slots equal the
    true f32 sum minus what crossed the wire — exactly, not approximately
    (the DynamiQ invariant the convergence claim rests on)."""
    n = 4
    mesh = build_mesh(MeshSpec(("data",), (n,)), jax.devices()[:n])
    rng = np.random.default_rng(3)
    per_rank = jnp.asarray(rng.normal(size=(n, 700)).astype(np.float32))
    res0 = jnp.zeros((n, 700), jnp.float32)

    from jax.sharding import PartitionSpec as P

    def body(x, r):
        synced, new_r = qcomm.compressed_psum(
            {"g": x[0]}, {"g": r}, "data", mode="int8")
        return synced["g"], new_r["g"]

    wire_sum, res = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P("data")), check_vma=False)(per_rank, res0)
    true_sum = np.asarray(per_rank).sum(axis=0)
    np.testing.assert_allclose(
        np.asarray(res).sum(axis=0),
        true_sum - np.asarray(wire_sum), rtol=0, atol=5e-5)


# ------------------------------------------------------------- step parity

def _fresh_state(variables, mode, explicit, n_data):
    v = jax.tree_util.tree_map(jnp.array, variables)
    residual = qcomm.init_residual(v["params"], mode, explicit=explicit,
                                   n_data=n_data)
    return TrainState.create(v, sgd_init(v["params"]), residual=residual)


def test_int8_step_parity_vs_f32():
    """3 explicit-collective steps: int8+EF params track f32 at loose
    tolerance, and the residual state is actually nonzero (EF is live)."""
    n = 4
    mesh = build_mesh(MeshSpec(("data",), (n,)), jax.devices()[:n])
    model = _MLP(classes=10)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    rng = np.random.default_rng(4)
    batches = [{
        "images": rng.normal(size=(16, 8, 8, 3)).astype(np.float32),
        "labels": rng.integers(0, 10, size=16).astype(np.int32),
        "weights": np.ones(16, np.float32),
    } for _ in range(3)]

    def run(mode):
        step = make_train_step(model, mesh, explicit_collectives=True,
                               grad_compress=mode)
        state = _fresh_state(variables, mode, True, n)
        for b in batches:
            state, metrics = step(state, b, jnp.float32(0.1))
        return state, float(metrics["loss"])

    s_f32, loss_f32 = run("none")
    s_int8, loss_int8 = run("int8")
    np.testing.assert_allclose(loss_int8, loss_f32, rtol=5e-3)
    _leaves_allclose(s_f32.params, s_int8.params, rtol=0.05, atol=5e-3)
    res_norm = sum(float(jnp.sum(jnp.abs(l)))
                   for l in jax.tree_util.tree_leaves(s_int8.residual))
    assert res_norm > 0.0


# ------------------------------------------------------------ wire fences

def _recipe_ledger(get_lowering, name):
    """Comm ledger for one shardlint recipe's cached session lowering —
    pure text parsing over the memoized compile (analysis.core), instead
    of a fresh per-test ``lower().compile()``."""
    from pytorch_distributed_tpu.obs import comms

    low = get_lowering(name)
    return comms.ledger_from_hlo_text(low.text, step=name,
                                      mesh_shape=low.mesh_shape)


def test_int8_wire_bytes_fence_and_analytic_parity(get_lowering):
    """The ISSUE-8 acceptance fence, measured from compiled HLO: int8
    grad_sync wire bytes shrink >= 3.5x vs f32, entries are labeled with
    the int8 wire encoding, and the analytic model lands within +-15%."""
    from pytorch_distributed_tpu.obs.flops import (
        comm_residual_pct,
        image_comm_bytes_compressed,
    )

    lg_f32 = _recipe_ledger(get_lowering, "train_image_explicit")
    lg_int8 = _recipe_ledger(get_lowering, "train_image_int8")

    gs_f32 = lg_f32.by_phase()["grad_sync"]
    gs_int8 = lg_int8.by_phase()["grad_sync"]
    ratio = gs_f32["wire_bytes"] / gs_int8["wire_bytes"]
    assert ratio >= 3.5, (ratio, gs_f32, gs_int8)

    encodings = lg_int8.phase_wire_encodings("grad_sync")
    assert "int8" in encodings, encodings
    # payload dominates the f32 scale side-cars
    assert encodings["int8"] > 10 * encodings.get("f32", 0.0), encodings

    # both recipes share _tiny_image_model; leaf sizes off the cached
    # lowering's own state argument
    leaf_sizes = [l.size for l in jax.tree_util.tree_leaves(
        get_lowering("train_image_int8").args[0].params)]
    pred = image_comm_bytes_compressed(leaf_sizes, dp=4, mode="int8")
    assert comm_residual_pct(
        pred.total_bytes, lg_int8.total_bytes) <= 15.0, (
        pred.total_bytes, lg_int8.total_bytes)


def test_wire_encoding_json_roundtrip(tmp_path, get_lowering):
    """Ledger JSON round-trips wire_encoding; legacy entries without the
    field load with the f32 default."""
    from pytorch_distributed_tpu.obs import comms

    lg = _recipe_ledger(get_lowering, "train_image_int8")
    path = os.path.join(tmp_path, "comm_ledger.json")
    comms.write_ledgers(path, [lg])
    loaded = comms.load_ledgers(path)["train_image_int8"]
    assert (loaded.phase_wire_encodings("grad_sync")
            == lg.phase_wire_encodings("grad_sync"))

    # legacy payload: entries with no wire_encoding key
    import json

    data = json.load(open(path))
    for e in data["train_image_int8"]["entries"]:
        e.pop("wire_encoding")
    with open(path, "w") as f:
        json.dump(data, f)
    legacy = comms.load_ledgers(path)["train_image_int8"]
    assert {e.wire_encoding for e in legacy.entries} == {"f32"}


def test_shardlint_baseline_fences_f32_fallback(get_lowering):
    """The pinned train_image_int8 budget has no room for an f32 gradient
    all-reduce: a fallback shows up as error-severity
    collective-regression findings on both the kind and the total."""
    from pytorch_distributed_tpu.analysis import core
    from pytorch_distributed_tpu.analysis.report import (
        baseline_entry,
        diff_against_baseline,
        load_baseline,
    )

    base = load_baseline(core.baseline_path())
    assert "train_image_int8" in base and "train_image_bf16" in base
    entry = base["train_image_int8"]
    # the pinned budget's all-reduce line is scalars-only (16 B), so any
    # f32 gradient fallback necessarily exceeds it
    assert entry["collectives"]["all-reduce"]["bytes"] < 100
    assert entry["collectives"]["all-to-all"]["bytes"] > 1000

    rep = core.analyze_lowering(get_lowering("train_image_int8"))
    assert diff_against_baseline(rep, entry) == []

    # simulate the fallback: gradient bytes land on all-reduce again
    fallback = core.analyze_lowering(get_lowering("train_image_explicit"))
    fallback.name = "train_image_int8"
    regress = diff_against_baseline(fallback, entry)
    errors = [f for f in regress if f.severity == "error"]
    assert any(f.where.endswith(":all-reduce") for f in errors), regress
    assert any(f.where.endswith(":total") for f in errors), regress
    # sanity: the real lowering regenerates its own pinned entry (the
    # entry pins synclint's schedule digest too: attach it, zero compiles)
    from pytorch_distributed_tpu.analysis import synclint

    synclint.annotate_reports([rep])
    assert baseline_entry(rep) == entry


# ------------------------------------------------------------- checkpoints

def test_checkpoint_residual_roundtrip(tmp_path):
    from pytorch_distributed_tpu.train.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    model = _MLP(classes=4)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))
    residual = qcomm.init_residual(variables["params"], "int8",
                                   explicit=True, n_data=4)
    residual = jax.tree_util.tree_map(
        lambda r: r + 0.25, residual)  # nonzero, so the restore is visible
    state = TrainState.create(variables, sgd_init(variables["params"]),
                              residual=residual)
    path = save_checkpoint(str(tmp_path), state, 0, "mlp", 0.0, False,
                           ft={"step": 3})

    template = TrainState.create(
        jax.tree_util.tree_map(jnp.zeros_like, variables),
        sgd_init(variables["params"]),
        residual=qcomm.init_residual(variables["params"], "int8",
                                     explicit=True, n_data=4))
    loaded, meta = load_checkpoint(path, template)
    _leaves_allclose(loaded.residual, state.residual, rtol=0, atol=0)
    assert meta["ft"]["step"] == 3

    # mode switch: an f32 template (no residual) loads the same payload
    plain = TrainState.create(
        jax.tree_util.tree_map(jnp.zeros_like, variables),
        sgd_init(variables["params"]))
    loaded2, _ = load_checkpoint(path, plain)
    assert jax.tree_util.tree_leaves(loaded2.residual) == []
    _leaves_allclose(loaded2.params, state.params, rtol=0, atol=0)


def test_checkpoint_legacy_payload_zero_residual(tmp_path):
    """A checkpoint written WITHOUT residuals restores into a quantized
    template with zero residuals (EF restarts cleanly on mode switch)."""
    from pytorch_distributed_tpu.train.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    model = _MLP(classes=4)
    variables = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 8, 8, 3)))
    state = TrainState.create(variables, sgd_init(variables["params"]))
    path = save_checkpoint(str(tmp_path), state, 0, "mlp", 0.0, False)

    template = TrainState.create(
        jax.tree_util.tree_map(jnp.zeros_like, variables),
        sgd_init(variables["params"]),
        residual=qcomm.init_residual(variables["params"], "int8",
                                     explicit=True, n_data=4))
    loaded, _ = load_checkpoint(path, template)
    for leaf in jax.tree_util.tree_leaves(loaded.residual):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    _leaves_allclose(loaded.params, state.params, rtol=0, atol=0)
