"""Fused tied-head+CE (ops/fused_ce.py): numerics must equal the unfused
logits-materializing path — op-level (values + all grads) and step-level
(one LM optimizer step)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_tpu.models.transformer import TransformerLM
from pytorch_distributed_tpu.ops.fused_ce import fused_ce_sums
from pytorch_distributed_tpu.parallel import data_parallel_mesh
from pytorch_distributed_tpu.parallel.tp import replicated_like
from pytorch_distributed_tpu.train.lm import make_lm_train_step
from pytorch_distributed_tpu.train.optim import sgd_init
from pytorch_distributed_tpu.train.state import TrainState

N, D, V = 24, 16, 50


def _naive_sums(h, e, t, w):
    logits = (h.astype(jnp.float32) @ e.astype(jnp.float32).T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    loss = jnp.sum((logz - true_logit) * w)
    correct = jnp.sum(
        (jnp.argmax(logits, axis=-1) == t).astype(jnp.float32) * w)
    return loss, correct


def _op_inputs(seed=0, n=N, d=D, v=V, hit_frac=0.25):
    """Random op-level inputs; a fraction of targets is set to the argmax
    row so correct_sum is exercised nonzero."""
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(0, 1, size=(n, d)), jnp.float32)
    e = jnp.asarray(rng.normal(0, 1, size=(v, d)), jnp.float32)
    t = np.asarray(rng.integers(0, v, size=(n,)), np.int32)
    am = np.asarray(jnp.argmax(h @ e.T, axis=-1))
    hits = rng.random(n) < hit_frac
    t[hits] = am[hits]
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(n,)), jnp.float32)
    return h, e, jnp.asarray(t), w


@pytest.mark.parametrize("chunks", [1, 2, 4, 8])
def test_fused_ce_matches_naive(chunks):
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(0, 1, size=(N, D)), jnp.float32)
    e = jnp.asarray(rng.normal(0, 1, size=(V, D)), jnp.float32)
    t = jnp.asarray(rng.integers(0, V, size=(N,)), jnp.int32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(N,)), jnp.float32)

    # value_and_grad needs a scalar; differentiate the loss output only
    fused_loss = lambda h, e: fused_ce_sums(h, e, t, w, chunks)[0]  # noqa: E731
    naive_loss = lambda h, e: _naive_sums(h, e, t, w)[0]  # noqa: E731
    lv_f, (gh_f, ge_f) = jax.value_and_grad(fused_loss, argnums=(0, 1))(h, e)
    lv_n, (gh_n, ge_n) = jax.value_and_grad(naive_loss, argnums=(0, 1))(h, e)
    np.testing.assert_allclose(float(lv_f), float(lv_n), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gh_f), np.asarray(gh_n),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ge_f), np.asarray(ge_n),
                               rtol=1e-5, atol=1e-6)
    # correct_sum (non-diff output) also matches
    _, cf = fused_ce_sums(h, e, t, w, chunks)
    _, cn = _naive_sums(h, e, t, w)
    np.testing.assert_allclose(float(cf), float(cn))


@pytest.mark.parametrize("scale", [1.0, 1.0 / 7.0],
                         ids=["cotangent_1", "cotangent_odd"])
@pytest.mark.parametrize("chunks", [1, 2, 4, 8])
def test_forward_pass_gradients_match_unfused(chunks, scale):
    """The gradients the forward rule took beside the logits, scaled by
    the loss's cotangent in the backward rule: ``dh``, ``dE`` and the
    weights' cotangent against the unfused ``cross_entropy`` path (its
    per-row losses, weighted), with uneven weights and a row count (21)
    that no chunk count here divides."""
    from pytorch_distributed_tpu.ops.loss import cross_entropy

    h, e, t, w = _op_inputs(seed=21, n=21)

    def unfused(h, e, w):
        logits = h @ e.T
        rows = jax.vmap(
            lambda row, tgt: cross_entropy(row[None], tgt[None]))(logits, t)
        return jnp.sum(rows * w) * scale

    def fused(h, e, w):
        return fused_ce_sums(h, e, t, w, chunks)[0] * scale

    want_v, want = jax.value_and_grad(unfused, argnums=(0, 1, 2))(h, e, w)
    got_v, got = jax.value_and_grad(fused, argnums=(0, 1, 2))(h, e, w)
    np.testing.assert_allclose(float(got_v), float(want_v), rtol=1e-6)
    for a, b, name in zip(got, want, ("dh", "dE", "dw")):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_bf16_rows_leave_in_their_own_types():
    """bf16 operands (the LM cells' policy): ``dh`` leaves in the rows'
    type and ``dE`` in the head's, after a float32 scaling."""
    h, e, t, w = _op_inputs(seed=5)
    hb, eb = h.astype(jnp.bfloat16), e.astype(jnp.bfloat16)
    gh, ge, gw = jax.grad(
        lambda h, e, w: fused_ce_sums(h, e, t, w, 4)[0] / 7.0,
        argnums=(0, 1, 2))(hb, eb, w)
    assert (gh.dtype, ge.dtype, gw.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.float32)
    want = jax.grad(
        lambda h, e, w: _naive_sums(h, e, t, w)[0] / 7.0,
        argnums=(0, 1, 2))(hb.astype(jnp.float32), eb.astype(jnp.float32), w)
    for a, b in zip((gh, ge, gw), want):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (the scan's
    body, a custom_vjp's call, a shard_map's) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _head_products(jaxpr):
    return sum(eqn.primitive.name == "dot_general" for eqn in _eqns(jaxpr))


def _holds_f32(jaxpr, shape):
    return any(v.aval.shape == shape and v.aval.dtype == jnp.float32
               for eqn in _eqns(jaxpr) for v in eqn.outvars)


def _variant(name):
    """``(fn(h, e, t, w, chunks), vocab shard a device's dE carry has)``
    on the 8 host devices."""
    from pytorch_distributed_tpu.ops.fused_ce import (
        fused_ce_sums_dp,
        fused_ce_sums_tp,
    )
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh

    if name == "replicated":
        return fused_ce_sums, 1
    if name == "dp":
        mesh = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
        return (lambda *a: fused_ce_sums_dp(*a, mesh)), 8
    mesh = build_mesh(MeshSpec(("data", "model"), (2, 4)), jax.devices()[:8])
    return (lambda *a: fused_ce_sums_tp(*a, mesh)), 4


@pytest.mark.parametrize("variant", ["replicated", "dp", "tp"])
def test_the_gradient_runs_three_head_products_a_chunk(variant):
    """Under differentiation one loop: a chunk's logits, ``dh`` and ``dE``
    (``GRAD_HEAD_PRODUCTS``), and no loop that computes the logits again.
    The plain call, what an eval step gets, runs the one product and
    carries no float32 ``[V, D]`` (or vocab shard of it): it does not pay
    for a gradient."""
    from pytorch_distributed_tpu.ops.fused_ce import GRAD_HEAD_PRODUCTS

    fn, shards = _variant(variant)
    h, e, t, w = _op_inputs(seed=3, v=64)
    acc = (64 // shards, D)

    plain = jax.make_jaxpr(lambda h, e, w: fn(h, e, t, w, 3))(h, e, w)
    assert _head_products(plain.jaxpr) == 1
    assert not _holds_f32(plain.jaxpr, acc)
    assert sum(eqn.primitive.name == "scan"
               for eqn in _eqns(plain.jaxpr)) == 1

    grad = jax.make_jaxpr(jax.grad(
        lambda h, e, w: fn(h, e, t, w, 3)[0] / 7.0, argnums=(0, 1, 2)))(
            h, e, w)
    assert GRAD_HEAD_PRODUCTS == 3
    assert _head_products(grad.jaxpr) == GRAD_HEAD_PRODUCTS
    assert _holds_f32(grad.jaxpr, acc)
    assert sum(eqn.primitive.name == "scan"
               for eqn in _eqns(grad.jaxpr)) == 1


@pytest.mark.parametrize("chunks", [0, 2])
def test_the_compiled_fit_step_names_the_loss_loop(chunks):
    """What the compiled program itself says (obs/trace.py
    ``compiled_scopes``), where a build constant among the step's metrics
    could not: a fit step on the fused loss holds a ``while`` and its
    products under the ``fused_ce`` scope, one loop, and an unfused step
    holds no such instruction.  The loop is booked *forward*: since PR 33
    the custom VJP's forward rule runs the gradient's products in it, and
    the backward rule's scaling by the loss's cotangent of 1 folds away, so
    no backward instruction carries the scope."""
    from pytorch_distributed_tpu.analysis import hlo
    from pytorch_distributed_tpu.obs import trace
    from pytorch_distributed_tpu.train.lm import (
        LMTrainer,
        SyntheticTokenDataset,
    )

    mesh = data_parallel_mesh()
    model = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=1)
    ds = SyntheticTokenDataset(32, 16, 64, seed=0)
    trainer = LMTrainer(model, mesh, ds, batch_size=8, lr=1e-2,
                        fused_ce_chunks=chunks)
    trainer.fit(2, print_freq=100)
    scopes = trace.compiled_scopes("jit_step")
    assert trace.STEP_PROGRAMS["jit_step"].jitted is trainer.step_fn
    loss = {n: s for n, s in scopes.items() if "fused_ce" in s.scopes}
    if not chunks:
        assert not loss
        return
    program = trace.STEP_PROGRAMS["jit_step"]
    opcodes = {i.name: i.opcode for i in hlo.parse_instructions(
        program.jitted.lower(*program.args).compile().as_text())}
    assert sum(opcodes[n] == "while" for n in loss) == 1
    assert {s.phase for s in loss.values()} == {"forward"}
    assert all(s.scopes == ("lm_forward", "fused_ce")
               for s in loss.values())
    # the rest of the step is named too: both passes and the update
    assert {s.phase for s in scopes.values() if s.scopes} >= {
        "forward", "backward", "optimizer"}


def test_fused_ce_pads_indivisible_rows():
    """N not divisible by num_chunks: weight-0 padding keeps values and
    grads exact (the LM's N = B*(L-1) is rarely chunk-aligned)."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(0, 1, size=(6, 4)), jnp.float32)
    e = jnp.asarray(rng.normal(0, 1, size=(5, 4)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 5, size=(6,)), jnp.int32)
    w = jnp.ones((6,), jnp.float32)
    fused = lambda h, e: fused_ce_sums(h, e, t, w, 4)[0]  # noqa: E731
    naive = lambda h, e: _naive_sums(h, e, t, w)[0]  # noqa: E731
    lv_f, g_f = jax.value_and_grad(fused, argnums=(0, 1))(h, e)
    lv_n, g_n = jax.value_and_grad(naive, argnums=(0, 1))(h, e)
    np.testing.assert_allclose(float(lv_f), float(lv_n), rtol=1e-6)
    for a, b in zip(g_f, g_n):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_tp_step_with_fused_ce_matches_replicated():
    """fused-CE composes with Megatron TP shardings under GSPMD: the
    chunked scan's per-block logits shard on the vocab axis and XLA
    inserts the logsumexp/softmax collectives — one TP step must equal
    the replicated fused step."""
    import jax.numpy as jnp

    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.parallel.tp import shard_state, tp_specs

    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2)
    model = TransformerLM(**cfg)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, size=(8, 17)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]

    def run(mesh, specs):
        fresh = jax.tree_util.tree_map(jnp.array, params)
        state = shard_state(
            TrainState.create({"params": fresh}, sgd_init(fresh)),
            specs, mesh)
        step = make_lm_train_step(model, mesh, specs, fused_ce_chunks=4)
        return step(state, tokens, jnp.float32(0.05))

    mesh_tp = build_mesh(MeshSpec(("data", "model"), (2, 4)),
                         jax.devices()[:8])
    mesh_dp = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
    s_tp, m_tp = run(mesh_tp, tp_specs(params))
    s_dp, m_dp = run(mesh_dp, replicated_like(params))
    np.testing.assert_allclose(float(m_tp["loss"]), float(m_dp["loss"]),
                               rtol=1e-4)
    np.testing.assert_allclose(float(m_tp["acc"]), float(m_dp["acc"]),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(s_tp.params),
                    jax.tree_util.tree_leaves(s_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_fused_ce_weights_grad_matches_naive():
    """The loss-path ``weights`` cotangent (ADVICE r5: _bwd used to return
    None): grad w.r.t. the per-row weights must match the naive
    logits-materializing autodiff — (logz − true_logit) per row."""
    h, e, t, w = _op_inputs(seed=7)
    gw_f = jax.grad(lambda w: fused_ce_sums(h, e, t, w, 4)[0])(w)
    gw_n = jax.grad(lambda w: _naive_sums(h, e, t, w)[0])(w)
    assert float(jnp.max(jnp.abs(gw_f))) > 0.0
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_n),
                               rtol=1e-5, atol=1e-6)


def test_dp_mode_matches_naive_op_level():
    """fused_ce_sums_dp on an 8-way data mesh: values, correct_sum and all
    three grads (h, e, w) ≡ the naive path; the backward's dE accumulator
    is a [V/8, D] vocab-row shard per device (the replicated-[V,D] fix)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.ops.fused_ce import fused_ce_sums_dp
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
    h, e, t, w = _op_inputs(seed=11, v=64)
    hs = jax.device_put(h, NamedSharding(mesh, P("data", None)))
    ts = jax.device_put(t, NamedSharding(mesh, P("data")))
    ws = jax.device_put(w, NamedSharding(mesh, P("data")))

    @jax.jit
    def vals_and_grads(h, e, w):
        def f(h, e, w):
            return fused_ce_sums_dp(h, e, ts, w, 3, mesh)[0]

        return jax.value_and_grad(f, argnums=(0, 1, 2))(h, e, w)

    lv, grads = vals_and_grads(hs, e, ws)
    ln, cn = _naive_sums(h, e, t, w)
    gn = jax.grad(lambda h, e, w: _naive_sums(h, e, t, w)[0],
                  argnums=(0, 1, 2))(h, e, w)
    np.testing.assert_allclose(float(lv), float(ln), rtol=1e-6)
    for got, want, name in zip(grads, gn, ("h", "e", "w")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    cd = fused_ce_sums_dp(hs, e, ts, ws, 3, mesh)[1]
    assert float(cn) > 0.0  # the hit fraction keeps this exercised
    np.testing.assert_allclose(float(cd), float(cn), rtol=1e-6)


def test_tp_mode_matches_replicated_with_vocab_sharded_embedding():
    """fused_ce_sums_tp under shard_map with the parallel/tp.py
    vocab-sharded embedding (P('model', None)) ≡ the replicated
    fused_ce_sums: values, correct_sum, and all grads — with e entering
    (and its cotangent leaving) vocab-sharded, never replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_tpu.ops.fused_ce import fused_ce_sums_tp
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(("data", "model"), (2, 4)),
                      jax.devices()[:8])
    h, e, t, w = _op_inputs(seed=13, v=64)
    es = jax.device_put(e, NamedSharding(mesh, P("model", None)))
    hs = jax.device_put(h, NamedSharding(mesh, P("data", None)))

    @jax.jit
    def vals_and_grads(h, e, w):
        def f(h, e, w):
            return fused_ce_sums_tp(h, e, t, w, 3, mesh)[0]

        return jax.value_and_grad(f, argnums=(0, 1, 2))(h, e, w)

    lv, grads = vals_and_grads(hs, es, w)
    lr_, cr = fused_ce_sums(h, e, t, w, 3)
    gr = jax.grad(lambda h, e, w: fused_ce_sums(h, e, t, w, 3)[0],
                  argnums=(0, 1, 2))(h, e, w)
    np.testing.assert_allclose(float(lv), float(lr_), rtol=1e-6)
    for got, want, name in zip(grads, gr, ("h", "e", "w")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5, err_msg=name)
    # e's cotangent must come back vocab-sharded (no dE replication)
    ge_spec = grads[1].sharding.spec
    assert ge_spec[0] == "model", ge_spec
    ct = fused_ce_sums_tp(hs, es, t, w, 3, mesh)[1]
    assert float(cr) > 0.0
    np.testing.assert_allclose(float(ct), float(cr), rtol=1e-6)


def test_dp_mode_step_matches_replicated_and_unfused():
    """Step-level DP parity on the 8-way data mesh: fused_ce_mode='dp' ≡
    'replicated' ≡ unfused — loss/acc and the updated params (i.e. the
    gradients) agree to fp-reassociation tolerance."""
    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2)
    model = TransformerLM(**cfg)
    mesh = data_parallel_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, size=(8, 17)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]

    def one_step(chunks, mode):
        state = TrainState.create(
            {"params": jax.tree_util.tree_map(jnp.copy, params)},
            sgd_init(params))
        step = make_lm_train_step(
            model, mesh, replicated_like(params), fused_ce_chunks=chunks,
            fused_ce_mode=mode)
        return step(state, tokens, jnp.float32(0.1))

    s_dp, m_dp = one_step(4, "dp")
    s_rep, m_rep = one_step(4, "replicated")
    s_un, m_un = one_step(0, "auto")
    for (s, m), tag in (((s_rep, m_rep), "dp-vs-replicated"),
                        ((s_un, m_un), "dp-vs-unfused")):
        np.testing.assert_allclose(float(m_dp["loss"]), float(m["loss"]),
                                   rtol=1e-5, err_msg=tag)
        np.testing.assert_allclose(float(m_dp["acc"]), float(m["acc"]),
                                   rtol=1e-5, atol=1e-5, err_msg=tag)
        want = dict(jax.tree_util.tree_leaves_with_path(s.params))
        for path, v in jax.tree_util.tree_leaves_with_path(s_dp.params):
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(want[path]), rtol=1e-4,
                atol=1e-5, err_msg=f"{tag}:{jax.tree_util.keystr(path)}")


def test_fused_ce_mode_validation():
    """Explicit mis-paired modes fail loudly at step-build time."""
    from pytorch_distributed_tpu.train.lm import resolve_fused_ce_mode

    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.parallel.tp import tp_specs

    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2)
    model = TransformerLM(**cfg)
    tokens0 = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens0)["params"]
    mesh_dp = build_mesh(MeshSpec(("data",), (8,)), jax.devices()[:8])
    mesh_tp = build_mesh(MeshSpec(("data", "model"), (2, 4)),
                         jax.devices()[:8])
    rep = replicated_like(params)
    # tp on a replicated spec → loud error
    with pytest.raises(ValueError, match="fused_ce_mode='tp'"):
        resolve_fused_ce_mode("tp", rep, mesh_dp, 64)
    # dp with a vocab the data axis doesn't divide → loud error
    with pytest.raises(ValueError, match="fused_ce_mode='dp'"):
        resolve_fused_ce_mode("dp", rep, mesh_dp, 65)
    # auto: indivisible vocab falls back to replicated, never crashes
    assert resolve_fused_ce_mode("auto", rep, mesh_dp, 65)[0] == "replicated"
    assert resolve_fused_ce_mode("auto", rep, mesh_dp, 64)[0] == "dp"
    mode, axis = resolve_fused_ce_mode(
        "auto", tp_specs(params), mesh_tp, 64)
    assert (mode, axis) == ("tp", "model")
    with pytest.raises(ValueError, match="auto|replicated|dp|tp"):
        resolve_fused_ce_mode("bogus", rep, mesh_dp, 64)


@pytest.mark.parametrize("mode", ["replicated", "dp"])
def test_lm_step_fused_equals_unfused_bf16(mode):
    """bf16 variant of the fused-vs-unfused step parity (ADVICE r5): the
    fused path casts ln_f hidden + embedding to bf16 before the chunked
    matmul, exactly like the unfused head's embed-dtype cast — pinned here
    at loose bf16 tolerance rather than asserted by docstring alone."""
    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
               dtype=jnp.bfloat16)
    model = TransformerLM(**cfg)
    mesh = data_parallel_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(9).integers(0, 64, size=(8, 17)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])["params"]

    def one_step(chunks, mode):
        state = TrainState.create(
            {"params": jax.tree_util.tree_map(jnp.copy, params)},
            sgd_init(params))
        step = make_lm_train_step(
            model, mesh, replicated_like(params), fused_ce_chunks=chunks,
            fused_ce_mode=mode)
        return step(state, tokens, jnp.float32(0.1))

    s_f, m_f = one_step(4, mode)
    s_n, m_n = one_step(0, "auto")
    # bf16 has ~3 decimal digits: fused and unfused heads round the same
    # operands through different summation orders.
    np.testing.assert_allclose(float(m_f["loss"]), float(m_n["loss"]),
                               rtol=2e-2)
    # acc is percent over 128 tokens: allow a single bf16 argmax tie-flip
    np.testing.assert_allclose(float(m_f["acc"]), float(m_n["acc"]),
                               rtol=2e-2, atol=1.0)
    want = dict(jax.tree_util.tree_leaves_with_path(s_n.params))
    for path, v in jax.tree_util.tree_leaves_with_path(s_f.params):
        np.testing.assert_allclose(
            np.asarray(v, jnp.float32), np.asarray(want[path], jnp.float32),
            rtol=2e-2, atol=2e-3, err_msg=jax.tree_util.keystr(path))


def test_lm_step_fused_equals_unfused():
    """One full LM optimizer step, fused_ce_chunks=4 vs 0 (f32): metrics
    and updated params must agree to fp tolerance."""
    cfg = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2)
    model = TransformerLM(**cfg)
    mesh = data_parallel_mesh()
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(8, 17)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    params = variables["params"]

    def one_step(chunks):
        state = TrainState.create(
            {"params": jax.tree_util.tree_map(jnp.copy, params)},
            sgd_init(params))
        step = make_lm_train_step(
            model, mesh, replicated_like(params), fused_ce_chunks=chunks)
        return step(state, tokens, jnp.float32(0.1))

    s_f, m_f = one_step(4)
    s_n, m_n = one_step(0)
    np.testing.assert_allclose(float(m_f["loss"]), float(m_n["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m_f["acc"]), float(m_n["acc"]),
                               rtol=1e-5, atol=1e-5)
    got = jax.tree_util.tree_leaves_with_path(s_f.params)
    want = dict(jax.tree_util.tree_leaves_with_path(s_n.params))
    for path, v in got:
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(want[path]), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))
