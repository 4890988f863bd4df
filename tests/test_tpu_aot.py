"""Compile for a TPU v5e 2x2 topology description — no chip needed.

The CPU mesh cannot see what the TPU compiler refuses: off-TPU the Pallas
kernels run in interpret mode, which lowers to plain HLO that GSPMD
partitions happily.  ``jax.experimental.topologies`` hands out v5e device
descriptions that ``jit(...).lower(...).compile()`` accepts, so the real
TPU compiler (Mosaic included) runs here in the sandbox.  Compile-only: it
says nothing about running, timing or memory at run time.

The suite's name sorts after the point where the tier-1 sweep hits its
time ceiling; run it directly: ``pytest tests/test_tpu_aot.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def v5e_devices():
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``interpret=None`` resolves from ``jax.default_backend()``, which is
    the CPU in this session whatever the compile target: pin the kernels to
    compiled mode, as they are on the chip."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("shape,causal,blocks", [
    ((2, 1024, 4, 64), True, (256, 1024)),    # aligned
    ((8, 197, 12, 64), False, (256, 1024)),   # ViT-B/16: one full block
    # the looped decoder's call (models/decoder.py MHA): 16 heads of 128
    # at 8,192 positions, the blocks the decoder asks for
    ((2, 8192, 16, 128), True, (1024, 1024)),
    # the latent-attention decoder's (MLA): 192-wide queries and keys,
    # 128-wide values (the last entry), the same blocks
    ((4, 8192, 16, 192, 128), True, (1024, 1024)),
])
def test_flash_fwd_bwd_compiles_for_v5e(v5e_devices, shape, causal, blocks):
    one = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)), P())
    x = jax.ShapeDtypeStruct(shape[:4], jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct(shape[:3] + shape[-1:], jnp.bfloat16,
                             sharding=one)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal, *blocks, False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, v).compile()
    assert _mosaic_calls(compiled) == 3  # forward, dq, dk/dv


@pytest.mark.parametrize("axes,shape,tp", [
    (("data",), (4,), False),
    (("data", "model"), (2, 2), True),
])
def test_gspmd_lm_step_with_flash_compiles_on_four_chips(
        v5e_devices, compiled_kernels, axes, shape, tp):
    """The GSPMD LM step with the Pallas kernel inside it, on a mesh of
    more than one device: the TPU compiler refuses a bare Mosaic call
    there ("Mosaic kernels cannot be automatically partitioned"), so this
    fails unless attention wraps the kernel in a shard_map."""
    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel.tp import replicated_like, tp_specs
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = Mesh(np.array(v5e_devices).reshape(shape), axes)
    model = TransformerLM(vocab_size=512, d_model=256, n_heads=4,
                          n_layers=1, dtype=jnp.bfloat16, attn_impl="flash")
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(tokens.shape, tokens.dtype)))["params"]
    state = jax.eval_shape(
        lambda p: TrainState.create({"params": p}, sgd_init(p)), params)
    specs = tp_specs(params) if tp else replicated_like(params)
    step = make_lm_train_step(model, mesh, specs)
    compiled = step.lower(
        state, tokens, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    assert _mosaic_calls(compiled) == 3
    assert "num_partitions=4" in compiled.as_text()


# --- the one-block fused attention of the ViTs (ops/short_attention.py) ---

@pytest.mark.parametrize("shape,dtype", [
    ((256, 197, 12, 64), jnp.bfloat16),   # vit-b16-b256, the benchmark's cell
    ((8, 50, 12, 64), jnp.bfloat16),      # vit_b_32
    ((8, 197, 16, 64), jnp.bfloat16),     # vit_l_16
    ((8, 197, 12, 64), jnp.float32),      # --precision fp32
    ((8, 384, 6, 128), jnp.bfloat16),     # the bound's edge, one head a group
])
def test_short_attention_fwd_bwd_compiles_for_v5e(v5e_devices, shape, dtype):
    from pytorch_distributed_tpu.ops import short_attention as sa

    one = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)), P())
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def loss(q, k, v):
        return sa.short_attention(q, k, v, False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert _mosaic_calls(compiled) == 2  # forward, one-pass backward
    B, L, H, D = shape
    # the compiler's count of the step's traffic (step_roofline reads it)
    # includes what the kernels declare: eight operand-sized arrays and
    # their results at the least
    operand = B * L * H * D * jnp.dtype(dtype).itemsize
    assert compiled.cost_analysis()["bytes accessed"] >= 12 * operand


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["gspmd", "explicit_collectives"])
def test_vit_step_with_fused_attention_compiles_on_four_chips(
        v5e_devices, monkeypatch, explicit):
    """The image step on four chips with the ViT's kernels in it.  GSPMD:
    ``make_train_step`` hands the model its mesh, and attention wraps the
    Mosaic calls in a shard_map over ``data`` (bare, the TPU compiler
    refuses them: they cannot be partitioned).  Explicit collectives: the
    step is a shard_map already and the calls stand in it bare.  ViT-B/16's
    widths, two blocks, 8 images a chip."""
    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.models import vit
    from pytorch_distributed_tpu.ops import short_attention as sa
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step

    # what the chip would see: a TPU backend, compiled kernels
    monkeypatch.setattr(
        vit, "pick_attention",
        lambda backend, *a, **kw: sa.pick_attention("tpu", *a, **kw))
    monkeypatch.setattr(sa, "_resolve_interpret", lambda interpret: False)
    mesh = Mesh(np.array(v5e_devices), ("data",))
    model = models.create_model("vit_b_16", num_classes=1000, n_layers=2,
                                dtype=jnp.bfloat16)
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=False))
    replicated = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        jax.eval_shape(lambda v: TrainState.create(
            v, sgd_init(v["params"])), variables))
    batch = {
        "images": jax.ShapeDtypeStruct((32, 224, 224, 3), jnp.float32,
                                       sharding=rows),
        "labels": jax.ShapeDtypeStruct((32,), jnp.int32, sharding=rows),
        "weights": jax.ShapeDtypeStruct((32,), jnp.float32, sharding=rows)}
    compiled = make_train_step(
        model, mesh, explicit_collectives=explicit).lower(
        state, batch,
        jax.ShapeDtypeStruct((), jnp.float32, sharding=replicated)).compile()
    text = compiled.as_text()
    assert _mosaic_calls(compiled) == 4  # two blocks, forward and backward
    assert "num_partitions=4" in text
    # no operation over the scores, whole ([32, 12, 197, 197]) or a
    # chip's share of them
    assert ",12,197,197]" not in text


# --- grouped key-value heads, and the tied head's one gradient buffer ---

def test_grouped_flash_fwd_bwd_compiles_for_v5e(v5e_devices):
    """The compressed-latent decoder's call (models/decoder.py CCA): 8
    query heads over 2 key-value heads of 128 at 8,192 positions.  The
    same three kernels; K, V and their gradients keep 2 heads."""
    one = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)), P())
    q = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((2, 8192, 2, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, True, 1024, 1024, False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert _mosaic_calls(compiled) == 3  # forward, dq, dk/dv
    out = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert [x.shape for x in out] == [q.shape, kv.shape, kv.shape]


def _tied_step_compiled(devices, vocab, d, batch, seq, chunks):
    """``make_lm_train_step`` of the tied top-1 decoder (tests/test_zaya1.py
    ``PRESET`` at another vocabulary and width) with AdamW and the fused
    loss, compiled for one described chip from shapes alone."""
    import warnings

    from pytorch_distributed_tpu.models.decoder import (
        DecoderConfig,
        DecoderLM,
    )
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import adamw
    from pytorch_distributed_tpu.train.state import TrainState
    from test_zaya1 import PRESET

    cfg = {**PRESET, "vocab_size": vocab, "hidden_size": d}
    mesh = Mesh(np.array(devices[:1]), ("data",))
    model = DecoderLM(DecoderConfig.from_dict(cfg), dtype=jnp.bfloat16)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    tx = adamw({"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                "weight_decay": 0.1})
    state = jax.eval_shape(lambda v: TrainState.create(
        {"params": v["params"], "batch_stats": v["router"]},
        tx.init(v["params"])), variables)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        step = make_lm_train_step(
            model, mesh, replicated_like(state.params), tx=tx,
            params=state.params, fused_ce_chunks=chunks)
    one = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), state)
    return step.lower(
        state, jax.ShapeDtypeStruct(tokens.shape, jnp.int32,
                                    sharding=NamedSharding(
                                        mesh, P("data", None))),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one)).compile()


def test_tied_step_sums_the_embeddings_gradient_into_one_buffer(
        v5e_devices):
    """The tied embedding's gradient has two parts: the head's, which the
    fused loss accumulates in a float32 [V, d] buffer, and the lookup's, a
    scatter-add of the first block's input gradient.  Compiled for the
    chip, the scatter-add lands in the head's buffer: the step's memory
    ledger (``obs/memory.py``) holds no [V, d] array of zeros for it to
    land in (at the cell's size that is 1.07 GB the step does not take:
    PERF.md 6, PR 32), and the sum is float32."""
    from pytorch_distributed_tpu.obs.memory import ledger_from_compiled

    vocab, d = 8192, 256    # no other array of this size in the step
    compiled = _tied_step_compiled(v5e_devices, vocab, d, 2, 128, 2)
    ledger = ledger_from_compiled(compiled)
    temps = [b for b in ledger.buffers
             if b.defined_at >= 0 and b.dims == [vocab, d]]
    scatter = [b for b in temps if "scatter-add" in b.op_name]
    assert len(scatter) == 1 and scatter[0].dtype == "f32", temps
    assert not any("broadcast" in b.op_name for b in temps), [
        (b.name, b.op_name) for b in temps]


def test_the_steps_memory_scheduler_is_accepted_and_holds_the_tied_peak(
        v5e_devices, monkeypatch):
    """``make_lm_train_step`` hands a TPU mesh's compiler
    ``xla_memory_scheduler=list`` (``step_compiler_options``; a CPU mesh
    gets no option).  The chip's compiler takes the option (an unknown one
    is refused at compile), and the tied step's temporaries under it are
    below what the compiler's own choice among its three schedules gives:
    that choice goes by an estimate, and for this step, whose loss holds
    the head's float32 gradient from its one loop on, it is the depth-first
    schedule, which applies every update after the whole backward pass
    (at the cell's size 16.31 GB against 13.89: PERF.md 6, PR 33).  Should
    the compiler come to choose as well by itself, this fails and the pin
    can go."""
    from pytorch_distributed_tpu.train import lm

    cpu = Mesh(np.array(jax.devices()[:1]), ("data",))
    tpu = Mesh(np.array(v5e_devices[:1]), ("data",))
    assert lm.step_compiler_options(cpu) is None
    assert lm.step_compiler_options(tpu) == {"xla_memory_scheduler": "list"}

    size = dict(vocab=32768, d=512, batch=2, seq=1024, chunks=4)
    pinned = _tied_step_compiled(v5e_devices, **size).memory_analysis()
    monkeypatch.setattr(lm, "step_compiler_options", lambda mesh: None)
    chosen = _tied_step_compiled(v5e_devices, **size).memory_analysis()
    assert pinned.temp_size_in_bytes < 0.9 * chosen.temp_size_in_bytes, (
        pinned.temp_size_in_bytes, chosen.temp_size_in_bytes)


def test_the_tied_step_compiled_for_v5e_says_whose_every_operation_is(
        v5e_devices):
    """The optimized module keeps the ``scope()`` names, and a capture's
    event is named by that module's instruction: ``obs/trace.py``
    ``scope_map`` on the chip compiler's own text.  The fused loss's one
    loop reads ``fused_ce`` (forward: since PR 33 its forward rule runs the
    gradient's products too, and nothing of the scope is left in the
    backward pass at a cotangent of 1); every ``ragged-dot`` Mosaic call,
    whose ``op_name`` the TPU compiler overwrites with ``ragged-dot-none``,
    takes ``moe_experts`` and the phase from the loop that runs it, in all
    three phases; at least 95% of the fusions the chip executes by
    themselves resolve to a scope (at this size 606 of 632: the rest are
    small top-level fusions without an ``op_name`` or under
    ``DecoderLM.update_state`` / ``step_counters``)."""
    from pytorch_distributed_tpu.analysis import hlo
    from pytorch_distributed_tpu.obs import trace

    text = _tied_step_compiled(v5e_devices, 8192, 256, 2, 1024, 2).as_text()
    scopes = trace.scope_map(text, set(trace.SCOPE_NAMES))
    opcodes = {i.name: i.opcode for i in hlo.parse_instructions(text)}

    loops = {n: s for n, s in scopes.items() if opcodes[n] == "while"}
    assert len(loops) >= 4 and all(s.scopes for s in loops.values()), loops
    loss = [s for s in loops.values() if s.scopes[-1] == "fused_ce"]
    assert [s.phase for s in loss] == ["forward"], loops
    under_loss = [s for s in scopes.values() if "fused_ce" in s.scopes]
    assert {s.phase for s in under_loss} == {"forward"}

    ragged = {n: s for n, s in scopes.items() if n.startswith("ragged-dot")}
    assert len(ragged) >= 12, sorted(ragged)
    assert all(s.scopes[-1] == "moe_experts" for s in ragged.values()), {
        n: s for n, s in ragged.items() if s.scopes[-1:] != ("moe_experts",)}
    assert {s.phase for s in ragged.values()} == {
        "forward", "backward", "recompute"}

    fusions = [s for n, s in scopes.items() if opcodes[n] == "fusion"]
    named = sum(bool(s.scopes) for s in fusions)
    assert len(fusions) > 300 and named >= 0.95 * len(fusions), (
        named, len(fusions))
    assert {s.phase for s in fusions if s.scopes} == {
        "forward", "backward", "recompute", "optimizer"}
