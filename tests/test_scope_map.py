"""The compiled step says which scope every device operation belongs to
(obs/trace.py ``compiled_scopes``): the names ``scope()`` puts into the
HLO's ``op_name`` metadata, read back from the optimized module with
``analysis/hlo.py``'s parsers, callers' scopes inherited, the persistent
cache's stale names seen through; and the reduction a capture's nested
events need before they join that map (benchmark/scope_times.py)."""

import json
import os
import sys

import jax
import jax.monitoring
import jax.numpy as jnp
import pytest

from pytorch_distributed_tpu.analysis import hlo
from pytorch_distributed_tpu.obs import trace

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")

NAMES = {"lm_forward", "attn", "moe_experts", "fused_ce", "optimizer",
         "grad_clip", "grad_sync", "lm_head"}


@pytest.mark.parametrize("op_name, scopes, phase", [
    ("jit(step)/jvp(lm_forward)/DecoderLM/layer_0/attn/dot_general",
     ("lm_forward", "attn"), "forward"),
    ("jit(step)/transpose(jvp(lm_forward))/DecoderLM/layer_0/attn/mul",
     ("lm_forward", "attn"), "backward"),
    # the forward pass run again: its path holds transpose( too
    ("jit(step)/transpose(jvp(lm_forward))/DecoderLM/checkpoint/"
     "rematted_computation/layer_2/moe/moe_experts/while",
     ("lm_forward", "moe_experts"), "recompute"),
    ("jit(step)/optimizer/add", ("optimizer",), "optimizer"),
    ("jit(step)/grad_clip/mul", ("grad_clip",), "optimizer"),
    ("jit(step)/grad_sync/b3/psum", ("grad_sync",), "optimizer"),
    # a name inside parentheses, and one that is a whole path component
    ("jit(step)/jvp(lm_head)/dot_general", ("lm_head",), "forward"),
    ("jit(step)/jvp(lm_forward)/checkpoint/blockB/mul", ("lm_forward",),
     "forward"),
    ("jit(step)/jvp(lm_forward)/fused_ce/while/body/dot_general",
     ("lm_forward", "fused_ce"), "forward"),
    # the compiler's own name: no scope, and a phase nobody should trust
    ("ragged-dot-none", (), "forward"),
    ("", (), "unknown"),
])
def test_scope_of_op_name(op_name, scopes, phase):
    assert trace.scope_of_op_name(op_name, NAMES) == (scopes, phase)


# What the TPU compiler's text looks like where it matters: a backward
# `while` under a scope, in its body a Mosaic call the compiler renamed
# and a fusion with no metadata at all, a fused computation (no events of
# its own), a `call`, and a conditional with two branches.
FIXTURE = """\
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(f32[8]{0} %p.1, f32[8]{0} %p.1), metadata={op_name="jit(step)/jvp(lm_forward)/attn/mul"}
}

%region_body.2 (arg.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %arg.2), index=1
  %ragged-dot.5 = f32[8]{0} custom-call(f32[8]{0} %gte.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %ragged-dot.5), kind=kLoop, calls=%fused_computation.1
  %gte.4 = s32[] get-tuple-element((s32[], f32[8]{0}) %arg.2), index=0
  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(s32[] %gte.4, f32[8]{0} %fusion.7)
}

%region_cond.3 (arg.3: (s32[], f32[8])) -> pred[] {
  %arg.3 = (s32[], f32[8]{0}) parameter(0)
  %gte.6 = s32[] get-tuple-element((s32[], f32[8]{0}) %arg.3), index=0
  %c.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(s32[] %gte.6, s32[] %c.1), direction=LT
}

%called.4 (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  ROOT %neg.4 = f32[8]{0} negate(f32[8]{0} %p.4)
}

%branch_a.5 (p.5: f32[8]) -> f32[8] {
  %p.5 = f32[8]{0} parameter(0)
  ROOT %abs.5 = f32[8]{0} abs(f32[8]{0} %p.5)
}

%branch_b.6 (p.6: f32[8]) -> f32[8] {
  %p.6 = f32[8]{0} parameter(0)
  ROOT %exp.6 = f32[8]{0} exponential(f32[8]{0} %p.6), metadata={op_name="jit(step)/optimizer/exp"}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %c.0 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %c.0, f32[8]{0} %x.1)
  %while.10 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%region_cond.3, body=%region_body.2, metadata={op_name="jit(step)/transpose(jvp(lm_forward))/DecoderLM/layer_2/moe/moe_experts/while" source_file="m.py" source_line=7}
  %gte.11 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %while.10), index=1
  %call.12 = f32[8]{0} call(f32[8]{0} %gte.11), to_apply=%called.4, metadata={op_name="jit(step)/jvp(lm_forward)/fused_ce/neg"}
  %pred.13 = pred[] constant(true)
  %cond.14 = f32[8]{0} conditional(pred[] %pred.13, f32[8]{0} %call.12, f32[8]{0} %call.12), true_computation=%branch_a.5, false_computation=%branch_b.6, metadata={op_name="jit(step)/grad_clip/cond"}
  ROOT %copy.15 = f32[8]{0} copy(f32[8]{0} %cond.14)
}
"""


def test_called_computations_of_every_kind():
    ins = {i.name: i for i in hlo.parse_instructions(FIXTURE)}
    assert hlo.called_computations(ins["while.10"]) == [
        ("condition", "region_cond.3"), ("body", "region_body.2")]
    assert hlo.called_computations(ins["fusion.7"]) == [
        ("calls", "fused_computation.1")]
    assert hlo.called_computations(ins["call.12"]) == [
        ("to_apply", "called.4")]
    assert hlo.called_computations(ins["cond.14"]) == [
        ("true_computation", "branch_a.5"),
        ("false_computation", "branch_b.6")]
    assert hlo.called_computations(ins["copy.15"]) == []
    many = hlo.Instruction(
        "cond.2", "conditional", [], "main", "%cond.2 = f32[8]{0} "
        "conditional(s32[] %i, f32[8]{0} %a), "
        "branch_computations={%b0.1, %b1.2, %b2.3}")
    assert hlo.called_computations(many) == [
        ("branch_computations", n) for n in ("b0.1", "b1.2", "b2.3")]


def test_an_instruction_without_a_scope_takes_its_callers():
    """The renamed Mosaic call and the bare fusion in the backward loop's
    body read ``moe_experts``, backward (the compiler's ``ragged-dot-none``
    alone would read forward); the loop's condition too; a call's and a
    conditional's computations take theirs; an instruction with a scope of
    its own keeps it; what is inside a fused computation is not listed;
    the entry's bare copy stays without a scope."""
    got = trace.scope_map(FIXTURE, NAMES)
    experts = (("lm_forward", "moe_experts"), "backward")
    assert got["while.10"] == experts
    assert got["ragged-dot.5"] == experts
    assert got["fusion.7"] == experts
    assert got["lt.1"] == experts
    assert "mul.9" not in got and "p.1" not in got
    assert got["neg.4"] == (("lm_forward", "fused_ce"), "forward")
    assert got["abs.5"] == (("grad_clip",), "optimizer")
    assert got["exp.6"] == (("optimizer",), "optimizer")
    assert got["copy.15"] == ((), "unknown")


def _toy_step(inner: str):
    """A step with two scopes, a checkpointed scan and an optimizer scope,
    built the way ``make_lm_train_step`` builds its own."""
    program = trace.StepProgram("jit_step")

    def step(x, w):
        program.note(x, w)

        def loss(w):
            def body(c, _):
                with trace.scope(inner):
                    return jnp.tanh(c @ w), None

            with trace.scope("toy_forward"):
                y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=3)
                return jnp.sum(y * y)

        g = jax.grad(loss)(w)
        with trace.scope("optimizer"):
            return w - 0.1 * g

    return program.jit(step)


def _opcodes(program: str):
    text = trace.STEP_PROGRAMS[program].jitted.lower(
        *trace.STEP_PROGRAMS[program].args).compile().as_text()
    return {i.name: i.opcode for i in hlo.parse_instructions(text)}


def test_compiled_scopes_gives_every_fusion_and_loop_its_scope():
    step = _toy_step("toy_block")
    step(jnp.ones((8, 16)), jnp.ones((16, 16)))
    got = trace.compiled_scopes("jit_step")
    assert trace.compiled_scopes("jit_step") is got  # kept
    assert not trace.STEP_PROGRAMS["jit_step"].recompiled
    opcodes = _opcodes("jit_step")
    executed = [n for n in got if opcodes[n] in ("fusion", "while", "dot")]
    assert len(executed) >= 8
    assert all(got[n].scopes for n in executed), {
        n: got[n] for n in executed if not got[n].scopes}
    assert {got[n].phase for n in executed} == {
        "forward", "backward", "recompute", "optimizer"}
    loops = [got[n] for n in got if opcodes[n] == "while"]
    assert {s.phase for s in loops} >= {"forward", "backward"}
    assert all(s.scopes[0] == "toy_forward" for s in loops)
    inner = [s for s in got.values() if s.scopes[-1:] == ("toy_block",)]
    assert {s.phase for s in inner} == {"forward", "backward", "recompute"}
    assert {s.scopes for s in got.values() if s.phase == "optimizer"} == {
        ("optimizer",)}


def test_a_step_that_was_never_traced_has_no_map():
    trace.StepProgram("jit_never_ran")
    with pytest.raises(LookupError, match="jit_never_ran"):
        trace.compiled_scopes("jit_never_ran")
    with pytest.raises(LookupError):
        trace.compiled_scopes("jit_nobody_built_this")
    assert trace.dump_scopes(lambda: None, os.devnull) == 0


def test_the_map_is_of_this_processes_names_whatever_the_cache_holds(
        tmp_path):
    """JAX leaves metadata out of the persistent cache's key, so the second
    of two steps that differ only in a scope's name is served the first's
    executable, whose text says the first's name.  ``compiled_scopes`` sees
    that the name it traced is missing, compiles once more with the cache
    out of the way, and says so."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    floors = (jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
        first = _toy_step("first_name")
        first(x, w)
        seen = []
        jax.monitoring.register_event_listener(
            lambda event, **kw: seen.append(event))
        names = {s for v in trace.compiled_scopes("jit_step").values()
                 for s in v.scopes}
        assert "first_name" in names
        assert not trace.STEP_PROGRAMS["jit_step"].recompiled
        # the map's own compile is answered, not made: the shapes the body
        # noted, with the jit's shardings, lower to the module that ran
        assert "/jax/compilation_cache/cache_misses" not in seen, seen
        assert sum(f.startswith("jit_step-")
                   for f in os.listdir(tmp_path)) == 1

        second = _toy_step("second_name")
        second(x, w)
        names = {s for v in trace.compiled_scopes("jit_step").values()
                 for s in v.scopes}
        assert "second_name" in names and "first_name" not in names
        assert trace.STEP_PROGRAMS["jit_step"].recompiled
        # the cache is back in place and still holds one step
        assert jax.config.jax_enable_compilation_cache
        assert sum(f.startswith("jit_step-")
                   for f in os.listdir(tmp_path)) == 1
    finally:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floors[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          floors[1])


def test_lm_fit_writes_scopes_json_beside_its_spans(tmp_path):
    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel import data_parallel_mesh
    from pytorch_distributed_tpu.train.lm import (
        LMTrainer,
        SyntheticTokenDataset,
    )

    mesh = data_parallel_mesh()
    model = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=1)
    ds = SyntheticTokenDataset(32, 16, 64, seed=0)
    out = tmp_path / "profile"
    LMTrainer(model, mesh, ds, batch_size=8, lr=1e-2, fused_ce_chunks=2,
              profile_dir=str(out), profile_steps="1").fit(
                  2, print_freq=100)
    with open(out / "scopes.json") as f:
        scopes = json.load(f)
    assert {tuple(v) for v in map(tuple, scopes.values())} == {
        ("scopes", "phase")}
    phases = {v["phase"] for v in scopes.values()}
    assert phases >= {"forward", "backward", "optimizer"}
    assert any(v["scopes"][-1:] == ["fused_ce"] for v in scopes.values())
    assert any(v["scopes"] == ["optimizer"] for v in scopes.values())
    with open(out / "spans.jsonl") as f:
        assert any(json.loads(line)["name"] == "dispatch" for line in f)
    assert any(name.endswith(".xplane.pb") for _, _, names in os.walk(out)
               for name in names)


@pytest.fixture
def scope_times():
    sys.path.insert(0, BENCH)
    try:
        import scope_times as module
        yield module
    finally:
        sys.path.remove(BENCH)


def test_self_time_is_duration_less_the_events_inside(scope_times):
    """A ``while``'s event covers its body's on the same line: the loop
    keeps what its children leave, a child of a child is taken from the
    child alone, and what follows the loop is untouched."""
    events = [
        (0.0, 10.0, "%while.1 = (s32[]) while(...)"),
        (1.0, 3.0, "%fusion.2 = f32[8]{0} fusion(...)"),
        (3.0, 7.0, "%while.3 = (s32[]) while(...)"),   # nested loop
        (4.0, 6.0, "%fusion.4 = f32[8]{0} fusion(...)"),
        (8.0, 10.0, "%attn.5 = (bf16[2,8,4]{2,1,0}) custom-call(...)"),
        (10.0, 12.0, "%fusion.6 = f32[8]{0} fusion(...)"),
    ]
    got = dict((scope_times.instruction(n), t)
               for n, t in scope_times.self_times(events[::-1]))
    assert got == {"while.1": 2.0, "fusion.2": 2.0, "while.3": 2.0,
                   "fusion.4": 2.0, "attn.5": 2.0, "fusion.6": 2.0}
    assert sum(got.values()) == 12.0   # the line's busy time, counted once
    assert scope_times.self_times([]) == []
