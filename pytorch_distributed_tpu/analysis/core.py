"""shardlint core: lower every jitted step builder on a CPU mesh and walk
its jaxpr + compiled HLO for the hazard classes that previously needed a
hand-grep per PR.

The analyzer cross-references two views of one program:

- the **jaxpr** (``jax.make_jaxpr`` over the jitted step) carries *global*
  logical shapes for every intermediate, plus structure: which values are
  scan/while loop carries, which convert_element_type equations upcast,
  which subtrees sit inside ``shard_map`` (already per-shard — excluded
  from the global view);
- the **post-optimization HLO** (``jitted.lower(...).compile().as_text()``)
  carries *per-device* truth: post-SPMD shapes, explicit collectives, and
  the ``input_output_alias`` donation map.

A global-shaped intermediate that shows up at FULL size in the per-device
module is materialized on every device — replicated (or all-gathered)
rather than sharded.  Severity follows structure:

- a **loop carry** at full global size is ``replicated-large-tensor``
  (error): an accumulator rebuilt per device per iteration — exactly the
  PR-1 fused-CE ``[V, D]`` dE bug, and the silent-DP-waste class of
  arxiv 2004.13336;
- a param-shaped one-shot intermediate (grads, updated params) is the
  *declared* pure-DP layout → ``replicated-state`` (info), the standing
  FSDP opportunity, not a regression;
- anything else at full size is ``replicated-large-tensor`` (error).

Donation accounting maps ``donate_argnums`` arguments to flattened entry
parameters and checks XLA actually aliased each one (``lost-donation``);
steps that never donate are probed for shape-matching input/output pairs
(``no-donation``).  Collective counts/bytes are pinned against
``analysis/baseline.json`` (EQuARX-style per-step collective budget,
arxiv 2506.17615).  The host-sync lint (analysis/astlint.py) runs over the
``HOT_LOOPS`` registry.

Donation audit record (why the sweep's expectations are what they are):

- ``make_train_step`` / ``make_lm_train_step`` donate state (argnum 0) —
  this covers all three pipeline schedules too, since gpipe/1f1b/
  interleaved steps are jitted through ``make_lm_train_step`` (the
  schedules themselves are shard_map bodies, not jit boundaries);
- ``make_eval_step`` / ``make_lm_eval_step`` must NOT donate: the trainer
  reuses one state across every eval batch, and the batch inputs have no
  shape-compatible outputs (metrics are scalars), so donating them would
  only produce XLA unused-donation warnings;
- speculative decode (models/speculative.py) does NOT donate its KV
  caches even though they are dead after each ``apply`` call: XLA dedups
  identical executable outputs (every layer's equal ``cache_index``
  scalar aliases one buffer), so donating the returned tree raises PJRT's
  "attempt to donate the same buffer twice" on the next call — attempted
  and reverted, documented at the jit site.
"""

from __future__ import annotations

import dataclasses
import os
from collections import Counter, OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pytorch_distributed_tpu.analysis import astlint
from pytorch_distributed_tpu.analysis import hlo as hlo_mod
from pytorch_distributed_tpu.analysis import jaxpr as jaxpr_mod
from pytorch_distributed_tpu.analysis.report import Finding, StepReport

# Replicated intermediates / upcasts below these sizes are noise at scale;
# tests and --selftest pass smaller thresholds to probe tiny fixtures.
DEFAULT_MIN_REPLICATED_BYTES = 1 << 20
DEFAULT_MIN_PROMOTION_BYTES = 1 << 20
# Missing donated leaves above this are errors (below: info — e.g. a step
# counter XLA chose not to alias is odd but harmless).
DEFAULT_MIN_DONATION_BYTES = 1 << 10
# A never-donating step warns only when at least this much input memory
# shape-matches its outputs.
DEFAULT_NO_DONATION_BYTES = 1 << 20

# Hot training loops lint_hot_loops() enforces the lazy-sync discipline
# on, as (path relative to the package root, qualified function names).
HOT_LOOPS: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("train/trainer.py", ("Trainer.train_epoch",)),
    ("train/lm.py", ("LMTrainer.fit",)),
)

# Tiny-but-structured sweep configs: small enough that every step compiles
# in seconds on the CPU mesh, big enough that shardings are nontrivial.
_LM = dict(vocab=64, d_model=32, n_heads=4, seq=16, batch=8)


def _leaf_bytes(leaf) -> int:
    try:
        return int(np.prod(leaf.shape, dtype=np.int64)
                   * np.dtype(leaf.dtype).itemsize)
    except Exception:
        return 0


@dataclasses.dataclass
class Lowering:
    """One recipe's lowered + compiled step, cached for reuse.

    Lowering and compiling the 12 step builders dominates shardlint's
    (and the test suite's) wall clock on the 1-core CI host; every
    analysis downstream of compilation — hazard detectors, collective
    budgets, the comm ledger — is pure text/jaxpr walking over this
    record, so one sweep can feed them all (``get_lowering``)."""

    name: str
    jitted: Any
    args: Tuple[Any, ...]
    donate: Optional[Tuple[int, ...]]
    mesh: Any
    text: str          # post-optimization HLO
    compiled: Any
    closed: Any        # closed jaxpr

    @property
    def mesh_shape(self) -> Dict[str, int]:
        return dict(self.mesh.shape) if self.mesh is not None else {}


def lower_jitted(jitted, args: Sequence[Any], *, name: str, mesh=None,
                 donate: Optional[Sequence[int]] = None) -> Lowering:
    """The expensive half of the analysis: lower + compile + jaxpr."""
    import jax

    count_compile()
    compiled = jitted.lower(*args).compile()
    return Lowering(
        name=name, jitted=jitted, args=tuple(args),
        donate=None if donate is None else tuple(donate), mesh=mesh,
        text=compiled.as_text(), compiled=compiled,
        closed=jax.make_jaxpr(jitted)(*args))


_LOWERING_CACHE: Dict[str, Lowering] = {}
_COMPILE_COUNT = 0


def compile_count() -> int:
    """AOT lower+compile sweeps paid by this process so far.  The
    zero-extra-compiles fence: tests snapshot it around the memory-ledger
    sweep to prove ledgering rides the cached lowerings, and
    analysis/lowering.py's budget assert fences the process total."""
    return _COMPILE_COUNT


def count_compile() -> None:
    """Book one AOT compile against the process-wide counter.  External
    lower+compile paths (the trainers' ledger emission via
    ``lowering.aot_ledgers``) call this so the compile budget sees every
    sweep in the process, not just the recipe cache's."""
    global _COMPILE_COUNT
    _COMPILE_COUNT += 1


def get_lowering(name: str) -> Lowering:
    """Session-memoized lowering for one recipe.  The detectors and the
    comm ledger are pure functions of this record, so repeated
    ``analyze_recipe`` calls (tests probing different thresholds, the
    comms sweep, the baseline diff) share one compile."""
    low = _LOWERING_CACHE.get(name)
    if low is None:
        jitted, args, donate, mesh = RECIPES[name]()
        low = lower_jitted(jitted, args, name=name, mesh=mesh, donate=donate)
        _LOWERING_CACHE[name] = low
    return low


def clear_lowering_cache() -> None:
    _LOWERING_CACHE.clear()


def analyze_jitted(
    jitted,
    args: Sequence[Any],
    *,
    name: str,
    mesh=None,
    donate: Optional[Sequence[int]] = None,
    **thresholds,
) -> StepReport:
    """Lower + compile one jitted step and emit its StepReport.

    ``donate``: the argnums the *caller* claims are donated — a tuple
    triggers the lost-donation check, ``()`` the no-donation opportunity
    probe, ``None`` skips donation accounting entirely (single-purpose
    kernels with no state)."""
    return analyze_lowering(
        lower_jitted(jitted, args, name=name, mesh=mesh, donate=donate),
        **thresholds)


def analyze_lowering(
    low: Lowering,
    *,
    min_replicated_bytes: int = DEFAULT_MIN_REPLICATED_BYTES,
    min_promotion_bytes: int = DEFAULT_MIN_PROMOTION_BYTES,
    min_donation_bytes: int = DEFAULT_MIN_DONATION_BYTES,
    declared_zero: bool = False,
) -> StepReport:
    """The cheap half: run every detector over an existing Lowering.

    ``declared_zero``: the step claims ``--zero wus`` weight-update
    sharding (parallel/zero.py), so replicated param-shaped optimizer
    state is no longer the *declared* layout — the ``replicated-state``
    info finding promotes to a hard error (the WUS sharding silently
    fell back to replicated DP)."""
    name, text, closed = low.name, low.text, low.closed
    args, donate = low.args, low.donate

    mesh_shape = low.mesh_shape
    n_devices = 1
    for v in mesh_shape.values():
        n_devices *= v

    report = StepReport(name=name, mesh_shape=mesh_shape)
    instrs = hlo_mod.parse_instructions(text)
    report.collectives = hlo_mod.collect_collectives(instrs)
    try:
        ma = low.compiled.memory_analysis()
        report.memory = {
            k: int(getattr(ma, k))
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes")
            if hasattr(ma, k)
        }
    except Exception:
        report.memory = {}

    if n_devices > 1:
        param_shapes = set(hlo_mod.entry_parameter_shapes(text))
        index = hlo_mod.nonparameter_shape_index(instrs)
        carries = jaxpr_mod.loop_carry_shapes(closed)
        globals_ = jaxpr_mod.global_intermediate_shapes(
            closed, min_bytes=min_replicated_bytes)
        for shape, meta in sorted(globals_.items(),
                                  key=lambda kv: -kv[1]["bytes"]):
            ins = index.get(shape)
            if ins is None:
                continue  # per-device shape is smaller: properly sharded
            dtype, dims = shape
            # Gradients of replicated params often appear transposed
            # (dot_general layout) — still the declared DP state layout.
            param_shaped = (shape in param_shapes
                            or (dtype, dims[::-1]) in param_shapes)
            carry = carries.get(shape)
            if carry is not None:
                report.add(Finding(
                    kind="replicated-large-tensor", severity="error",
                    where=name, bytes=meta["bytes"], shape=dims, dtype=dtype,
                    message=(
                        f"loop-carried accumulator materialized at full "
                        f"global size on every device of the {n_devices}-"
                        f"device mesh (carry of {carry['primitive']} at "
                        f"{carry['source']}; HLO {ins.opcode} '{ins.name}')"
                        " — shard the carry (the PR-1 fused-CE dE class)"),
                ))
            elif param_shaped:
                if declared_zero:
                    report.add(Finding(
                        kind="replicated-state", severity="error",
                        where=name, bytes=meta["bytes"], shape=dims,
                        dtype=dtype,
                        message=(
                            f"param-shaped intermediate ({meta['primitive']}"
                            f" at {meta['source']}) updated at full size per "
                            "device under a step declared --zero wus — the "
                            "weight-update sharding fell back to replicated "
                            "DP (check the momentum shardings reach the jit "
                            "in_shardings)"),
                    ))
                else:
                    report.add(Finding(
                        kind="replicated-state", severity="info",
                        where=name, bytes=meta["bytes"], shape=dims,
                        dtype=dtype,
                        message=(
                            f"param-shaped intermediate ({meta['primitive']}"
                            f" at {meta['source']}) updated at full size per "
                            "device — the declared replicated (pure-DP) "
                            "state layout; standing FSDP/ZeRO opportunity"),
                    ))
            else:
                report.add(Finding(
                    kind="replicated-large-tensor", severity="error",
                    where=name, bytes=meta["bytes"], shape=dims, dtype=dtype,
                    message=(
                        f"intermediate ({meta['primitive']} at "
                        f"{meta['source']}; HLO {ins.opcode} '{ins.name}') "
                        f"materialized at full global size on every device "
                        f"of the {n_devices}-device mesh — add a sharding"),
                ))

    for prom in jaxpr_mod.find_dtype_promotions(closed, min_promotion_bytes):
        report.add(Finding(
            kind="dtype-promotion", severity="warn", where=name,
            bytes=prom["bytes"], shape=tuple(prom["shape"]),
            dtype=prom["to"],
            message=(f"{prom['from']}->{prom['to']} upcast of a large "
                     f"intermediate at {prom['source']} — doubles its "
                     "footprint; keep backward math in the narrow dtype or "
                     "use preferred_element_type for accumulation"),
        ))

    if donate is not None:
        _donation_findings(report, text, args, tuple(donate),
                           min_donation_bytes)
    return report


def _donation_findings(report: StepReport, text: str, args: Sequence[Any],
                       donate: Tuple[int, ...], min_bytes: int) -> None:
    import jax

    aliased = set(hlo_mod.aliased_param_numbers(text))
    flat: List[Tuple[Any, Any]] = []  # (key path, leaf) in entry-param order
    ranges: List[Tuple[int, int]] = []
    pos = 0
    for a in args:
        leaves, _ = jax.tree_util.tree_flatten_with_path(a)
        ranges.append((pos, pos + len(leaves)))
        flat.extend(leaves)
        pos += len(leaves)
    report.donation = {"aliased_params": sorted(aliased), "arg_leaves": pos}
    if donate:
        n_entry = len(hlo_mod.entry_parameter_shapes(text))
        if n_entry and n_entry != pos:
            # Unused-argument pruning / constant hoisting changed the
            # parameter list; the leaf->parameter-number mapping would be
            # wrong, so don't guess.
            report.donation["note"] = (
                f"entry parameter count {n_entry} != flattened arg leaf "
                f"count {pos}; donation mapping skipped")
            return
        expected = set()
        for argnum in donate:
            expected |= set(range(*ranges[argnum]))
        missing = sorted(expected - aliased)
        missing_bytes = sum(_leaf_bytes(flat[i][1]) for i in missing)
        report.donation.update({
            "expected": len(expected),
            "aliased": len(expected & aliased),
            "missing": missing,
            "missing_bytes": missing_bytes,
        })
        if missing:
            names = ", ".join(
                f"arg{_argnum_of(ranges, i)}{jax.tree_util.keystr(flat[i][0])}"
                for i in missing[:6])
            more = "" if len(missing) <= 6 else f" (+{len(missing) - 6} more)"
            report.add(Finding(
                kind="lost-donation",
                severity="error" if missing_bytes >= min_bytes else "info",
                where=report.name, bytes=missing_bytes,
                message=(
                    f"{len(missing)} donated leaves not input/output-aliased "
                    f"by XLA: {names}{more} — a shape/dtype/sharding mismatch "
                    "between the donated input and every output drops the "
                    "donation silently (double-buffered state)"),
            ))
    else:
        if not aliased:
            big_in = Counter(
                s for s in hlo_mod.entry_parameter_shapes(text)
                if hlo_mod.shape_bytes(s) >= min_bytes)
            outs = Counter(hlo_mod.entry_output_shapes(text))
            opportunity = sum(
                hlo_mod.shape_bytes(s) * min(c, outs[s])
                for s, c in big_in.items() if s in outs)
            report.donation["opportunity_bytes"] = opportunity
            if opportunity >= max(min_bytes, DEFAULT_NO_DONATION_BYTES):
                report.add(Finding(
                    kind="no-donation", severity="warn", where=report.name,
                    bytes=opportunity,
                    message=(
                        f"step never donates, but "
                        f"{opportunity / 2**20:.1f} MiB of inputs shape-"
                        "match outputs — pass donate_argnums for state that "
                        "is dead after the step"),
                ))


def _argnum_of(ranges: Sequence[Tuple[int, int]], leaf_index: int) -> int:
    for argnum, (lo, hi) in enumerate(ranges):
        if lo <= leaf_index < hi:
            return argnum
    return -1


# ------------------------------------------------------------- host-sync

def lint_hot_loops() -> StepReport:
    """Run the astlint pass over the registered training hot loops."""
    import pytorch_distributed_tpu as pkg

    base = os.path.dirname(os.path.abspath(pkg.__file__))
    report = StepReport(name="hot-loops")
    for rel, functions in HOT_LOOPS:
        path = os.path.join(base, rel)
        for f in astlint.lint_file(path, hot_functions=functions):
            report.add(f)
    return report


# ------------------------------------------------------------ the sweep

def _require_devices(n: int) -> None:
    import jax

    if len(jax.devices()) < n:
        raise RuntimeError(
            f"shardlint needs a {n}-way CPU mesh; run with XLA_FLAGS="
            f"'--xla_force_host_platform_device_count={max(n, 8)}' set "
            "before jax is imported (scripts/shardlint.py does this)")


def _mesh(axes: Tuple[str, ...], shape: Tuple[int, ...]):
    import jax

    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh

    n = 1
    for s in shape:
        n *= s
    _require_devices(n)
    return build_mesh(MeshSpec(axes, shape), jax.devices()[:n])


def _image_batch(batch=16, image=8, classes=10, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return {
        "images": jnp.asarray(
            rng.normal(size=(batch, image, image, 3)), jnp.float32),
        "labels": jnp.asarray(
            rng.integers(0, classes, size=batch), jnp.int32),
        "weights": jnp.ones((batch,), jnp.float32),
    }


def _tiny_image_model(classes=10):
    import flax.linen as nn

    class TinyMLP(nn.Module):
        """BN-free classifier: isolates the step/collective plumbing."""

        classes: int = 10

        @nn.compact
        def __call__(self, x, train: bool = True):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(self.classes)(x)

    return TinyMLP(classes=classes)


def _image_state(model, grad_compress: str = "none", explicit: bool = False,
                 n_data: int = 4):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops import qcomm
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8, 8, 3)), train=False)
    residual = qcomm.init_residual(variables["params"], grad_compress,
                                   explicit=explicit, n_data=n_data)
    return TrainState.create(variables, sgd_init(variables["params"]),
                             residual=residual)


def _recipe_train_image(explicit: bool, grad_compress: str = "none",
                        overlap: str = "none", bucket_mb: float = 4.0):
    import jax.numpy as jnp

    from pytorch_distributed_tpu.train.steps import make_train_step

    mesh = _mesh(("data",), (4,))
    model = _tiny_image_model()
    state = _image_state(model, grad_compress=grad_compress,
                         explicit=explicit)
    step = make_train_step(model, mesh, explicit_collectives=explicit,
                           grad_compress=grad_compress, overlap=overlap,
                           bucket_mb=bucket_mb)
    return step, (state, _image_batch(), jnp.float32(0.1)), (0,), mesh


def _recipe_train_image_zero(grad_compress: str = "none"):
    """Explicit-collectives image step under ``--zero wus`` (parallel/
    zero.py): the hand-written grad allreduce becomes a reduce-scatter +
    delta all-gather and momentum lives as stacked 1/N chunks."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.ops import qcomm
    from pytorch_distributed_tpu.parallel import zero as zero_lib
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step

    mesh = _mesh(("data",), (4,))
    model = _tiny_image_model()
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8, 8, 3)), train=False)
    quantized = grad_compress in qcomm.QUANTIZED_MODES
    residual = qcomm.init_residual(variables["params"], grad_compress,
                                   explicit=True, n_data=4)
    state = TrainState.create(
        variables,
        zero_lib.init_wus_momentum(variables["params"], 4,
                                   quantized=quantized),
        residual=residual)
    step = make_train_step(model, mesh, explicit_collectives=True,
                           grad_compress=grad_compress, zero="wus")
    return step, (state, _image_batch(), jnp.float32(0.1)), (0,), mesh


def _recipe_lm_overlap(grad_compress: str = "none"):
    """Explicit shard_map DP LM step under the bucketed comm-overlap
    scheduler (parallel/overlap.py): the grad sync lowers as per-bucket
    collectives scope-labeled ``b<k>``, and with ``--grad-compress int8``
    the compiled wire carries s8 payloads + f32 scales — the HLO-ledger
    evidence that compression rides the real collectives, not a numerics
    emulation."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.ops import qcomm
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = _mesh(("data",), (4,))
    model = TransformerLM(
        vocab_size=_LM["vocab"], d_model=_LM["d_model"],
        n_heads=_LM["n_heads"], n_layers=1)
    tokens = jnp.zeros((_LM["batch"], _LM["seq"]), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    residual = qcomm.init_residual(params, grad_compress, explicit=True,
                                   n_data=4)
    state = TrainState.create({"params": params}, sgd_init(params),
                              residual=residual)
    # ~8 KiB buckets so even the tiny model splits into several buckets
    # and the ledger exercises multi-bucket b<k> attribution.
    step = make_lm_train_step(model, mesh, replicated_like(params),
                              grad_compress=grad_compress,
                              overlap="bucketed", bucket_mb=1 / 128)
    return step, (state, tokens, jnp.float32(0.1)), (0,), mesh


def _recipe_train_lm_zero():
    """GSPMD LM step with ``zero='wus'``: momentum leaves take fsdp_specs
    data-axis shardings, XLA derives the weight-update collectives."""
    import jax.numpy as jnp

    mesh = _mesh(("data",), (4,))
    _, _, state, tokens, step = _lm_setup(mesh, zero="wus")
    return step, (state, tokens, jnp.float32(0.1)), (0,), mesh


def _recipe_eval_image():
    from pytorch_distributed_tpu.train.steps import make_eval_step

    mesh = _mesh(("data",), (4,))
    model = _tiny_image_model()
    state = _image_state(model)
    step = make_eval_step(model, mesh)
    return step, (state, _image_batch()), (), mesh


def _lm_setup(mesh, specs=None, vocab=None, **step_kw):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    model = TransformerLM(
        vocab_size=vocab or _LM["vocab"], d_model=_LM["d_model"],
        n_heads=_LM["n_heads"], n_layers=1)
    tokens = jnp.zeros((_LM["batch"], _LM["seq"]), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    if specs is None:
        specs = replicated_like(params)
    elif callable(specs):
        specs = specs(params)
    state = TrainState.create({"params": params}, sgd_init(params))
    if step_kw.get("zero") == "wus":
        step_kw["params"] = params  # wus sizes its momentum specs from these
    step = make_lm_train_step(model, mesh, specs, **step_kw)
    return model, specs, state, tokens, step


def _recipe_lm_train(fused_ce_mode: Optional[str],
                     vocab: Optional[int] = None):
    """The GSPMD DP LM step, unfused or on the fused loss.  ``vocab``:
    another vocabulary than the sweep's (tests/test_memory.py ranks the
    loss variants' peaks at one whose logits outweigh the hidden rows)."""
    import jax.numpy as jnp

    mesh = _mesh(("data",), (4,))
    kw = {} if fused_ce_mode is None else dict(
        fused_ce_chunks=2, fused_ce_mode=fused_ce_mode)
    _, _, state, tokens, step = _lm_setup(mesh, vocab=vocab, **kw)
    return step, (state, tokens, jnp.float32(0.1)), (0,), mesh


def _recipe_lm_fused_tp():
    import jax.numpy as jnp

    from pytorch_distributed_tpu.parallel.tp import tp_specs

    mesh = _mesh(("data", "model"), (2, 2))
    _, _, state, tokens, step = _lm_setup(
        mesh, specs=tp_specs, fused_ce_chunks=2, fused_ce_mode="tp")
    return step, (state, tokens, jnp.float32(0.1)), (0,), mesh


def _recipe_lm_eval():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel.tp import replicated_like
    from pytorch_distributed_tpu.train.lm import make_lm_eval_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = _mesh(("data",), (4,))
    model = TransformerLM(
        vocab_size=_LM["vocab"], d_model=_LM["d_model"],
        n_heads=_LM["n_heads"], n_layers=1)
    tokens = jnp.zeros((_LM["batch"], _LM["seq"]), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    state = TrainState.create({"params": params}, sgd_init(params))
    step = make_lm_eval_step(model, mesh, replicated_like(params))
    return step, (state, tokens), (), mesh


def _recipe_pipeline(schedule: str):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.pipeline_lm import (
        PipelinedTransformerLM,
        pp_specs,
    )
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    if schedule == "interleaved":
        mesh = _mesh(("data", "pipe"), (2, 2))
        stages, micro, virtual = 2, 2, 2
    else:
        mesh = _mesh(("data", "pipe"), (1, 4))
        stages, micro, virtual = 4, 4, 1
    model = PipelinedTransformerLM(
        vocab_size=_LM["vocab"], d_model=_LM["d_model"],
        n_heads=_LM["n_heads"], n_layers=4, n_stages=stages,
        n_microbatches=micro, mesh=mesh, schedule=schedule,
        n_virtual=virtual)
    tokens = jnp.zeros((_LM["batch"], _LM["seq"]), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    state = TrainState.create({"params": params}, sgd_init(params))
    step = make_lm_train_step(model, mesh, pp_specs(params))
    return step, (state, tokens, jnp.float32(0.1)), (0,), mesh


def _recipe_decode():
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.models.generate import _make_run
    from pytorch_distributed_tpu.models.transformer import TransformerLM

    B, P, new = 2, 8, 4
    run = _make_run(B, P, new, _LM["vocab"], _LM["d_model"],
                    _LM["n_heads"], 1, "float32", 0.0, 0, 0.0, "", False)
    model = TransformerLM(
        vocab_size=_LM["vocab"], d_model=_LM["d_model"],
        n_heads=_LM["n_heads"], n_layers=1, attn_impl="dense",
        decode=True, max_len=P + new)
    prompt = jnp.zeros((B, P), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    # Single-device decode: no mesh, no donation by design (the cache is
    # created inside the jit; params are reused across calls).
    return run, (params, prompt, jax.random.PRNGKey(0)), None, None


def _recipe_serve(phase: str):
    """The serving engine's jitted steps (serving/engine.py), at the
    engine's own tiny reference shapes.  ``_make_steps`` is lru-cached,
    so these lowerings ARE the callables a same-config engine runs — the
    recipe sweep, shardlint, and the ledgers fence serving traffic with
    no second trace.  No donation (pools thread through like the decode
    cache); a 1-device data mesh so the baseline sweep books the entry.
    """
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.serving.engine import _make_steps
    from pytorch_distributed_tpu.serving.kvpool import init_pools

    B, NB, BS, W, C = 2, 8, 4, 4, 8
    steps = _make_steps(_LM["vocab"], _LM["d_model"], _LM["n_heads"], 1,
                        BS, 0.0, 0, 1.0, "")
    pk, pv = init_pools(1, NB, BS, _LM["n_heads"],
                        _LM["d_model"] // _LM["n_heads"])
    table1 = jnp.zeros((1, W), jnp.int32)
    params = steps.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32), pk, pv,
        table1, jnp.zeros((1, 1), jnp.int32))["params"]
    key = jax.random.PRNGKey(0)
    mesh = _mesh(("data",), (1,))
    if phase == "prefill":
        args = (params, pk, pv, jnp.zeros((1, C), jnp.int32),
                jnp.int32(0), jnp.int32(C), table1, key)
        return steps.prefill, args, None, mesh
    args = (params, pk, pv, jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32), jnp.zeros((B, W), jnp.int32), key)
    return steps.decode, args, None, mesh


# Every jitted step builder in the framework, as zero-arg constructors
# returning (jitted, example_args, donate_argnums-or-None, mesh-or-None).
RECIPES: "OrderedDict[str, Callable[[], tuple]]" = OrderedDict([
    ("train_image_gspmd", lambda: _recipe_train_image(False)),
    ("train_image_explicit", lambda: _recipe_train_image(True)),
    # Compressed gradient sync (ops/qcomm.py) over the explicit shard_map
    # path: the pinned per-kind byte budgets make an accidental f32
    # fallback in grad_sync a hard collective-regression error.
    ("train_image_bf16", lambda: _recipe_train_image(True, "bf16")),
    ("train_image_int8", lambda: _recipe_train_image(True, "int8")),
    # Weight-update sharding (parallel/zero.py): the pinned reduce-scatter
    # / all-gather budgets make an accidental allreduce fallback (or a
    # momentum layout regression) a hard collective-regression error.
    # Bucketed comm-overlap scheduler (parallel/overlap.py): grad sync
    # splits into per-bucket collectives (scope b<k>) so each can overlap
    # the remaining backward.  Bucketing must not change totals — the
    # pinned budgets fence a bucket-count or per-bucket-bytes drift, and
    # the int8 variant pins that compression survives onto the real wire.
    ("train_image_bucketed",
     lambda: _recipe_train_image(True, overlap="bucketed",
                                 bucket_mb=1 / 128)),
    ("lm_train_bucketed", lambda: _recipe_lm_overlap()),
    ("lm_train_bucketed_int8", lambda: _recipe_lm_overlap("int8")),
    ("train_image_zero", _recipe_train_image_zero),
    ("train_lm_zero", _recipe_train_lm_zero),
    ("eval_image", _recipe_eval_image),
    ("lm_train_dp", lambda: _recipe_lm_train(None)),
    ("lm_fused_ce_replicated", lambda: _recipe_lm_train("replicated")),
    ("lm_fused_ce_dp", lambda: _recipe_lm_train("dp")),
    ("lm_fused_ce_tp", _recipe_lm_fused_tp),
    ("lm_eval", _recipe_lm_eval),
    ("lm_pp_gpipe", lambda: _recipe_pipeline("gpipe")),
    ("lm_pp_1f1b", lambda: _recipe_pipeline("1f1b")),
    ("lm_pp_interleaved", lambda: _recipe_pipeline("interleaved")),
    ("decode_greedy", _recipe_decode),
    ("serve_prefill", lambda: _recipe_serve("prefill")),
    ("serve_decode", lambda: _recipe_serve("decode")),
])


# Recipes that declare --zero wus: analyze_recipe promotes their
# replicated-state finding from info to error (the declared layout IS
# sharded optimizer state, so a replicated fallback is a regression).
ZERO_RECIPES = frozenset({"train_image_zero", "train_lm_zero"})


def analyze_recipe(name: str, **thresholds) -> StepReport:
    """Analyze one recipe, reusing the session's cached lowering: only the
    first call per step pays the compile; threshold variations re-run just
    the detectors."""
    thresholds.setdefault("declared_zero", name in ZERO_RECIPES)
    return analyze_lowering(get_lowering(name), **thresholds)


def comm_ledger_for(name: str):
    """The itemized comm ledger (obs/comms.py) for one recipe, off the
    shared lowering cache."""
    from pytorch_distributed_tpu.obs import comms

    low = get_lowering(name)
    return comms.ledger_from_hlo_text(low.text, step=name,
                                      mesh_shape=low.mesh_shape)


def sweep_comm_ledgers(names: Optional[Sequence[str]] = None):
    """Ledgers for every (or the named subset of) recipe step builders —
    what ``scripts/shardlint.py --comm-ledger`` serializes to
    ``comm_ledger.json``."""
    selected = list(RECIPES) if names is None else [
        n for n in names if n in RECIPES]
    return [comm_ledger_for(n) for n in selected]


def mem_ledger_for(name: str):
    """The live-range memory ledger (obs/memory.py) for one recipe, off
    the shared lowering cache — the ``memory_analysis()`` ground truth
    and per-argument buffer classes ride the same compiled record, so
    the whole sweep is zero extra compiles."""
    from pytorch_distributed_tpu.obs import comms, memory

    low = get_lowering(name)
    return memory.ledger_from_hlo_text(
        low.text, step=name, mesh_shape=low.mesh_shape,
        arg_classes=memory.arg_classes_of(low.args),
        measured_peak_bytes=comms.compiled_peak_bytes(low.compiled))


def sweep_mem_ledgers(names: Optional[Sequence[str]] = None):
    """Memory ledgers for every (or the named subset of) recipe step —
    ``scripts/shardlint.py --mem-ledger`` serializes these to
    ``mem_ledger.json``."""
    selected = list(RECIPES) if names is None else [
        n for n in names if n in RECIPES]
    return [mem_ledger_for(n) for n in selected]


def analyze_all(names: Optional[Sequence[str]] = None,
                include_lint: bool = True, **thresholds) -> List[StepReport]:
    """Analyze every recipe step (or the named subset) + the hot-loop lint."""
    selected = list(RECIPES) if names is None else list(names)
    unknown = [n for n in selected if n not in RECIPES and n != "hot-loops"]
    if unknown:
        raise KeyError(f"unknown steps {unknown}; known: {list(RECIPES)}")
    reports = [analyze_recipe(n, **thresholds)
               for n in selected if n in RECIPES]
    if include_lint and (names is None or "hot-loops" in selected):
        reports.append(lint_hot_loops())
    return reports


def baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


# ----------------------------------------------------------- the selftest

def build_synthetic_bad_step(mesh, data_axis: str = "data"):
    """A step with all three compiled-level hazards planted:

    1. a replicated ``f32[2048, 128]`` (1 MiB) scan-carry accumulator;
    2. a ``bf16[8, 65536]`` → f32 (2 MiB) materialized upcast;
    3. a donated argument no output can alias (the donation is lost).

    Returns ``(jitted, args, donate_argnums)`` for ``analyze_jitted``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    N, D = 2048, 128
    B, F = 8, 65536

    def bad_step(w, xb):
        act = (xb * jnp.bfloat16(1.5)).astype(jnp.float32)  # planted upcast
        s = jnp.sum(act) / act.size

        def body(c, _):
            return c * 0.999 + s, ()

        # planted replicated accumulator: a full-size global carry on a
        # >1-device mesh (nothing shards it)
        acc, _ = jax.lax.scan(
            body, jnp.full((N, D), s, jnp.float32), jnp.arange(4))
        # outputs deliberately share no shape with w: donation is lost
        return acc.astype(jnp.bfloat16), s + jnp.sum(w)

    rep = NamedSharding(mesh, P())
    jitted = jax.jit(
        bad_step,
        in_shardings=(rep, NamedSharding(mesh, P(data_axis, None))),
        out_shardings=(rep, rep),
        donate_argnums=(0,),
    )
    args = (jnp.ones((N, D // 2), jnp.float32),
            jnp.ones((B, F), jnp.bfloat16))
    return jitted, args, (0,)


_SYNTHETIC_BAD_LOWERING: Optional[Lowering] = None


def get_synthetic_bad_lowering() -> Lowering:
    """Session-memoized lowering of the planted synthetic-bad step on the
    4-way data mesh — the same one-compile discipline as
    :func:`get_lowering`, so ``selftest`` and the shardlint tests share a
    single compile instead of each paying their own."""
    global _SYNTHETIC_BAD_LOWERING
    if _SYNTHETIC_BAD_LOWERING is None:
        mesh = _mesh(("data",), (4,))
        jitted, args, donate = build_synthetic_bad_step(mesh)
        _SYNTHETIC_BAD_LOWERING = lower_jitted(
            jitted, args, name="synthetic-bad", mesh=mesh, donate=donate)
    return _SYNTHETIC_BAD_LOWERING


_PLANTED_SYNC_SRC = '''\
def fit(self, steps):
    total = 0.0
    for i in range(steps):
        state, metrics = self.step_fn(state, batch)
        total += float(metrics["loss"])          # planted sync 1
        acc = np.asarray(metrics["acc"])         # planted sync 2
        metrics["loss"].block_until_ready()      # planted sync 3
        ok = float(metrics["loss"])  # shardlint: allow-sync
    return total


def assemble(batch):
    # not a hot loop member unless selected; float() here is host-side
    for row in batch:
        yield float(row)
'''


def selftest(verbose: bool = False) -> Dict[str, Any]:
    """Planted-hazard checks: every detector must fire on the synthetic bad
    step and stay silent on the fenced-good fused-CE paths.  Raises
    ``AssertionError`` on any miss; returns a summary dict."""
    V, Dm = _LM["vocab"], _LM["d_model"]
    summary: Dict[str, Any] = {}

    def log(msg):
        if verbose:
            print(f"  [selftest] {msg}")

    # 1. planted hazards all detected (memoized: one compile per session
    #    shared with the shardlint tests)
    rep = analyze_lowering(get_synthetic_bad_lowering())
    kinds = {f.kind for f in rep.findings}
    assert "replicated-large-tensor" in kinds, rep.findings
    assert any(f.kind == "replicated-large-tensor" and f.shape == (2048, 128)
               for f in rep.findings), rep.findings
    assert "dtype-promotion" in kinds, rep.findings
    assert "lost-donation" in kinds, rep.findings
    summary["synthetic_bad_findings"] = len(rep.findings)
    log(f"synthetic bad step: {sorted(kinds)}")

    # 2. planted host syncs: exactly the 3 unsuppressed calls in fit()
    lint = astlint.lint_source(_PLANTED_SYNC_SRC, "planted.py",
                               hot_functions=("fit",))
    assert len(lint) == 3, lint
    summary["planted_syncs"] = len(lint)
    log("planted host syncs: 3/3")

    # 3. the real hot loops are currently clean
    hot = lint_hot_loops()
    assert not hot.findings, hot.findings
    log("hot loops clean")

    # 4. fused-CE fence: replicated mode carries the full [V, D] dE per
    # device; dp and tp modes must not (the PR-1 regression fence)
    bad = analyze_recipe("lm_fused_ce_replicated",
                         min_replicated_bytes=4096)
    assert any(f.kind == "replicated-large-tensor" and f.shape == (V, Dm)
               for f in bad.findings), bad.findings
    for mode in ("lm_fused_ce_dp", "lm_fused_ce_tp"):
        good = analyze_recipe(mode, min_replicated_bytes=4096)
        assert not good.by_kind("replicated-large-tensor"), (
            mode, good.findings)
        log(f"{mode}: no replicated accumulator")
    summary["fused_ce_fence"] = "ok"

    # 5. the LM train step's donation fully aliases
    donated = analyze_recipe("lm_train_dp")
    assert donated.donation.get("missing") == [], donated.donation
    assert not donated.by_kind("lost-donation"), donated.findings
    summary["lm_train_donation"] = donated.donation.get("aliased")
    log(f"lm_train_dp aliased {donated.donation.get('aliased')} leaves")
    summary["ok"] = True
    return summary
