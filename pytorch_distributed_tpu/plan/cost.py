"""Plan costing: feasibility pruning + analytic scoring.

Every number here comes from the already-fenced cost models in
``obs/flops.py`` — ``StepCost`` (±10% vs XLA ``cost_analysis()``),
``CommCost`` arithmetic (±15% vs the compiled ledger), ``MemCost``
(±15% vs the static HBM watermark) — composed over the plan's mesh
factorization.  AMP-style strategy search (arXiv:2210.07297) works
exactly when the cost model is trustworthy, which is why the planner
refuses to invent new magnitudes: each collective a plan implies is an
``(kind, per-device result bytes, group, overlappable)`` entry whose
bytes reuse the fenced formulas, and time scoring is overlap-centric
(arXiv:1810.11112) — wire bytes a backward-phase gradient sync can hide
under compute don't count against the step, boundary psums and pipeline
hops on the critical path do.

Jax-free by design: ``HW`` capabilities come from the device-kind
string tables in obs/flops.py (env-overridable), never a live backend.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from pytorch_distributed_tpu.obs import flops
from pytorch_distributed_tpu.obs.comms import wire_bytes
from pytorch_distributed_tpu.plan.space import ModelSpec, Plan

# Fraction of compute time backward-phase gradient collectives can hide
# under (bucketed sync overlaps the tail of backward; arXiv:1810.11112).
# Env PTD_PLAN_OVERLAP overrides everything; a measured value flows in
# via ``autoplan.py --overlap-from <timeline.json>`` (the profiler's
# observed overlap_pct_mean) through the ``overlap=`` kwarg below.
DEFAULT_OVERLAP = 0.6

# Fraction of per-chip HBM a plan may fill before pruning: headroom for
# the allocator, framework scratch, and the compiler's fusion temps the
# analytic model doesn't itemize.
HBM_FILL_FRACTION = 0.9

_FUSED_CE_CHUNKS = 8  # the chunk count Plan.flags() emits


@dataclasses.dataclass(frozen=True)
class HW:
    """Per-chip capabilities the scorer divides by."""

    name: str
    peak_flops: float
    hbm_bytes: float
    link_bytes: float


def hw_for(chip: Optional[str] = None) -> HW:
    """HW record for a chip name ("v4", "v5e", "tpu v5p", ... or None/
    "cpu" for the simulated-mesh placeholder).  A name that is not in
    the chip tables (obs/flops.py) raises."""
    if chip is None or chip.lower() in ("cpu", "host"):
        kind, name = None, "cpu"
    else:
        name = chip.lower()
        kind = name if name.startswith("tpu") else f"tpu {name}"
    return HW(name=name,
              peak_flops=flops.chip_peak_flops(kind),
              hbm_bytes=flops.chip_hbm_bytes(kind),
              link_bytes=flops.chip_link_bytes(kind))


def step_cost_for(plan: Plan) -> flops.StepCost:
    """The fenced per-step FLOPs model at the plan's recompute knob
    (``remat``).  The fused loss is none: it runs the head's three
    products like the unfused one (ops/fused_ce.py), so a plan's time does
    not read ``fused_ce_mode`` and fused plans tie with their plain twins,
    fewest knobs first; the mode moves memory (``mem_cost_for``).  The loss
    row a sequence that the fused head skips is left out of the tie: 1 /
    seq of the head is below what this model resolves."""
    spec = plan.spec
    if spec.family == "image":
        return flops.image_step_cost(spec.arch, spec.batch, spec.image_size,
                                     spec.num_classes, remat=plan.remat)
    return flops.lm_step_cost(spec.vocab, spec.d_model, spec.n_layers,
                              spec.batch, spec.seq,
                              mlp_ratio=spec.mlp_ratio, remat=plan.remat)


def bucketed_overlap(grad_bytes: float, bucket_mb: float = 4.0,
                     max_overlap: float = 0.95) -> float:
    """Schedule-derived backward-overlap fraction for the bucketed
    comm-overlap scheduler (parallel/overlap.py) — the replacement for
    the assumed ``DEFAULT_OVERLAP`` guess when ``--overlap bucketed``
    is actually in the recipe.

    With ``K = ceil(grad_bytes / bucket_mb·MiB)`` reverse-autodiff
    buckets, every bucket's collective except the final one is issued
    while backward compute remains, so the hideable fraction is
    ``(K-1)/K`` — capped at ``max_overlap`` because the tail bucket (and
    ramp effects) always stay exposed."""
    import math

    if bucket_mb <= 0:
        raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
    k = max(1, math.ceil(float(grad_bytes) / (bucket_mb * 1024.0 * 1024.0)))
    return min(max_overlap, (k - 1) / k)


def spec_bucketed_overlap(spec: ModelSpec, bucket_mb: float = 4.0) -> float:
    """``bucketed_overlap`` over a spec's full f32 gradient bytes (the
    DP sync payload before any tp/pp sharding — the conservative,
    plan-independent schedule estimate the autoplan CLI uses)."""
    plan = Plan(spec=spec, chips=1)
    return bucketed_overlap(4.0 * step_cost_for(plan).params, bucket_mb)


# --------------------------------------------------------------- comms

@dataclasses.dataclass(frozen=True)
class CommEntry:
    kind: str
    payload: float        # per-device result bytes (ledger convention)
    group: int
    overlappable: bool    # backward grad sync: hideable under compute
    what: str

    @property
    def wire(self) -> float:
        return wire_bytes(self.kind, self.payload, self.group)


def _chunk_layout(size: int, n: int, block: int = 256) -> Tuple[int, int]:
    """(padded_total, blocks_per_chunk) — the pure arithmetic of
    ops/qcomm.py ``chunk_layout``, restated here so the analytic path
    never imports jax."""
    chunk = -(-size // n)
    blk = min(block, chunk)
    chunk = -(-chunk // blk) * blk
    return n * chunk, chunk // blk


def comm_entries(plan: Plan, cost: flops.StepCost) -> List[CommEntry]:
    """Every collective the plan implies, with fenced byte magnitudes."""
    spec, dp, tp, pp = plan.spec, plan.dp, plan.tp, plan.pp
    out: List[CommEntry] = []
    if spec.family == "image":
        params = cost.params
        if dp > 1:
            scalars = 4.0 * 5
            gc = plan.grad_compress
            if gc in ("int8", "fp8"):
                padded, nb = _chunk_layout(params, dp)
                per_hop = padded + 4.0 * dp * nb
                out.append(CommEntry("all-to-all", per_hop, dp, True,
                                     "grad_sync_q_scatter"))
                out.append(CommEntry("all-gather", per_hop, dp, True,
                                     "grad_sync_q_gather"))
            elif plan.zero == "wus":
                elem = 2.0 if gc == "bf16" else 4.0
                padded, _ = _chunk_layout(params, dp)
                out.append(CommEntry("reduce-scatter", elem * padded / dp,
                                     dp, True, "wus_grad_scatter"))
                out.append(CommEntry("all-gather", elem * padded, dp, True,
                                     "wus_delta_gather"))
            else:
                elem = 2.0 if gc == "bf16" else 4.0
                out.append(CommEntry("all-reduce", elem * params, dp, True,
                                     "grad_sync"))
            out.append(CommEntry("all-reduce", scalars, dp, False,
                                 "metric_scalars"))
        return out
    # LM: the fenced lm_comm_bytes terms, decomposed per mesh axis.
    V, D, L = spec.vocab, spec.d_model, spec.n_layers
    grad = 4.0 * (cost.params + V * D) / max(1, tp) / max(1, pp)
    act = (spec.batch / max(1, dp)) * spec.seq * D * 4.0
    if dp > 1:
        if plan.fsdp:
            # ZeRO-3: params gather forward + re-gather backward, grads
            # reduce-scatter back — replaces the gradient all-reduce.
            out.append(CommEntry("all-gather", grad, dp, False,
                                 "fsdp_param_gather_fwd"))
            out.append(CommEntry("all-gather", grad, dp, True,
                                 "fsdp_param_gather_bwd"))
            out.append(CommEntry("reduce-scatter", grad / dp, dp, True,
                                 "fsdp_grad_scatter"))
        elif plan.zero == "wus":
            out.append(CommEntry("reduce-scatter", grad / dp, dp, True,
                                 "wus_grad_scatter"))
            out.append(CommEntry("all-gather", grad, dp, True,
                                 "wus_delta_gather"))
        else:
            out.append(CommEntry("all-reduce", grad, dp, True, "grad_sync"))
        out.append(CommEntry("all-reduce", 8.0, dp, False, "loss_scalars"))
    if tp > 1:
        out.append(CommEntry("all-reduce", 4.0 * L * act, tp, False,
                             "tp_layer_psums"))
        out.append(CommEntry("all-reduce", 1.5 * act, tp, False,
                             "tp_embed_psums"))
        out.append(CommEntry("collective-permute", 3.0 * L * act, 2, False,
                             "tp_head_permutes"))
    if pp > 1:
        # Stage-boundary activations: (pp-1) hops forward + (pp-1)
        # gradient hops backward, full per-data-shard activation block.
        out.append(CommEntry("collective-permute", 2.0 * (pp - 1) * act, 2,
                             False, "pp_boundary_acts"))
    return out


def comm_totals(entries: List[CommEntry]) -> Dict[str, float]:
    payload = sum(e.payload for e in entries)
    wire = sum(e.wire for e in entries)
    exposed = sum(e.wire for e in entries if not e.overlappable)
    return {"payload_bytes": payload, "wire_bytes": wire,
            "exposed_wire_bytes": exposed,
            "overlappable_wire_bytes": wire - exposed}


# -------------------------------------------------------------- memory

def mem_cost_for(plan: Plan, cost: Optional[flops.StepCost] = None
                 ) -> flops.MemCost:
    """Per-chip peak-HBM model at the plan's layout.

    The pure-DP base cases reduce EXACTLY to the fenced obs/flops models
    (``lm_train_mem_peak`` / ``train_mem_peak``), so the planner's
    feasibility pruning inherits their ±15% ledger fences; tp/pp/fsdp
    extend them by sharding the same terms over the extra axes."""
    spec = plan.spec
    cost = cost or step_cost_for(plan)
    dp, tp, pp = max(1, plan.dp), max(1, plan.tp), max(1, plan.pp)
    if spec.family == "image":
        params = cost.params
        # StepCost.bytes = 24*params + 2*(4*act_elts*batch): recover the
        # activation side and shard it over dp with the batch.
        act = max(0.0, (cost.bytes - 24.0 * params) / 2.0) / dp
        data = (spec.batch / dp) * spec.image_size ** 2 * 3 * 4.0
        explicit = (plan.zero != "none" or plan.grad_compress != "none")
        return flops.train_mem_peak(4.0 * params, act, data_bytes=data,
                                    dp=dp, zero=plan.zero == "wus",
                                    explicit_sync=explicit)
    V, D, L, H = spec.vocab, spec.d_model, spec.n_layers, spec.n_heads
    m = spec.mlp_ratio
    b = spec.batch / dp
    shard = tp * pp * (dp if plan.fsdp else 1)
    param_bytes = 4.0 * cost.params / shard
    momentum = param_bytes / (dp if (plan.zero == "wus" and not plan.fsdp)
                              else 1)
    grads = param_bytes
    # Activation schedule (lm_act_bytes terms, remat/fused/pp/tp aware):
    per_token = 9.0 * D + 2.0 * m * D
    scores = 2.0 * H * spec.seq
    L_stage = L / pp
    if plan.remat:
        # stash block inputs only + one live block in recompute
        stack = b * spec.seq * (L_stage * D + per_token + scores)
    else:
        stack = b * spec.seq * L_stage * (per_token + scores)
    head = 3.0 * b * spec.seq * V
    if plan.fused_ce_mode != "none":
        head = head / _FUSED_CE_CHUNKS + b * spec.seq * D
    act = 4.0 * (stack + head) / tp
    tokens = 4.0 * b * spec.seq + 8.0
    return flops.MemCost(
        argument_bytes=param_bytes + momentum + tokens,
        output_bytes=param_bytes + momentum + 256.0,
        temp_bytes=grads + act,
        breakdown={"params": param_bytes, "momentum": momentum,
                   "data": tokens, "grads": grads, "activations": act,
                   "grad_sync_scratch": 0.0, "metrics": 256.0})


# --------------------------------------------------------- feasibility

def feasibility(plan: Plan, hw: HW,
                hbm_budget: Optional[float] = None) -> List[str]:
    """Static reasons this plan cannot run (empty list = feasible)."""
    spec = plan.spec
    reasons: List[str] = []
    if plan.dp * plan.tp * plan.pp != plan.chips:
        reasons.append(f"dp*tp*pp = {plan.dp * plan.tp * plan.pp} "
                       f"!= {plan.chips} chips")
    if spec.batch % max(1, plan.dp):
        reasons.append(f"global batch {spec.batch} not divisible by "
                       f"dp={plan.dp}")
    if spec.family == "lm":
        if plan.tp > 1 and spec.vocab % plan.tp:
            reasons.append(f"vocab {spec.vocab} not divisible by "
                           f"tp={plan.tp}")
        if plan.tp > 1 and spec.n_heads % plan.tp:
            reasons.append(f"n_heads {spec.n_heads} not divisible by "
                           f"tp={plan.tp}")
        if plan.pp > 1 and spec.n_layers % plan.pp:
            reasons.append(f"n_layers {spec.n_layers} not divisible by "
                           f"pp={plan.pp} stages")
        if plan.pp > 1 and plan.microbatches == 0:
            reasons.append(
                f"no microbatch count >= pp={plan.pp} divides the "
                f"per-shard batch {spec.batch // max(1, plan.dp)}")
        if plan.fused_ce_mode == "tp" and plan.tp <= 1:
            reasons.append("fused-ce-mode tp needs a model axis (tp > 1)")
    if plan.zero == "wus" and plan.dp <= 1:
        reasons.append("--zero wus shards over the data axis; needs dp > 1")
    if plan.fsdp and plan.dp <= 1:
        reasons.append("--fsdp shards over the data axis; needs dp > 1")
    budget = (hbm_budget if hbm_budget is not None
              else HBM_FILL_FRACTION * hw.hbm_bytes)
    peak = mem_cost_for(plan).peak_bytes
    if peak > budget:
        reasons.append(
            f"predicted per-chip peak {peak / 1e9:.2f} GB exceeds the "
            f"{budget / 1e9:.2f} GB HBM budget on {hw.name}")
    return reasons


# ------------------------------------------------------------- scoring

@dataclasses.dataclass(frozen=True)
class PlanScore:
    """Analytic per-step prediction for one feasible plan."""

    compute_s: float
    comm_s: float
    exposed_comm_s: float
    bubble_s: float
    step_time_s: float
    payload_bytes: float
    wire_bytes: float
    peak_hbm_bytes: float
    mfu_pct: float
    hfu_pct: float

    def to_dict(self) -> Dict[str, float]:
        return {
            "step_time_ms": 1e3 * self.step_time_s,
            "compute_ms": 1e3 * self.compute_s,
            "comm_ms": 1e3 * self.comm_s,
            "exposed_comm_ms": 1e3 * self.exposed_comm_s,
            "bubble_ms": 1e3 * self.bubble_s,
            "payload_bytes": self.payload_bytes,
            "wire_bytes": self.wire_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "mfu_pct": self.mfu_pct,
            "hfu_pct": self.hfu_pct,
        }


def plan_complexity(plan: Plan) -> int:
    """Non-default knob count — the rank tie-break: at equal predicted
    step time the *simplest* recipe wins (fewer knobs to go wrong;
    memory headroom is a constraint, not an objective)."""
    return (int(plan.tp > 1) + int(plan.pp > 1) + int(plan.fsdp)
            + int(plan.remat) + int(plan.fused_ce_mode != "none")
            + int(plan.zero != "none") + int(plan.grad_compress != "none"))


def score_plan(plan: Plan, hw: HW,
               overlap: float = DEFAULT_OVERLAP) -> PlanScore:
    import os

    overlap = float(os.environ.get("PTD_PLAN_OVERLAP", overlap))
    cost = step_cost_for(plan)
    chips = max(1, plan.chips)
    compute = cost.hardware_flops / (chips * hw.peak_flops)
    entries = comm_entries(plan, cost)
    totals = comm_totals(entries)
    comm = totals["wire_bytes"] / hw.link_bytes
    exposed = (totals["exposed_wire_bytes"] / hw.link_bytes
               + max(0.0, totals["overlappable_wire_bytes"] / hw.link_bytes
                     - overlap * compute))
    bubble = 0.0
    if plan.pp > 1 and plan.microbatches > 0:
        bubble = compute * (plan.pp - 1) / plan.microbatches
    step = compute + bubble + exposed
    denom = step * chips * hw.peak_flops
    return PlanScore(
        compute_s=compute, comm_s=comm, exposed_comm_s=exposed,
        bubble_s=bubble, step_time_s=step,
        payload_bytes=totals["payload_bytes"],
        wire_bytes=totals["wire_bytes"],
        peak_hbm_bytes=mem_cost_for(plan, cost).peak_bytes,
        mfu_pct=100.0 * cost.model_flops / denom,
        hfu_pct=100.0 * cost.hardware_flops / denom)
