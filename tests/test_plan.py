"""The autoplan subsystem (pytorch_distributed_tpu/plan/).

Three layers of coverage, mirroring the package's layering contract:

- pure planning (space/cost/planner): enumeration exclusions, feasibility
  pruning with itemized reasons, score monotonicity in chip count, and
  rank stability against the checked-in expectation table
  (tests/data/autoplan_expect.json) — no mesh, no compiles;
- the lowering service (analysis/lowering.py): artifact persist/load
  round-trip, the jaxlib persistent-cache version guard + memoized
  self-check, and the tier-1 compile-budget fence — shardlint detectors,
  both ledger sweeps, and autoplan's top-k validation must all ride ONE
  shared AOT sweep with zero extra compiles;
- top-k validation parity on the simulated 4-way mesh: the planner's
  analytic predictions for the tiny-LM winner must agree with the real
  compiled ledgers within the existing acceptance fences (±15% comm
  payload, ±15% peak HBM, ±10% ledger-vs-measured).
"""

import json
import os

import pytest

from pytorch_distributed_tpu.plan import cost as cost_mod
from pytorch_distributed_tpu.plan import planner, space
from pytorch_distributed_tpu.plan.space import (
    ModelSpec,
    Plan,
    elastic_worlds,
    enumerate_plans,
    lm_spec,
    resnet50_spec,
    tiny_lm_spec,
)

EXPECT_PATH = os.path.join(os.path.dirname(__file__), "data",
                           "autoplan_expect.json")


def _lm(**overrides) -> ModelSpec:
    base = dict(name="lm-test", family="lm", batch=8, vocab=64, d_model=32,
                n_layers=2, n_heads=4, seq=16)
    base.update(overrides)
    return ModelSpec(**base)


# --------------------------------------------------------- enumeration

def test_enumerate_image_is_dp_times_knobs():
    plans = enumerate_plans(resnet50_spec(), 8)
    assert len(plans) == 2 * 4  # zero x grad_compress
    assert all(p.dp == 8 and p.tp == 1 and p.pp == 1 for p in plans)
    assert {p.grad_compress for p in plans} == {"none", "bf16", "int8",
                                                "fp8"}


def test_enumerate_lm_structural_exclusions():
    plans = enumerate_plans(_lm(), 8)
    assert plans
    for p in plans:
        # Megatron TP always pairs with the vocab-sharded fused head.
        assert not (p.tp > 1 and p.fused_ce_mode != "tp"), p.key()
        assert not (p.tp == 1 and p.fused_ce_mode == "tp"), p.key()
        # ZeRO-3 already shards what WUS would.
        assert not (p.fsdp and p.zero == "wus"), p.key()
        assert p.dp * p.tp * p.pp == 8, p.key()


def test_factorizations_cover_the_lattice():
    facts = set(space._factorizations(8, 3))
    assert all(a * b * c == 8 for a, b, c in facts)
    assert (8, 1, 1) in facts and (2, 2, 2) in facts and (1, 1, 8) in facts
    assert len(facts) == 10


def test_microbatches_gpipe_rule():
    # per-dp batch 8, 2 stages: largest divisor in [2, 8] is 8.
    assert Plan(spec=_lm(batch=16), chips=4, dp=2, pp=2).microbatches == 8
    # prime per-dp shard with no divisor >= pp: infeasible marker 0.
    assert Plan(spec=_lm(batch=17), chips=2, dp=1,
                pp=2).microbatches == 0
    assert Plan(spec=_lm(), chips=4, dp=4).microbatches == 1


# --------------------------------------------------------- feasibility

def _hw():
    return cost_mod.hw_for("v5p")


def _reasons(plan, hbm_budget=None):
    return cost_mod.feasibility(plan, _hw(), hbm_budget=hbm_budget)


def test_feasibility_mesh_product_mismatch():
    rs = _reasons(Plan(spec=_lm(), chips=8, dp=2, tp=1, pp=1))
    assert rs and any("8" in r for r in rs)


def test_feasibility_indivisible_vocab_and_heads():
    rs = _reasons(Plan(spec=_lm(vocab=65), chips=4, dp=2, tp=2,
                       fused_ce_mode="tp"))
    assert any("vocab" in r for r in rs), rs
    rs = _reasons(Plan(spec=_lm(n_heads=3), chips=4, dp=2, tp=2,
                       fused_ce_mode="tp"))
    assert any("head" in r for r in rs), rs


def test_feasibility_indivisible_stages_and_microbatch():
    rs = _reasons(Plan(spec=_lm(n_layers=5), chips=4, dp=2, pp=2))
    assert any("stage" in r for r in rs), rs
    rs = _reasons(Plan(spec=_lm(batch=17), chips=2, dp=1, pp=2))
    assert any("microbatch" in r for r in rs), rs


def test_feasibility_hbm_budget_prunes_everything():
    plan = Plan(spec=_lm(), chips=4, dp=4)
    assert not _reasons(plan)
    rs = _reasons(plan, hbm_budget=1.0)
    assert any("exceeds" in r and "HBM" in r for r in rs), rs


def test_pruned_histogram_buckets_by_reason_class():
    ranked, pruned = planner.rank_plans(tiny_lm_spec(), 4, _hw(),
                                        hbm_budget=1.0)
    assert not ranked
    assert "peak HBM over budget" in pruned, pruned
    # buckets are reason classes, never per-value strings
    assert not any("GB" in k for k in pruned), pruned


# ------------------------------------------------------------- scoring

def test_score_monotonic_in_chip_count():
    """Doubling the pod never slows the predicted step: the fastest plan's
    step time is non-increasing in chips for both families on v5p."""
    for spec in (lm_spec(), resnet50_spec()):
        prev = None
        for chips in (4, 8, 16, 32):
            ranked, _ = planner.rank_plans(spec, chips, _hw())
            assert ranked, f"{spec.name}@{chips} has no feasible plan"
            score = ranked[0][1]
            assert 0.0 < score.mfu_pct <= 100.0
            assert score.step_time_s > 0
            if prev is not None:
                assert score.step_time_s <= prev, (
                    f"{spec.name}: step time rose from {prev} at "
                    f"{chips // 2} chips to {score.step_time_s} at {chips}")
            prev = score.step_time_s


def test_score_fields_are_consistent():
    plan = Plan(spec=lm_spec(), chips=8, dp=8, remat=True)
    score = cost_mod.score_plan(plan, _hw())
    d = score.to_dict()
    assert d["step_time_ms"] == pytest.approx(score.step_time_s * 1e3)
    assert d["wire_bytes"] > 0 and d["payload_bytes"] > 0
    assert d["peak_hbm_bytes"] > 0
    assert score.step_time_s >= score.compute_s


def test_rank_tiebreak_prefers_fewer_knobs():
    # At tiny shapes ZeRO-1 WUS ties plain DP on predicted wire bytes by
    # construction; the complexity tie-break must keep the fully-fenced
    # plain-DP recipe on top.
    ranked, _ = planner.rank_plans(tiny_lm_spec(), 4, cost_mod.hw_for(None))
    assert ranked[0][0].key() == "c4/dp4"
    assert cost_mod.plan_complexity(ranked[0][0]) == 0


# ------------------------------------------------ measured overlap fold

def test_overlap_from_timeline_folds_into_scores(tmp_path):
    """ISSUE 13 S2: ``--overlap-from`` replaces the assumed backward-
    overlap fraction with the profiler's measured overlap_pct_mean, and
    the fold is visible in the score — a lower measured overlap exposes
    more comm, so no plan's predicted step gets faster."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import autoplan as autoplan_cli

    report = {"captures": [
        {"file": "a.xplane.pb",
         "aggregate": {"steps": 2, "overlap_pct_mean": 30.0}},
        {"file": "b.xplane.pb",
         "aggregate": {"steps": 2, "overlap_pct_mean": 50.0}},
        {"file": "idle.xplane.pb", "aggregate": {"steps": 0}},  # skipped
    ]}
    path = tmp_path / "timeline.json"
    path.write_text(json.dumps(report))
    frac = autoplan_cli.overlap_from_timeline(str(path))
    assert frac == pytest.approx(0.40)  # mean of the step-bearing captures

    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"captures": []}))
    with pytest.raises(ValueError):
        autoplan_cli.overlap_from_timeline(str(empty))

    assumed = planner.autoplan("lm", 32, chip="v5p", top_k=3,
                               elastic=False)
    measured = planner.autoplan("lm", 32, chip="v5p", top_k=3,
                                elastic=False, overlap=frac)
    assert assumed["overlap_source"] == "assumed"
    assert assumed["overlap"] == cost_mod.DEFAULT_OVERLAP
    assert measured["overlap_source"] == "measured"
    assert measured["overlap"] == pytest.approx(frac)
    by_key = {e["plan"]["key"]: e["predicted"]["step_time_ms"]
              for e in assumed["ranked"]}
    for e in measured["ranked"]:
        if e["plan"]["key"] in by_key:
            assert (e["predicted"]["step_time_ms"]
                    >= by_key[e["plan"]["key"]] - 1e-9)

    # CLI end to end: measured overlap flows through the sweep
    assert autoplan_cli.main(["lm-tiny", "--chips", "4", "--no-elastic",
                              "--overlap-from", str(path)]) == 0


# --------------------------------------------- schedule-derived overlap

def test_bucketed_overlap_schedule_math():
    """ISSUE 16: the bucketed scheduler's hideable fraction is (K-1)/K —
    every reverse-autodiff bucket's collective except the last overlaps
    remaining backward — capped below 1.0 (tail bucket + dispatch are
    never free)."""
    mib = 1024.0 * 1024.0
    assert cost_mod.bucketed_overlap(3.5 * mib, bucket_mb=4.0) == 0.0
    assert cost_mod.bucketed_overlap(16 * mib, bucket_mb=4.0) \
        == pytest.approx(3 / 4)
    assert cost_mod.bucketed_overlap(17 * mib, bucket_mb=4.0) \
        == pytest.approx(4 / 5)  # ceil: a partial tail bucket counts
    assert cost_mod.bucketed_overlap(4096 * mib, bucket_mb=4.0) == 0.95
    with pytest.raises(ValueError):
        cost_mod.bucketed_overlap(16 * mib, bucket_mb=0.0)

    # spec wrapper: full f32 gradient bytes of the spec's param count
    spec = tiny_lm_spec()
    params = cost_mod.step_cost_for(space.Plan(spec=spec, chips=1)).params
    assert cost_mod.spec_bucketed_overlap(spec, bucket_mb=4.0) \
        == cost_mod.bucketed_overlap(4.0 * params, bucket_mb=4.0)


def test_autoplan_overlap_source_schedule(tmp_path):
    """``overlap_source="schedule"`` flows through the payload (planner
    kwarg and the ``--overlap-schedule`` CLI), distinct from the
    measured-timeline provenance."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import autoplan as autoplan_cli

    frac = cost_mod.spec_bucketed_overlap(lm_spec(), bucket_mb=4.0)
    payload = planner.autoplan("lm", 32, chip="v5p", top_k=3,
                               elastic=False, overlap=frac,
                               overlap_source="schedule")
    assert payload["overlap_source"] == "schedule"
    assert payload["overlap"] == pytest.approx(frac)
    # the explicit kwarg never mislabels the default provenance
    assert planner.autoplan("lm", 32, chip="v5p", top_k=3,
                            elastic=False)["overlap_source"] == "assumed"

    # CLI end to end, and exclusive with --overlap-from
    assert autoplan_cli.main(["lm-tiny", "--chips", "4", "--no-elastic",
                              "--overlap-schedule"]) == 0
    report = tmp_path / "timeline.json"
    report.write_text(json.dumps({"captures": [
        {"file": "a", "aggregate": {"steps": 1,
                                    "overlap_pct_mean": 50.0}}]}))
    with pytest.raises(SystemExit):
        autoplan_cli.main(["lm-tiny", "--chips", "4", "--no-elastic",
                           "--overlap-schedule", "--overlap-from",
                           str(report)])


# ------------------------------------------------- rank stability table

def test_rank_stability_against_checked_in_table():
    """The planner's ranking is a pure function of the checked-in cost
    tables; any drift (a flops-table edit, a new exclusion) must show up
    as a reviewed diff of tests/data/autoplan_expect.json, not silently."""
    with open(EXPECT_PATH) as f:
        expect = json.load(f)

    def keys(payload):
        return [e["plan"]["key"] for e in payload["ranked"]]

    p = planner.autoplan("lm-tiny", 4, top_k=5)
    want = expect["lm-tiny@4"]
    assert keys(p) == want["top"]
    assert p["feasible"] == want["feasible"]
    assert p["enumerated"] == want["enumerated"]
    got_elastic = {w: (e["plan"]["key"] if e else None)
                   for w, e in p["elastic"].items()}
    assert got_elastic == want["elastic"]

    p = planner.autoplan("lm", 8, chip="v5p", top_k=5)
    want = expect["lm@8:v5p"]
    assert keys(p) == want["top"]
    assert p["feasible"] == want["feasible"]
    assert p["ranked"][0]["predicted"]["mfu_pct"] == pytest.approx(
        want["top_mfu_pct"], abs=0.01)

    p = planner.autoplan("resnet50", 32, chip="v5p", top_k=5)
    want = expect["resnet50@32:v5p"]
    assert keys(p) == want["top"]
    assert p["feasible"] == want["feasible"]


# ------------------------------------------------------ flags / payload

def test_lm_flags_match_recipe_spellings():
    plan = Plan(spec=_lm(batch=16), chips=8, dp=2, tp=2, pp=2, fsdp=True,
                remat=True, fused_ce_mode="tp")
    flags = plan.flags()
    for needle in ("--vocab", "--d-model", "--n-layers", "--n-heads",
                   "--seq-len", "--batch-size", "--tp", "--pp",
                   "--microbatches", "--fsdp", "--remat", "--fused-ce",
                   "--fused-ce-mode"):
        assert needle in flags, (needle, flags)
    assert flags[flags.index("--tp") + 1] == "2"
    assert flags[flags.index("--fused-ce-mode") + 1] == "tp"
    assert plan.cli().startswith(
        "python -m pytorch_distributed_tpu.recipes.lm_pretrain ")


def test_image_flags_match_config_spellings():
    plan = Plan(spec=resnet50_spec(), chips=4, dp=4, zero="wus",
                grad_compress="int8")
    flags = plan.flags()
    assert flags[:2] == ["-a", "resnet50"]
    assert flags[flags.index("--zero") + 1] == "wus"
    assert flags[flags.index("--grad-compress") + 1] == "int8"
    assert "--batch-size" in flags
    assert plan.cli().startswith("python main.py ")


def test_elastic_worlds_and_payload():
    assert elastic_worlds(32) == [32, 31, 16]
    assert elastic_worlds(2) == [2, 1]
    payload = planner.autoplan("resnet50", 8, chip="v5p", top_k=2)
    assert payload["schema_version"] == planner.PLAN_SCHEMA_VERSION
    assert set(payload["elastic"]) == {"7", "4"}
    for entry in payload["ranked"]:
        assert entry["predicted"]["mfu_pct"] > 0
        assert "--batch-size" in entry["plan"]["cli"]
    assert "validation" not in payload  # jax-free unless asked


def test_predicted_mfu_and_best_plan():
    mfu = planner.predicted_mfu("resnet50", 4, chip="v5p")
    assert mfu is not None and 0.0 < mfu <= 100.0
    best = planner.best_plan("lm-tiny", 4)
    assert best is not None and best.chips == 4 and best.key() == "c4/dp4"


# ------------------------------------------- compile cache placement

@pytest.fixture
def cache_dir_config():
    """Restore jax's compilation-cache directory after the test."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    yield jax.config
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path,
                                           cache_dir_config):
    """$JAX_COMPILATION_CACHE_DIR set: that directory is reported and no
    directory is set in code (jax reads the variable by itself)."""
    from pytorch_distributed_tpu.utils import compile_cache

    cache_dir_config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert cache_dir_config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch,
                                                         cache_dir_config):
    from pytorch_distributed_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want  # never moves
    assert cache_dir_config.jax_compilation_cache_dir == want


# ------------------------------------------- shared sweep + validation

def test_compile_budget_arithmetic():
    from pytorch_distributed_tpu.analysis import core, lowering

    assert lowering.compile_budget() == (
        len(core.RECIPES) + lowering.EXTRA_COMPILE_ALLOWANCE)


def test_service_persist_load_roundtrip(get_lowering):
    """Disk artifacts reproduce the live ledgers exactly: a subprocess
    reading <name>.hlo/<name>.json gets the same comm/memory truth as the
    in-process sweep, with no recompile."""
    from pytorch_distributed_tpu.analysis import core

    low = get_lowering("lm_train_dp")
    svc = get_lowering.service
    assert svc.has("lm_train_dp")
    assert "lm_train_dp" in svc.names()
    cached = svc.load("lm_train_dp")
    assert cached.mesh_shape == dict(low.mesh_shape)
    assert cached.measured_peak_bytes > 0
    live_comm = core.comm_ledger_for("lm_train_dp")
    disk_comm = cached.comm_ledger()
    assert disk_comm.total_bytes == live_comm.total_bytes
    assert disk_comm.total_wire_bytes == live_comm.total_wire_bytes
    live_mem = core.mem_ledger_for("lm_train_dp")
    disk_mem = cached.mem_ledger()
    assert disk_mem.peak_bytes == live_mem.peak_bytes
    assert "params" in cached.arg_classes


def test_validate_top_k_parity_on_cpu_mesh(get_lowering):
    """The acceptance fence: the tiny-LM winner's analytic predictions
    agree with its lowered step's ledgers within the existing thresholds
    (±15% comm payload, ±15% peak HBM, ±10% ledger-vs-measured)."""
    from pytorch_distributed_tpu.plan import validate as validate_mod

    ranked, _ = planner.rank_plans(tiny_lm_spec(), 4, cost_mod.hw_for(None))
    recs = validate_mod.validate_top_k([p for p, _ in ranked], k=3,
                                       service=get_lowering.service)
    assert len(recs) == 3
    top = recs[0]
    assert top["plan"] == "c4/dp4" and top["recipe"] == "lm_train_dp"
    assert top["ok"] is True
    comm = top["checks"]["comm"]
    assert comm["fenced"] and comm["ok"]
    assert comm["residual_pct"] <= validate_mod.COMM_FENCE_PCT
    mem = top["checks"]["mem"]
    assert mem["fenced"] and mem["ok"]
    assert mem["residual_pct"] <= validate_mod.MEM_FENCE_PCT
    led = top["checks"]["ledger_vs_measured"]
    assert led["ok"] and led["residual_pct"] <= validate_mod.LEDGER_FENCE_PCT
    # every validated record either passed its fences or was analytic-only
    assert all(r["ok"] is not False for r in recs)


def test_one_sweep_feeds_every_static_consumer(get_lowering):
    """The tier-1 compile-budget fence (the tentpole's zero-extra-compiles
    contract): with the recipe sweep warm, the shardlint detector pass,
    both ledger sweeps, AND autoplan's validated top-k must add ZERO
    compiles — and the process-wide total must sit under the budget."""
    from pytorch_distributed_tpu.analysis import core, lowering

    for name in core.RECIPES:
        get_lowering(name)
    before = get_lowering.compile_count()

    reports = core.analyze_all()
    assert len(reports) >= len(core.RECIPES)
    comm_ledgers = core.sweep_comm_ledgers()
    mem_ledgers = core.sweep_mem_ledgers()
    assert comm_ledgers and mem_ledgers
    payload = planner.autoplan("lm-tiny", 4, validate=True, validate_k=3)
    assert payload["validation_ok"] is True
    assert len(payload["validation"]) == 3

    grew = get_lowering.compile_count() - before
    assert grew == 0, (
        f"static consumers paid {grew} extra compile(s); they must all "
        f"ride the shared lowering sweep")
    assert get_lowering.compile_count() <= get_lowering.compile_budget()
    lowering.assert_compile_budget()
