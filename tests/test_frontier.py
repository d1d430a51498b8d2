"""Budget-sweep frontier engine invariants (DESIGN.md §6).

The contract under test: ``sweep_budgets`` is a pure restructuring of N
serial searches — byte-identical plans at every budget on a shared
quantization grid, with consistent cache telemetry; the frontier is
monotone, feasible at its own budgets, and JSON round-trips; and
``clear_cache()`` returns the optimizer to a bit-exact cold start.
"""
import json

import pytest

from repro.core import (GalvatronOptimizer, PlanFrontier, ParallelPlan,
                        Strategy, galvatron_variant, paper_8gpu)
from repro.core.cost_model import CostModelConfig
from repro.core.frontier import FrontierPoint
from repro.core.layerspec import dense_layer

GB = 1024 ** 3
BUDGETS = [4 * GB, 6 * GB, 8 * GB, 12 * GB]


def _specs(n=8, seq=512, d=1024):
    return [dense_layer(f"l{i}", seq, d, 16, 16, 4 * d,
                        store_attn_matrix=True) for i in range(n)]


def _mkopt(specs, cluster=None, *, budget=None, quant=None, variant="bmw",
           cost=None, **kw):
    cfg = galvatron_variant(variant)
    cfg.batch_grid = [8, 16]
    cfg.n_bins = 128
    cfg.micro_candidates = 2
    cfg.budget_bytes = budget
    cfg.quant_bytes = quant
    for k, v in kw.items():
        setattr(cfg, k, v)
    return GalvatronOptimizer(specs, cluster or paper_8gpu(), cfg, cost)


def _canon(plan):
    return plan.canonical_dumps() if plan is not None else None


# ---------------------------------------------------------------------------
# differential: sweep == dedicated optimize() per budget
# ---------------------------------------------------------------------------

def test_single_point_sweep_matches_plain_optimize():
    """sweep_budgets([b]) degenerates to optimize() at budget b — same
    quantization grid, byte-identical plan JSON."""
    specs = _specs(8)
    for b in (5 * GB, 8 * GB, 12 * GB):
        serial = _mkopt(specs, paper_8gpu().with_budget(b)).optimize()
        frontier = _mkopt(specs, paper_8gpu().with_budget(b)).sweep_budgets([b])
        assert frontier.quant_bytes == b
        assert _canon(frontier.points[0].plan) == _canon(serial)


@pytest.mark.parametrize("variant,seq,budgets,kw", [
    pytest.param("bmw", 512, BUDGETS, {}, id="bmw"),
    pytest.param("base", 512, BUDGETS, {}, id="base"),
    # the schedule axis, interleaved at V 2
    pytest.param("bmw", 512, BUDGETS,
                 dict(schedules=("1f1b", "zb-h1", "1f1b-interleaved"),
                      vpp_candidates=(2,)), id="schedules"),
    # budgets tight enough without checkpointing that some points OOM
    pytest.param("bmw", 512, [1.2 * GB, 2 * GB, 4 * GB],
                 dict(allow_ckpt=False), id="tight-no-ckpt"),
    # ring-attention SP at a long sequence, with the physical batch floor
    pytest.param("bmw", 8192, BUDGETS,
                 dict(use_sp=True, batch_grid=[1, 2, 4],
                      cost=CostModelConfig(min_samples_per_device=1.0)),
                 id="sp-long-seq"),
])
def test_sweep_matches_serial_grid(variant, seq, budgets, kw):
    """Every frontier point is byte-identical to an independent serial
    optimize() at that budget pinned to the sweep's quantization grid."""
    specs = _specs(8, seq=seq)
    frontier = _mkopt(specs, variant=variant, **kw).sweep_budgets(budgets)
    assert frontier.feasible_points(), "test setup: all budgets OOMed"
    for p in frontier.points:
        serial = _mkopt(specs, budget=p.budget_bytes, quant=max(budgets),
                        variant=variant, **kw).optimize()
        assert _canon(p.plan) == _canon(serial), p.budget_bytes / GB
    plans = [p.plan for p in frontier.feasible_points()]
    if "schedules" in kw:
        assert {p.schedule for p in plans} <= set(kw["schedules"])
    if kw.get("use_sp"):
        assert any(p.sp_degree > 1 for p in plans)


def test_sweep_pinned_to_min_budget_matches_dedicated_searches():
    """Anchoring the grid at min(budgets) gives every point the resolution
    a dedicated optimize() at that budget would use — including budgets
    *above* the anchor, whose bin caps exceed n_bins."""
    specs = _specs(8)
    frontier = _mkopt(specs, quant=min(BUDGETS)).sweep_budgets(BUDGETS)
    assert frontier.quant_bytes == min(BUDGETS)
    for p in frontier.points:
        dedicated = _mkopt(specs, budget=p.budget_bytes,
                           quant=min(BUDGETS)).optimize()
        assert _canon(p.plan) == _canon(dedicated), p.budget_bytes / GB
    # the smallest point IS the plain single-budget search (quant == budget)
    plain = _mkopt(specs, paper_8gpu().with_budget(min(BUDGETS))).optimize()
    assert _canon(frontier.points[0].plan) == _canon(plain)


def test_sweep_stats_consistent():
    """The frontier carries the sweep's telemetry, and every stage search
    is either a cache hit or a miss."""
    opt = _mkopt(_specs(8))
    frontier = opt.sweep_budgets(BUDGETS)
    assert frontier.search_stats == opt.stats
    stats = frontier.search_stats
    assert (stats["stage_cache_hits"] + stats["stage_cache_misses"]
            == stats["stage_searches"])
    assert stats["stage_cache_misses"] > 0 and stats["stage_cache_hits"] > 0
    # every (B, P) pair of the grid is visited once (nothing OOMs here)
    assert stats["bp_candidates"] == 2 * len(opt.search_space.per_pp)


# ---------------------------------------------------------------------------
# frontier invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def frontier_and_opt():
    specs = _specs(8)
    opt = _mkopt(specs)
    return opt.sweep_budgets(BUDGETS), opt, specs


def test_throughput_nondecreasing_in_budget(frontier_and_opt):
    frontier, _, _ = frontier_and_opt
    tpts = frontier.throughputs()
    assert all(b >= a - 1e-12 for a, b in zip(tpts, tpts[1:]))


def test_every_plan_feasible_at_its_own_budget(frontier_and_opt):
    """Peak stage memory (Eq. 2, recomputed through the scalar cost-model
    path, independent of the DP) fits under each point's budget."""
    frontier, opt, specs = frontier_and_opt
    assert frontier.feasible_points(), "test setup: all budgets OOMed"
    for p in frontier.feasible_points():
        mems = opt.cost.plan_peak_stage_mem(specs, p.plan)
        assert max(mems) <= p.budget_bytes * (1 + 1e-9)
        # and the search's own estimate agrees with the recompute
        assert max(p.plan.est_stage_mem) <= p.budget_bytes
        assert mems == pytest.approx(p.plan.est_stage_mem, rel=1e-9)


def test_frontier_json_roundtrip(frontier_and_opt):
    frontier, _, _ = frontier_and_opt
    again = PlanFrontier.loads(frontier.dumps())
    assert again == frontier
    assert again.budgets() == frontier.budgets()
    assert [_canon(p.plan) for p in again.points] \
        == [_canon(p.plan) for p in frontier.points]


def test_frontier_roundtrip_preserves_schedule_and_vpp():
    """PR-2 plan fields (schedule, vpp_degree) survive the frontier JSON."""
    plan = ParallelPlan(
        n_devices=8, pp_degree=4, partition=[2, 2, 2, 2],
        strategies=[Strategy((("dp", 2),))] * 8, global_batch=16, n_micro=8,
        schedule="1f1b-interleaved", vpp_degree=2,
        est_iter_time=0.5, est_throughput=32.0,
        est_stage_mem=[1.0 * GB] * 4)
    fr = PlanFrontier(points=[
        FrontierPoint(2 * GB, None, 0.0),
        FrontierPoint(4 * GB, plan, plan.est_throughput),
    ], quant_bytes=4 * GB)
    again = PlanFrontier.loads(fr.dumps())
    assert again == fr
    got = again.points[1].plan
    assert got.schedule == "1f1b-interleaved" and got.vpp_degree == 2
    assert not again.points[0].feasible


def test_plan_at_and_knee_points(frontier_and_opt):
    frontier, _, _ = frontier_and_opt
    # query between swept points: best feasible plan at or below the query
    mid = (BUDGETS[1] + BUDGETS[2]) / 2
    got = frontier.plan_at(mid)
    best_below = max(
        (p for p in frontier.feasible_points() if p.budget_bytes <= mid),
        key=lambda p: p.predicted_throughput)
    assert got == best_below.plan
    assert frontier.plan_at(0.0) is None
    knees = frontier.knee_points()
    tpts = [p.predicted_throughput for p in knees]
    assert tpts == sorted(set(tpts))     # strictly increasing
    # knee flags land in the JSON
    d = frontier.to_json()
    assert sum(1 for p in d["points"] if p["knee"]) == len(knees)


# ---------------------------------------------------------------------------
# cache lifecycle
# ---------------------------------------------------------------------------

def test_clear_cache_reproduces_cold_start():
    """Audit: clear_cache() drops all four memo dicts and zeroes stats —
    the instance then replays a bit-exact cold-start search."""
    specs = _specs(8)
    opt = _mkopt(specs)
    p1 = opt.optimize()
    cold = {k: v for k, v in opt.stats.items() if k != "search_seconds"}
    assert any(cold.values())
    opt.optimize()                       # warm the caches further
    opt.clear_cache()
    for cache in (opt._stage_cache, opt._table_cache, opt._ref_cache,
                  opt._part_cache):
        assert len(cache) == 0
    assert all(v == 0 for v in opt.stats.values())
    p2 = opt.optimize()
    assert _canon(p2) == _canon(p1)
    assert {k: v for k, v in opt.stats.items()
            if k != "search_seconds"} == cold


def test_budget_axis_switch_keeps_budget_independent_caches():
    """Re-searching with a different budget axis drops only the stage
    cache; cost tables / reference costs / seed partitions are reused —
    the incremental-re-search path when only the budget changes."""
    specs = _specs(8)
    opt = _mkopt(specs)
    fr1 = opt.sweep_budgets(BUDGETS)
    builds = opt.stats["table_builds"]
    assert builds > 0 and len(opt._table_cache) > 0
    fr2 = opt.sweep_budgets([5 * GB, 9 * GB])
    # no new table builds: the (strategy-set, B_m, inflight) keys are
    # budget-independent, so the second sweep runs entirely off the memo
    assert opt.stats["table_builds"] == builds
    assert opt.stats["table_hits"] > 0
    # and the incremental answer matches a cold sweep
    fresh = _mkopt(specs).sweep_budgets([5 * GB, 9 * GB])
    assert [_canon(p.plan) for p in fr2.points] \
        == [_canon(p.plan) for p in fresh.points]
    assert fr1.budgets() == sorted(BUDGETS)


def test_sweep_budgets_validates_input():
    opt = _mkopt(_specs(4))
    with pytest.raises(ValueError):
        opt.sweep_budgets([])


def test_canonical_dumps_drops_only_stats():
    specs = _specs(6)
    plan = _mkopt(specs).optimize()
    assert plan.search_stats is not None
    d = json.loads(plan.canonical_dumps())
    assert "search_stats" not in d
    assert ParallelPlan.from_json(d) == plan
