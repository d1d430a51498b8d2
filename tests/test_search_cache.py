"""Tentpole invariants: the memoized + vectorized search engine must be a
pure speedup — byte-identical plans, bit-identical cost tables."""
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (CostModel, GalvatronOptimizer, enumerate_strategies,
                        galvatron_variant, paper_8gpu, paper_16gpu_low,
                        strategy_set_id)
from repro.core.dp_search import dp_search_stage, dp_search_stage_reference
from repro.core.layerspec import dense_layer, head_layer, moe_layer

GB = 1024 ** 3


def _specs(n=8, seq=512, d=1024):
    return [dense_layer(f"l{i}", seq, d, 16, 16, 4 * d,
                        store_attn_matrix=True) for i in range(n)]


def _optimize(specs, cluster, **kw):
    cfg = galvatron_variant("bmw")
    cfg.batch_grid = [8, 16]
    cfg.n_bins = 128
    cfg.micro_candidates = 2
    for k, v in kw.items():
        setattr(cfg, k, v)
    opt = GalvatronOptimizer(specs, cluster, cfg)
    return opt.optimize(), opt.stats


# ---------------------------------------------------------------------------
# memo cache: byte-identical plans, nonzero hit counts
# ---------------------------------------------------------------------------

def test_cache_on_off_identical_plans_and_nonzero_hits():
    specs = _specs(8)
    cluster = paper_8gpu().with_budget(8 * GB)
    cached, stats = _optimize(specs, cluster)
    uncached, stats_off = _optimize(specs, cluster, enable_stage_cache=False)
    assert cached is not None and uncached is not None
    assert cached == uncached                   # ParallelPlan equality
    assert stats["stage_cache_hits"] > 0
    assert stats_off["stage_cache_hits"] == 0


def test_seed_mode_identical_plans():
    """Full legacy mode (reference DP + no caches) finds the same plan."""
    specs = _specs(8)
    cluster = paper_16gpu_low().with_budget(6 * GB)
    fast, _ = _optimize(specs, cluster)
    seed, _ = _optimize(specs, cluster, enable_stage_cache=False,
                        vectorized_cost=False)
    assert fast == seed


def test_stage_cache_persists_across_optimize_calls():
    """ROADMAP "next rungs" item: repeated optimize() on one instance
    reuses the stage cache; clear_cache() is the escape hatch."""
    specs = _specs(8)
    cluster = paper_8gpu().with_budget(8 * GB)
    cfg = galvatron_variant("bmw")
    cfg.batch_grid = [8, 16]
    cfg.n_bins = 128
    cfg.micro_candidates = 2
    opt = GalvatronOptimizer(specs, cluster, cfg)
    p1 = opt.optimize()
    h1, m1 = opt.stats["stage_cache_hits"], opt.stats["stage_cache_misses"]
    p2 = opt.optimize()
    assert p2 == p1
    # second sweep is identical -> every stage search is a hit, no new misses
    assert opt.stats["stage_cache_misses"] == m1
    assert opt.stats["stage_cache_hits"] > h1
    # cumulative telemetry is threaded into the plan
    assert p2.search_stats["stage_cache_hits"] == opt.stats["stage_cache_hits"]
    opt.clear_cache()
    # clear_cache() zeroes the telemetry too: the instance is
    # indistinguishable from a freshly constructed one
    assert all(v == 0 for v in opt.stats.values())
    p3 = opt.optimize()
    assert p3 == p1
    # cache really dropped: the re-search replays the cold-start miss count
    # (all hits would leave misses at 0)
    assert opt.stats["stage_cache_misses"] == m1
    assert opt.stats["stage_cache_hits"] == h1


def test_plan_carries_search_stats_but_compares_equal():
    specs = _specs(6)
    cluster = paper_8gpu().with_budget(8 * GB)
    plan, _ = _optimize(specs, cluster)
    assert plan.search_stats is not None
    assert plan.search_stats["stage_searches"] > 0
    # telemetry must not break plan equality (compare=False field)
    other, _ = _optimize(specs, cluster, enable_stage_cache=False)
    assert plan.search_stats != other.search_stats
    assert plan == other


# ---------------------------------------------------------------------------
# vectorized tables == scalar layer_costs
# ---------------------------------------------------------------------------

@given(st.integers(min_value=1, max_value=4),
       st.sampled_from([2, 4, 8]),
       st.floats(min_value=0.5, max_value=64.0),
       st.integers(min_value=1, max_value=6),
       st.booleans())
@settings(max_examples=20, deadline=None)
def test_tables_match_scalar_within_1e9(n_layers, group, B_m, inflight, moe):
    cluster = paper_16gpu_low()
    specs = [dense_layer(f"l{i}", 256 * (1 + i % 3), 512, 8, 8, 2048,
                         store_attn_matrix=bool(i % 2))
             for i in range(n_layers)]
    if moe:
        specs.append(moe_layer("moe", 256, 512, 8, 8, 1024, 8, 2))
    specs.append(head_layer("head", 256, 512, 32000))
    cm = CostModel(cluster, profiled_times={"l0": 1.3e-3})
    strategies = enumerate_strategies(group)
    tb = cm.layer_cost_tables(specs, strategies, B_m, inflight=inflight)
    for l, sp in enumerate(specs):
        for j, s in enumerate(strategies):
            c = cm.layer_costs(sp, s, B_m, inflight=inflight)
            r = cm.reshard_cost(sp, s, B_m)
            for got, want in [(tb.time_sync[l, j], c.time),
                              (tb.time_nosync[l, j], c.time_nosync),
                              (tb.time_fwd[l, j], c.time_fwd),
                              (tb.mem_f[l, j], c.mem_f),
                              (tb.mem_b[l, j], c.mem_b),
                              (tb.mem_ms[l, j], c.mem_ms),
                              (tb.reshard[l, j], r)]:
                assert got == pytest.approx(want, rel=1e-9, abs=1e-30)


# ---------------------------------------------------------------------------
# vectorized stage DP == seed reference implementation
# ---------------------------------------------------------------------------

@given(st.integers(min_value=1, max_value=6),
       st.floats(min_value=1.0, max_value=16.0),
       st.integers(min_value=1, max_value=4),
       st.sampled_from([1, 4, 8]))
@settings(max_examples=15, deadline=None)
def test_dp_matches_reference_implementation(n_layers, budget_gb, inflight,
                                             n_micro):
    cm = CostModel(paper_8gpu())
    specs = _specs(n_layers, seq=256, d=512)
    strategies = enumerate_strategies(8)
    kw = dict(inflight=inflight, n_bins=128, n_micro=n_micro)
    fast = dp_search_stage(specs, strategies, cm, 8.0, budget_gb * GB, **kw)
    ref = dp_search_stage_reference(specs, strategies, cm, 8.0,
                                    budget_gb * GB, **kw)
    assert fast.feasible == ref.feasible
    if ref.feasible:
        assert fast.time == ref.time
        assert fast.time_nosync == ref.time_nosync
        assert fast.e_all == ref.e_all
        assert fast.e_fwd == ref.e_fwd
        assert fast.strategies == ref.strategies


def test_strategy_set_id_stable():
    a = enumerate_strategies(8)
    b = enumerate_strategies(8)
    assert a is not b
    assert strategy_set_id(a) == strategy_set_id(b)
    assert strategy_set_id(a) != strategy_set_id(enumerate_strategies(4))


def test_cost_tables_row_slice_is_view():
    cm = CostModel(paper_8gpu())
    tb = cm.layer_cost_tables(_specs(6), enumerate_strategies(4), 8.0)
    sl = tb.rows(2, 5)
    assert sl.time_sync.shape[0] == 3
    assert np.shares_memory(sl.time_sync, tb.time_sync)
