"""Outer-loop invariants of the serial search: a budget sweep's batch axis
(strict-``>`` incumbent, per-budget two-consecutive-OOM stop) matches
dedicated per-budget searches, the batch grid is canonicalized, the caches
are auditable, and the plans the benchmark cells run are pinned."""
import json
import pathlib
from collections import Counter

import pytest

from hypothesis import given, settings, strategies as st

from repro.core import (GalvatronOptimizer, OptimizerConfig,
                        galvatron_variant, normalize_batch_grid, paper_8gpu)
from repro.core.layerspec import dense_layer

GB = 1024 ** 3
REPO = pathlib.Path(__file__).resolve().parent.parent


def _specs(n=8, seq=512, d=1024):
    return [dense_layer(f"l{i}", seq, d, 16, 16, 4 * d,
                        store_attn_matrix=True) for i in range(n)]


def _cfg(**kw):
    cfg = galvatron_variant("bmw")
    cfg.batch_grid = [8, 16, 24, 32]
    cfg.n_bins = 128
    cfg.micro_candidates = 2
    cfg.schedules = ("1f1b", "zb-h1")
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def _sweep(budgets, **kw):
    opt = GalvatronOptimizer(_specs(), paper_8gpu(), _cfg(**kw))
    frontier = opt.sweep_budgets(budgets)
    dumps = [p.plan.canonical_dumps() if p.plan is not None else None
             for p in frontier.points]
    return dumps, dict(opt.stats), opt


def _dedicated(budgets, **kw):
    """One fresh optimize() per budget, on the sweep's quantization grid."""
    out = []
    for b in sorted(budgets):
        opt = GalvatronOptimizer(
            _specs(), paper_8gpu(),
            _cfg(budget_bytes=b, quant_bytes=max(budgets), **kw))
        out.append(opt.optimize())
    return out


# ---------------------------------------------------------------------------
# the batch axis: sweep == dedicated searches
# ---------------------------------------------------------------------------

def test_two_oom_stop_trajectory_preserved():
    """Tight budgets where the batch axis hits the two-consecutive-OOM stop:
    each budget of the sweep stops where a dedicated search at that budget
    stops, and returns the same plan."""
    budgets = [1.0 * GB, 1.6 * GB]
    grid = [8, 16, 32, 64, 128, 256]
    dumps, stats, opt = _sweep(budgets, allow_ckpt=False, batch_grid=grid)
    plans = _dedicated(budgets, allow_ckpt=False, batch_grid=grid)
    assert dumps == [p.canonical_dumps() if p is not None else None
                     for p in plans]
    assert all(d is not None for d in dumps)
    # the stop fired: not every (B, P) pair of the grid was searched
    assert stats["bp_candidates"] < len(grid) * len(opt.search_space.per_pp)


@given(st.sampled_from([(8, 16), (8, 16, 24), (8, 16, 32, 48),
                        (8, 24, 40, 56, 72)]),
       st.sampled_from([(1.5, 3.0), (2.0, 4.0, 8.0), (1.2, 1.8, 2.6)]),
       st.booleans())
@settings(max_examples=8, deadline=None)
def test_sweep_keeps_argmax_batch(grid, budgets_gb, allow_ckpt):
    """Per budget, the sweep's winning global batch size is the one a
    search dedicated to that budget picks (None where both OOM)."""
    budgets = [b * GB for b in budgets_gb]
    _, _, opt = _sweep(budgets, batch_grid=list(grid), allow_ckpt=allow_ckpt)
    frontier = opt.sweep_budgets(budgets)
    plans = _dedicated(budgets, batch_grid=list(grid), allow_ckpt=allow_ckpt)
    for p, d in zip(frontier.points, plans):
        if d is None:
            assert p.plan is None
        else:
            assert p.plan is not None
            assert p.plan.global_batch == d.global_batch


# ---------------------------------------------------------------------------
# batch_grid / config validation
# ---------------------------------------------------------------------------

def test_normalize_batch_grid_dedupes_and_sorts():
    assert normalize_batch_grid([32, 8, 16, 8]) == [8, 16, 32]
    assert normalize_batch_grid(None) is None


@pytest.mark.parametrize("bad", [[], [0], [-8], [8.5], [True], ["8"]])
def test_normalize_batch_grid_rejects(bad):
    with pytest.raises(ValueError):
        normalize_batch_grid(bad)


def test_config_normalizes_unsorted_grid():
    cfg = OptimizerConfig(batch_grid=[64, 8, 8, 16])
    assert cfg.batch_grid == [8, 16, 64]


# ---------------------------------------------------------------------------
# cache audit: the coefficient cache is registered with clear_cache()
# ---------------------------------------------------------------------------

def test_clear_cache_covers_bound_and_coeff_caches():
    _, _, opt = _sweep([1.5 * GB, 3.0 * GB])
    opt.cost._group_coeffs("all_reduce", 4)
    assert opt.cost._coeff_cache                # coeff lookups memoized
    opt.clear_cache()
    assert not opt.cost._coeff_cache
    assert not opt._stage_cache
    assert all(v == 0 for v in opt.stats.values())
    # the instance still searches correctly after the wipe
    base, _, _ = _sweep([1.5 * GB, 3.0 * GB])
    frontier = opt.sweep_budgets([1.5 * GB, 3.0 * GB])
    assert [p.plan.canonical_dumps() if p.plan is not None else None
            for p in frontier.points] == base


# ---------------------------------------------------------------------------
# the plans the training cells run (bench/configs at the cells' traffic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,seq,batch,chips,want", [
    ("mamba2-370m", 2048, 8, 1, dict(
        pp_degree=1, schedule="1f1b", n_micro=2, vpp_degree=1,
        partition=[50],
        counts={(1, 1, False): 17, (1, 1, True): 33},
        model_axis=1, tp=False, zero=False, remat=(True,),
        est_throughput=14.379039064696412)),
    ("qwen3-4b-l9", 4096, 16, 4, dict(
        pp_degree=2, schedule="1f1b", n_micro=4, vpp_degree=1,
        partition=[7, 4],
        counts={(1, 1, False): 1, (1, 1, True): 1, (1, 2, True): 2,
                (2, 1, False): 2, (2, 1, True): 5},
        model_axis=2, tp=True, zero=True, remat=(True,),
        est_throughput=7.005043281101456)),
])
def test_search_plan_pins_bench_cells(name, seq, batch, chips, want):
    """``launch/train.py::search_plan`` on a benchmark configuration at its
    cell's seq, batch and chips returns the plan, mesh axis and policy
    recorded here."""
    from repro.configs import get_config
    from repro.launch.train import search_plan
    from repro.runtime.plan_bridge import model_axis_size, policy_from_plan
    from repro.runtime.sharding import ShardPolicy

    config = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                        .read_text())
    cfg = get_config(config["program_arch"]).with_(
        **config.get("program_overrides", {}))
    plan = search_plan(cfg, seq, batch, n_devices=chips)
    assert (plan.pp_degree, plan.schedule, plan.n_micro, plan.vpp_degree,
            plan.partition) == (want["pp_degree"], want["schedule"],
                                want["n_micro"], want["vpp_degree"],
                                want["partition"])
    assert Counter((s.tp, s.sdp, s.ckpt)
                   for s in plan.strategies) == want["counts"]
    assert model_axis_size(plan) == want["model_axis"]
    assert policy_from_plan(cfg, plan) == ShardPolicy(
        tp=want["tp"], zero=want["zero"], remat_segments=want["remat"],
        shard_cache_seq=True, expert_axis="model", seq_shard=False,
        sp_degree=1, ep_degree=1)
    assert plan.est_throughput == pytest.approx(want["est_throughput"],
                                                rel=1e-12)
