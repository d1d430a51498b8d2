"""Expert parallelism as a searched axis: cost-model EP terms, the opt-in
search-space extension, the MoE throughput flip (the PR's acceptance
criterion), PLN012 lint, v5 plan round-trip, and the plan -> runtime
policy bridge."""
import json

import numpy as np
import pytest

from repro.core import CLUSTERS, GalvatronOptimizer, ParallelPlan, Strategy
from repro.core.cost_model import (CostModel, CostModelConfig,
                                   _SP_INVALID_TIME)
from repro.core.layerspec import dense_layer, moe_layer
from repro.core.optimizer import OptimizerConfig
from repro.core.strategy import EP, EP_PARADIGMS, PARADIGMS, SP, SP_PARADIGMS

GB = 1024 ** 3
CLUSTER = CLUSTERS["8x-rtx-titan-pcie"]


def _moe_spec(i=0, E=8, k=2, cf=1.25):
    return moe_layer(f"l{i}", 2048, 2048, 16, 16, 8192, E, k,
                     capacity_factor=cf)


def _dense_spec(seq=2048):
    return dense_layer("body", seq, 2048, 16, 16, 8192)


# ---------------------------------------------------------------------------
# search space
# ---------------------------------------------------------------------------

def test_ep_paradigm_is_opt_in():
    assert EP not in PARADIGMS           # paper leaf counts preserved
    assert EP not in SP_PARADIGMS
    assert EP_PARADIGMS == PARADIGMS + (SP, EP)
    opt = GalvatronOptimizer([_moe_spec()], CLUSTER, OptimizerConfig())
    assert all(s.ep == 1
               for pp in opt.search_space.per_pp.values() for s in pp)
    opt_ep = GalvatronOptimizer([_moe_spec()], CLUSTER,
                                OptimizerConfig(use_ep=True))
    assert any(s.ep > 1
               for pp in opt_ep.search_space.per_pp.values() for s in pp)


def test_use_ep_composes_with_use_sp():
    opt = GalvatronOptimizer([_moe_spec()], CLUSTER,
                             OptimizerConfig(use_sp=True, use_ep=True))
    degrees = {(s.sp, s.ep)
               for pp in opt.search_space.per_pp.values() for s in pp}
    assert any(sp > 1 for sp, _ in degrees)
    assert any(ep > 1 for _, ep in degrees)


def test_max_ep_caps_the_searched_degree():
    opt = GalvatronOptimizer([_moe_spec()], CLUSTER,
                             OptimizerConfig(use_ep=True, max_ep=2))
    eps = {s.ep for pp in opt.search_space.per_pp.values() for s in pp}
    assert max(eps) == 2


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_ep_shards_expert_states_and_prices_all_to_all():
    cm = CostModel(CLUSTER)
    spec = _moe_spec()
    plain = cm.layer_costs(spec, Strategy((("dp", 8),), ckpt=False), 4.0)
    ep8 = cm.layer_costs(spec, Strategy((("ep", 8),), ckpt=False), 4.0)
    # expert params / optimizer state shrink by ep (dense part replicates)
    assert ep8.mem_ms < plain.mem_ms
    exp_frac = spec.expert_param_frac
    expect = plain.mem_ms * ((1 - exp_frac) + exp_frac / 8)
    assert ep8.mem_ms == pytest.approx(expect, rel=1e-6)
    # all-to-all is on the critical path: finite, positive time
    assert 0 < ep8.time < _SP_INVALID_TIME


def test_ep_invalid_for_dense_and_non_dividing_experts():
    cm = CostModel(CLUSTER)
    c = cm.layer_costs(_dense_spec(), Strategy((("ep", 4),), ckpt=False), 4.0)
    assert c.time == _SP_INVALID_TIME            # no experts to shard
    odd = _moe_spec(E=6)                         # 6 % 4 != 0
    c2 = cm.layer_costs(odd, Strategy((("ep", 4),), ckpt=False), 4.0)
    assert c2.time == _SP_INVALID_TIME
    ok = cm.layer_costs(odd, Strategy((("ep", 2),), ckpt=False), 4.0)
    assert ok.time < _SP_INVALID_TIME            # 6 % 2 == 0
    assert np.isfinite(c2.mem_f) and np.isfinite(c2.mem_ms)


def test_ep_imbalance_penalizes_hot_ranks():
    spec = _moe_spec()
    even = CostModel(CLUSTER).layer_costs(
        spec, Strategy((("ep", 8),), ckpt=False), 4.0)
    hot = CostModel(CLUSTER, CostModelConfig(ep_imbalance=0.5)).layer_costs(
        spec, Strategy((("ep", 8),), ckpt=False), 4.0)
    assert hot.time > even.time
    # imbalance does not touch ep=1 strategies at all
    s1 = Strategy((("dp", 8),), ckpt=False)
    assert (CostModel(CLUSTER, CostModelConfig(ep_imbalance=0.5))
            .layer_costs(spec, s1, 4.0).time
            == CostModel(CLUSTER).layer_costs(spec, s1, 4.0).time)


def test_scalar_and_vectorized_ep_tables_agree_exactly():
    cm = CostModel(CLUSTER, CostModelConfig(ep_imbalance=0.2))
    specs = [_moe_spec(), _moe_spec(E=6), _dense_spec()]
    strats = [Strategy((("ep", 8),), ckpt=False),
              Strategy((("ep", 2), ("dp", 4)), ckpt=True),
              Strategy((("ep", 2), ("tp", 2), ("sdp", 2)), ckpt=False),
              Strategy((("sp", 2), ("ep", 4)), ckpt=False),
              Strategy((("dp", 8),), ckpt=False)]
    tables = cm.layer_cost_tables(specs, strats, 8.0, inflight=2)
    for i, spec in enumerate(specs):
        for j, s in enumerate(strats):
            c = cm.layer_costs(spec, s, 8.0, inflight=2)
            assert tables.time_sync[i, j] == c.time, (i, j)
            assert tables.time_nosync[i, j] == c.time_nosync, (i, j)
            assert tables.mem_f[i, j] == c.mem_f, (i, j)
            assert tables.mem_ms[i, j] == c.mem_ms, (i, j)


# ---------------------------------------------------------------------------
# the acceptance criterion: MoE throughput flip
# ---------------------------------------------------------------------------

def _moe_setup():
    specs = [_moe_spec(i) for i in range(4)]
    base = dict(batch_grid=(8,), micro_candidates=2, n_bins=64)
    return specs, base


def test_moe_slower_at_ep1_faster_with_ep():
    """At the pinned 6 GB budget every ep=1 plan is strictly slower than
    the certified ep>1 plan the EP-enabled search finds — the flip
    BENCH_moe.json records."""
    specs, base = _moe_setup()
    budget = [6 * GB]
    p1 = GalvatronOptimizer(specs, CLUSTER, OptimizerConfig(**base)) \
        .sweep_budgets(budget).points[0].plan
    p2 = GalvatronOptimizer(specs, CLUSTER,
                            OptimizerConfig(use_ep=True, **base)) \
        .sweep_budgets(budget).points[0].plan
    assert p1 is not None and p2 is not None
    assert p1.ep_degree == 1
    assert p2.ep_degree > 1
    assert p2.est_throughput > p1.est_throughput
    # the emitted plan certifies (no errors; PLN012 included)
    from repro.analysis import verify_plan_json
    diags = verify_plan_json(p2.to_json())
    assert not [d for d in diags if d.severity == "error"], diags


def test_ep1_plans_bit_identical_with_use_ep_off():
    """use_ep=False (the default) must not perturb the search at all —
    byte-identical canonical plans, the default-off discipline."""
    specs, base = _moe_setup()
    p1 = GalvatronOptimizer(specs, CLUSTER, OptimizerConfig(**base)) \
        .sweep_budgets([8 * GB]).points[0].plan
    p2 = GalvatronOptimizer(specs, CLUSTER,
                            OptimizerConfig(use_ep=False, **base)) \
        .sweep_budgets([8 * GB]).points[0].plan
    assert p1.canonical_dumps() == p2.canonical_dumps()
    assert p1.ep_degree == 1


def test_ep_search_where_ep_loses_never_hurts():
    # ample budget: the ep=1 winner survives the superset search
    specs, base = _moe_setup()
    p1 = GalvatronOptimizer(specs, CLUSTER, OptimizerConfig(**base)) \
        .sweep_budgets([12 * GB]).points[0].plan
    p2 = GalvatronOptimizer(specs, CLUSTER,
                            OptimizerConfig(use_ep=True, **base)) \
        .sweep_budgets([12 * GB]).points[0].plan
    assert p1 is not None and p2 is not None
    assert p2.est_throughput >= p1.est_throughput * (1 - 1e-9)


# ---------------------------------------------------------------------------
# PLN012 lint
# ---------------------------------------------------------------------------

def _plan(ep_degree=1, strategies=None, pp=1, n_dev=8):
    strategies = strategies or [Strategy((("dp", 8 // pp),), ckpt=False)] * 4
    return ParallelPlan(
        n_devices=n_dev, pp_degree=pp, partition=[4 // pp] * pp,
        strategies=strategies, global_batch=8, n_micro=1,
        ep_degree=ep_degree)


def _diags(plan):
    from repro.analysis import verify_plan_json
    return [d for d in verify_plan_json(plan.to_json())
            if d.rule == "PLN012"]


def test_pln012_ep_degree_must_divide_device_groups():
    strats = [Strategy((("ep", 2), ("dp", 4)),)] * 4
    bad = _plan(ep_degree=3, strategies=strats)
    assert any(d.severity == "error" and "divide" in d.message
               for d in _diags(bad)), _diags(bad)
    ok = _plan(ep_degree=2, strategies=strats)
    assert not [d for d in _diags(ok) if d.severity == "error"]


def test_pln012_layer_ep_exceeding_stamp_is_an_error():
    strats = [Strategy((("ep", 4), ("dp", 2)),)] * 4
    bad = _plan(ep_degree=2, strategies=strats)
    assert any(d.severity == "error" and "ep_degree" in d.location
               for d in _diags(bad))


def test_pln012_unused_axis_is_a_warning():
    # stamp claims ep=2 but every layer runs ep=1: the axis buys nothing
    bad = _plan(ep_degree=2)
    found = _diags(bad)
    assert any(d.severity == "warning" for d in found), found


def test_pln012_mixed_degrees_dense_plus_moe_is_info_only():
    strats = ([Strategy((("dp", 8),), ckpt=False)] * 2
              + [Strategy((("ep", 2), ("dp", 4)),)] * 2)
    found = _diags(_plan(ep_degree=2, strategies=strats))
    assert found and all(d.severity == "info" for d in found), found


def test_pln012_silent_on_ep1_plans():
    assert _diags(_plan()) == []


# ---------------------------------------------------------------------------
# plan format v5
# ---------------------------------------------------------------------------

def test_v5_ep_degree_roundtrips_and_validates():
    strats = [Strategy((("ep", 2), ("dp", 4)),)] * 4
    plan = _plan(ep_degree=2, strategies=strats)
    plan2 = ParallelPlan.loads(plan.dumps())
    assert plan2 == plan
    assert plan2.ep_degree == 2
    with pytest.raises(ValueError, match="ep_degree"):
        _plan(ep_degree=0)


def test_v4_json_without_ep_degree_still_loads():
    d = _plan().to_json()
    del d["ep_degree"]                # v4-era plan JSON has no ep key
    d["format_version"] = 4
    plan = ParallelPlan.from_json(d)
    assert plan.ep_degree == 1


def test_detect_format_version_ep():
    from repro.analysis.plan_lint import detect_format_version
    d = json.loads(_plan(ep_degree=2).dumps())
    del d["format_version"]
    assert detect_format_version(d) == 5
    d1 = json.loads(_plan().dumps())
    del d1["format_version"]          # ep_degree=1 alone does not imply v5
    del d1["ep_degree"]
    assert detect_format_version(d1) < 5


# ---------------------------------------------------------------------------
# plan -> runtime bridge
# ---------------------------------------------------------------------------

def test_policy_from_plan_carries_ep_degree():
    from repro.configs import get_config
    from repro.runtime.plan_bridge import policy_from_plan
    cfg = get_config("qwen3-4b")
    strats = [Strategy((("ep", 4), ("dp", 2)),)] * cfg.n_layers
    plan = ParallelPlan(
        n_devices=8, pp_degree=1, partition=[cfg.n_layers],
        strategies=strats, global_batch=8, n_micro=1, ep_degree=4)
    pol = policy_from_plan(cfg, plan)
    assert pol.ep_degree == 4
    assert pol.expert_axis == "expert"
    pol1 = policy_from_plan(cfg, ParallelPlan(
        n_devices=8, pp_degree=1, partition=[cfg.n_layers],
        strategies=[Strategy((("dp", 8),), ckpt=False)] * cfg.n_layers,
        global_batch=8, n_micro=1))
    assert pol1.ep_degree == 1 and pol1.expert_axis == "model"


def test_shard_policy_from_strategy_stamps_ep():
    from repro.runtime import ShardPolicy
    pol = ShardPolicy.from_strategy(Strategy((("ep", 4), ("dp", 2)),))
    assert pol.ep_degree == 4 and pol.expert_axis == "expert"
    pol1 = ShardPolicy.from_strategy(Strategy((("dp", 8),), ckpt=False))
    assert pol1.ep_degree == 1 and pol1.expert_axis == "model"


def test_search_cli_wires_ep_flags():
    from repro.launch.search import build_optimizer
    import argparse
    args = argparse.Namespace(
        variant="bmw", batch_grid="", n_bins=64, micro_candidates=2,
        max_pp=0, schedules="", sp=False, max_sp=0, ep=True, max_ep=2,
        min_samples_per_device=0.0)
    opt = build_optimizer([_moe_spec()], CLUSTER, args)
    assert opt.cfg.use_ep and opt.cfg.max_ep == 2
    eps = {s.ep for pp in opt.search_space.per_pp.values() for s in pp}
    assert max(eps) == 2
