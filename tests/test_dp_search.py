"""Dynamic-programming search: optimality vs brute force, monotonicity,
budget compliance (Alg. 3)."""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CostModel, Strategy, dp_search_stage,
                        enumerate_strategies, paper_8gpu)
from repro.core.dp_search import _exact_e_all
from repro.core.layerspec import dense_layer

GB = 1024 ** 3


def _specs(n=4, seq=512, d=512):
    return [dense_layer(f"l{i}", seq, d, 8, 8, 4 * d, causal=False,
                        gated=False, store_attn_matrix=True)
            for i in range(n)]


def _brute_force(specs, strategies, cm, mb, budget):
    best = (float("inf"), None)
    L = len(specs)
    tables = [[cm.layer_costs(sp, s, mb) for s in strategies] for sp in specs]
    for choice in itertools.product(range(len(strategies)), repeat=L):
        mem_f = np.array([[tables[l][j].mem_f for j in range(len(strategies))]
                          for l in range(L)])
        mem_b = np.array([[tables[l][j].mem_b for j in range(len(strategies))]
                          for l in range(L)])
        mem_ms = np.array([[tables[l][j].mem_ms for j in range(len(strategies))]
                           for l in range(L)])
        e_all = _exact_e_all(mem_f, mem_b, mem_ms, list(choice))
        if e_all > budget:
            continue
        t = sum(tables[l][j].time for l, j in enumerate(choice))
        for l in range(1, L):
            if strategies[choice[l]].levels != strategies[choice[l - 1]].levels:
                t += cm.reshard_cost(specs[l], strategies[choice[l]], mb)
        if t < best[0]:
            best = (t, choice)
    return best


@pytest.mark.parametrize("budget_gb", [2.0, 4.0, 8.0])
def test_dp_matches_brute_force(budget_gb):
    cm = CostModel(paper_8gpu())
    specs = _specs(3)
    strategies = enumerate_strategies(4)[:6]   # keep brute force tractable
    res = dp_search_stage(specs, strategies, cm, 8.0, budget_gb * GB,
                          n_bins=2048)
    bf_t, bf_choice = _brute_force(specs, strategies, cm, 8.0, budget_gb * GB)
    if bf_choice is None:
        assert not res.feasible
        return
    assert res.feasible
    # DP quantizes memory into bins -> allow small slack vs exact brute force
    assert res.time <= bf_t * 1.05 + 1e-9
    assert res.e_all <= budget_gb * GB * 1.01


@given(st.floats(min_value=1.0, max_value=12.0))
@settings(max_examples=10, deadline=None)
def test_monotone_in_budget(budget_gb):
    cm = CostModel(paper_8gpu())
    specs = _specs(4)
    strategies = enumerate_strategies(8)
    small = dp_search_stage(specs, strategies, cm, 8.0, budget_gb * GB)
    big = dp_search_stage(specs, strategies, cm, 8.0, 2 * budget_gb * GB)
    if small.feasible:
        assert big.feasible
        assert big.time <= small.time + 1e-9


def test_budget_respected():
    cm = CostModel(paper_8gpu())
    specs = _specs(6)
    strategies = enumerate_strategies(8)
    budget = 4.0 * GB
    res = dp_search_stage(specs, strategies, cm, 16.0, budget)
    assert res.feasible
    assert res.e_all <= budget * 1.001
    assert len(res.strategies) == 6


def test_infeasible_when_budget_tiny():
    cm = CostModel(paper_8gpu())
    res = dp_search_stage(_specs(4), enumerate_strategies(8), cm, 64.0,
                          16 * 1024 ** 2)   # 16MB: nothing fits
    assert not res.feasible


def test_ckpt_chosen_under_pressure():
    """With a tight budget the DP should turn CKPT on for some layers."""
    cm = CostModel(paper_8gpu())
    specs = _specs(8, seq=1024, d=1024)
    strategies = enumerate_strategies(8)
    loose = dp_search_stage(specs, strategies, cm, 32.0, 20 * GB)
    tight = dp_search_stage(specs, strategies, cm, 32.0, 3 * GB)
    assert loose.feasible and tight.feasible
    n_ckpt_tight = sum(s.ckpt for s in tight.strategies)
    n_ckpt_loose = sum(s.ckpt for s in loose.strategies)
    assert n_ckpt_tight >= n_ckpt_loose
    assert tight.time >= loose.time
