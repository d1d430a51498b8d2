"""BMW balance machinery: exact partitioning, balance degrees, Eq. 7/8
invariants of the adjustment step."""
import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pipeline_balance import (PartitionEval, adjust_partition,
                                         balance_degrees,
                                         inflight_microbatches,
                                         memory_balanced_partition,
                                         stage_bounds,
                                         time_balanced_partition,
                                         validate_adjustment)


def _brute_partition(loads, P):
    L = len(loads)
    best, best_p = float("inf"), None
    for cuts in itertools.combinations(range(1, L), P - 1):
        bounds = [0, *cuts, L]
        parts = [bounds[i + 1] - bounds[i] for i in range(P)]
        m = max(sum(loads[bounds[i]:bounds[i + 1]]) for i in range(P))
        if m < best:
            best, best_p = m, parts
    return best, best_p


@given(st.lists(st.floats(min_value=0.1, max_value=10.0), min_size=4,
                max_size=9), st.integers(min_value=2, max_value=4))
@settings(max_examples=30, deadline=None)
def test_time_partition_optimal(loads, P):
    if P > len(loads):
        return
    parts = time_balanced_partition(loads, P)
    assert sum(parts) == len(loads) and len(parts) == P
    assert all(p >= 1 for p in parts)
    got = max(sum(loads[a:b]) for a, b in stage_bounds(parts))
    best, _ = _brute_partition(loads, P)
    assert got <= best + 1e-9


def test_inflight_1f1b_vs_gpipe():
    # 1F1B: stage 0 of 4 holds 4 micro-batches, last stage holds 1
    assert inflight_microbatches(0, 4, 8) == 4
    assert inflight_microbatches(3, 4, 8) == 1
    assert inflight_microbatches(0, 4, 2) == 2      # capped by m
    assert inflight_microbatches(0, 4, 8, "gpipe") == 8


def test_inflight_interleaved_per_chunk_accounting():
    # P=4, V=2: device 0 warms up 2*3 + (2-1)*4 + 1 = 11 chunk activation
    # sets = 5.5 full-stage units; device 3 (last) 2*0 + 4 + 1 = 5 -> 2.5
    assert inflight_microbatches(0, 4, 16, "1f1b-interleaved", vpp=2) == 5.5
    assert inflight_microbatches(3, 4, 16, "1f1b-interleaved", vpp=2) == 2.5
    # capped by the m*V chunks that exist
    assert inflight_microbatches(0, 4, 4, "1f1b-interleaved", vpp=2) == 4.0
    # V=1 falls back to plain 1F1B
    assert inflight_microbatches(0, 4, 8, "1f1b-interleaved", vpp=1) == 4


def test_memory_partition_counteracts_1f1b():
    """Uniform layers: the memory-balanced 1F1B partition puts FEWER layers
    on shallow stages (they hold more in-flight micro-batches)."""
    mems = [1.0] * 16
    p = memory_balanced_partition(mems, 4, n_micro=8)
    assert sum(p) == 16
    assert p[0] <= p[-1]


def test_balance_degrees_bounds():
    t, m = balance_degrees([1.0, 1.0, 1.0, 1.0], [4.0, 3.0, 2.0, 1.0])
    assert abs(t - 0.75) < 1e-9          # perfect time balance: 1 - 1/P
    assert 0.0 <= m <= 0.75


def test_adjust_moves_from_slowest():
    parts = adjust_partition([4, 4, 4, 4], [1.0, 9.0, 1.0, 1.0])
    assert [3, 5] not in parts           # moved from stage 1 only
    assert any(p[1] == 3 for p in parts)
    for p in parts:
        assert sum(p) == 16


def test_validate_criteria():
    ok = PartitionEval([3, 5], [1.0, 2.0], [1.0, 2.0], [5.0, 5.0], True)
    assert validate_adjustment(ok, prev_max_time=3.0, budget=6.0,
                               pt_max_mem=5.5)
    # (1) slower than previous max
    assert not validate_adjustment(ok, 1.5, 6.0, 5.5)
    # (2) over budget
    assert not validate_adjustment(ok, 3.0, 4.0, 5.5)
    # (3) above time-balanced partition's max memory
    assert not validate_adjustment(ok, 3.0, 6.0, 4.0)
    bad = PartitionEval([3, 5], [1.0, 2.0], [1.0, 2.0], [5.0, 5.0], False)
    assert not validate_adjustment(bad, 3.0, 6.0, 5.5)
