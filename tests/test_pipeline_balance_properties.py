"""Property-based verification of the pipeline-balance core (§IV-B).

Fuzzed over random layer-time/memory vectors, stage counts, schedules and
virtual-chunk degrees: every partition helper must return a *structurally
valid* partition (sums to L, no empty stage), the balance degrees of Eq. 6
must stay in [0, 1], and the greedy §IV-B2 adjustment must never shed a
stage to empty.
"""
import itertools

import numpy as np

from hypothesis import given, settings, strategies as st

from repro.core.pipeline_balance import (adjust_partition, balance_degrees,
                                         inflight_microbatches,
                                         memory_balanced_partition,
                                         stage_bounds,
                                         time_balanced_partition)

SCHEDULES = ("gpipe", "1f1b", "1f1b-interleaved")


def _check_partition(part, L, P):
    assert len(part) == P
    assert sum(part) == L
    assert min(part) >= 1
    # stage_bounds must tile [0, L) exactly
    bounds = stage_bounds(part)
    assert bounds[0][0] == 0 and bounds[-1][1] == L
    assert all(b0 < b1 for b0, b1 in bounds)
    assert all(bounds[i][1] == bounds[i + 1][0] for i in range(P - 1))


# ---------------------------------------------------------------------------
# partitions: sum to L, >= 1 layer per stage
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(min_value=0.0, max_value=10.0),
                min_size=1, max_size=24),
       st.integers(min_value=1, max_value=8))
@settings(max_examples=40, deadline=None)
def test_time_balanced_partition_is_valid(times, P):
    P = min(P, len(times))
    part = time_balanced_partition(times, P)
    _check_partition(part, len(times), P)


@given(st.lists(st.floats(min_value=0.0, max_value=1e9),
                min_size=1, max_size=24),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=16),
       st.sampled_from(SCHEDULES),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=40, deadline=None)
def test_memory_balanced_partition_is_valid(mems, P, n_micro, schedule, vpp):
    P = min(P, len(mems))
    part = memory_balanced_partition(mems, P, n_micro, schedule, vpp)
    _check_partition(part, len(mems), P)


@given(st.lists(st.floats(min_value=0.01, max_value=5.0),
                min_size=2, max_size=8),
       st.integers(min_value=2, max_value=3))
@settings(max_examples=15, deadline=None)
def test_time_balanced_partition_is_optimal(times, P):
    """The O(P·L²) DP must actually minimize the max stage load — checked
    against brute-force enumeration of all contiguous cut placements."""
    L = len(times)
    P = min(P, L)
    part = time_balanced_partition(times, P)
    pref = np.concatenate([[0.0], np.cumsum(times)])

    def max_load(cuts):
        edges = [0, *cuts, L]
        return max(pref[b] - pref[a] for a, b in zip(edges, edges[1:]))

    best = min(max_load(c) for c in itertools.combinations(range(1, L), P - 1))
    got = max_load(list(np.cumsum(part))[:-1])
    assert got <= best + 1e-9


# ---------------------------------------------------------------------------
# balance degrees (Eq. 6) in [0, 1]
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(min_value=0.0, max_value=1e6),
                min_size=1, max_size=16),
       st.lists(st.floats(min_value=0.0, max_value=1e12),
                min_size=1, max_size=16))
@settings(max_examples=40, deadline=None)
def test_balance_degrees_in_unit_interval(times, mems):
    a_t, a_m = balance_degrees(times, mems)
    assert 0.0 <= a_t <= 1.0
    assert 0.0 <= a_m <= 1.0
    # max/sum >= 1/n  =>  alpha <= 1 - 1/n
    assert a_t <= 1.0 - 1.0 / len(times) + 1e-12
    assert a_m <= 1.0 - 1.0 / len(mems) + 1e-12


def test_balance_degrees_extremes():
    # perfectly balanced 4 stages: alpha = 1 - 1/4
    assert balance_degrees([1, 1, 1, 1], [2, 2, 2, 2]) == (0.75, 0.75)
    # one stage carries everything: alpha = 0
    a_t, a_m = balance_degrees([5, 0, 0], [7, 0, 0])
    assert a_t == 0.0 and a_m == 0.0


# ---------------------------------------------------------------------------
# greedy adjustment (§IV-B2) never empties a stage
# ---------------------------------------------------------------------------

@given(st.lists(st.floats(min_value=0.0, max_value=10.0),
                min_size=2, max_size=24),
       st.integers(min_value=2, max_value=8),
       st.lists(st.floats(min_value=0.0, max_value=1.0),
                min_size=8, max_size=8))
@settings(max_examples=40, deadline=None)
def test_adjust_partition_never_empties_a_stage(times, P, noise):
    P = min(P, len(times))
    part = time_balanced_partition(times, P)
    stage_times = [(noise[i % len(noise)] + 0.1) * (1 + i) for i in range(P)]
    for cand in adjust_partition(part, stage_times):
        _check_partition(cand, len(times), P)
        # exactly one boundary layer moved to an adjacent stage
        delta = [a - b for a, b in zip(cand, part)]
        assert sum(delta) == 0 and sum(abs(d) for d in delta) == 2


def test_adjust_partition_single_layer_slowest_stage_yields_nothing():
    # the slowest stage has 1 layer -> nothing can be shed
    assert adjust_partition([1, 3], [10.0, 1.0]) == []


# ---------------------------------------------------------------------------
# in-flight micro-batch accounting
# ---------------------------------------------------------------------------

@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=1, max_value=32),
       st.sampled_from(SCHEDULES),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=40, deadline=None)
def test_inflight_microbatches_bounds(P, m, schedule, vpp):
    for i in range(P):
        infl = inflight_microbatches(i, P, m, schedule, vpp)
        assert 0.0 < infl <= m  # never more than every micro-batch in flight
    # 1F1B flush: shallower stages hold at least as much as deeper ones
    if schedule == "1f1b":
        vals = [inflight_microbatches(i, P, m, schedule, 1) for i in range(P)]
        assert all(a >= b for a, b in zip(vals, vals[1:]))
