"""Search-space construction: reproduces the paper's exact counts and rules."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import construct_search_space, enumerate_strategies
from repro.core.strategy import DP, SDP, TP, Strategy


def test_paper_counts_8_gpus():
    # §III-B: 68 strategies before Takeaway #3, 44 after.
    assert construct_search_space(8, prune_dp_sdp=False).total_leaves() == 68
    assert construct_search_space(8).total_leaves() == 44


def test_per_pp_counts_8_gpus():
    ss = construct_search_space(8)
    assert len(ss.strategies(8)) == 2     # group=1: serial +/- ckpt
    assert len(ss.strategies(4)) == 6     # group=2
    assert len(ss.strategies(2)) == 14    # group=4
    assert len(ss.strategies(1)) == 22    # group=8


def test_no_dp_sdp_mix():
    for pp, strats in construct_search_space(16).per_pp.items():
        for s in strats:
            used = {p for p, _ in s.levels}
            assert not ({DP, SDP} <= used), s.name()


def test_ckpt_doubles_space():
    with_ = construct_search_space(8, allow_ckpt=True).total_leaves()
    without = construct_search_space(8, allow_ckpt=False).total_leaves()
    assert with_ == 2 * without


@given(st.integers(min_value=0, max_value=6))
@settings(max_examples=7, deadline=None)
def test_strategies_cover_group(k):
    n = 2 ** k
    for s in enumerate_strategies(n):
        assert s.total == n
        for _, deg in s.levels:
            assert deg >= 2 and (deg & (deg - 1)) == 0   # power of two
        # paradigms never repeat across levels
        paras = [p for p, _ in s.levels]
        assert len(paras) == len(set(paras))


def test_max_tp_filter():
    ss = construct_search_space(8, max_tp=2)
    for strats in ss.per_pp.values():
        assert all(s.tp <= 2 for s in strats)


def test_strategy_roundtrip():
    s = Strategy((("dp", 4), ("tp", 2)), ckpt=True)
    assert Strategy.from_json(s.to_json()) == s
    assert s.dp == 4 and s.tp == 2 and s.sdp == 1
    assert s.data_degree == 4 and s.total == 8
    assert s.name() == "dp4-tp2-ckpt"
