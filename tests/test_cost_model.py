"""Cost estimator: paper Table I validation, Takeaway #3, overlap slowdown."""
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CostModel, CostModelConfig, Strategy, paper_8gpu
from repro.core.layerspec import dense_layer, total_params
from repro.configs.paper_models import paper_model_specs

GB = 1024 ** 3

# paper Table I ground truth: (params, activation bytes / sample)
TABLE_I = {
    "bert-huge-32": (672e6, 3149.39),
    "bert-huge-48": (987e6, 4657.51),
    "bert-xhuge": (10.2e9, 24210.05),
    "vit-huge-32": (632e6, 646.5),
    "vit-huge-48": (947e6, 968.59),
    "vit-xhuge": (10.1e9, 5313.9),
    "t5-large-32": (502e6, 4119.66),
    "t5-large-48": (737e6, 6107.75),
    "t5-512/4-32": (502e6, 1777.06),
    "t5-512/4-48": (737e6, 2473.10),
    "swin-huge-32": (701e6, 726.59),
    "swin-huge-48": (1016e6, 1016.8),
    "gpt3-15b": (15.4e9, None),
    "gpt3-39b": (39.1e9, None),
    "gpt3-65b": (64.9e9, None),
}


@pytest.mark.parametrize("name,expected", list(TABLE_I.items()))
def test_param_counts_match_table1(name, expected):
    params, _ = expected
    got = total_params(paper_model_specs(name))
    assert abs(got - params) / params < 0.12, (name, got / 1e6)


@pytest.mark.parametrize("name", [k for k, v in TABLE_I.items() if v[1]])
def test_activation_sizes_order_of_table1(name):
    """Activations are profiled quantities in the paper; our analytic model
    with one global calibration constant should land within 2x for every
    model (it's the RELATIVE layer costs that drive the search)."""
    _, act_mb = TABLE_I[name]
    specs = paper_model_specs(name)
    got_mb = sum(s.bnd_bytes_per_sample + s.int_bytes_per_sample
                 for s in specs) / (1024 ** 2)
    assert 0.5 < got_mb / act_mb < 2.0, (name, got_mb, act_mb)


def _mk_layer():
    return dense_layer("l", 512, 1024, 16, 16, 4096, causal=False,
                       gated=False, store_attn_matrix=True)


def test_takeaway3_sdp_beats_dp_sdp_mix():
    """Pure SDP total COMMUNICATION VOLUME < any DP x SDP mixture
    (Takeaway #3: 3(N-1)/N < 2(N1-1)/N1 + 3(N2-1)/N2 for N1*N2=N).
    The paper's proof is about volume, so we isolate communication with a
    zero-FLOP layer (with compute, overlap can hide either side)."""
    import dataclasses
    cm = CostModel(paper_8gpu())
    spec = dataclasses.replace(_mk_layer(), flops_per_sample=0.0)
    pure = cm.layer_costs(spec, Strategy((("sdp", 8),)), 8.0)
    for (d, s) in [(2, 4), (4, 2)]:
        mixed = cm.layer_costs(
            spec, Strategy((("dp", d), ("sdp", s))), 8.0)
        assert pure.time <= mixed.time + 1e-12
        assert pure.mem_ms <= mixed.mem_ms + 1e-6


def test_ckpt_trades_memory_for_time():
    cm = CostModel(paper_8gpu())
    spec = _mk_layer()
    s = Strategy((("dp", 8),))
    base = cm.layer_costs(spec, s, 8.0)
    ck = cm.layer_costs(spec, s.with_ckpt(), 8.0)
    assert ck.mem_f < base.mem_f          # forward stash shrinks
    assert ck.time > base.time            # recompute costs time
    assert ck.mem_b > base.mem_b          # backward peak appears


def test_tp_shards_states_dp_replicates():
    cm = CostModel(paper_8gpu())
    spec = _mk_layer()
    dp = cm.layer_costs(spec, Strategy((("dp", 8),)), 8.0)
    tp = cm.layer_costs(spec, Strategy((("tp", 8),)), 8.0)
    sdp = cm.layer_costs(spec, Strategy((("sdp", 8),)), 8.0)
    assert dp.mem_ms > tp.mem_ms
    assert dp.mem_ms > sdp.mem_ms
    # DP has no fwd comm; TP does
    assert dp.time_fwd < tp.time_fwd


def test_overlap_slowdown_increases_cost():
    cluster = paper_8gpu()
    import dataclasses
    no_slow = dataclasses.replace(
        cluster, device=dataclasses.replace(cluster.device,
                                            overlap_slowdown=1.0))
    spec = _mk_layer()
    s = Strategy((("dp", 8),))
    t_slow = CostModel(cluster).layer_costs(spec, s, 64.0).time
    t_fast = CostModel(no_slow).layer_costs(spec, s, 64.0).time
    assert t_slow > t_fast


@given(st.integers(min_value=1, max_value=6),
       st.floats(min_value=1.0, max_value=64.0))
@settings(max_examples=20, deadline=None)
def test_memory_positive_and_monotone_in_batch(k, b):
    cm = CostModel(paper_8gpu())
    spec = _mk_layer()
    s = Strategy((("dp", 2 ** min(k, 3)),))
    c1 = cm.layer_costs(spec, s, b)
    c2 = cm.layer_costs(spec, s, 2 * b)
    assert c1.mem_f > 0 and c1.mem_ms > 0
    assert c2.mem_f > c1.mem_f
    assert c2.time >= c1.time
