"""Optimizer, data pipeline, checkpointing, plan serialization, roofline."""
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ParallelPlan, Strategy
from repro.data import DataConfig, batch_specs, synthetic_lm_batches, text_corpus_batches
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def test_adamw_converges_on_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = adamw_update(params, g, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clip_limits_update():
    params = {"w": jnp.zeros(4)}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    g = {"w": jnp.full((4,), 1e6)}
    _, _, metrics = adamw_update(params, g, opt, cfg)
    assert float(metrics["grad_norm"]) > 1e5   # reported raw norm


def test_cosine_schedule_shape():
    assert float(cosine_schedule(0, 10, 100)) < 0.2
    assert abs(float(cosine_schedule(10, 10, 100)) - 1.0) < 1e-5
    assert float(cosine_schedule(100, 10, 100)) <= 0.11


def test_adamw_states_match_param_tree():
    params = {"a": jnp.zeros((2, 3), jnp.bfloat16), "b": [jnp.ones(4)]}
    opt = adamw_init(params)
    assert opt["master"]["a"].dtype == jnp.float32
    assert opt["m"]["b"][0].shape == (4,)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_synthetic_batches_deterministic():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab_size=100, seed=7)
    a = next(synthetic_lm_batches(cfg))
    b = next(synthetic_lm_batches(cfg))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 16)
    assert a["labels"].shape == (4, 16)
    assert a["tokens"].max() < 100


def test_batch_specs_match_generator():
    cfg = DataConfig(seq_len=16, global_batch=4, vocab_size=100,
                     vision_tokens=8, d_vision=32)
    batch = next(synthetic_lm_batches(cfg))
    specs = batch_specs(cfg)
    assert set(batch) == set(specs)
    for k in batch:
        assert batch[k].shape == specs[k].shape, k


def test_text_corpus_packing(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_text("hello world, this is a tiny corpus for packing! " * 50)
    cfg = DataConfig(seq_len=32, global_batch=2, vocab_size=256, seed=1)
    gen = text_corpus_batches(p, cfg)
    b1 = next(gen)
    assert b1["tokens"].shape == (2, 32)
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpointing import restore_train_state, save_train_state
    params = {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
              "nested": {"b": jnp.ones((4,), jnp.float32)}}
    opt = adamw_init(params)
    d = save_train_state(42, params, opt, tmp_path)
    assert (d / "params.npz").exists()
    p2, o2, step = restore_train_state(params, opt, tmp_path)
    assert step == 42
    np.testing.assert_array_equal(np.asarray(p2["w"], np.float32),
                                  np.asarray(params["w"], np.float32))
    assert p2["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(o2["m"]["nested"]["b"]),
                                  np.asarray(opt["m"]["nested"]["b"]))


# ---------------------------------------------------------------------------
# plan serialization
# ---------------------------------------------------------------------------

def test_plan_roundtrip():
    plan = ParallelPlan(
        n_devices=8, pp_degree=2, partition=[3, 3],
        strategies=[Strategy((("dp", 2), ("tp", 2)), ckpt=True)] * 6,
        global_batch=64, n_micro=8, est_throughput=12.5)
    plan2 = ParallelPlan.loads(plan.dumps())
    assert plan2.pp_degree == 2
    assert plan2.strategies == plan.strategies
    assert plan2.micro_batch_size == 8
    assert "dp2-tp2-ckpt" in plan2.summary()


# ---------------------------------------------------------------------------
# roofline HLO parsing
# ---------------------------------------------------------------------------

def test_collective_parse_synthetic():
    from repro.roofline import collective_bytes_from_hlo
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(bf16[1,512] %x), dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(f32[256] %y), to_apply=%add
  %rs = bf16[2,64]{1,0} reduce-scatter(bf16[16,64] %z), dimensions={0}
  %a2a = f32[8,32]{1,0} all-to-all(f32[8,32] %w), dimensions={0}
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4] %v), source_target_pairs={{0,1}}
  %not_a_collective = f32[999] add(f32[999] %a, f32[999] %b)
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 16 * 512 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 2 * 64 * 2
    assert out["all-to-all"] == 8 * 32 * 4
    assert out["collective-permute"] == 4 * 4 * 2


def test_modeled_memory_sanity():
    """Key §Perf finding: the paper-faithful baseline (remat, no sequence
    parallelism) does NOT fit qwen3-8b train_4k on 16GB v5e — the stash of
    layer inputs alone exceeds HBM; sequence-sharding the stash over the
    model axis (Megatron SP, our beyond-paper optimization) fixes it."""
    from repro.configs import get_config
    from repro.configs.specs import layerspecs_for
    from repro.roofline.analysis import modeled_memory
    cfg = get_config("qwen3-8b")
    specs = layerspecs_for(cfg, 4096)
    base = modeled_memory(specs, mode="train", chips=256, tp=16,
                          data_shards=16, remat=True, batch=256)
    assert base.traffic_bytes_per_device > 0
    assert not base.fits                          # stash alone > 16GB
    sp = modeled_memory(specs, mode="train", chips=256, tp=16,
                        data_shards=16, remat=True, batch=256, seq_shard=16)
    assert sp.fits
    assert sp.resident_bytes_per_device < base.resident_bytes_per_device


def test_cross_entropy_matches_naive():
    from repro.models.layers import cross_entropy_loss
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (2, 5, 11))
    labels = jax.random.randint(key, (2, 5), 0, 11)
    got = cross_entropy_loss(logits, labels)
    lf = jax.nn.log_softmax(logits, -1)
    ref = -jnp.take_along_axis(lf, labels[..., None], -1).mean()
    assert abs(float(got) - float(ref)) < 1e-5


# ---------------------------------------------------------------------------
# profiler + plan bridge + low-precision optimizer states
# ---------------------------------------------------------------------------

def test_profiler_produces_positive_times_and_feeds_cost_model():
    from repro.core import CostModel, Strategy, paper_8gpu
    from repro.core.layerspec import dense_layer
    from repro.core.profiler import measure_matmul_throughput, profile_layerspecs
    assert measure_matmul_throughput(256, iters=2) > 1e8   # >0.1 GFLOP/s
    specs = [dense_layer(f"l{i}", 128, 256, 4, 4, 512) for i in range(2)]
    times = profile_layerspecs(specs, iters=1)
    assert set(times) == {"l0", "l1"}
    assert all(t > 0 for t in times.values())
    cm = CostModel(paper_8gpu(), profiled_times=times)
    c = cm.layer_costs(specs[0], Strategy((("dp", 8),)), 8.0)
    assert c.time > 0


def test_plan_bridge_policies():
    from repro.configs import get_config
    from repro.configs.specs import layerspecs_for
    from repro.core import ParallelPlan, Strategy
    from repro.runtime.plan_bridge import policy_from_plan
    cfg = get_config("qwen3-8b")
    s = Strategy((("sdp", 16), ("tp", 16)), ckpt=True)
    plan = ParallelPlan(n_devices=256, pp_degree=1, partition=[cfg.n_layers],
                        strategies=[s] * cfg.n_layers, global_batch=256,
                        n_micro=1)
    pol = policy_from_plan(cfg, plan, specs=layerspecs_for(cfg, 4096))
    assert pol.tp and pol.zero
    assert pol.remat_segments == (True,)
    assert pol.seq_shard        # 8B stash overflows 16G -> §Perf rule fires
    # small model: no seq shard needed
    cfg4 = get_config("qwen3-4b")
    plan4 = ParallelPlan(n_devices=256, pp_degree=1,
                         partition=[cfg4.n_layers],
                         strategies=[s] * cfg4.n_layers, global_batch=256,
                         n_micro=1)
    pol4 = policy_from_plan(cfg4, plan4, specs=layerspecs_for(cfg4, 4096))
    assert not pol4.seq_shard


def test_plan_bridge_takes_most_memory_saving_choice():
    """A layer-wise hybrid plan runs on one policy that holds no more per
    device than any of its layers was sized for."""
    from repro.configs import get_config
    from repro.runtime.plan_bridge import model_axis_size, policy_from_plan
    cfg = get_config("qwen3-4b").with_(n_layers=8)
    tp4, sdp4, dp4 = (Strategy(((k, 4),)) for k in ("tp", "sdp", "dp"))
    dp4_ckpt = Strategy((("dp", 4),), ckpt=True)
    # embedding and head tp4, a body that mostly replicates and rarely
    # checkpoints: the majority would say no TP, no ZeRO, no remat
    body = [sdp4] + [dp4] * 6 + [dp4_ckpt]
    plan = ParallelPlan(n_devices=4, pp_degree=1, partition=[10],
                        strategies=[tp4] + body + [tp4], global_batch=4,
                        n_micro=1)
    pol = policy_from_plan(cfg, plan)
    assert model_axis_size(plan) == 4
    assert pol.tp and pol.zero
    assert pol.remat_segments == (True,)
    flat = ParallelPlan(n_devices=4, pp_degree=1, partition=[10],
                        strategies=[dp4] * 10, global_batch=4, n_micro=1)
    pol = policy_from_plan(cfg, flat)
    assert model_axis_size(flat) == 1
    assert not pol.tp and not pol.zero
    assert pol.remat_segments == (False,)


def test_bf16_optimizer_state_memory_and_convergence():
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    params = {"w": jnp.array([4.0, -2.0])}
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, state_dtype="bf16")
    opt = adamw_init(params, cfg)
    assert opt["m"]["w"].dtype == jnp.bfloat16
    assert opt["master"]["w"].dtype == jnp.float32
    for _ in range(150):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = adamw_update(params, g, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 5e-2
