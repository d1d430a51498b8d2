"""Qwen3-4B trained as one pipeline stage (9 of its 36 layers, published
widths) on a four-chip host: the plan searched for it, the layout the
GSPMD executor runs it on, the same step sharded over four devices as on
one, the line that names what the executor drops of the plan, and the
readers of the collective time its traced runs record."""
import json
import sys

import pytest

from conftest import REPO, run_subprocess


def _stage_plan(batch):
    from repro.configs import get_config
    from repro.launch.train import search_plan
    cfg = get_config("qwen3-4b").with_(n_layers=9, tie_embeddings=True)
    return cfg, search_plan(cfg, 4096, batch, n_devices=4)


@pytest.mark.parametrize("batch", [8, 16])
def test_search_plan_for_the_stage_is_feasible_and_runs_as_tp_zero(batch):
    """At seq 4096 on 4 v5e chips the planner asks for two pipeline
    stages; the executor runs TP2 + ZeRO + remat in one stage."""
    from repro.runtime.plan_bridge import model_axis_size, policy_from_plan
    cfg, plan = _stage_plan(batch)
    assert plan.n_devices == 4 and plan.global_batch == batch
    assert plan.est_throughput > 0
    assert plan.pp_degree == 2
    assert model_axis_size(plan) == 2
    policy = policy_from_plan(cfg, plan)
    assert (policy.tp, policy.zero, policy.remat_segments) == (
        True, True, (True,))


@pytest.mark.parametrize("batch", [8, 16])
def test_execution_line_names_the_dropped_pipeline(batch):
    from repro.runtime.plan_bridge import execution_line, policy_from_plan
    cfg, plan = _stage_plan(batch)
    line = execution_line(plan, policy_from_plan(cfg, plan),
                          {"data": 2, "model": 2}, {"flash"})
    searched, executed, dropped = line.split(" | ")
    assert searched.startswith(f"searched: pp2 {plan.schedule}")
    assert f"m={plan.n_micro}" in searched
    assert f"ckpt x{sum(s.ckpt for s in plan.strategies)}" in searched
    assert executed == ("executed: one GSPMD stage on mesh "
                        "{'data': 2, 'model': 2}, tp=True zero=True "
                        "remat=True attn=flash")
    assert dropped.startswith(f"dropped: pp2 {plan.schedule}")


def test_execution_line_of_a_one_stage_plan_drops_no_pipeline():
    from repro.runtime.plan_bridge import execution_line, policy_from_plan
    cfg, plan = _stage_plan(4)
    assert plan.pp_degree == 1
    line = execution_line(plan, policy_from_plan(cfg, plan),
                          {"data": 1, "model": 4})
    assert line.endswith("attn=none | dropped: per-layer tp/sdp/ckpt")


def test_trainer_prints_the_execution_line(capsys):
    from repro.launch import train
    res = train.main(["--arch", "qwen3-4b", "--reduced", "--layers", "2",
                      "--d-model", "64", "--steps", "1", "--batch", "2",
                      "--seq", "16"])
    out = capsys.readouterr().out
    line = next(x for x in out.splitlines() if x.startswith("searched: "))
    assert f"searched: pp{res.plan.pp_degree} " in line
    assert f"executed: one GSPMD stage on mesh {res.mesh_shape}" in line


def test_stage_step_on_four_devices_matches_one_device():
    """A tiny Qwen3-shaped model (QK-norm, GQA 4/2, tied embedding) under
    the policy and mesh the stage's searched plan gives on 4 devices,
    against the same step on one device.  The four-device step splits
    the bf16 matmuls over the model axis and the batch over the data
    axis, so partial sums are rounded to bf16 at other points: the loss
    (an f32 mean of f32 cross entropies of bf16 logits) agrees to 1e-3
    relative, each leaf's first-step gradient norm (bf16 gradients, a
    bf16 ulp is 2**-8 = 0.4%) to 2e-2 relative."""
    out = run_subprocess("""
import json
import jax, numpy as np
from jax.sharding import AxisType, Mesh
from repro.configs import get_config
from repro.data import DataConfig, batch_specs, synthetic_lm_batches
from repro.launch.mesh import make_local_mesh
from repro.launch.train import search_plan
from repro.optim import AdamWConfig
from repro.runtime import init_train_state, make_train_step
from repro.runtime.plan_bridge import model_axis_size, policy_from_plan
stage = get_config("qwen3-4b").with_(n_layers=9, tie_embeddings=True)
plan = search_plan(stage, 4096, 16, n_devices=4)
policy = policy_from_plan(stage, plan)
four = make_local_mesh(model=model_axis_size(plan))
assert dict(four.shape) == {"data": 2, "model": 2}, four.shape
assert four.devices.size == 4
one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
           axis_types=(AxisType.Auto,) * 2)
cfg = get_config("qwen3-4b").with_(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    d_ff=256, vocab_size=512, tie_embeddings=True)
dcfg = DataConfig(seq_len=64, global_batch=4, vocab_size=cfg.vocab_size)
batch = next(synthetic_lm_batches(dcfg))
out = {}
for name, mesh in (("four", four), ("one", one)):
    with mesh:
        step = make_train_step(cfg, mesh, policy, batch_specs(dcfg),
                               AdamWConfig())
        params, opt = init_train_state(cfg, mesh, policy, seed=5)
        params, opt, m = step.fn(params, opt, batch)
        assert len(opt["m"]["embed"].sharding.device_set) == mesh.size
        out[name] = {"loss": float(m["loss"]),
                     "grad_norm": float(m["grad_norm"]),
                     "leaves": {jax.tree_util.keystr(p): float(
                         np.linalg.norm(np.asarray(x, np.float32)))
                         for p, x in jax.tree_util.tree_flatten_with_path(
                             opt["m"])[0]}}
print(json.dumps(out))
""", devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    four, one = res["four"], res["one"]
    assert four["loss"] == pytest.approx(one["loss"], rel=1e-3)
    assert four["grad_norm"] == pytest.approx(one["grad_norm"], rel=2e-2)
    assert four["leaves"].keys() == one["leaves"].keys()
    for k, v in one["leaves"].items():
        assert four["leaves"][k] == pytest.approx(v, rel=2e-2), k


def _reader(name):
    sys.path.insert(0, str(REPO))
    from bench.harness import registry
    return registry.metric_reader(name)


def _record():
    """A 100 ns window on two chips.  Chip 0: compute 0-40, an all-reduce
    30-50 (10 under compute, 10 exposed), an all-gather 60-70 alone.
    Chip 1: compute 0-100 with an async all-gather 20-30 under it."""
    sys.path.insert(0, str(REPO))
    from bench.harness import trace
    dev0 = {"ops": [(0.0, 40.0, "%fusion.1 = bf16[8]{0} fusion()"),
                    (30.0, 50.0, "%all-reduce.2 = f32[8]{0} all-reduce()"),
                    (60.0, 70.0, "%all-gather.3 = bf16[8]{0} all-gather()")],
            "async": [], "modules": []}
    dev1 = {"ops": [(0.0, 100.0, "%fusion.4 = bf16[8]{0} fusion()")],
            "async": [(20.0, 30.0, "%all-gather-start.5 = bf16[8]{0} x()")],
            "modules": []}
    events = {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
              "spans": [(0.0, 100.0, trace.WINDOW)]}
    return {"kind": "train", "trace": trace.reduce(events)}


@pytest.mark.parametrize("name,expected", [
    # chip 0: 20 + 10 of collectives, chip 1: 10 -> 40 / 2 chips / 100
    ("collective_pct.train", 20.0),
    # chip 0: 10 + 10 exposed, chip 1: none -> 20 / 2 / 100
    ("collective_exposed_pct.train", 10.0),
])
def test_collective_readers_on_a_hand_built_trace(name, expected):
    reader = _reader(name)
    assert reader.read(_record()) == pytest.approx(expected)
    assert reader.read({"kind": "train"}) is None
    assert reader.read({"kind": "serve", "trace": {}}) is None
