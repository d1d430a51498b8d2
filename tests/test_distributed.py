"""Multi-device integration tests (subprocesses with fake host devices —
the main process must keep seeing 1 CPU device)."""
import pytest

from conftest import run_subprocess


@pytest.mark.slow
def test_executor_tp_zero_training_8dev():
    out = run_subprocess("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(model=2)
from repro.configs import get_config
from repro.runtime import ShardPolicy, make_train_step, init_train_state
from repro.data import DataConfig, synthetic_lm_batches, batch_specs
cfg = get_config("qwen3-4b").reduced(n_layers=2, d_model=256)
dcfg = DataConfig(seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)
pol = ShardPolicy(tp=True, zero=True, remat_segments=(True,))
with mesh:
    step = make_train_step(cfg, mesh, pol, batch_specs(dcfg))
    params, opt = init_train_state(cfg, mesh, pol)
    gen = synthetic_lm_batches(dcfg)
    losses = []
    for i in range(8):
        b = {k: jnp.asarray(v) for k, v in next(gen).items()}
        params, opt, m = step.fn(params, opt, b)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
    # verify params actually sharded over model axis
    wq = params["stacks"][0]["attn"]["wq"]
    assert len(wq.sharding.device_set) == 8
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_runtime_matches_reference_8dev():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pipeline_mesh
mesh = make_pipeline_mesh(4, 2)
from repro.configs import get_config
from repro.models import init_lm, lm_loss
from repro.runtime.pipeline import make_pipeline_loss, stage_split_params
cfg = get_config("qwen3-4b").reduced(n_layers=4, d_model=128)
key = jax.random.PRNGKey(0)
params = init_lm(key, cfg)
m, Bm, S = 6, 4, 16
toks = jax.random.randint(key, (m, Bm, S), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.PRNGKey(1), (m, Bm, S), 0, cfg.vocab_size)
with mesh:
    ps = stage_split_params(params, 4)
    loss_fn = make_pipeline_loss(cfg, mesh, n_micro=m)
    loss, grads = jax.jit(loss_fn)(ps, {"tokens": toks, "labels": labels})
flat = {"tokens": toks.reshape(m*Bm, S), "labels": labels.reshape(m*Bm, S)}
ref = lm_loss(params, flat, cfg)
rg = jax.grad(lambda p: lm_loss(p, flat, cfg))(params)
assert abs(float(loss) - float(ref)) < 1e-3
for name in ["embed", "final_norm", "head"]:
    g = np.asarray(grads[name], np.float32); r = np.asarray(rg[name], np.float32)
    assert np.abs(g - r).max() < 0.02 * max(np.abs(r).max(), 1e-3) + 1e-4, name
gs = np.asarray(grads["stacks"][0]["attn"]["wq"], np.float32).reshape(4, -1)
rs = np.asarray(rg["stacks"][0]["attn"]["wq"], np.float32).reshape(4, -1)
assert np.abs(gs - rs).max() < 0.02 * np.abs(rs).max() + 1e-4
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_moe_expert_parallel_serving_8dev():
    out = run_subprocess("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(model=4)
from repro.configs import get_config
from repro.runtime import ShardPolicy, make_serve_step
from repro.models import init_lm, init_decode_state
cfg = get_config("kimi-k2-1t-a32b").reduced()
pol = ShardPolicy(tp=True, zero=False)
key = jax.random.PRNGKey(0)
with mesh:
    sstep = make_serve_step(cfg, mesh, pol, batch=4, context=64)
    params = jax.jit(lambda k: init_lm(k, cfg),
                     out_shardings=sstep.in_shardings[0])(key)
    st = jax.jit(lambda: init_decode_state(cfg, 4, 64),
                 out_shardings=sstep.in_shardings[1])()
    tok = jnp.zeros((4,), jnp.int32)
    for _ in range(3):
        logits, st = sstep.fn(params, st, tok)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    assert bool(jnp.isfinite(logits).all())
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_entrypoint_tiny():
    """End-to-end dryrun driver on a small arch/shape (full 512-dev mesh)."""
    import subprocess, sys, os, pathlib
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-370m",
         "--shape", "decode_32k"],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "1 ok, 0 failed" in res.stdout


@pytest.mark.slow
def test_moe_shmap_dispatch_matches_einsum_16dev():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(model=4)
from repro.configs import get_config
from repro.models.flags import batch_sharding
from repro.models.moe import init_moe, moe_ffn
cfg = get_config("kimi-k2-1t-a32b").reduced().with_(dtype=jnp.float32,
                                                    capacity_factor=8.0)
p = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model), jnp.float32)
with mesh:
    with batch_sharding(("data",), mesh=mesh):
        o1, a1 = jax.jit(lambda p, x: moe_ffn(p, x, cfg, dispatch="shmap"))(p, x)
    o2, a2 = moe_ffn(p, x, cfg, dispatch="einsum")
np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-4)
assert abs(float(a1) - float(a2)) < 1e-5
print("OK")
""", devices=16)
    assert "OK" in out


@pytest.mark.slow
def test_seq_shard_policy_same_loss_8dev():
    """The §Perf stash-only sequence-parallel policy must be numerically
    identical to the baseline (it only moves shardings)."""
    out = run_subprocess("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh(model=4)
from repro.configs import get_config
from repro.runtime import ShardPolicy, make_train_step, init_train_state
from repro.data import DataConfig, synthetic_lm_batches, batch_specs
cfg = get_config("qwen3-4b").reduced(n_layers=2, d_model=256).with_(
    dtype=jnp.float32)
dcfg = DataConfig(seq_len=64, global_batch=4, vocab_size=cfg.vocab_size)
losses = {}
for seq_shard in (False, True):
    pol = ShardPolicy(tp=True, zero=True, remat_segments=(True,),
                      seq_shard=seq_shard)
    with mesh:
        step = make_train_step(cfg, mesh, pol, batch_specs(dcfg))
        params, opt = init_train_state(cfg, mesh, pol)
        gen = synthetic_lm_batches(dcfg)
        ls = []
        for _ in range(3):
            b = {k: jnp.asarray(v) for k, v in next(gen).items()}
            params, opt, m = step.fn(params, opt, b)
            ls.append(float(m["loss"]))
    losses[seq_shard] = ls
for a, b in zip(losses[False], losses[True]):
    assert abs(a - b) < 2e-4, (losses[False], losses[True])
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_all_schedules_match_reference_8dev():
    """Schedule-equivalence: gpipe / 1f1b / 1f1b-interleaved (V=2) /
    zb-h1 all reproduce the non-pipelined executor-path loss and
    gradients (the zero-bubble program executes its forward projection;
    autodiff realizes the B/W split)."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pipeline_mesh
mesh = make_pipeline_mesh(4, 2)
from repro.configs import get_config
from repro.models import init_lm, lm_loss
from repro.runtime.pipeline import make_pipeline_loss, stage_split_params
cfg = get_config("qwen3-4b").reduced(n_layers=8, d_model=128)
key = jax.random.PRNGKey(0)
params = init_lm(key, cfg)
m, Bm, S = 6, 4, 16
toks = jax.random.randint(key, (m, Bm, S), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.PRNGKey(1), (m, Bm, S), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": labels}
flat = {"tokens": toks.reshape(m*Bm, S), "labels": labels.reshape(m*Bm, S)}
ref = lm_loss(params, flat, cfg)
rg = jax.grad(lambda p: lm_loss(p, flat, cfg))(params)
rs = np.asarray(rg["stacks"][0]["attn"]["wq"], np.float32)
with mesh:
    for sched, V in [("gpipe", 1), ("1f1b", 1), ("1f1b-interleaved", 2),
                     ("zb-h1", 1)]:
        ps = stage_split_params(params, 4, V)
        loss_fn = make_pipeline_loss(cfg, mesh, n_micro=m, schedule=sched,
                                     n_chunks=V)
        loss, grads = jax.jit(loss_fn)(ps, batch)
        assert abs(float(loss) - float(ref)) < 1e-3, sched
        for name in ["embed", "final_norm"]:
            g = np.asarray(grads[name], np.float32)
            r = np.asarray(rg[name], np.float32)
            assert np.abs(g - r).max() < 0.02 * max(np.abs(r).max(), 1e-3) + 1e-4, (sched, name)
        gs = np.asarray(grads["stacks"][0]["attn"]["wq"], np.float32)
        # undo the (P, V, Lc) round-robin placement: stage s = v*P + i
        order = np.transpose(gs, (1, 0, 2) + tuple(range(3, gs.ndim)))
        flat_g = order.reshape(rs.shape)
        assert np.abs(flat_g - rs).max() < 0.02 * np.abs(rs).max() + 1e-4, sched
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_pipeline_1f1b_memory_schedule_matches_gpipe_8dev():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_pipeline_mesh
mesh = make_pipeline_mesh(4, 2)
from repro.configs import get_config
from repro.models import init_lm
from repro.runtime.pipeline import make_pipeline_loss, stage_split_params
cfg = get_config("qwen3-4b").reduced(n_layers=4, d_model=128)
key = jax.random.PRNGKey(0)
params = init_lm(key, cfg)
m, Bm, S = 4, 4, 16
toks = jax.random.randint(key, (m, Bm, S), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.PRNGKey(1), (m, Bm, S), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": labels}
with mesh:
    ps = stage_split_params(params, 4)
    lg = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=m, schedule="gpipe"))
    l1 = jax.jit(make_pipeline_loss(cfg, mesh, n_micro=m, schedule="1f1b"))
    loss_g, grads_g = lg(ps, batch)
    loss_1, grads_1 = l1(ps, batch)
assert abs(float(loss_g) - float(loss_1)) < 1e-4
g0 = np.asarray(grads_g["stacks"][0]["attn"]["wq"], np.float32)
g1 = np.asarray(grads_1["stacks"][0]["attn"]["wq"], np.float32)
assert np.abs(g0 - g1).max() < 1e-3 * max(1.0, np.abs(g0).max())
print("OK")
""")
    assert "OK" in out
