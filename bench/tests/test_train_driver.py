"""The training driver at a tiny size on the CPU: it stays the trainer it
times, a sound run is correct, and each fault a one-chip training cell
can have, planted under the timed path, makes ``correct`` false."""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import pytest

from bench.harness import gen, registry
from conftest import measure, tiny_ssm


def test_first_two_losses_equal_the_trainers():
    """Same seed (init 0, data 1234, as ``launch/train.main``), same plan
    search, same jitted step: the same first two losses."""
    from repro.configs import get_config
    from repro.launch import train
    res = train.main(["--arch", "mamba2-370m", "--reduced", "--layers", "2",
                      "--d-model", "64", "--steps", "2", "--batch", "4",
                      "--seq", "64", "--log-every", "1"])
    drv = registry.driver("train")
    drv.program_config = lambda c: get_config("mamba2-370m").reduced(
        n_layers=2, d_model=64)
    c, t = tiny_ssm()
    for k in ("time_step_min", "time_step_max"):    # main's own dt_bias
        c.pop(k, None)
    cfg, plan, mesh, step, params, opt = drv.build(c, t, 1, seed=0)
    assert plan.summary() == res.plan.summary()
    batches = list(itertools.islice(
        gen.zipf_batches(cfg.vocab_size, 4, 64, 1234), 2))
    with mesh:
        prog, *_ = drv.check_steps(step.fn, params, opt, batches,
                                   t["optimizer"]["beta1"])
    assert prog["losses"] == pytest.approx(res.losses, rel=1e-6)


def _measure(driver):
    c, t = tiny_ssm()
    return measure("mamba2-370m.train-seq2k", c, t, driver, seed=11,
                   seconds=0.5)


def _broken(wrap):
    """The train driver with its built step wrapped by ``wrap``."""
    drv = registry.driver("train")
    build = drv.build

    def broken_build(*a, **k):
        cfg, plan, mesh, step, params, opt = build(*a, **k)
        step.fn = wrap(step.fn)
        return cfg, plan, mesh, step, params, opt

    drv.build = broken_build
    return drv


def test_sound_run_is_correct():
    line = _measure(registry.driver("train"))
    assert line["correct"], line["checks"]
    assert line["metrics"]["train_tok_per_s"]["value"] > 0


def test_state_left_unchanged_is_not_correct():
    def wrap(fn):
        def step(p, o, b):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, m = fn(copy(p), copy(o), b)
            return p, o, m
        return step
    line = _measure(_broken(wrap))
    assert not line["correct"]
    assert line["checks"]["change_leaf_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct():
    def wrap(fn):
        def step(p, o, b):
            half = {k: v[:v.shape[0] // 2] for k, v in b.items()}
            return fn(p, o, half)
        return step
    line = _measure(_broken(wrap))
    assert not line["correct"], line["checks"]


def test_control_fails_the_limits():
    """The fp8 reference put in the program's place (matmul and SSD
    operands in float8_e4m3, the rest in bf16) reads over one of the
    cell's limits against the float32 reference.  16 layers at width 256,
    seq 256: at the tiny width the control's rounding reads under them
    (the readings at the cell's own size are in PERF.md)."""
    from bench.harness import compare
    c, t = tiny_ssm()
    c.update(hidden_size=256, num_hidden_layers=16, state_size=128,
             n_heads=8, vocab_size=50280, chunk_size=64)
    t.update(seq_len=256, global_batch=2)
    ref = registry.reference("ssm")
    batches = list(itertools.islice(gen.zipf_batches(50280, 2, 256, 2), 3))
    f32 = ref.train_readings(c, t, 2, batches)
    gaps = compare.train_gaps(
        ref.train_readings(c, t, 2, batches, precision="fp8"), f32)
    assert any(gaps[k] > v for k, v in t["limits"].items()), gaps
