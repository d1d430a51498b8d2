"""The training driver on a dense GQA decoder at a tiny size on the CPU,
checked against ``bench/reference/dense_train.py``: it stays the trainer
it times, a sound run is correct, and each fault a training cell can
have, planted under the timed path, makes ``correct`` false."""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import pytest

from bench.harness import compare, gen, registry
from conftest import measure, tiny_dense

CELL = "qwen3-4b-l9.train-seq4k-4chip"

# The norms of a leaf of thousands of numbers move more by rounding than
# those of a leaf of millions, so two of the cell's limits, set at its
# own size (PERF.md), are set again for this size from readings on the
# CPU: sound and the bf16 witness read grad_leaf_gap 4.6e-4 to 2.8e-3
# and change_leaf_gap 3.2e-4 to 9.4e-4, the fp8 control 1.1e-2 to 1.7e-2
# and 5.6e-3 to 1.1e-2.
TINY_LIMITS = {"grad_leaf_gap": 6e-3, "change_leaf_gap": 2.5e-3}


def tiny_dense_train():
    """Qwen3-shaped (QK-norm, GQA 4/2, tied) at a tiny size, with the
    cell's traffic cut to seq 64, batch 4."""
    c, _ = tiny_dense()
    c.update(arch_type="dense_train", chips=4)
    t = registry.traffic("train-seq4k")
    t.update(seq_len=64, global_batch=4)
    t["limits"] = {**t["limits"], **TINY_LIMITS}
    return c, t


def test_first_two_losses_equal_the_trainers():
    """Same seed (init 0, data 1234, as ``launch/train.main``), same plan
    search, same jitted step: the same first two losses."""
    from repro.configs import get_config
    from repro.launch import train
    res = train.main(["--arch", "qwen3-4b", "--reduced", "--layers", "2",
                      "--d-model", "128", "--steps", "2", "--batch", "4",
                      "--seq", "64", "--log-every", "1"])
    drv = registry.driver("train")
    drv.program_config = lambda c: get_config("qwen3-4b").reduced(
        n_layers=2, d_model=128)
    c, t = tiny_dense_train()
    cfg, plan, mesh, step, params, opt = drv.build(c, t, 1, seed=0)
    assert plan.summary() == res.plan.summary()
    batches = list(itertools.islice(
        gen.zipf_batches(cfg.vocab_size, 4, 64, 1234), 2))
    with mesh:
        prog, *_ = drv.check_steps(step.fn, params, opt, batches,
                                   t["optimizer"]["beta1"])
    assert prog["losses"] == pytest.approx(res.losses, rel=1e-6)


def test_reference_keys_are_the_programs_leaves():
    """Every parameter of the program has its gradient and change in the
    reference, under the same path and shape."""
    c, t = tiny_dense_train()
    drv = registry.driver("train")
    _, _, _, _, params, _ = drv.build(c, t, 1, seed=3)
    batches = list(itertools.islice(gen.zipf_batches(512, 4, 64, 3), 1))
    ref = registry.reference("dense_train").train_readings(c, t, 3, batches)
    shapes = {jax.tree_util.keystr(p): x.shape for p, x in
              jax.tree_util.tree_flatten_with_path(params)[0]}
    assert {k: v.shape for k, v in ref["grads"].items()} == shapes
    assert ref["change_norms"].keys() == shapes.keys()


def _measure(driver, seed=11):
    c, t = tiny_dense_train()
    return measure(CELL, c, t, driver, seed=seed, seconds=0.5)


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
    line = _measure(registry.driver("train"), seed=2718281828459)
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
    """The fp8 reference put in the program's place (matmul and attention
    operands in float8_e4m3, the rest in bf16) reads over one of the
    cell's limits against the float32 reference (the readings at the
    cell's own size are in PERF.md)."""
    c, t = tiny_dense_train()
    ref = registry.reference("dense_train")
    batches = list(itertools.islice(gen.zipf_batches(512, 4, 64, 2), 3))
    f32 = ref.train_readings(c, t, 2, batches)
    gaps = compare.train_gaps(
        ref.train_readings(c, t, 2, batches, precision="fp8"), f32)
    assert any(gaps[k] > v for k, v in t["limits"].items()), gaps


def test_witness_keeps_the_limits():
    """The bf16 witness (every tensor the program holds in bf16 rounded
    so, computed by the reference) reads under every limit: the limits
    leave sound bf16 arithmetic its room."""
    c, t = tiny_dense_train()
    ref = registry.reference("dense_train")
    batches = list(itertools.islice(gen.zipf_batches(512, 4, 64, 5), 3))
    gaps = compare.train_gaps(
        ref.train_readings(c, t, 5, batches, precision="bf16"),
        ref.train_readings(c, t, 5, batches))
    assert all(gaps[k] <= v for k, v in t["limits"].items()), gaps
