"""Training cells: the step of the plan that the planner searched.

Set-up follows ``repro.launch.train.main``'s GSPMD path: ``search_plan``
for the cell's chips and global batch, ``policy_from_plan``, the mesh of
``model_axis_size(plan)``, ``make_train_step`` and ``init_train_state``
from the seed, with what the configuration states of the initial state
and the program's init does not (``published_init``).  Steps 1 to 3 go
through that same jitted step and feed on distinct batches; they warm
it up (step 1 compiles) and are the steps the float32 reference
follows.  The window then runs as many further steps as fit in
``--seconds`` at the warm step time, with no host sync inside it, and
ends on ``block_until_ready``.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp

from bench.harness import compare, gen, registry, trace
from bench.harness.context import Context, Outcome, memory_peak_bytes
from bench.harness.program import program_config, published_init

CHECK_STEPS = 3
TRACE_STEPS = 3


def build(config: Dict, traffic: Dict, chips: int, seed: int):
    """The program's plan, mesh, jitted step and state for this seed."""
    from repro.data import DataConfig, batch_specs
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import search_plan
    from repro.optim import AdamWConfig
    from repro.runtime import init_train_state, make_train_step
    from repro.runtime.plan_bridge import model_axis_size, policy_from_plan

    cfg = program_config(config)
    seq, batch = traffic["seq_len"], traffic["global_batch"]
    plan = search_plan(cfg, seq, batch, n_devices=chips)
    policy = policy_from_plan(cfg, plan)
    mesh = make_local_mesh(model=model_axis_size(plan))
    dcfg = DataConfig(seq_len=seq, global_batch=batch,
                      vocab_size=cfg.vocab_size)
    with mesh:
        step = make_train_step(cfg, mesh, policy, batch_specs(dcfg),
                               AdamWConfig(**traffic["optimizer"]))
        params, opt = published_init(
            config, *init_train_state(cfg, mesh, policy, seed=seed))
    return cfg, plan, mesh, step, params, opt


def feed(batch) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _paths(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def check_steps(step_fn, params, opt, batches, beta1: float):
    """Run the checked steps through ``step_fn``; return the readings the
    reference is compared on, the state, and the warm step time.

    First gradient: Adam's first moment after one step is
    (1 - beta1) * clip * g, so g = m / ((1 - beta1) * clip); it is kept
    on the host.  Change per leaf: the float32 master weights after the
    last checked step less the initial weights, copied to the host before
    step 1 (the step donates them)."""
    p0 = jax.device_get(params)
    losses, t_warm = [], []
    for i, b in enumerate(batches):
        t0 = time.perf_counter()
        params, opt, m = step_fn(params, opt, feed(b))
        jax.block_until_ready(m)
        t_warm.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        if i == 0:
            gnorm = float(m["grad_norm"])
            clip = min(1.0, 1.0 / (gnorm + 1e-9))
            grads = {k: v / ((1.0 - beta1) * clip) for k, v in
                     zip(_paths(opt["m"]), jax.device_get(
                         jax.tree_util.tree_leaves(opt["m"])))}
    change = _change_norms(opt["master"], jax.device_put(p0))
    paths = _paths(opt["m"])
    readings = {"losses": losses, "grads": grads,
                "change_norms": dict(zip(paths, map(float, change)))}
    step_s = min(t_warm[1:]) if len(t_warm) > 1 else t_warm[0]
    return readings, params, opt, step_s


@jax.jit
def _change_norms(master, p0):
    return [jnp.sqrt(jnp.sum(jnp.square(m - p.astype(jnp.float32))))
            for m, p in zip(jax.tree_util.tree_leaves(master),
                            jax.tree_util.tree_leaves(p0))]


def run(ctx: Context) -> Outcome:
    c, t = ctx.config, ctx.traffic
    chips = ctx.cell["chips"]
    cfg, plan, mesh, step, params, opt = build(c, t, chips, ctx.seed)
    batches = gen.zipf_batches(cfg.vocab_size, t["global_batch"],
                               t["seq_len"], ctx.seed)
    checked = [next(batches) for _ in range(CHECK_STEPS)]
    tokens_per_step = t["global_batch"] * t["seq_len"]
    with mesh:
        prog, params, opt, step_s = check_steps(
            step.fn, params, opt, checked, t["optimizer"]["beta1"])
        n_steps = max(1, round(ctx.seconds / step_s))
        setup_s = ctx.setup_s()

        t0 = time.perf_counter()
        for _ in range(n_steps):
            with jax.profiler.TraceAnnotation("bench.feed"):
                b = feed(next(batches))
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt, m = step.fn(params, opt, b)
        jax.block_until_ready((params, opt, m))
        window_s = time.perf_counter() - t0
        tok_per_s = n_steps * tokens_per_step / window_s

        record = {}
        if ctx.trace:
            tdir = ctx.work_dir / f"trace-{ctx.seed}"
            with trace.capture(tdir):
                with jax.profiler.TraceAnnotation(trace.WINDOW):
                    for _ in range(TRACE_STEPS):
                        with jax.profiler.TraceAnnotation("bench.feed"):
                            b = feed(next(batches))
                        with jax.profiler.TraceAnnotation("bench.dispatch"):
                            params, opt, m = step.fn(params, opt, b)
                    with jax.profiler.TraceAnnotation("bench.sync"):
                        jax.block_until_ready((params, opt, m))
            record["trace"] = trace.reduce(trace.load(trace.find_xplane(tdir)))
    peak = memory_peak_bytes(ctx.devices)
    del params, opt, m, b, step
    gc.collect()

    fl = registry.flops(c["arch_type"])
    record.update({
        "kind": "train",
        "tok_per_s": tok_per_s,
        "plan_est_tok_per_s": plan.est_throughput * t["seq_len"],
        "flops_per_token": fl.train_flops_per_token(c, t["seq_len"]),
        "peak_flops_per_s": ctx.peaks["bf16_flops_per_s"] * chips,
    })
    ref = registry.reference(c["arch_type"]).train_readings(
        c, t, ctx.seed, checked)
    gaps = compare.train_gaps(prog, ref)
    checks = [{"name": k, "value": gaps[k], "limit": v}
              for k, v in t["limits"].items()]
    return Outcome(e2e={"train_tok_per_s": tok_per_s, "setup_s": setup_s},
                   record=record, checks=checks, attempted=n_steps,
                   failed=0, memory_peak_bytes=peak)
