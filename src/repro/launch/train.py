"""Training driver: Galvatron-searched plan -> sharded training run.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \\
        --steps 100 --batch 8 --seq 128

The plan is searched for the devices JAX sees (``len(jax.devices())``
v5e chips, batch ``--batch``) and executed on a local mesh of those
devices: the GSPMD executor by default, the shard_map pipeline runtime
with ``--pipeline``.  ``--reduced`` shrinks the model for CPU runs;
without it the config keeps its published widths unless ``--layers`` /
``--d-model`` override them.  ``main`` returns a :class:`TrainResult`.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import pathlib
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.configs.specs import layerspecs_for
from repro.core import (GalvatronOptimizer, ParallelPlan, galvatron_variant,
                        tpu_v5e_pod)
from repro.data import DataConfig, batch_specs, synthetic_lm_batches, text_corpus_batches
from repro.checkpointing import save_train_state
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim import AdamWConfig
from repro.runtime import init_train_state, make_train_step
from repro.runtime.plan_bridge import (execution_line, model_axis_size,
                                       policy_from_plan)


@dataclasses.dataclass
class TrainResult:
    """What a run produced: the trained state and its per-step record."""
    plan: ParallelPlan
    mesh_shape: Dict[str, int]
    losses: List[float]
    grad_norms: List[float]
    first_step_s: float        # step 1 wall time, compilation included
    steady_tok_per_s: float    # steps 2.. (0.0 with fewer than 2 steps)
    params: Any = None
    opt_state: Any = None


def search_plan(cfg, seq_len: int, batch: int,
                n_devices: Optional[int] = None) -> ParallelPlan:
    """Search the plan for ``n_devices`` v5e chips (default: the devices
    JAX sees) at global batch ``batch``."""
    specs = layerspecs_for(cfg, seq_len)
    ocfg = galvatron_variant("bmw")
    ocfg.batch_grid = [batch]
    ocfg.n_bins = 96
    ocfg.micro_candidates = 2
    ocfg.max_pp = 4
    # the schedule is a searched dimension (DESIGN.md §5, docs/schedules.md):
    # plain 1F1B vs interleaved virtual stages (bubble for hand-off traffic)
    # vs zero-bubble ZB-H1 (bubble for deferred weight-grad memory)
    ocfg.schedules = ("1f1b", "1f1b-interleaved", "zb-h1")
    ocfg.vpp_candidates = (2,)
    cluster = tpu_v5e_pod(n_devices or len(jax.devices()))
    plan = GalvatronOptimizer(specs, cluster, ocfg).optimize()
    if plan is None:
        raise RuntimeError("no feasible plan")
    return plan


def _run_steps(step_fn, state, batches, args,
               on_step: Optional[Callable] = None) -> Tuple[Any, List, float,
                                                            float]:
    """Drive ``step_fn(*state, batch) -> (*state, metrics)`` for
    ``args.steps`` steps.  Step 1 (compilation included) and the steady
    steps after it are timed apart, each ending on the device."""
    metrics_seen = []
    t0 = time.perf_counter()
    first_s = 0.0
    for i in range(1, args.steps + 1):
        *state, metrics = step_fn(*state, next(batches))
        metrics_seen.append(metrics)
        if on_step is not None:
            on_step(i, state)
        if i == 1:
            jax.block_until_ready(metrics)
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
        if i % args.log_every == 0 or i == args.steps:
            print(f"step {i:5d}  loss={float(metrics['loss']):.4f}  "
                  f"gnorm={float(metrics['grad_norm']):.3f}")
    jax.block_until_ready(state)
    steady = time.perf_counter() - t0
    tok_s = ((args.steps - 1) * args.batch * args.seq / steady
             if args.steps > 1 else 0.0)
    print(f"step 1 (with compile) {first_s:.2f}s; steady tok/s={tok_s:,.0f}")
    return state, metrics_seen, first_s, tok_s


def run_pipeline(cfg, plan: ParallelPlan, args, gen) -> TrainResult:
    """Execute the plan's searched pipeline schedule via the shard_map
    runtime, scaled down to whatever pipe degree the local devices and the
    (possibly reduced) layer count support."""
    from repro.launch.mesh import make_pipeline_mesh
    from repro.models import init_lm
    from repro.optim import adamw_init, adamw_update
    from repro.runtime import make_pipeline_loss, stage_split_params

    n_dev = len(jax.devices())
    P = 1
    for cand in range(min(n_dev, plan.pp_degree, cfg.n_layers), 0, -1):
        if n_dev % cand == 0 and cfg.n_layers % cand == 0:
            P = cand
            break
    sched, V = plan.schedule, plan.vpp_degree
    while V > 1 and cfg.n_layers % (P * V):
        V -= 1
    if V == 1 and sched == "1f1b-interleaved":
        sched = "1f1b"          # interleaving degenerated away locally
    m = math.gcd(plan.n_micro, args.batch)
    # the data axis shards the per-micro batch; shrink it (idling spare
    # devices) rather than hand shard_map a non-divisible batch dim
    n_data = math.gcd(n_dev // P, args.batch // m)
    mesh = make_pipeline_mesh(P, n_data)
    print(f"pipeline runtime: schedule={sched} P={P} V={V} m={m} "
          f"(plan asked {plan.schedule} P={plan.pp_degree} "
          f"V={plan.vpp_degree} m={plan.n_micro})")
    ocfg = AdamWConfig(lr=args.lr)
    with mesh:
        loss_fn = make_pipeline_loss(cfg, mesh, m, schedule=sched,
                                     n_chunks=V)
        params = init_lm(jax.random.PRNGKey(0), cfg)
        ps = stage_split_params(params, P, V)
        opt = adamw_init(ps, ocfg)

        @jax.jit
        def step(ps, opt, batch):
            loss, grads = loss_fn(ps, batch)
            ps, opt, metrics = adamw_update(ps, grads, opt, ocfg)
            metrics["loss"] = loss
            return ps, opt, metrics

        batches = ({k: jnp.asarray(v).reshape(m, args.batch // m, args.seq)
                    for k, v in b.items()} for b in gen)
        (ps, opt), metrics, first_s, tok_s = _run_steps(
            step, (ps, opt), batches, args)
    print("done.")
    return _result(plan, mesh, metrics, first_s, tok_s, ps, opt)


def _result(plan, mesh, metrics, first_s, tok_s, params, opt) -> TrainResult:
    return TrainResult(
        plan=plan, mesh_shape=dict(mesh.shape),
        losses=[float(x["loss"]) for x in metrics],
        grad_norms=[float(x["grad_norm"]) for x in metrics],
        first_step_s=first_s, steady_tok_per_s=tok_s,
        params=params, opt_state=opt)


def main(argv=None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--corpus", default=None, help="text file (byte-level LM)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--plan", default=None, metavar="FILE",
                    help="load a searched plan JSON (verified by "
                         "repro.analysis on load) instead of re-searching")
    ap.add_argument("--strict", action="store_true",
                    help="reject deprecated v0/v1 --plan files with a "
                         "structured deprecation diagnostic (PLN001)")
    ap.add_argument("--plan-out", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--pipeline", action="store_true",
                    help="execute the searched pipeline schedule via the "
                         "shard_map runtime (pipe mesh over local devices) "
                         "instead of the GSPMD executor path")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=args.layers or 2,
                          d_model=args.d_model or 256)
    elif args.layers or args.d_model:
        cfg = cfg.with_(n_layers=args.layers or cfg.n_layers,
                        d_model=args.d_model or cfg.d_model)

    use_compile_cache()
    # 1) the plan: loaded from a verified file, or searched fresh by the
    #    paper's engine for the local devices, including the
    #    pipeline-schedule dimension
    if args.plan:
        from repro.analysis import load_plan_file
        plan, report = load_plan_file(args.plan, strict=args.strict)
        for d in report.warnings():
            print(d.format())
        print(f"loaded plan {args.plan} (verified: "
              f"{len(report.warnings())} warning(s))")
    else:
        plan = search_plan(cfg, args.seq, args.batch)
    print("plan:", plan.summary())
    print(f"schedule: {plan.schedule} vpp={plan.vpp_degree} "
          f"m={plan.n_micro}")
    if args.plan_out:
        pathlib.Path(args.plan_out).write_text(plan.dumps())

    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size,
                      vision_tokens=cfg.vision_tokens,
                      d_vision=cfg.d_vision,
                      encoder_seq=cfg.encoder_seq, d_model=cfg.d_model)
    gen = (text_corpus_batches(args.corpus, dcfg) if args.corpus
           else synthetic_lm_batches(dcfg))

    # 2a) pipeline mode: execute the searched schedule itself
    if args.pipeline:
        return run_pipeline(cfg, plan, args, gen)

    # 2b) map the plan onto the local mesh (GSPMD executor path)
    policy = policy_from_plan(cfg, plan)
    mesh = make_local_mesh(model=model_axis_size(plan))
    print(execution_line(plan, policy, mesh.shape))

    def checkpoint(i, state):
        if args.ckpt_dir and i % args.ckpt_every == 0:
            print(f"  checkpoint -> {save_train_state(i, *state, args.ckpt_dir)}")

    with mesh:
        step = make_train_step(cfg, mesh, policy, batch_specs(dcfg),
                               AdamWConfig(lr=args.lr))
        params, opt = init_train_state(cfg, mesh, policy)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        print(f"model: {args.arch} ({n_params/1e6:.1f}M params), "
              f"mesh={dict(mesh.shape)}, policy={policy}")
        batches = ({k: jnp.asarray(v) for k, v in b.items()} for b in gen)
        (params, opt), metrics, first_s, tok_s = _run_steps(
            step.fn, (params, opt), batches, args, on_step=checkpoint)
    print("done.")
    return _result(plan, mesh, metrics, first_s, tok_s, params, opt)


if __name__ == "__main__":
    main()
