"""Serving driver.

Two engines behind one CLI:

  * ``--engine paged`` (default) — the continuous-batching engine over the
    paged KV cache (``repro.serving``): batched chunked prefill
    disaggregated from decode, slot recycling, shared page pools.
  * ``--engine dense`` — the reference dense-cache path: one KV ring
    buffer per lane at full ``--context``, prompts fed one token per
    decode step.  Kept as the greedy-token oracle the paged engine is
    differentially tested against, and as the memory baseline
    ``benchmarks/bench_serve.py`` compares page occupancy to.

``--plan plan.json`` drives the paged engine from a searched v3 plan's
``serving`` section (page size, pool size, decode batch, prefill chunk) —
the file goes through the verified loading path (``repro.analysis``), so a
malformed or SLO-inconsistent plan is a structured diagnostic, not a
crash mid-serve.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --reduced \\
        --requests 16 --batch 4 --max-new 32
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import init_decode_state, init_lm
from repro.runtime import ShardPolicy, make_serve_step


class Request:
    def __init__(self, rid: int, prompt: List[int], max_new: int):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.generated: List[int] = []
        self.done = False


def serve(cfg, requests: List[Request], batch: int, context: int,
          *, eos_id: Optional[int] = None, greedy: bool = True,
          seed: int = 0, verbose: bool = True):
    """Dense-cache reference: one shared KV state, slot-per-lane.

    Each lane carries its *own* cache index (per-lane positions), so a
    recycled slot restarts at position 0 and the ring-cache validity mask
    hides the previous request's K/V — recycling never leaks context
    across requests.  Prompts are fed one token per step (the paged
    engine's chunked prefill replaces this; kept here as the oracle)."""
    mesh = make_local_mesh()
    policy = ShardPolicy(tp=False, zero=False)
    key = jax.random.PRNGKey(seed)
    with mesh:
        step = make_serve_step(cfg, mesh, policy, batch=batch, context=context)
        params = jax.jit(lambda k: init_lm(k, cfg),
                         out_shardings=step.in_shardings[0])(key)
        state = jax.jit(lambda: init_decode_state(cfg, batch, context),
                        out_shardings=step.in_shardings[1])()
        # scalar shared index -> per-lane positions
        state["index"] = jnp.zeros((batch,), jnp.int32)

        queue = deque(requests)
        lanes: List[Optional[Request]] = [None] * batch
        cursor = [0] * batch                  # next prompt position per lane
        tok = np.zeros((batch,), np.int32)
        n_steps = 0
        t0 = time.time()
        while queue or any(l is not None for l in lanes):
            for i in range(batch):
                if lanes[i] is None and queue:
                    r = queue.popleft()
                    lanes[i] = r
                    cursor[i] = 1
                    tok[i] = r.prompt[0]
                    # recycled slot starts over at position 0; stale ring
                    # slots are masked by the per-lane validity window
                    state["index"] = state["index"].at[i].set(0)
            logits, state = step.fn(params, state, jnp.asarray(tok))
            n_steps += 1
            if greedy:
                nxt = np.asarray(jnp.argmax(logits, -1))
            else:
                key, sub = jax.random.split(key)
                nxt = np.asarray(jax.random.categorical(sub, logits, -1))
            for i in range(batch):
                r = lanes[i]
                if r is None:
                    continue
                if cursor[i] < len(r.prompt):     # still feeding prompt
                    tok[i] = r.prompt[cursor[i]]
                    cursor[i] += 1
                    continue
                t = int(nxt[i])
                r.generated.append(t)
                tok[i] = t
                if (eos_id is not None and t == eos_id) or \
                        len(r.generated) >= r.max_new:
                    r.done = True
                    lanes[i] = None
        dt = time.time() - t0
        total_new = sum(len(r.generated) for r in requests)
        if verbose:
            print(f"served {len(requests)} requests, {total_new} tokens in "
                  f"{dt:.2f}s ({total_new/dt:.1f} tok/s, {n_steps} steps)")
    return requests


def serve_paged(cfg, requests: List[Request], ecfg, *,
                seed: int = 0, verbose: bool = True):
    """Continuous-batching serve over the paged KV cache.

    Returns the engine's :class:`~repro.serving.ServeMetrics`; generated
    tokens are written back into each :class:`Request`."""
    from repro.serving import ServeRequest, ServingEngine

    mesh = make_local_mesh()
    key = jax.random.PRNGKey(seed)
    params = jax.jit(lambda k: init_lm(k, cfg))(key)
    engine = ServingEngine(cfg, params, mesh, ecfg)
    sreqs = [ServeRequest(rid=str(r.rid), prompt=list(r.prompt),
                          max_new=r.max_new) for r in requests]
    metrics = engine.run(sreqs, verbose=False)
    for r, s in zip(requests, sreqs):
        r.generated = list(s.tokens)
        r.done = s.done
    if verbose:
        summ = metrics.summary()
        print(f"served {summ['completed']} requests, {summ['new_tokens']} "
              f"tokens in {summ['wall_s']:.2f}s "
              f"({summ['tok_per_s']:.1f} tok/s, "
              f"{summ['decode_steps']} decode steps, "
              f"{summ['prefill_chunks']} prefill chunks, "
              f"peak page occupancy {summ['page_occupancy_max']:.2f}; "
              f"decode attention {summ['decode_attn']}, live page share "
              f"{summ['live_page_share']:.3f})")
        phases = ", ".join(f"{k} {v:.3f}s/{summ['phase_n'][k]}"
                           for k, v in sorted(summ["phase_s"].items()))
        print(f"host: {phases}; {summ['host_syncs']} host syncs, "
              f"{summ['compiles']} compiles ({summ['compile_s']:.2f}s), "
              f"queue wait p50/p90 {summ['queue_wait_ms_p50']:.1f}/"
              f"{summ['queue_wait_ms_p90']:.1f} ms")
    return metrics


def engine_config_from_args(args, cfg):
    """Resolve the paged-engine geometry: ``--plan``'s serving section when
    given, CLI flags otherwise (flags override plan fields when set)."""
    from repro.serving import EngineConfig

    page_size, n_pages = args.page_size, args.pages
    batch, context = args.batch, args.context
    prefill_chunk, eos = args.prefill_chunk, args.eos_id
    if args.plan:
        from repro.analysis import load_plan_file
        plan, _ = load_plan_file(args.plan)
        sv = plan.serving
        if sv is None:
            raise SystemExit(
                f"{args.plan}: plan has no serving section (a v3 serving "
                "plan comes from `search --slo-sweep`)")
        page_size = sv.page_size
        context = min(sv.max_context, context) if context else sv.max_context
        batch = min(sv.decode_batch, batch) if batch else sv.decode_batch
        prefill_chunk = prefill_chunk or sv.prefill_chunk
        n_pages = n_pages or sv.kv_pool_pages
    context = context or 128
    batch = batch or 4
    page_size = page_size or 16
    context = -(-context // page_size) * page_size   # round up to pages
    n_pages = n_pages or (batch * (context // page_size))
    return EngineConfig(
        page_size=page_size, n_pages=n_pages, decode_slots=batch,
        max_context=context,
        prefill_batch=min(4, batch),
        prefill_chunk=prefill_chunk or min(32, context),
        eos_id=eos)


def main(argv=None) -> List[Request]:
    """Serve ``--requests`` synthetic prompts; returns them, each with
    its generated tokens."""
    ap = argparse.ArgumentParser(
        prog="serve.py",
        description="Serve synthetic requests with the paged "
                    "continuous-batching engine or the dense reference.")
    ap.add_argument("--arch", choices=list_archs(), default="qwen3-4b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="shrink the model for local runs "
                         "(--no-reduced serves the full config)")
    ap.add_argument("--engine", choices=("paged", "dense"), default="paged")
    ap.add_argument("--plan", default=None, metavar="PLAN.json",
                    help="drive the paged engine from a searched v3 plan's "
                         "serving section (verified load)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=0,
                    help="decode lanes (0 = from plan, default 4)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--context", type=int, default=0,
                    help="per-lane context cap (0 = from plan, default 128)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged engine: tokens per KV page")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged engine: shared pool pages per layer")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="paged engine: prompt tokens per prefill call")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=4).tolist(),
                    args.max_new) for i in range(args.requests)]
    if args.engine == "paged":
        ecfg = engine_config_from_args(args, cfg)
        serve_paged(cfg, reqs, ecfg, seed=args.seed)
    else:
        serve(cfg, reqs, args.batch or 4, args.context or 128,
              eos_id=args.eos_id, seed=args.seed)
    for r in reqs[:3]:
        print(f"req {r.rid}: prompt={r.prompt} -> {r.generated[:8]}...")
    return reqs


if __name__ == "__main__":
    main()
