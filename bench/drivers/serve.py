"""Serving cells: ``ServingEngine.run`` under an open-loop request schedule.

Set-up makes the weights on the device in one jitted call from the seed,
builds the paged engine with the traffic file's geometry, and warms it up
with 1 to ``prefill_batch`` short requests admitted together (every
prefill, decode and page-table shape the window uses).  The window hands
the engine the whole schedule: each request is due at its ``arrival_s``
on the engine's clock, and the engine admits it once it is due and a
lane and pages are free.  Latencies count from the due time.  The run
lasts until every request due in the window has finished.

After the window a sample of finished requests, the longest among them,
is run through the float32 reference with its served tokens; the widest
gap by which a served token's logit lies below the reference's best
decides ``correct``, with every request finished with all its tokens.
"""
from __future__ import annotations

import gc
import math
import sys
from typing import Dict, List

import jax
import numpy as np

from bench.harness import compare, gen, registry, trace
from bench.harness.context import Context, Outcome, memory_peak_bytes
from bench.harness.program import program_config

WARM_PROMPT = 8


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def build(config: Dict, traffic: Dict, seed: int):
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_lm
    from repro.serving import EngineConfig, ServingEngine

    cfg = program_config(config)
    params = jax.jit(lambda k: init_lm(k, cfg))(jax.random.PRNGKey(seed))
    engine = ServingEngine(cfg, params, make_local_mesh(),
                           EngineConfig(**traffic["engine"]))
    return cfg, engine


def warm(engine, vocab: int) -> None:
    from repro.serving import ServeRequest
    from repro.serving.metrics import ServeMetrics
    rng = np.random.default_rng(0)
    for k in range(1, engine.ecfg.prefill_batch + 1):
        engine.run([ServeRequest(
            rid=f"warm{k}.{i}", max_new=2,
            prompt=rng.integers(0, vocab, WARM_PROMPT).tolist())
            for i in range(k)])
    engine.metrics = ServeMetrics()


class TracedRounds:
    """Wraps the engine's host steps in ``bench.*`` spans; profiles the
    decode rounds between two times on the engine's clock, and records
    the context of every active lane in each profiled round."""

    def __init__(self, engine, work_dir, start_s: float, stop_s: float):
        self.engine, self.dir = engine, work_dir
        self.start_s, self.stop_s = start_s, stop_s
        self.rounds: List[List[int]] = []
        self.n_prefill = 0
        self._cms = None
        for name in ("_admit_batch", "_prefill_admitted", "_finish"):
            setattr(engine, name, self._span(getattr(engine, name),
                                             "bench." + name.strip("_")))
        decode, prefill, rnd = engine._decode, engine._prefill, \
            engine._decode_round
        # the profiler names a program after its jitted function
        self.decode_program = "jit_" + getattr(decode, "__name__", "?")

        def decode_call(*a):
            if self._cms:
                self.rounds.append([len(r.prompt) + len(r.tokens)
                                    for r in engine._slot_req
                                    if r is not None])
            with jax.profiler.TraceAnnotation("bench.decode_call"):
                return decode(*a)

        def prefill_call(*a):
            if self._cms:
                self.n_prefill += 1
            with jax.profiler.TraceAnnotation("bench.prefill_call"):
                return prefill(*a)

        def decode_round(t0):
            import time
            now = time.perf_counter() - t0
            if self._cms is None and self.start_s <= now < self.stop_s:
                self.begin()
            elif self._cms and now >= self.stop_s:
                self.end()
            with jax.profiler.TraceAnnotation("bench.decode_round"):
                return rnd(t0)

        engine._decode, engine._prefill = decode_call, prefill_call
        engine._decode_round = decode_round

    @staticmethod
    def _span(fn, name):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    def begin(self):
        cap = trace.capture(self.dir)
        cap.__enter__()
        win = jax.profiler.TraceAnnotation(trace.WINDOW)   # after the start
        win.__enter__()
        self._cms = (cap, win)

    def end(self):
        if self._cms:
            cap, win = self._cms
            win.__exit__(None, None, None)
            cap.__exit__(None, None, None)
            self._cms = False


def decode_roofline(c: Dict, tracer: TracedRounds, peaks, tr
                    ) -> Dict[str, float]:
    """Least time of the traced decode rounds, and the device time of
    the decode program: the one program named after the engine's jitted
    decode function that ran exactly once per traced round.  Any other
    count, or two such programs, is an error: the metric would read the
    wrong program."""
    fl = registry.flops(c["arch_type"])
    rounds = tracer.rounds
    least = 0.0
    for ctxs in rounds:
        flops, nbytes = fl.decode_step_cost(c, ctxs)
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    named = {k: v for k, v in tr["modules"].items()
             if k.split("(")[0] == tracer.decode_program}
    exact = [k for k, v in named.items() if v["n"] == len(rounds)]
    if not rounds or len(exact) != 1:
        raise ValueError(
            f"decode program not identified: {len(rounds)} decode rounds "
            f"and {tracer.n_prefill} prefill calls traced; programs "
            f"{tracer.decode_program}(...) ran "
            f"{sorted(v['n'] for v in named.values())} times")
    return {"decode_least_s": least, "decode_device_s": named[exact[0]]["s"],
            "decode_module": exact[0], "decode_rounds": len(rounds)}


def sample(reqs, n: int, seed: int) -> List:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in reqs if r.done]
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def teacher_forced(reqs, T: int):
    """Each prompt with its served tokens, the last one left off (n, T);
    the positions that predict each served token, and those tokens."""
    toks = np.zeros((len(reqs), T), np.int32)
    where, target = [], []
    for i, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.tokens[:-1])
        toks[i, :len(seq)] = seq
        p = len(r.prompt)
        where += [(i, p - 1 + k) for k in range(len(r.tokens))]
        target += list(r.tokens)
    return toks, np.asarray(where, np.int32), np.asarray(target, np.int32)


def run(ctx: Context) -> Outcome:
    from repro.serving import ServeRequest
    c, t = ctx.config, ctx.traffic
    cfg, engine = build(c, t, ctx.seed)
    warm(engine, cfg.vocab_size)
    reqs = [ServeRequest(rid=str(i), prompt=p, max_new=o, arrival_s=due)
            for i, (due, p, o) in enumerate(gen.request_schedule(
                t, ctx.seconds, ctx.seed, cfg.vocab_size))]
    tracer = None
    if ctx.trace:
        start = t["trace"]["start_frac"] * ctx.seconds
        tracer = TracedRounds(engine, ctx.work_dir / f"trace-{ctx.seed}",
                              start, start + t["trace"]["seconds"])
    setup_s = ctx.setup_s()

    metrics = engine.run(reqs)
    if tracer:
        tracer.end()
    peak = memory_peak_bytes(ctx.devices)

    by_rid = {m.rid: m for m in metrics.requests}
    ok = [r for r in reqs if r.done and len(r.tokens) == r.max_new
          and r.rid in by_rid]
    failed = len(reqs) - len(ok)
    ttft = [(by_rid[r.rid].first_token_s - r.arrival_s) * 1e3 for r in ok]
    tpot = [(by_rid[r.rid].finish_s - by_rid[r.rid].first_token_s)
            / (len(r.tokens) - 1) * 1e3 for r in ok if len(r.tokens) > 1]
    late = [by_rid[r.rid].arrival_s - r.arrival_s for r in ok]
    span = (max(by_rid[r.rid].finish_s for r in ok)
            - min(r.arrival_s for r in reqs))
    e2e = {"ttft_mean_ms": sum(ttft) / len(ttft),
           "tpot_p90_ms": percentile(tpot, 0.9),
           "serve_tok_per_s": sum(len(r.tokens) for r in ok) / span,
           "setup_s": setup_s}
    print(f"serve: {len(reqs)} requests due over {ctx.seconds:g}s, "
          f"{len(ok)} finished, last at {span:.3f}s; admission after due "
          f"p50 {percentile(late, 0.5) * 1e3:.3f} ms, p90 "
          f"{percentile(late, 0.9) * 1e3:.3f} ms, max "
          f"{max(late) * 1e3:.3f} ms; decode rounds "
          f"{metrics.decode_steps}, prefill chunks {metrics.prefill_chunks}",
          file=sys.stderr)
    for name, v in (("ttft_ms", ttft), ("tpot_ms", tpot)):
        print(f"serve: {name} p50 {percentile(v, 0.5)!r} p75 "
              f"{percentile(v, 0.75)!r} p90 {percentile(v, 0.9)!r} mean "
              f"{sum(v) / len(v)!r} max {max(v)!r}", file=sys.stderr, flush=True)

    record = {"kind": "serve", "decode_steps": metrics.decode_steps}
    if tracer:
        record["trace"] = trace.reduce(trace.load(trace.find_xplane(
            tracer.dir)))
        record.update(decode_roofline(c, tracer, ctx.peaks,
                                      record["trace"]))
    del engine, metrics, tracer
    gc.collect()

    picked = sample(ok, t["check"]["sample"], ctx.seed)
    toks, where, target = teacher_forced(picked, t["engine"]["max_context"])
    logits = registry.reference(c["arch_type"]).logits_at(
        c, ctx.seed, toks, where)
    gap = compare.widest_logit_gap(logits, target)
    checks = [{"name": "served_logit_gap", "value": gap,
               "limit": t["limits"]["served_logit_gap"]}]
    print(f"serve: checked {len(target)} served tokens of {len(picked)} "
          f"requests against the reference", file=sys.stderr, flush=True)
    return Outcome(e2e=e2e, record=record, checks=checks,
                   attempted=len(reqs), failed=failed,
                   memory_peak_bytes=peak)
