#!/usr/bin/env python3
"""Rate sweep of a serving cell on the chip: the same engine, warmed
once, under the cell's traffic at each rate in turn.

    python3 bench/tools/sweep.py --workload <cell> --rates 1,2,3 \\
        [--seconds 30] [--seed 1]

Prints one JSON line per rate: TTFT and per-token tails, tokens/s, the
backlog left when the schedule ends (last finish less the window) and
the longest wait between a request's due time and its admission.  The
knee is the highest rate whose backlog stays bounded; the cell's rate is
set once from it and written into its traffic file.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import gen, registry  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("sweep: JAX found no TPU")
    from repro.launch.compile_cache import use_compile_cache
    from repro.serving import ServeRequest
    from repro.serving.metrics import ServeMetrics
    use_compile_cache()
    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    c = registry.config(spec, cell["config"])
    t = registry.traffic(cell["traffic"])
    drv = registry.driver(t["driver"])
    cfg, engine = drv.build(c, t, args.seed)
    drv.warm(engine, cfg.vocab_size)
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(t, rate_per_s=rate)
        reqs = [ServeRequest(rid=str(i), prompt=p, max_new=o, arrival_s=due)
                for i, (due, p, o) in enumerate(gen.request_schedule(
                    tr, args.seconds, args.seed, cfg.vocab_size))]
        engine.metrics = ServeMetrics()
        m = engine.run(reqs)
        by = {x.rid: x for x in m.requests}
        ttft = [(by[r.rid].first_token_s - r.arrival_s) * 1e3 for r in reqs]
        tpot = [(by[r.rid].finish_s - by[r.rid].first_token_s)
                / (len(r.tokens) - 1) * 1e3 for r in reqs
                if len(r.tokens) > 1]
        wait = [by[r.rid].arrival_s - r.arrival_s for r in reqs]
        last = max(x.finish_s for x in m.requests)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs),
            "ttft_p50_ms": drv.percentile(ttft, 0.5),
            "ttft_p90_ms": drv.percentile(ttft, 0.9),
            "tpot_p50_ms": drv.percentile(tpot, 0.5),
            "tpot_p90_ms": drv.percentile(tpot, 0.9),
            "tok_per_s": sum(len(r.tokens) for r in reqs) / last,
            "backlog_s": last - args.seconds,
            "admission_wait_max_s": max(wait),
            "decode_rounds": m.decode_steps,
            "prefill_chunks": m.prefill_chunks}), flush=True)


if __name__ == "__main__":
    main()
