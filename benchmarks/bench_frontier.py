"""Benchmark the budget-sweep frontier engine (DESIGN.md §6).

Times an 8-point ``sweep_budgets`` against 8 independent serial
``optimize()`` calls (one fresh optimizer per budget, pinned to the sweep's
quantization grid so plans are comparable bin-for-bin).  The sweep must
return byte-identical plans at every budget — it is a pure restructuring of
the same search — and the wall-clock ratio is the tentpole win: the stage
DP runs once with a budget axis and the budget-independent memo caches
(cost tables, reference costs, seed partitions) are shared across budgets
instead of rebuilt per call.  The sweep's cache telemetry must also add
up (hits + misses == lookups).

Results land in ``BENCH_frontier.json`` at the repo root.

Usage:  PYTHONPATH=src python benchmarks/bench_frontier.py [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

from repro.core import GalvatronOptimizer, galvatron_variant, paper_8gpu

try:
    from benchmarks.common import bert_huge_like
except ImportError:          # invoked as a plain script
    from common import bert_huge_like

GB = 1024 ** 3
REPO = pathlib.Path(__file__).resolve().parent.parent


def bench_configs(smoke: bool):
    budgets = [b * GB for b in (4, 6, 8, 10, 12, 14, 16, 18)]
    if smoke:
        return [("bert-huge-like-8L-8dev", bert_huge_like(8), paper_8gpu(),
                 dict(batch_grid=[16]), budgets)]
    common = dict(batch_grid=[8, 16, 32], micro_candidates=3)
    return [
        ("bert-huge-like-16L-8dev", bert_huge_like(16), paper_8gpu(),
         dict(common), budgets),
        ("bert-huge-like-32L-8dev", bert_huge_like(32), paper_8gpu(),
         dict(common), budgets),
    ]


def make_opt(specs, cluster, tweaks, *, budget=None, quant=None):
    cfg = galvatron_variant("bmw")
    cfg.micro_candidates = 2
    cfg.n_bins = 128
    for k, v in tweaks.items():
        setattr(cfg, k, v)
    cfg.budget_bytes = budget
    cfg.quant_bytes = quant
    return GalvatronOptimizer(specs, cluster, cfg)


def canonical(plan):
    return plan.canonical_dumps() if plan is not None else None


def run_config(name, specs, cluster, tweaks, budgets, repeats):
    quant = max(budgets)
    t_serial = t_sweep = float("inf")
    serial_plans = frontier = None
    stats = {}
    for _ in range(max(1, repeats)):
        # ---- N independent serial optimize() calls ---------------------
        t0 = time.perf_counter()
        serial_plans = {}
        for b in budgets:
            opt = make_opt(specs, cluster, tweaks, budget=b, quant=quant)
            serial_plans[b] = opt.optimize()
        t_serial = min(t_serial, time.perf_counter() - t0)
        # ---- one budget-axis sweep -------------------------------------
        opt = make_opt(specs, cluster, tweaks)
        t0 = time.perf_counter()
        frontier = opt.sweep_budgets(budgets)
        t_sweep = min(t_sweep, time.perf_counter() - t0)
        stats = dict(opt.stats)

    identical = all(
        canonical(p.plan) == canonical(serial_plans[p.budget_bytes])
        for p in frontier.points)
    counters_ok = (stats["stage_cache_hits"] + stats["stage_cache_misses"]
                   == stats["stage_searches"])
    speedup = t_serial / t_sweep if t_sweep > 0 else float("inf")
    return {
        "n_layers": len(specs),
        "n_devices": cluster.n_devices,
        "budgets_gb": [b / GB for b in budgets],
        "serial_seconds": round(t_serial, 4),
        "sweep_seconds": round(t_sweep, 4),
        "speedup": round(speedup, 2),
        "identical_plans": bool(identical),
        "cache_counters_consistent": bool(counters_ok),
        "throughputs": frontier.throughputs(),
        "knee_budgets_gb": [p.budget_bytes / GB
                            for p in frontier.knee_points()],
        "sweep_stats": {k: stats.get(k) for k in
                        ("stage_searches", "stage_cache_hits",
                         "stage_cache_misses", "table_builds", "table_hits")},
    }, identical and counters_ok, speedup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="single small config (CI)")
    ap.add_argument("--repeats", type=int, default=2,
                    help="timed repetitions (min is reported)")
    ap.add_argument("--out", default=str(REPO / "BENCH_frontier.json"))
    args = ap.parse_args(argv)

    results = {}
    worst = float("inf")
    ok = True
    for name, specs, cluster, tweaks, budgets in bench_configs(args.smoke):
        row, row_ok, speedup = run_config(name, specs, cluster, tweaks,
                                          budgets, args.repeats)
        results[name] = row
        worst = min(worst, speedup)
        ok = ok and row_ok
        print(f"{name}: serial {row['serial_seconds']:.3f}s  "
              f"sweep {row['sweep_seconds']:.3f}s  "
              f"speedup {speedup:.1f}x  identical={row['identical_plans']}")
        if not row_ok:
            print(f"ERROR: {name}: sweep diverged from serial optimizes "
                  f"(or cache counters inconsistent)", file=sys.stderr)

    out = {
        "benchmark": "budget-sweep frontier engine (one budget-axis search) "
                     "vs N independent serial optimize() calls",
        "smoke": args.smoke,
        "n_budgets": len(bench_configs(args.smoke)[0][4]),
        "min_speedup": round(worst, 2),
        "configs": results,
    }
    pathlib.Path(args.out).write_text(json.dumps(out, indent=2) + "\n")
    print(f"wrote {args.out}  (min speedup {worst:.1f}x)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
