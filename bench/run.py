#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
the mix names the driver that runs it.  The driver builds the program
under test from the seed, warms up every shape it will use (set-up),
measures for ``--seconds``, then checks what the timed path produced
against the plain float32 reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics.
The last line of standard output is one JSON object; the numbers that
decide ``correct`` are printed beside their limits as the last lines of
standard error and under ``checks`` in that object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import registry  # noqa: E402
from bench.harness.context import Context  # noqa: E402


def fail(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def measure(spec, cell, config, traffic, driver, *, seed: int,
            seconds: float, traced: bool, devices, peaks, t_start: float):
    """Run ``driver`` for one cell and build the result line."""
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=seed,
                  seconds=seconds, trace=traced, devices=devices,
                  peaks=peaks, t_start=t_start,
                  work_dir=CHECKOUT / ".bench" / cell["name"])
    out = driver.run(ctx)
    gc.collect()

    if traced:
        metrics = {}
        for m in registry.per_layer_for(spec, cell["name"]):
            v = registry.metric_reader(m["name"]).read(out.record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out.e2e[m["name"]], "unit": m["unit"]}
                   for m in registry.end_to_end_for(spec, cell["name"])}

    correct = out.failed == 0 and all(c["value"] <= c["limit"]
                                      for c in out.checks)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if traced:
        tr = out.record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {c["name"]: {"value": c["value"] if math.isfinite(
        c["value"]) else repr(c["value"]), "limit": c["limit"]}
        for c in out.checks}
    line["checks"]["failed_requests"] = {"value": out.failed, "limit": 0}
    return line


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    config = registry.config(spec, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    driver = registry.driver(traffic["driver"])

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, "
             f"JAX sees {len(devs)}")
    devs = devs[:cell["chips"]]
    peaks = registry.peaks(devs[0].device_kind)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program of the cell, small ones too, comes from the cache
    # after the first run in a checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    line = measure(spec, cell, config, traffic, driver, seed=args.seed,
                   seconds=args.seconds, traced=bool(args.trace),
                   devices=devs, peaks=peaks, t_start=T_START)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
