#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip at
the cell's own size, many seeds in one process (one compilation).

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 15] [--out chiprun_out/readings.jsonl]

For each seed it prints one JSON line:

  training cell: the gaps of the program's first steps against the
    float32 reference ("sound"), of the bf16 witness (the reference with
    every tensor the program holds in bf16 rounded to bf16: what sound
    bf16 arithmetic reads), of the control (the reference in fp8, put
    in the program's place) and of the faults "half of the batch left
    out" (the reference on the first half of the rows) and "a step that
    returns its state unchanged" (losses at the initial weights, no
    first moment, no change); besides the compared numbers, per leaf
    the first gradient's difference from the reference's (norm, over
    the reference's norm) and its cosine;
  serving cell: the widest served-token logit gap of the program's run
    at the cell's load ("sound"), the gap of the token the control puts
    first at each position ("control"), and the gap a token altered
    where it is produced would read ("altered": the smallest and the
    median over positions, a wrong token drawn from the seed).

"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import compare, gen, registry  # noqa: E402


def leaf_agreement(prog, ref):
    """Per leaf of the first gradient: |prog - ref| / |ref| (the cell
    compares the worst leaf's) and the cosine of the two."""
    import numpy as np
    out = {}
    for k, r in ref["grads"].items():
        a, b = np.ravel(prog["grads"][k]), np.ravel(r)
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        out[k] = {"diff": float(np.linalg.norm(a - b) / max(nb, 1e-30)),
                  "cos": float(np.dot(a, b) / max(na * nb, 1e-30))}
    return out


def train_seed(c, t, chips, seed, drv, ref):
    cfg, plan, mesh, step, params, opt = drv.build(c, t, chips, seed)
    batches = gen.zipf_batches(cfg.vocab_size, t["global_batch"],
                               t["seq_len"], seed)
    checked = [next(batches) for _ in range(drv.CHECK_STEPS)]
    with mesh:
        prog, params, opt, step_s = drv.check_steps(
            step.fn, params, opt, checked, t["optimizer"]["beta1"])
    del params, opt, step
    gc.collect()
    f32 = ref.train_readings(c, t, seed, checked)
    runs = {"sound": prog,
            "witness": ref.train_readings(c, t, seed, checked,
                                          precision="bf16"),
            "control": ref.train_readings(c, t, seed, checked,
                                          precision="fp8"),
            "half_batch": ref.train_readings(c, t, seed, checked,
                                             rows=t["global_batch"] // 2),
            "unchanged": {"losses": ref.initial_losses(c, seed, checked),
                          "grads": {k: 0.0 * v
                                    for k, v in f32["grads"].items()},
                          "change_norms": {k: 0.0
                                           for k in f32["change_norms"]}}}
    out = {k: compare.train_gaps(r, f32) for k, r in runs.items()}
    out["leaves"] = {k: leaf_agreement(runs[k], f32)
                     for k in ("sound", "witness", "control")}
    out.update(step_s=step_s,
               losses={k: runs[k]["losses"] for k in
                       ("sound", "witness", "control")},
               ref_losses=f32["losses"])
    return out


def serve_seed(c, t, seconds, seed, drv, ref):
    import jax.numpy as jnp
    import numpy as np
    from repro.serving import ServeRequest
    cfg, engine = drv.build(c, t, seed)
    drv.warm(engine, cfg.vocab_size)
    reqs = [ServeRequest(rid=str(i), prompt=p, max_new=o, arrival_s=due)
            for i, (due, p, o) in enumerate(gen.request_schedule(
                t, seconds, seed, cfg.vocab_size))]
    engine.run(reqs)
    del engine
    gc.collect()
    ok = [r for r in reqs if r.done and len(r.tokens) == r.max_new]
    picked = drv.sample(ok, t["check"]["sample"], seed)
    toks, where, target = drv.teacher_forced(picked,
                                             t["engine"]["max_context"])
    f32 = ref.logits_at(c, seed, toks, where)
    sound = compare.widest_logit_gap(f32, target)
    fp8 = ref.logits_at(c, seed, toks, where, precision="fp8")
    control = compare.widest_logit_gap(f32, jnp.argmax(fp8, axis=-1))
    wrong = (target + np.random.default_rng(seed).integers(
        1, c["vocab_size"], len(target))) % c["vocab_size"]
    best = jnp.max(f32, axis=-1)
    alt = best - jnp.take_along_axis(f32, jnp.asarray(wrong)[:, None],
                                     -1)[:, 0]
    return {"sound": sound, "control": control,
            "altered_min": float(jnp.min(alt)),
            "altered_median": float(jnp.median(alt)),
            "requests": len(reqs), "finished": len(ok),
            "tokens_checked": int(len(target))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: JAX found no TPU")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    spec = registry.load_spec()
    cell = registry.cell(spec, args.workload)
    c = registry.config(spec, cell["config"])
    t = registry.traffic(cell["traffic"])
    drv = registry.driver(t["driver"])
    ref = registry.reference(c["arch_type"])
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if t["driver"] == "train":
            r = train_seed(c, t, cell["chips"], seed, drv, ref)
        else:
            r = serve_seed(c, t, args.seconds, seed, drv, ref)
        r.update(workload=cell["name"], seed=seed,
                 seconds=time.perf_counter() - t0)
        line = json.dumps(r)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        gc.collect()


if __name__ == "__main__":
    main()
