#!/usr/bin/env python3
"""Smoke run of the main paths on TPU chips, through the user entry points.

    python3 chip_smoke.py              # one chip: trainer + paged server
    python3 chip_smoke.py --four-chip  # four chips: sharded trainer only

One chip (no option):
  * trainer — ``repro.launch.train.main``: mamba2-370m, all 48 layers at
    published widths, seq 2048, batch 4, 6 steps on a plan searched for
    this chip; every step's loss and grad norm must be finite;
  * server — ``repro.launch.serve.main --no-reduced``: qwen3-4b at full
    size, 8 requests of 16 new tokens over 4 decode lanes, context 512, on
    the paged engine; every request must complete with all its tokens.
    The dense engine serves the same requests and the share of generated
    tokens the two engines agree on is printed.

Four chips (``--four-chip``): qwen3-4b at published widths cut to 8
layers (~1.59B params, ~25 GB of train state, more than one chip holds),
plan searched for the 4 chips, 3 steps on the GSPMD executor path (and
the pipeline runtime too when the plan pipelines).  The same step is done
unsharded on the first device: step 1's loss (one sequence at a time)
must agree to 1e-3 relative, and step 1's gradient norm and step 2's loss
(after the one AdamW update, also unsharded) to 2e-2.  Every device must
hold at least a quarter of the mean bytes in use.

Weights are random (seeded).  Times printed here are smoke figures, not
benchmark results.  The last line of output is one JSON object naming
the device; it is printed only when every phase passed.  Without a TPU
the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
from typing import Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

FOUR_CHIP_LR = 3e-4
LOSS_REL_TOL = 1e-3      # step 1 loss: the same params on the same batch
UPDATE_REL_TOL = 2e-2    # step 1 grad norm, step 2 loss: after one update


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def require_tpu(four_chip: bool):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    need = 4 if four_chip else 1
    if len(devs) != need:
        fail(f"this phase needs {need} TPU chip(s), JAX sees {len(devs)}")
    return devs


def bytes_in_use(devs):
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def train_one_chip() -> None:
    from repro.launch import train
    res = train.main(["--arch", "mamba2-370m", "--steps", "6", "--batch",
                      "4", "--seq", "2048", "--log-every", "1"])
    res.params = res.opt_state = None            # free the chip for serving
    gc.collect()
    if len(res.losses) != 6:
        fail(f"trainer ran {len(res.losses)} steps, expected 6")
    bad = [i + 1 for i, (l, g) in enumerate(zip(res.losses, res.grad_norms))
           if not (math.isfinite(l) and math.isfinite(g))]
    if bad:
        fail(f"non-finite loss or grad norm at steps {bad}")
    print(f"[train] losses {res.losses}")
    print(f"[train] smoke figures (not a benchmark): step 1 incl. compile "
          f"{res.first_step_s:.1f}s, steady {res.steady_tok_per_s:,.0f} tok/s",
          flush=True)


def serve_one_chip() -> None:
    from repro.launch import serve
    common = ["--arch", "qwen3-4b", "--no-reduced", "--requests", "8",
              "--max-new", "16", "--batch", "4", "--context", "512"]
    paged = serve.main(common + ["--engine", "paged"])
    short = [r.rid for r in paged
             if not r.done or len(r.generated) != r.max_new]
    if short:
        fail(f"paged engine left requests {short} incomplete")
    gc.collect()                  # the paged engine's weights leave first
    dense = serve.main(common + ["--engine", "dense"])
    same = sum(a == b for p, d in zip(paged, dense)
               for a, b in zip(p.generated, d.generated))
    total = sum(len(p.generated) for p in paged)
    print(f"[serve] paged engine completed {len(paged)}/{len(paged)} "
          f"requests, {total} tokens")
    print(f"[serve] paged/dense agreement: {same}/{total} generated tokens "
          f"({same / total:.3f})", flush=True)


def unsharded_reference(cfg, batches, dev) -> Tuple[float, float, float]:
    """The trainer's first two steps done on ``dev`` with no sharding:
    step 1's loss and gradient norm, and step 2's loss after the one AdamW
    update.  Params come from the trainer's seed, 0."""
    import jax
    import numpy as np
    from repro.models import init_lm, lm_loss
    from repro.optim import AdamWConfig, adamw_init, adamw_update

    ocfg = AdamWConfig(lr=FOUR_CHIP_LR)
    one = jax.sharding.SingleDeviceSharding(dev)
    params = jax.jit(lambda k: init_lm(k, cfg), out_shardings=one)(
        jax.random.PRNGKey(0))
    seq_loss = jax.jit(lambda p, t, l: lm_loss(
        p, {"tokens": t[None], "labels": l[None]}, cfg))

    def mean_loss(p, b):             # one sequence at a time
        return float(np.mean([float(seq_loss(p, t, l))
                              for t, l in zip(b["tokens"], b["labels"])]))

    b1, b2 = (jax.device_put(b, one) for b in batches)
    loss1 = mean_loss(params, b1)
    # remat changes no value, only what the whole-batch gradient stashes
    grads = jax.jit(jax.grad(lambda p, b: lm_loss(
        p, b, cfg, remat_segments=[True])))(params, b1)
    params, metrics = jax.jit(
        lambda p, g: adamw_update(p, g, adamw_init(p, ocfg), ocfg)[::2],
        donate_argnums=0)(params, grads)
    del grads
    return loss1, float(metrics["grad_norm"]), mean_loss(params, b2)


def rel_gap(x: float, ref: float) -> float:
    return abs(x - ref) / abs(ref)


def train_four_chips(devs) -> None:
    import itertools
    from repro.configs import get_config
    from repro.data import DataConfig, synthetic_lm_batches
    from repro.launch import train

    arch, layers, batch, seq = "qwen3-4b", 8, 4, 1024
    argv = ["--arch", arch, "--layers", str(layers), "--steps", "3",
            "--batch", str(batch), "--seq", str(seq), "--lr",
            str(FOUR_CHIP_LR), "--log-every", "1"]

    cfg = get_config(arch).with_(n_layers=layers)
    batches = list(itertools.islice(synthetic_lm_batches(DataConfig(
        seq_len=seq, global_batch=batch, vocab_size=cfg.vocab_size)), 2))
    ref_loss1, ref_gnorm1, ref_loss2 = unsharded_reference(cfg, batches,
                                                           devs[0])
    gc.collect()

    res = train.main(argv)
    stats = bytes_in_use(devs)
    print(f"[4chip] plan {res.plan.summary()} mesh {res.mesh_shape}")
    print(f"[4chip] losses {res.losses}")
    print(f"[4chip] bytes_in_use per device {stats}", flush=True)
    checks = [("step-1 loss", res.losses[0], ref_loss1, LOSS_REL_TOL),
              ("step-1 grad norm", res.grad_norms[0], ref_gnorm1,
               UPDATE_REL_TOL),
              ("step-2 loss", res.losses[1], ref_loss2, UPDATE_REL_TOL)]
    for name, got, ref, tol in checks:
        print(f"[4chip] {name} {got:.6f} vs unsharded reference {ref:.6f}: "
              f"relative gap {rel_gap(got, ref):.2e} (limit {tol:.0e})")
    print(f"[4chip] smoke figures (not a benchmark): step 1 incl. compile "
          f"{res.first_step_s:.1f}s, steady {res.steady_tok_per_s:,.0f} tok/s",
          flush=True)
    if not all(map(math.isfinite, res.losses + res.grad_norms)):
        fail("non-finite loss or grad norm")
    for name, got, ref, tol in checks:
        if rel_gap(got, ref) > tol:
            fail(f"{name} off the unsharded reference by "
                 f"{rel_gap(got, ref):.2e} relative")
    if min(stats) < 0.25 * (sum(stats) / len(stats)):
        fail(f"uneven placement: bytes_in_use {stats}")
    pp = res.plan.pp_degree
    res = None
    gc.collect()
    if pp > 1:
        piped = train.main(argv + ["--pipeline"])
        if not all(map(math.isfinite, piped.losses)):
            fail("pipeline runtime: non-finite loss")
        print(f"[4chip] pipeline runtime losses {piped.losses}", flush=True)
    else:
        print("[4chip] plan does not pipeline (pp_degree=1): pipeline "
              "runtime not run")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded four-chip trainer phase")
    args = ap.parse_args(argv)
    devs = require_tpu(args.four_chip)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    if args.four_chip:
        train_four_chips(devs)
    else:
        train_one_chip()
        serve_one_chip()
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)


if __name__ == "__main__":
    main()
