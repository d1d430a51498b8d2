"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, and emit roofline rows.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]

The entry points (``main`` here, ``launch/hillclimb.py``) fake 512 host
devices with :func:`force_host_devices` before JAX's backend starts —
jax locks the device count at first use.  Importing this module changes
nothing; tests and benchmarks see the real (1-device) platform.
"""
import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.configs import get_config, list_archs
from repro.configs.specs import layerspecs_for
from repro.core.layerspec import LayerSpec
from repro.launch.inputs import config_for_shape, decode_dims, input_specs
from repro.launch.mesh import make_production_mesh
from repro.models.common import INPUT_SHAPES, ModelConfig
from repro.roofline import model_flops, roofline_report
from repro.runtime import (ShardPolicy, make_prefill_step, make_serve_step,
                           make_train_step)

ASSIGNED = ["qwen2-72b", "qwen2.5-14b", "internvl2-26b", "kimi-k2-1t-a32b",
            "qwen3-4b", "zamba2-1.2b", "whisper-medium", "mamba2-370m",
            "arctic-480b", "qwen3-8b"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def force_host_devices(n: int = 512) -> None:
    """Fake ``n`` CPU devices for the production meshes; must run before
    JAX's backend starts."""
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={n}"
                               ).strip()


def default_policy(cfg: ModelConfig, mode: str,
                   overrides: Optional[Dict[str, Any]] = None) -> ShardPolicy:
    """Paper-faithful baseline mapping: the Galvatron plan for the
    production cluster resolves to SDP x TP with CKPT for training
    (see EXPERIMENTS.md §Dry-run); serving uses TP only."""
    kw: Dict[str, Any] = {}
    if mode == "train":
        n_seg = 2 if (cfg.n_experts > 1 and cfg.first_k_dense) else 1
        kw = dict(tp=True, zero=True, remat_segments=(True,) * n_seg)
    else:
        kw = dict(tp=True, zero=False)
    kw.update(overrides or {})
    return ShardPolicy(**kw)


def depth_scaled(cfg: ModelConfig, n: int) -> ModelConfig:
    """Same architecture at reduced depth (scan-linear probe point)."""
    kw: Dict[str, Any] = {"n_layers": n}
    if cfg.is_encoder_decoder:
        kw["n_enc_layers"] = n
    return cfg.with_(**kw)


def probe_depths(cfg: ModelConfig):
    """Two shallow depths whose linear extrapolation reproduces the full
    model's per-device HLO cost (scan bodies are depth-homogeneous)."""
    if cfg.arch_type == "hybrid" and cfg.attn_every:
        return cfg.attn_every, 2 * cfg.attn_every
    if cfg.n_experts > 1 and cfg.first_k_dense:
        return cfg.first_k_dense + 1, cfg.first_k_dense + 2
    return 2, 4


def _model_flops_global(cfg: ModelConfig, shape, train: bool) -> float:
    specs = layerspecs_for(config_for_shape(cfg, shape), shape.seq_len)
    n = sum(s.param_count for s in specs)
    n_active = sum(s.active_param_count() for s in specs)
    toks = shape.global_batch * (shape.seq_len if shape.mode != "decode" else 1)
    return model_flops(n, toks, active_params=n_active, train=train)


def _compile_step(cfg: ModelConfig, shape, mesh,
                  policy_overrides: Optional[Dict[str, Any]] = None):
    if shape.mode == "train":
        pol = default_policy(cfg, "train", policy_overrides)
        built = make_train_step(cfg, mesh, pol, input_specs(cfg, shape))
    elif shape.mode == "prefill":
        pol = default_policy(cfg, "serve", policy_overrides)
        built = make_prefill_step(cfg, mesh, pol, input_specs(cfg, shape))
    else:  # decode
        pol = default_policy(cfg, "serve", policy_overrides)
        B, ctx = decode_dims(cfg, shape)
        built = make_serve_step(cfg, mesh, pol, batch=B, context=ctx)
    return built.fn.lower(*built.abstract_args).compile()


def _per_device_costs(compiled) -> Dict[str, float]:
    from repro.roofline import collective_bytes_from_hlo
    cost = compiled.cost_analysis()
    colls = collective_bytes_from_hlo(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(colls.values())),
        "colls": colls,
    }


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            policy_overrides: Optional[Dict[str, Any]] = None,
            config_overrides: Optional[Dict[str, Any]] = None,
            variant: str = "baseline",
            verbose: bool = True) -> Dict[str, Any]:
    shape = INPUT_SHAPES[shape_name]
    cfg = config_for_shape(get_config(arch), shape)
    if config_overrides:
        cfg = cfg.with_(**config_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 2 * 256 if multi_pod else 256
    t0 = time.time()

    with mesh:
        # (1) full-depth compile: proves lowering succeeds and memory fits
        compiled = _compile_step(cfg, shape, mesh, policy_overrides)
        # (2) two shallow probes: XLA cost_analysis counts a scan body once
        # regardless of trip count, so we linearly extrapolate per-device
        # FLOPs/bytes/collective-bytes from two depths (exact for
        # homogeneous scan stacks).
        from repro.models.flags import force_unroll
        d1, d2 = probe_depths(cfg)
        with force_unroll():
            c1 = _per_device_costs(_compile_step(depth_scaled(cfg, d1), shape,
                                                 mesh, policy_overrides))
            c2 = _per_device_costs(_compile_step(depth_scaled(cfg, d2), shape,
                                                 mesh, policy_overrides))

    alpha = (cfg.n_layers - d1) / (d2 - d1)
    ext = {k: c1[k] + alpha * (c2[k] - c1[k]) for k in ("flops", "bytes", "coll")}
    colls = {k: c1["colls"][k] + alpha * (c2["colls"][k] - c1["colls"][k])
             for k in c1["colls"]}

    mem = compiled.memory_analysis()
    rep = roofline_report(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        cost_analysis={"flops": ext["flops"], "bytes accessed": ext["bytes"]},
        hlo_text="", model_flops_global=_model_flops_global(
            cfg, shape, shape.mode == "train"))
    # overwrite collective numbers with the extrapolated parse
    rep.collective_bytes = ext["coll"] * chips
    rep.per_op_collectives = colls
    rep.t_collective = rep.collective_bytes / (chips * 50e9)

    # modeled (fusion-aware) HBM traffic + residency; keep the raw unfused
    # XLA:CPU number alongside as an upper bound.
    from repro.roofline.analysis import modeled_memory
    specs = layerspecs_for(cfg, shape.seq_len)
    cache_total = 0.0
    if shape.mode == "decode":
        if cfg.arch_type in ("ssm", "hybrid"):
            n_ssm = cfg.n_layers
            cache_total += n_ssm * shape.global_batch * cfg.ssm_heads \
                * cfg.ssm_head_dim * cfg.ssm_state * 4.0
        if cfg.arch_type != "ssm" and cfg.n_kv_heads:
            span = min(shape.seq_len, cfg.sliding_window or shape.seq_len)
            n_attn = (cfg.n_layers if cfg.arch_type != "hybrid"
                      else max(1, cfg.n_layers // (cfg.attn_every or 6)))
            cache_total += n_attn * shape.global_batch * span \
                * cfg.n_kv_heads * cfg.dh * 2 * 2.0
    data_shards = 16 * (2 if multi_pod else 1)
    seq_shard = 16 if (policy_overrides or {}).get("seq_shard") else 1
    mm = modeled_memory(
        specs, mode=shape.mode, chips=chips, tp=16, data_shards=data_shards,
        remat=shape.mode == "train", batch=shape.global_batch,
        cache_bytes_total=cache_total, seq_shard=seq_shard)
    rep.t_memory, raw_t_memory = mm.t_memory(), rep.t_memory

    def _mem_attr(name):
        v = getattr(mem, name, None)
        return float(v) if v is not None else None

    row = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "mode": shape.mode, "chips": chips, "variant": variant,
        "compile_seconds": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": _mem_attr("argument_size_in_bytes"),
            "output_bytes": _mem_attr("output_size_in_bytes"),
            "temp_bytes": _mem_attr("temp_size_in_bytes"),
            "generated_code_bytes": _mem_attr("generated_code_size_in_bytes"),
        },
        "t_memory_unfused_s": raw_t_memory,
        "modeled_resident_bytes_per_device": mm.resident_bytes_per_device,
        "modeled_fits_16g": mm.fits,
        **rep.row(),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compile={row['compile_seconds']}s "
              f"bottleneck={rep.bottleneck} "
              f"t=(c{rep.t_compute:.4f} m{rep.t_memory:.4f} "
              f"x{rep.t_collective:.4f})s "
              f"useful={rep.useful_flops_ratio:.2f}")
        print("  memory_analysis:", row["memory"])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default=None)
    ap.add_argument("--shape", choices=SHAPES, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--isolate", action="store_true",
                    help="run each combo in its own subprocess")
    args = ap.parse_args(argv)
    force_host_devices()

    combos = []
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        for a in ASSIGNED:
            for s in SHAPES:
                for mp in meshes:
                    combos.append((a, s, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape, mp) for mp in meshes]

    out_path = pathlib.Path(args.out) if args.out else None
    if out_path:
        out_path.parent.mkdir(parents=True, exist_ok=True)

    def emit(row):
        if out_path:
            with out_path.open("a") as f:
                f.write(json.dumps(row) + "\n")

    n_ok, failures = 0, []
    if args.isolate:
        # one subprocess per combo: an OOM-killed compile only loses that
        # combo, and each compile's RSS is returned to the OS afterwards.
        import subprocess
        done = set()
        if out_path and out_path.exists():
            for line in out_path.read_text().splitlines():
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass
        for a, s, mp in combos:
            key = (a, s, "2x16x16" if mp else "16x16")
            if key in done:
                print(f"[skip cached] {key}")
                n_ok += 1
                continue
            cmd = [sys.executable, "-u", "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s]
            if mp:
                cmd.append("--multi-pod")
            if args.out:
                cmd += ["--out", str(out_path)]
            res = subprocess.run(cmd, timeout=3600)
            if res.returncode == 0:
                n_ok += 1
            else:
                failures.append((a, s, mp, f"rc={res.returncode}"))
    else:
        for a, s, mp in combos:
            try:
                emit(run_one(a, s, multi_pod=mp))
                n_ok += 1
            except Exception as e:  # noqa: BLE001 — report all failures
                traceback.print_exc()
                failures.append((a, s, mp, repr(e)))
    print(f"\ndry-run: {n_ok} ok, {len(failures)} failed", flush=True)
    for f_ in failures:
        print("  FAIL", f_)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
