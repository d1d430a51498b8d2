"""Plan → executor bridge: derive a concrete mesh execution policy from a
Galvatron-searched ``ParallelPlan``.

The search is layer-granular; the GSPMD executor applies policies per
layer-stack *segment* (scan-over-layers keeps segments homogeneous), so the
bridge reduces the strategies to the most memory-saving choice any of them
makes, so that no device holds more than the plan was sized for:

  * TP on the `model` axis iff any layer's plan has tp > 1, the axis sized
    by the plan's largest TP degree (``model_axis_size``),
  * ZeRO (SDP) on the batch axes iff any layer uses sdp > 1,
  * remat per segment iff any of the segment's layers has CKPT,
  * sequence parallelism iff the modeled stash exceeds the HBM budget
    (the §Perf policy rule),
  * ring-attention SP degree copied verbatim from ``plan.sp_degree``
    (the searched axis, format v4) — the executor shards token dims over
    the mesh's ``seq`` axis and runs the ring kernel via
    runtime/sequence.py,
  * expert-parallel degree copied verbatim from ``plan.ep_degree``
    (the searched axis, format v5) — expert weights shard over the mesh's
    ``expert`` axis and MoE dispatch runs the all-to-all path
    (models/moe.py::_moe_ep),
  * one pipeline stage whatever ``plan.pp_degree`` is (the shard_map
    pipeline runtime, ``pipeline_loss_from_plan``, is the other path);
    ``execution_line`` names what the executor drops of the plan.
"""
from __future__ import annotations

from collections import Counter
from typing import Iterable, List, Optional, Sequence

from repro.core.layerspec import LayerSpec
from repro.core.plan import ParallelPlan
from repro.models.common import ModelConfig
from repro.models.transformer import build_stacks
from repro.roofline.analysis import modeled_memory
from repro.runtime.schedules import ScheduleProgram, compile_schedule
from repro.runtime.sharding import ShardPolicy


def _segment_bounds(cfg: ModelConfig) -> List[int]:
    sizes = [n for _, n in build_stacks(cfg)]
    return sizes


def model_axis_size(plan: ParallelPlan) -> int:
    """Size of the ``model`` mesh axis the executor runs ``plan`` on: its
    largest TP degree over every layer, embedding and head included."""
    return max(s.tp for s in plan.strategies)


def policy_from_plan(cfg: ModelConfig, plan: ParallelPlan, *,
                     specs: Optional[Sequence[LayerSpec]] = None,
                     seq_len: int = 4096, chips: int = 256,
                     hbm_capacity: float = 16e9) -> ShardPolicy:
    tp = model_axis_size(plan) > 1
    zero = any(s.sdp > 1 for s in plan.strategies)

    # remat follows the body layers (embed/head specs may pad the plan at
    # either end)
    strategies = plan.strategies
    n_body = cfg.n_layers
    if len(strategies) > n_body:
        off = (len(strategies) - n_body) // 2
        strategies = strategies[off:off + n_body]
    remat: List[bool] = []
    i = 0
    for seg in _segment_bounds(cfg):
        seg_s = strategies[i:i + seg] or strategies[-1:]
        remat.append(any(s.ckpt for s in seg_s))
        i += seg

    seq_shard = False
    if specs is not None:
        mm = modeled_memory(
            list(specs), mode="train", chips=chips, tp=16, data_shards=16,
            remat=any(remat), batch=plan.global_batch,
            hbm_capacity=hbm_capacity)
        seq_shard = not mm.fits      # §Perf rule: only when stash overflows
    ep = plan.ep_degree
    return ShardPolicy(tp=tp, zero=zero, remat_segments=tuple(remat),
                       seq_shard=seq_shard, sp_degree=plan.sp_degree,
                       ep_degree=ep,
                       expert_axis="expert" if ep > 1 else "model")


def execution_line(plan: ParallelPlan, policy: ShardPolicy,
                   mesh_shape, attn: Iterable[str] = ()) -> str:
    """One line: the searched plan (pipeline degree, schedule,
    micro-batches, per-layer TP/SDP/CKPT counts) beside what the GSPMD
    executor runs of it (policy, mesh, and ``attn``, the attention
    kernels the traced step's layers resolved to, ``none`` without
    attention; see ``models.attention.recording_attention``), naming
    what it drops.  ``policy_from_plan`` keeps one stage whatever
    ``plan.pp_degree`` is, and one TP, ZeRO and remat choice for every
    layer."""
    def counts(name: str) -> str:
        n = Counter(getattr(s, name) for s in plan.strategies)
        return ", ".join(f"{name}{k} x{v}" for k, v in sorted(n.items()))

    sched = plan.schedule + (f"(V={plan.vpp_degree})"
                             if plan.vpp_degree > 1 else "")
    dropped = [f"pp{plan.pp_degree} {sched} m={plan.n_micro}"
               ] if plan.pp_degree > 1 else []
    if len({(s.tp, s.sdp, s.ckpt) for s in plan.strategies}) > 1:
        dropped.append("per-layer tp/sdp/ckpt")
    return (f"searched: pp{plan.pp_degree} {sched} m={plan.n_micro}; "
            f"{len(plan.strategies)} layer specs: {counts('tp')}; "
            f"{counts('sdp')}; ckpt x{sum(s.ckpt for s in plan.strategies)}"
            f" | executed: one GSPMD stage on mesh {dict(mesh_shape)}, "
            f"tp={policy.tp} zero={policy.zero} "
            f"remat={any(policy.remat_segments or ())} "
            f"attn={'+'.join(sorted(attn)) or 'none'} | dropped: "
            + (", ".join(dropped) or "nothing"))


def schedule_program_from_plan(plan: ParallelPlan, *,
                               validate: bool = False) -> ScheduleProgram:
    """Compile the plan's searched (schedule, pp_degree, n_micro,
    vpp_degree) into the tick program the pipeline runtime executes.

    Three-phase plans (``schedule="zb-h1"``) compile to the full F/B/W
    table; the executor runs its forward projection (see
    ``runtime/pipeline.py::make_pipeline_loss_from_program``).

    An uncompilable (schedule, P, m, V) combo raises a structured
    :class:`repro.analysis.DiagnosticError` naming the offending plan
    field (rule ``PLN004``) instead of leaking ``compile_schedule``'s
    bare ``ValueError``; ``validate=True`` additionally runs the full
    schedule verifier on the compiled table."""
    from repro.analysis.diagnostics import DiagnosticError, error
    try:
        return compile_schedule(plan.schedule, plan.pp_degree, plan.n_micro,
                                plan.vpp_degree, validate=validate)
    except DiagnosticError:
        raise
    except ValueError as e:
        raise DiagnosticError([error(
            "PLN004", "plan.schedule",
            f"plan prescribes an uncompilable schedule combo "
            f"(schedule={plan.schedule!r}, pp_degree={plan.pp_degree}, "
            f"n_micro={plan.n_micro}, vpp_degree={plan.vpp_degree}): {e}",
            "run `python -m repro.analysis --plan <file>` for the full "
            "verdict")], context="schedule_program_from_plan") from e


def pipeline_loss_from_plan(cfg: ModelConfig, mesh, plan: ParallelPlan):
    """shard_map pipeline loss executing the plan's searched schedule.

    The mesh's ``pipe`` axis size must equal ``plan.pp_degree`` (the
    program tables are compiled for exactly that stage count); a mismatch
    raises a structured diagnostic (rule ``PLN006``) up front rather than
    a shape error from deep inside ``shard_map``."""
    from repro.runtime.pipeline import make_pipeline_loss_from_program
    n_pipe = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pipe", 1)
    if n_pipe != plan.pp_degree:
        from repro.analysis.diagnostics import DiagnosticError, error
        raise DiagnosticError([error(
            "PLN006", "plan.pp_degree",
            f"plan was searched for pp_degree={plan.pp_degree} but the "
            f"mesh's 'pipe' axis has {n_pipe} device(s)",
            "build the mesh with make_pipeline_mesh(n_stages="
            f"{plan.pp_degree}, ...) or re-search for this cluster")],
            context="pipeline_loss_from_plan")
    prog = schedule_program_from_plan(plan)
    return make_pipeline_loss_from_program(cfg, mesh, prog)
