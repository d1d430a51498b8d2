"""Pipeline-parallel runtime: micro-batch pipelining as a ``shard_map``
over a ``pipe`` mesh axis with ``lax.ppermute`` stage hand-off, composable
with data parallelism on a ``data`` axis.

Takeaway #1 maps this axis onto the slowest interconnect — across pods in
the production mesh.

The *schedule* is pluggable (DESIGN.md §5, docs/schedules.md):
``runtime/schedules.py`` compiles a named schedule (``gpipe`` / ``1f1b``
/ ``1f1b-interleaved`` / ``zb-h1``) into per-tick program tables —
(micro-batch, virtual chunk, validity, loss, phase) per (tick, stage) —
and this module executes whatever program it is handed with one generic
``lax.scan`` tick loop (three-phase zero-bubble tables run through their
forward projection; see ``make_pipeline_loss_from_program``).  Params are
split into ``P × V`` virtual chunks (``stage_split_params``); the
interleaved schedule walks each device through its ``V`` chunks per
micro-batch group.

Hand-off / compute overlap: each tick *first* issues the ring ``ppermute``
on the previous tick's output, *then* runs the stage body — the two have
no data dependency, so XLA schedules the send/recv concurrently with the
compute (the permute of tick ``t`` rides under the compute of tick
``t+1``'s body in the unrolled trace).

Differentiating straight through the pipelined scan gives GPipe autodiff
semantics; the ``1f1b`` family rematerializes the tick body so only the
boundary carries are stashed (the 1F1B-flush memory profile — the cost
model accounts the schedules' time/memory split analytically, Eq. 5/9).
The stage computation runs *locally* per device (pure jnp inside
shard_map), so this runtime composes PP x DP; TP/SDP within a stage are
served by the GSPMD executor path.  Heterogeneous multi-stack models
(zamba2 / whisper) use the executor path only — see DESIGN.md §3.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.embedding import embed
from repro.models.layers import cross_entropy_loss, rms_norm
from repro.models.transformer import _BLOCK_APPLY, build_stacks
from repro.runtime.schedules import ScheduleProgram, compile_schedule


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the stage
    bodies mix replicated and per-device values freely)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def stage_split_params(params, n_stages: int, n_chunks: int = 1):
    """Reshape every stacked (L, ...) leaf to (P, V, L/(P·V), ...).

    dim0 shards over the pipe axis so each device holds exactly its V
    virtual chunks.  Chunk ``v`` on device ``i`` carries the layers of
    global virtual stage ``v·P + i`` (the interleaved round-robin layer
    placement); with V = 1 this is the plain contiguous stage split.
    """
    stacks = params["stacks"]
    assert len(stacks) == 1, "pipeline runtime requires one homogeneous stack"
    PV = n_stages * n_chunks

    def resh(v):
        L = v.shape[0]
        assert L % PV == 0, (f"{L} layers not divisible by "
                             f"{n_stages} stages x {n_chunks} chunks")
        # (L, ...) -> (V, P, Lc, ...) [virtual stage s = v*P + i -> (v, i)]
        # -> (P, V, Lc, ...) so dim0 is the device (pipe) dim
        out = v.reshape(n_chunks, n_stages, L // PV, *v.shape[1:])
        return out.swapaxes(0, 1)

    out = dict(params)
    out["stacks"] = [jax.tree.map(resh, stacks[0])]
    return out


def pipeline_specs(params_split, mesh: Mesh):
    """Pipe-sharded specs for split params: stage dim over 'pipe'."""
    def leaf_spec(path, v):
        names = [getattr(k, "key", None) for k in path]
        if "stacks" in names:
            return NamedSharding(mesh, P("pipe", *([None] * (v.ndim - 1))))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(leaf_spec, params_split)


def make_pipeline_loss(cfg: ModelConfig, mesh: Mesh, n_micro: int,
                       schedule: str = "gpipe",
                       n_chunks: Optional[int] = None):
    """Returns loss(params_split, batch) running the compiled schedule.

    batch: tokens/labels (m, B_m, S) — micro dim leading, batch dim sharded
    over 'data', replicated over 'pipe'.  ``params_split`` must come from
    ``stage_split_params(params, P, V)`` with the matching (P, V).

    The schedule name selects a :class:`ScheduleProgram` (see
    ``runtime/schedules.py``); the tick loop below is schedule-agnostic —
    it just replays the program tables.
    """
    n_stages = mesh.shape["pipe"]
    prog = compile_schedule(schedule, n_stages, n_micro, n_chunks)
    return make_pipeline_loss_from_program(cfg, mesh, prog)


def make_pipeline_loss_from_program(cfg: ModelConfig, mesh: Mesh,
                                    prog: ScheduleProgram):
    """Generic tick-loop executor for any compiled :class:`ScheduleProgram`.

    Three-phase (zero-bubble) programs are executed through their
    :meth:`~repro.runtime.schedules.ScheduleProgram.forward_program`: the
    scan replays the F ticks on the dense flush diagonal, autodiff of the
    rematerialized tick body realizes the B ticks, and XLA's backward
    placement realizes the deferred W ticks.  The three-phase table's
    tick *timing* is the analytic object the cost model prices
    (``docs/schedules.md``).
    """
    prog = prog.forward_program()
    n_stages = mesh.shape["pipe"]
    assert prog.n_stages == n_stages, (prog.n_stages, n_stages)
    m, V, T = prog.n_micro, prog.n_chunks, prog.n_ticks
    (kind, _), = build_stacks(cfg)
    block = _BLOCK_APPLY[kind]

    def stage_fn(chunk_params, x, positions):
        def body(carry, lp):
            h, _ = block(lp, carry, positions, cfg, window=cfg.sliding_window)
            return h, None
        x, _ = jax.lax.scan(body, x, chunk_params)
        return x

    def local_step(params, tokens, labels):
        # tokens/labels: (m, B_loc, S) local shards
        stage = jax.lax.axis_index("pipe")
        _, B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        stack = jax.tree.map(lambda v: v[0], params["stacks"][0])  # (V, Lc, ...)
        d = cfg.d_model
        # i -> i+1 carries the same-chunk hand-off; the P-1 -> 0 wrap link
        # carries the chunk v -> v+1 hand-off and is only needed when V > 1
        # (with V = 1 stage 0 always starts from the embedding, so a full
        # ring would ship the last stage's output back just to discard it)
        if V > 1:
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        else:
            perm = [(i, i + 1) for i in range(n_stages - 1)]
        mb_tab = jnp.asarray(prog.mb_index)        # (T, P)
        ch_tab = jnp.asarray(prog.chunk_index)     # (T, P)
        loss_tab = jnp.asarray(prog.loss_valid)    # (T, P)

        def tick(carry, t):
            y_prev, acc = carry
            # hand-off overlap: issue the permute on the PREVIOUS tick's
            # output before this tick's stage body — no data dependency, so
            # the collective runs under the compute
            x_recv = jax.lax.ppermute(y_prev, "pipe", perm)
            mb_idx = mb_tab[t, stage]
            chunk = ch_tab[t, stage]
            mb = jax.lax.dynamic_index_in_dim(tokens, mb_idx, 0, False)
            x_emb = embed(params["embed"], mb).astype(cfg.dtype)
            # virtual stage 0 (device 0, chunk 0) starts from the embedding;
            # everyone else consumes the ring hand-off
            first = (stage == 0) & (chunk == 0)
            x_in = jnp.where(first, x_emb, x_recv)
            chunk_stack = jax.tree.map(
                lambda v: jax.lax.dynamic_index_in_dim(v, chunk, 0, False),
                stack)
            y = stage_fn(chunk_stack, x_in, positions)
            # last virtual stage: head + loss for the just-finished mb;
            # bubble slots compute too but their loss is masked out (their
            # outputs are never consumed — every valid slot's producer one
            # tick earlier is itself valid)
            lb = jax.lax.dynamic_index_in_dim(labels, mb_idx, 0, False)
            h = rms_norm(y, params["final_norm"], cfg.norm_eps)
            logits = h @ (params["head"] if "head" in params
                          else params["embed"].T)
            loss_t = cross_entropy_loss(logits, lb)
            acc = acc + jnp.where(loss_tab[t, stage], loss_t, 0.0)
            return (y, acc), None

        y0 = jnp.zeros((B, S, d), cfg.dtype)
        tick_fn = (jax.checkpoint(tick, prevent_cse=False)
                   if prog.remat else tick)
        (_, acc), _ = jax.lax.scan(tick_fn, (y0, jnp.zeros((), jnp.float32)),
                                   jnp.arange(T))
        # NOTE: no collective here — the loss lives on the last stage only.
        # Summing across stages inside the differentiated objective would
        # multiply every gradient by P (the VJP of psum is a psum of the
        # all-ones cotangents); the caller psums the *value* after autodiff.
        return acc / m

    def loss_and_grads(params_split, batch):
        def inner(params, tokens, labels):
            loss_local, grads = jax.value_and_grad(
                lambda p: local_step(p, tokens, labels))(params)
            loss = jax.lax.psum(loss_local, "pipe")   # value: last stage only
            # pipe-replicated params (embed/head/final_norm) get gradient
            # contributions from different stages -> sum them; stack grads
            # stay local to their stage.
            grads = {k: (v if k == "stacks"
                         else jax.lax.psum(v, "pipe"))
                     for k, v in grads.items()}
            # DP gradient sync
            if "data" in mesh.axis_names:
                grads = jax.lax.pmean(grads, "data")
                loss = jax.lax.pmean(loss, "data")
            return loss, grads

        pspecs = pipeline_specs(params_split, mesh)
        pspec_tree = jax.tree.map(lambda s: s.spec, pspecs)
        data_axes = tuple(a for a in ("data",) if a in mesh.axis_names)
        tok_spec = P(None, data_axes if data_axes else None, None)
        fn = shard_map(inner, mesh,
                       in_specs=(pspec_tree, tok_spec, tok_spec),
                       out_specs=(P(), pspec_tree))
        return fn(params_split, batch["tokens"], batch["labels"])

    return loss_and_grads
