"""Plan execution: build sharded, jit-compiled train / prefill / decode
steps for any architecture on any mesh.

``make_train_step`` / ``make_serve_step`` return (fn, in_shardings,
abstract_args) so callers can either run them (examples, tests) or
``.lower().compile()`` them against ShapeDtypeStructs (the multi-pod
dry-run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import (decode_step, encdec_loss, init_decode_state,
                          init_encdec, init_encdec_decode_state, init_lm,
                          init_paged_state, lm_loss, paged_decode_step,
                          paged_prefill_step)
from repro.models.common import ModelConfig
from repro.models.flags import batch_sharding
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.runtime.sharding import (ShardPolicy, batch_shardings,
                                    decode_state_shardings, opt_shardings,
                                    paged_state_shardings, param_shardings)


# --------------------------------------------------------------------------
# abstract state builders (no allocation — safe at any scale)
# --------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    key = jax.random.PRNGKey(0)
    if cfg.is_encoder_decoder:
        return jax.eval_shape(lambda k: init_encdec(k, cfg), key)
    return jax.eval_shape(lambda k: init_lm(k, cfg), key)


def abstract_opt_state(aparams, opt_cfg: Optional[AdamWConfig] = None):
    return jax.eval_shape(lambda p: adamw_init(p, opt_cfg), aparams)


def abstract_decode_state(cfg: ModelConfig, batch: int, context: int,
                          aparams=None):
    if cfg.is_encoder_decoder:
        frames = jax.ShapeDtypeStruct((batch, cfg.encoder_seq, cfg.d_model),
                                      jnp.float32)
        return jax.eval_shape(
            lambda p, f: init_encdec_decode_state(p, f, cfg, context),
            aparams, frames)
    return jax.eval_shape(lambda: init_decode_state(cfg, batch, context))


# --------------------------------------------------------------------------
# step builders
# --------------------------------------------------------------------------

@dataclasses.dataclass
class BuiltStep:
    fn: Callable                        # jit-wrapped
    abstract_args: Tuple[Any, ...]      # ShapeDtypeStructs for lowering
    in_shardings: Tuple[Any, ...]


def loss_fn_for(cfg: ModelConfig, policy: ShardPolicy):
    remat = list(policy.remat_segments) if policy.remat_segments else None
    if cfg.is_encoder_decoder:
        return functools.partial(encdec_loss, cfg=cfg,
                                 remat=bool(remat and remat[0]))
    return functools.partial(lm_loss, cfg=cfg, remat_segments=remat)


def make_train_step(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                    batch_abstract: Dict[str, jax.ShapeDtypeStruct],
                    opt_cfg: Optional[AdamWConfig] = None) -> BuiltStep:
    opt_cfg = opt_cfg or AdamWConfig()
    aparams = abstract_params(cfg)
    aopt = abstract_opt_state(aparams, opt_cfg)
    loss_fn = loss_fn_for(cfg, policy)

    from repro.runtime.sharding import batch_axes as _bt

    seq_ax = ("model" if (policy.seq_shard and "model" in mesh.axis_names)
              else None)
    seq_sz = mesh.shape.get("model", 1) if seq_ax else 1

    def train_step(params, opt_state, batch):
        with batch_sharding(_bt(mesh), seq_axis=seq_ax, seq_axis_size=seq_sz,
                            mesh=mesh):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(params)
        new_params, new_opt, metrics = adamw_update(params, grads, opt_state,
                                                    opt_cfg)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    ps = param_shardings(aparams, mesh, policy)
    os_ = opt_shardings(aopt, mesh, policy)
    bs = batch_shardings(batch_abstract, mesh)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(train_step,
                 in_shardings=(ps, os_, bs),
                 out_shardings=(ps, os_, rep),
                 donate_argnums=(0, 1))
    return BuiltStep(fn=fn, abstract_args=(aparams, aopt, batch_abstract),
                     in_shardings=(ps, os_, bs))


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                      batch_abstract) -> BuiltStep:
    """Inference forward (loss-free) over a long prompt."""
    aparams = abstract_params(cfg)

    if cfg.is_encoder_decoder:
        from repro.models import encode
        from repro.models.encdec import decode_train

        def prefill(params, batch):
            from repro.runtime.sharding import batch_axes as _bt
            with batch_sharding(_bt(mesh), mesh=mesh):
                enc = encode(params, batch["frames"], cfg)
                return decode_train(params, batch["tokens"], enc, cfg)
    else:
        from repro.models import lm_forward

        def prefill(params, batch):
            from repro.runtime.sharding import batch_axes as _bt
            with batch_sharding(_bt(mesh), mesh=mesh):
                logits, _ = lm_forward(params, batch["tokens"], cfg,
                                       patches=batch.get("patches"))
            return logits

    ps = param_shardings(aparams, mesh, policy)
    bs = batch_shardings(batch_abstract, mesh)
    fn = jax.jit(prefill, in_shardings=(ps, bs))
    return BuiltStep(fn=fn, abstract_args=(aparams, batch_abstract),
                     in_shardings=(ps, bs))


def make_serve_step(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                    batch: int, context: int) -> BuiltStep:
    """One-token decode step with KV/SSM state."""
    aparams = abstract_params(cfg)
    astate = abstract_decode_state(cfg, batch, context, aparams)
    atoken = jax.ShapeDtypeStruct((batch,), jnp.int32)

    if cfg.is_encoder_decoder:
        from repro.models import encdec_decode_step as _step
    else:
        _step = functools.partial(decode_step)

    def serve_step(params, state, token):
        return _step(params, state, token, cfg)

    ps = param_shardings(aparams, mesh, policy)
    ss = decode_state_shardings(astate, mesh, policy)
    bt = batch_shardings({"t": atoken}, mesh)["t"]
    fn = jax.jit(serve_step, in_shardings=(ps, ss, bt),
                 donate_argnums=(1,))
    return BuiltStep(fn=fn, abstract_args=(aparams, astate, atoken),
                     in_shardings=(ps, ss, bt))


def abstract_paged_state(cfg: ModelConfig, n_pages: int, page_size: int):
    return jax.eval_shape(lambda: init_paged_state(cfg, n_pages, page_size))


def make_paged_decode_step(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                           n_slots: int, n_pages: int, page_size: int,
                           pages_per_slot: int) -> BuiltStep:
    """One-token decode over the shared KV page pools (serving engine).
    The decode attention resolves from the backend, the shapes and
    whether ``policy`` shards the pools over ``mesh``
    (:func:`repro.models.attention.resolve_decode_impl`).

    Signature of the built fn:
    ``(params, pools, token (B,), page_rows (B,P), lengths (B,))``
    -> ``(logits (B,V), new_pools)`` with the pools donated."""
    aparams = abstract_params(cfg)
    apools = abstract_paged_state(cfg, n_pages, page_size)
    atoken = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    arows = jax.ShapeDtypeStruct((n_slots, pages_per_slot), jnp.int32)
    alens = jax.ShapeDtypeStruct((n_slots,), jnp.int32)

    ps = param_shardings(aparams, mesh, policy)
    pls = paged_state_shardings(apools, mesh, policy)
    rep = NamedSharding(mesh, P())
    pools_sharded = not all(s.is_fully_replicated
                            for s in jax.tree.leaves(pls))

    def step(params, pools, token, page_rows, lengths):
        return paged_decode_step(params, pools, token, page_rows, lengths,
                                 cfg, pools_sharded=pools_sharded)

    fn = jax.jit(step, in_shardings=(ps, pls, rep, rep, rep),
                 donate_argnums=(1,))
    return BuiltStep(fn=fn,
                     abstract_args=(aparams, apools, atoken, arows, alens),
                     in_shardings=(ps, pls, rep, rep, rep))


def make_paged_prefill_step(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                            prefill_batch: int, prefill_chunk: int,
                            n_pages: int, page_size: int,
                            pages_per_slot: int) -> BuiltStep:
    """Chunked prefill filling the KV page pools (serving engine).

    Signature of the built fn:
    ``(params, pools, tokens (PB,S), page_rows (PB,P), base, prompt_len (PB,))``
    -> ``(last-prompt-position logits (PB,V), new_pools)``; ``base`` is a
    traced scalar so the whole chunk loop reuses one compilation."""
    aparams = abstract_params(cfg)
    apools = abstract_paged_state(cfg, n_pages, page_size)
    atokens = jax.ShapeDtypeStruct((prefill_batch, prefill_chunk), jnp.int32)
    arows = jax.ShapeDtypeStruct((prefill_batch, pages_per_slot), jnp.int32)
    abase = jax.ShapeDtypeStruct((), jnp.int32)
    alens = jax.ShapeDtypeStruct((prefill_batch,), jnp.int32)

    def step(params, pools, tokens, page_rows, base, prompt_len):
        return paged_prefill_step(params, pools, tokens, page_rows, base,
                                  prompt_len, cfg)

    ps = param_shardings(aparams, mesh, policy)
    pls = paged_state_shardings(apools, mesh, policy)
    rep = NamedSharding(mesh, P())
    fn = jax.jit(step, in_shardings=(ps, pls, rep, rep, rep, rep),
                 donate_argnums=(1,))
    return BuiltStep(
        fn=fn,
        abstract_args=(aparams, apools, atokens, arows, abase, alens),
        in_shardings=(ps, pls, rep, rep, rep, rep))


# --------------------------------------------------------------------------
# convenience: fully materialized training state (examples / tests)
# --------------------------------------------------------------------------

def init_train_state(cfg: ModelConfig, mesh: Mesh, policy: ShardPolicy,
                     seed: int = 0):
    key = jax.random.PRNGKey(seed)
    init = (init_encdec if cfg.is_encoder_decoder else init_lm)
    aparams = abstract_params(cfg)
    ps = param_shardings(aparams, mesh, policy)
    params = jax.jit(lambda k: init(k, cfg), out_shardings=ps)(key)
    aopt = jax.eval_shape(adamw_init, aparams)
    os_ = opt_shardings(aopt, mesh, policy)
    opt_state = jax.jit(adamw_init, out_shardings=os_)(params)
    return params, opt_state
