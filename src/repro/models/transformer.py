"""Decoder-only language models: dense, MoE, SSM, hybrid — built from the
component blocks, stacked with ``lax.scan`` (scan-over-layers keeps the HLO
O(1) in depth, which matters for 512-device GSPMD compiles).

Parameter layout::

  params = {
    "embed":  (V, d),
    "stacks": [ {"params": <stacked block pytree with leading L_i>,
                 "kind": "dense"|"moe"|"ssm", "n": L_i}, ... ],
    "shared_attn": {...}?          # zamba2-style shared block
    "projector": {...}?            # VLM frontend projector
    "final_norm": (d,),
    "head": (d, V)?                # absent when tied
  }

Remat (CKPT) is applied per stack segment when the plan asks for it.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attention, attention_decode, attention_decode_paged,
                        attention_decode_paged_in_place,
                        attention_prefill_paged, init_attention,
                        init_kv_cache, init_page_pool, record_attention_impl,
                        resolve_decode_impl, write_decode_tokens)
from .common import ModelConfig
from .flags import constrain_batch, constrain_batch_only, scan_unroll
from .embedding import embed, init_embedding, init_projector, project
from .layers import cross_entropy_loss, init_dense, rms_norm
from .mlp import init_swiglu, swiglu_mlp
from .moe import init_moe, moe_ffn
from .ssm import (init_ssm, init_ssm_state, ssm_block, ssm_block_decode)


# --------------------------------------------------------------------------
# single blocks
# --------------------------------------------------------------------------

def init_dense_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": init_attention(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        "mlp": init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.dtype),
    }


def dense_block(p, x, positions, cfg: ModelConfig, *,
                window: Optional[int] = None, causal: bool = True):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + attention(p["attn"], h, positions, cfg, causal=causal,
                      window=window)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + swiglu_mlp(p["mlp"], h)
    return x, jnp.zeros((), jnp.float32)


def init_moe_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
        "attn": init_attention(k1, cfg),
        "ln2": jnp.ones((cfg.d_model,), cfg.dtype),
        "moe": init_moe(k2, cfg),
    }


def moe_block(p, x, positions, cfg: ModelConfig, *,
              window: Optional[int] = None, causal: bool = True):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + attention(p["attn"], h, positions, cfg, causal=causal,
                      window=window)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    y, aux = moe_ffn(p["moe"], h, cfg)
    return x + y, aux


def init_ssm_block_p(key, cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.dtype),
        "ssm": init_ssm(key, cfg),
    }


def ssm_block_outer(p, x, positions, cfg: ModelConfig, **_):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + ssm_block(p["ssm"], h, cfg)
    return x, jnp.zeros((), jnp.float32)


_BLOCK_INIT = {"dense": init_dense_block, "moe": init_moe_block,
               "ssm": init_ssm_block_p}
_BLOCK_APPLY = {"dense": dense_block, "moe": moe_block,
                "ssm": ssm_block_outer}


# --------------------------------------------------------------------------
# stacking
# --------------------------------------------------------------------------

def init_stack(key, cfg: ModelConfig, kind: str, n: int) -> Dict[str, Any]:
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _BLOCK_INIT[kind](k, cfg))(keys)


def apply_stack(stack_params, kind, x, positions, cfg: ModelConfig, *,
                remat: bool = False, window: Optional[int] = None,
                causal: bool = True):
    fn = _BLOCK_APPLY[kind]

    def body(carry, layer_params):
        h, aux = carry
        # Sequence parallelism, stash-only: the scan carry (= the remat
        # stash) stays seq-sharded (constrain_batch adds the seq axis when
        # the policy enables it); compute runs on the gathered tensor so
        # GSPMD keeps the baseline head-parallel attention layout.
        h = constrain_batch_only(h)
        h, a = fn(layer_params, h, positions, cfg, window=window,
                  causal=causal)
        return (constrain_batch(h), aux + a), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    n_layers = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               stack_params, unroll=scan_unroll(n_layers))
    return x, aux


# --------------------------------------------------------------------------
# whole LM
# --------------------------------------------------------------------------

def build_stacks(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """Sequence of (kind, n_layers) segments for the architecture."""
    if cfg.arch_type == "ssm":
        return [("ssm", cfg.n_layers)]
    if cfg.arch_type == "hybrid":
        # handled layer-by-layer (shared attention interleave)
        return [("ssm", cfg.n_layers)]
    if cfg.n_experts > 1:
        segs = []
        if cfg.first_k_dense:
            segs.append(("dense", cfg.first_k_dense))
        segs.append(("moe", cfg.n_layers - cfg.first_k_dense))
        return segs
    return [("dense", cfg.n_layers)]


def init_lm(key, cfg: ModelConfig) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, cfg.dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    stacks = []
    for i, (kind, n) in enumerate(build_stacks(cfg)):
        stacks.append(init_stack(ks[1 + i], cfg, kind, n))
    params["stacks"] = stacks
    if cfg.arch_type == "hybrid" and cfg.attn_every:
        params["shared_attn"] = {
            "ln": jnp.ones((cfg.d_model,), cfg.dtype),
            "attn": init_attention(ks[5], cfg),
        }
    if cfg.arch_type == "vlm":
        params["projector"] = init_projector(ks[6], cfg.d_vision, cfg.d_model,
                                             cfg.dtype)
    if not cfg.tie_embeddings:
        params["head"] = init_dense(ks[7], cfg.d_model, cfg.vocab_size,
                                    cfg.dtype)
    return params


def _logits(params, x, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if "head" in params:
        return x @ params["head"]
    return x @ params["embed"].T


def _hybrid_forward(params, x, positions, cfg: ModelConfig, *,
                    remat_segments: Optional[List[bool]] = None):
    """Zamba2-style: SSM stack with a weight-shared attention block applied
    every ``attn_every`` layers.  Executed as scans over equal segments."""
    stack_params = params["stacks"][0]
    n = cfg.n_layers
    k = cfg.attn_every or (n + 1)
    aux = jnp.zeros((), jnp.float32)
    sa = params.get("shared_attn")

    def seg_slice(tree, a, b):
        return jax.tree.map(lambda v: v[a:b], tree)

    i = 0
    si = 0
    while i < n:
        j = min(n, i + k)
        seg = seg_slice(stack_params, i, j)
        # remat_segments may be shorter than the segment count (e.g. a
        # single-element policy meaning "all segments"): clamp the index.
        remat = (bool(remat_segments[min(si, len(remat_segments) - 1)])
                 if remat_segments else False)
        x, a = apply_stack(seg, "ssm", x, positions, cfg, remat=remat)
        aux = aux + a
        if sa is not None and (j % k == 0):
            h = rms_norm(x, sa["ln"], cfg.norm_eps)
            x = x + attention(sa["attn"], h, positions, cfg, causal=True,
                              window=cfg.sliding_window)
        i = j
        si += 1
    return x, aux


def lm_forward(params, tokens: jax.Array, cfg: ModelConfig, *,
               patches: Optional[jax.Array] = None,
               remat_segments: Optional[List[bool]] = None,
               window: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """tokens (B,S) -> logits (B,S,V), aux loss.  For VLM, ``patches``
    (B, n_vis, d_vision) are projected and prepended."""
    x = constrain_batch(embed(params["embed"], tokens))
    if cfg.arch_type == "vlm" and patches is not None:
        vis = project(params["projector"], patches.astype(cfg.dtype))
        x = jnp.concatenate([vis, x], axis=1)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    win = window if window is not None else cfg.sliding_window

    if cfg.arch_type == "hybrid":
        x, aux = _hybrid_forward(params, x, positions, cfg,
                                 remat_segments=remat_segments)
    else:
        aux = jnp.zeros((), jnp.float32)
        for si, (kind, _) in enumerate(build_stacks(cfg)):
            remat = (bool(remat_segments[min(si, len(remat_segments) - 1)])
                     if remat_segments else False)
            x, a = apply_stack(params["stacks"][si], kind, x, positions, cfg,
                               remat=remat, window=win)
            aux = aux + a
    if cfg.arch_type == "vlm" and patches is not None:
        x = x[:, patches.shape[1]:]
    return _logits(params, x, cfg), aux


def lm_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig, *,
            remat_segments: Optional[List[bool]] = None) -> jax.Array:
    logits, aux = lm_forward(params, batch["tokens"], cfg,
                             patches=batch.get("patches"),
                             remat_segments=remat_segments)
    loss = cross_entropy_loss(logits, batch["labels"])
    return loss + cfg.router_aux_coef * aux


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, context: int) -> Dict[str, Any]:
    """Per-layer caches, stacked to match the scan layout."""
    state: Dict[str, Any] = {}
    stacks = []
    for kind, n in build_stacks(cfg):
        if kind == "ssm":
            one = init_ssm_state(cfg, batch)
        else:
            one = init_kv_cache(cfg, batch, context)
        stacks.append(jax.tree.map(
            lambda v: jnp.broadcast_to(v, (n,) + v.shape), one))
    state["stacks"] = stacks
    if cfg.arch_type == "hybrid" and cfg.attn_every:
        n_attn = cfg.n_layers // cfg.attn_every
        one = init_kv_cache(cfg, batch, context)
        state["shared_attn"] = [one for _ in range(n_attn)]
    state["index"] = jnp.zeros((), jnp.int32)
    return state


def _decode_block(kind: str):
    def dense_step(p, x, cache, index, cfg, window):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        a, new_cache = attention_decode(p["attn"], h, cache, index, cfg,
                                        window=window)
        x = x + a
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if "mlp" in p:
            x = x + swiglu_mlp(p["mlp"], h)
        else:
            y, _ = moe_ffn(p["moe"], h, cfg)
            x = x + y
        return x, new_cache

    def ssm_step_(p, x, cache, index, cfg, window):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        y, new_cache = ssm_block_decode(p["ssm"], h, cache, cfg)
        return x + y, new_cache

    return ssm_step_ if kind == "ssm" else dense_step


def decode_step(params, state, token: jax.Array, cfg: ModelConfig, *,
                window: Optional[int] = None) -> Tuple[jax.Array, Dict]:
    """One decode step. token (B,) -> logits (B, V) + new state."""
    x = embed(params["embed"], token)[:, None, :]
    index = state["index"]
    win = window if window is not None else cfg.sliding_window
    new_state = {"index": index + 1, "stacks": []}

    if cfg.arch_type == "hybrid":
        # layer-by-layer python loop with shared-attention interleave
        stack_params = params["stacks"][0]
        cache = state["stacks"][0]
        new_cache = jax.tree.map(lambda v: v, cache)
        sa = params.get("shared_attn")
        sa_caches = list(state.get("shared_attn", []))
        k = cfg.attn_every or (cfg.n_layers + 1)
        ai = 0
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda v: v[i], stack_params)
            lc = jax.tree.map(lambda v: v[i], cache)
            h = rms_norm(x, lp["ln1"], cfg.norm_eps)
            y, lc2 = ssm_block_decode(lp["ssm"], h, lc, cfg)
            x = x + y
            new_cache = jax.tree.map(
                lambda full, upd, ii=i: full.at[ii].set(upd), new_cache, lc2)
            if sa is not None and (i + 1) % k == 0 and ai < len(sa_caches):
                h = rms_norm(x, sa["ln"], cfg.norm_eps)
                a, sc = attention_decode(sa["attn"], h, sa_caches[ai], index,
                                         cfg, window=win)
                x = x + a
                sa_caches[ai] = sc
                ai += 1
        new_state["stacks"] = [new_cache]
        new_state["shared_attn"] = sa_caches
    else:
        for (kind, _), stack_params, cstack in zip(
                build_stacks(cfg), params["stacks"], state["stacks"]):
            step = _decode_block(kind)

            def body(carry, inp):
                h = carry
                lp, lc = inp
                h, lc2 = step(lp, h, lc, index, cfg, win)
                return h, lc2

            n_l = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
            x, new_cache = jax.lax.scan(body, x, (stack_params, cstack),
                                        unroll=scan_unroll(n_l))
            new_state["stacks"].append(new_cache)

    logits = _logits(params, x, cfg)[:, 0]
    return logits, new_state


# --------------------------------------------------------------------------
# paged decode (serving engine)
# --------------------------------------------------------------------------

def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Paged serving covers pure-attention stacks (dense / MoE decoders);
    SSM/hybrid state is not paged and enc-dec needs cross-attention."""
    return (not cfg.is_encoder_decoder
            and all(kind != "ssm" for kind, _ in build_stacks(cfg))
            and cfg.arch_type not in ("ssm", "hybrid"))


def init_paged_state(cfg: ModelConfig, n_pages: int, page_size: int,
                     *, dtype=None) -> Dict[str, Any]:
    """Per-layer K/V page pools, stacked to match the scan layout.

    Unlike :func:`init_decode_state` the pools are shared across lanes:
    total KV memory is n_pages * page_size tokens per layer regardless of
    how many lanes are configured."""
    if not supports_paged_decode(cfg):
        raise NotImplementedError(
            f"paged decode does not support arch_type={cfg.arch_type!r}")
    stacks = []
    for _, n in build_stacks(cfg):
        one = init_page_pool(cfg, n_pages, page_size, dtype=dtype)
        stacks.append(jax.tree.map(
            lambda v: jnp.broadcast_to(v, (n,) + v.shape), one))
    return {"stacks": stacks}


def _paged_block(lp, h, cfg: ModelConfig, attn_fn):
    """One layer of a paged step: ``attn_fn(attn params, normed h)`` ->
    (attention output, what the scan emits), then the FFN."""
    a, out = attn_fn(lp["attn"], rms_norm(h, lp["ln1"], cfg.norm_eps))
    h = h + a
    hn = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if "mlp" in lp:
        return h + swiglu_mlp(lp["mlp"], hn), out
    y, _ = moe_ffn(lp["moe"], hn, cfg)
    return h + y, out


def _paged_scan(params, pools, x, cfg, attn_fn):
    """Scan ``attn_fn`` + FFN over each stack, each layer's pool as a scan
    input and its updated pool as the output; returns (x, new pools)."""
    new_stacks = []
    for stack_params, pstack in zip(params["stacks"], pools["stacks"]):

        def body(h, inp):
            lp, lpool = inp
            return _paged_block(lp, h, cfg,
                                lambda p, hn: attn_fn(p, hn, lpool))

        n_l = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
        x, new_pool = jax.lax.scan(body, x, (stack_params, pstack),
                                   unroll=scan_unroll(n_l))
        new_stacks.append(new_pool)
    return x, {"stacks": new_stacks}


def _paged_decode_in_place(params, pools, x, page_rows, lengths, cfg):
    """The decode layers over pools read in place: each stack's pools are
    a loop-invariant operand of the scan that the paged-attention kernel
    reads at the layer index, the scan emits only each layer's new token
    K/V, and one scatter a pool writes them after the scan into the
    (donated) pools.  Returns (x, new pools)."""
    new_stacks = []
    for stack_params, pstack in zip(params["stacks"], pools["stacks"]):

        def body(h, inp, pstack=pstack):
            lp, layer = inp
            return _paged_block(
                lp, h, cfg, lambda p, hn: attention_decode_paged_in_place(
                    p, hn, pstack, layer, page_rows, lengths, cfg))

        n_l = jax.tree_util.tree_leaves(stack_params)[0].shape[0]
        x, (k_new, v_new) = jax.lax.scan(
            body, x, (stack_params, jnp.arange(n_l, dtype=jnp.int32)),
            unroll=scan_unroll(n_l))
        new_stacks.append(write_decode_tokens(pstack, k_new, v_new,
                                              page_rows, lengths))
    return x, {"stacks": new_stacks}


def paged_decode_step(params, pools, token: jax.Array,
                      page_rows: jax.Array, lengths: jax.Array,
                      cfg: ModelConfig, *,
                      window: Optional[int] = None,
                      pools_sharded: bool = False
                      ) -> Tuple[jax.Array, Dict]:
    """One decode step on the paged KV cache.

    token (B,) -> logits (B, V) + new pools.  ``page_rows`` (B, P) /
    ``lengths`` (B,) come from the serving engine's page table (same table
    for every layer; each layer owns its own pool rows).

    The decode attention is what
    :func:`~repro.models.attention.resolve_decode_impl` picks
    (``pools_sharded``: the pools' sharding splits them over devices):
    ``paged_kernel`` reads each lane's live pages in place and writes the
    new tokens after the layer scan; ``gather`` gathers every lane's
    pages in each layer and writes its token there.  The choice is
    recorded under :func:`~repro.models.attention.recording_attention`."""
    x = embed(params["embed"], token)[:, None, :]
    win = window if window is not None else cfg.sliding_window
    impl = resolve_decode_impl(cfg, pools["stacks"][0]["k"].shape[2],
                               window=win, pools_sharded=pools_sharded)
    record_attention_impl(impl)
    if impl == "paged_kernel":
        x, new_pools = _paged_decode_in_place(params, pools, x, page_rows,
                                              lengths, cfg)
    else:
        x, new_pools = _paged_scan(
            params, pools, x, cfg,
            lambda p, h, lpool: attention_decode_paged(
                p, h, lpool, page_rows, lengths, cfg, window=win))
    logits = _logits(params, x, cfg)[:, 0]
    return logits, new_pools


def paged_prefill_step(params, pools, tokens: jax.Array,
                       page_rows: jax.Array, base: jax.Array,
                       prompt_len: jax.Array, cfg: ModelConfig, *,
                       window: Optional[int] = None
                       ) -> Tuple[jax.Array, Dict]:
    """One chunked-prefill step: process prompt chunk ``tokens`` (B, S)
    covering absolute positions [base, base + S), writing K/V into the
    page pools.  Returns logits (B, V) taken at each lane's *last prompt
    position* (meaningful only for lanes whose prompt ends inside this
    chunk) plus the updated pools."""
    B, S = tokens.shape
    x = embed(params["embed"], tokens)
    win = window if window is not None else cfg.sliding_window
    x, new_pools = _paged_scan(
        params, pools, x, cfg,
        lambda p, h, lpool: attention_prefill_paged(
            p, h, lpool, page_rows, base, prompt_len, cfg, window=win))
    last = jnp.clip(prompt_len - 1 - base, 0, S - 1)        # (B,)
    xl = x[jnp.arange(B), last][:, None, :]                 # (B,1,d)
    logits = _logits(params, xl, cfg)[:, 0]
    return logits, new_pools
