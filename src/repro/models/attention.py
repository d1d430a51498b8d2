"""Grouped-query attention with RoPE, optional QK-norm / QKV-bias /
sliding-window masking, KV-cache decode, and a pluggable inner kernel
(pure-jnp reference here; Pallas flash kernel in repro.kernels)."""
from __future__ import annotations

import contextlib
import math
from typing import Any, Dict, Optional, Set, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import ModelConfig
from .flags import (constrain_batch_only, current_batch_axes, current_mesh,
                    scan_unroll, seq_sharding_active, unroll_scans)
from .layers import apply_rope, init_dense, rms_norm

NEG_INF = -1e30

# set by ``recording_attention``: the inner kernels ``attention`` resolved
# to while tracing under it
_RESOLVED: Optional[Set[str]] = None


@contextlib.contextmanager
def recording_attention():
    """Yield the set of inner kernels (``flash``, ``chunked``, ``ref``,
    ``ring``) that :func:`attention` resolves to, and the decode attention
    (``paged_kernel``, ``gather``) a paged decode step resolves to, while
    a step is traced under this context — what a trainer reports of its
    layers and a server of its decode step."""
    global _RESOLVED
    outer, _RESOLVED = _RESOLVED, set()
    try:
        yield _RESOLVED
    finally:
        _RESOLVED = outer


def record_attention_impl(impl: str) -> None:
    """Note ``impl`` in the set an enclosing :func:`recording_attention`
    yields (no-op outside one)."""
    if _RESOLVED is not None:
        _RESOLVED.add(impl)


def init_attention(key, cfg: ModelConfig, *, d_model: Optional[int] = None,
                   cross: bool = False) -> Dict[str, Any]:
    d = d_model or cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, cfg.q_dim, cfg.dtype),
        "wk": init_dense(ks[1], d, cfg.kv_dim, cfg.dtype),
        "wv": init_dense(ks[2], d, cfg.kv_dim, cfg.dtype),
        "wo": init_dense(ks[3], cfg.q_dim, d, cfg.dtype),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.q_dim,), cfg.dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), cfg.dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.dh,), cfg.dtype)
        p["k_norm"] = jnp.ones((cfg.dh,), cfg.dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions, *, rope: bool = True):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.dh)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.dh)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def sdpa_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
             causal: bool, window: Optional[int] = None,
             q_offset: Any = 0,
             kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Reference grouped-query attention.

    q (B,S,H,dh); k/v (B,T,KV,dh).  ``q_offset`` is the absolute position of
    q[0] (for decode: cache length).  ``kv_len`` masks cache positions >= it.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, dh)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(S)              # (S,)
    kpos = jnp.arange(T)                         # (T,)
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask_bt = mask[None, None, None]
    if kv_len is not None:
        valid = kpos[None, :] < jnp.asarray(kv_len).reshape(-1, 1)   # (B,T)
        mask_bt = mask_bt & valid[:, None, None, None, :]
    scores = jnp.where(mask_bt, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, dh).astype(q.dtype)


def sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool, window: Optional[int] = None,
                 block_q: int = 512) -> jax.Array:
    """Memory-efficient attention: q is processed in blocks (scan +
    rematerialized block body), so peak score memory is
    (B, H, block_q, T) instead of (B, H, S, T).  This is the pure-jnp
    analogue of the Pallas flash kernel.  ``attention(impl="auto")``
    takes it at S >= 1024 wherever :func:`resolve_impl` does not pick
    the kernel: off TPU (CPU tests), under ``force_unroll`` (the
    dry-run's roofline probes, whose ``cost_analysis`` counts nothing
    inside a custom call), at a head dim off the 128 lanes, or with
    heads that do not split over the mesh's ``model`` axis."""
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    nq = S // bq
    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, dh), 1, 0)     # (nq,B,bq,H,dh)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    # Under sequence parallelism, pin K/V to seq-replicated (batch-sharded
    # only): GSPMD would otherwise re-all-gather them for EVERY q chunk of
    # the rematerialized scan body (64x per layer-pass); one explicit gather
    # is tiny thanks to GQA (kv_dim << q_dim).  Without seq sharding the
    # pin is left off — it perturbs GSPMD's (cheaper) baseline layout.
    if seq_sharding_active():
        kf = constrain_batch_only(k.astype(jnp.float32))
        vf = constrain_batch_only(v.astype(jnp.float32))
    else:
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
    kpos = jnp.arange(T)

    def block(carry, inp):
        i, qc = inp                                          # qc (B,bq,H,dh)
        qg = qc.reshape(B, bq, KV, G, dh).astype(jnp.float32)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kf) * scale
        qpos = i * bq + jnp.arange(bq)
        mask = jnp.ones((bq, T), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", pr, vf)
        return carry, o.reshape(B, bq, H, dh).astype(q.dtype)

    _, ob = jax.lax.scan(jax.checkpoint(block, prevent_cse=False),
                         0, (jnp.arange(nq), qb), unroll=scan_unroll(nq))
    return jnp.moveaxis(ob, 0, 1).reshape(B, S, H, dh)


def resolve_impl(S: int, cfg: ModelConfig) -> str:
    """The inner kernel ``attention(impl="auto")`` takes for a sequence
    of S tokens: the Pallas flash kernel on TPU at S >= 1024 when the
    head dim fills the 128 lanes and both head counts split over the
    ambient mesh's ``model`` axis (outside ``force_unroll``); else
    ``sdpa_chunked`` at S >= 1024 and ``sdpa_ref`` below."""
    if S < 1024:
        return "ref"
    mesh = current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if (jax.default_backend() == "tpu" and not unroll_scans()
            and cfg.dh % 128 == 0 and cfg.n_heads % tp == 0
            and cfg.n_kv_heads % tp == 0):
        return "flash"
    return "chunked"


def _flash_on_mesh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool, window: Optional[int]) -> jax.Array:
    """The flash kernel under the ambient mesh.  GSPMD cannot split a
    ``pallas_call``, so it runs in ``shard_map``: batch over the batch
    axes, heads over ``model``, the sequence whole (a sequence-sharded
    q/k/v is gathered on the way in)."""
    from repro.kernels.ops import flash_attention as _flash

    def local(q, k, v):
        return _flash(q, k, v, causal=causal, window=window)

    mesh = current_mesh()
    if mesh is None:
        return local(q, k, v)
    bt = current_batch_axes()
    split = bt and q.shape[0] % math.prod(mesh.shape[a] for a in bt) == 0
    heads = "model" if mesh.shape.get("model", 1) > 1 else None
    spec = P(bt if split else None, None, heads, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention(p, x: jax.Array, positions: jax.Array, cfg: ModelConfig, *,
              causal: bool = True,
              window: Optional[int] = None,
              impl: str = "auto",
              sp_axis: str = "seq", sp_size: int = 1) -> jax.Array:
    """Full-sequence (train / prefill) self-attention.

    ``impl="ring"`` runs sequence-parallel ring attention: x/positions are
    this shard's slice of a sequence split over the ``sp_axis`` mesh axis
    (size ``sp_size``), and the call must sit inside ``shard_map``
    (``runtime/sequence.py``).  ``positions`` must be the shard's absolute
    token positions so RoPE agrees with the single-device kernel.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    if impl == "auto":
        impl = resolve_impl(S, cfg)
    record_attention_impl(impl)
    if impl == "ring":
        from repro.kernels.ops import ring_flash_attention as _ring
        out = _ring(q, k, v, causal=causal, window=window,
                    axis_name=sp_axis, axis_size=sp_size)
    elif impl == "flash":
        out = _flash_on_mesh(q, k, v, causal=causal, window=window)
    elif impl == "chunked":
        out = sdpa_chunked(q, k, v, causal=causal, window=window)
    else:
        out = sdpa_ref(q, k, v, causal=causal, window=window)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"]


def attention_decode(p, x: jax.Array, cache: Dict[str, jax.Array],
                     cache_index: jax.Array, cfg: ModelConfig, *,
                     window: Optional[int] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode with a ring or linear KV cache.

    x (B,1,d).  cache["k"/"v"]: (B, C, KV, dh) with C = max context (full) or
    the sliding window span.  ``cache_index`` — number of tokens already in
    context (absolute position of the new token); a scalar shared by every
    lane, or per-lane ``(B,)`` when lanes sit at different positions (the
    continuous-batching serve path after slot recycling).
    """
    B, S, _ = x.shape
    assert S == 1
    C = cache["k"].shape[1]
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
    q, k, v = _project_qkv(p, x, cfg, idx.reshape(B, 1))
    slot = (idx % C).astype(jnp.int32)                      # (B,)
    lane = jnp.arange(B)
    new_k = cache["k"].at[lane, slot].set(k[:, 0].astype(cache["k"].dtype))
    new_v = cache["v"].at[lane, slot].set(v[:, 0].astype(cache["v"].dtype))

    # position stored in each ring slot: the latest p with p % C == slot
    # and p <= cache_index
    kpos = jnp.arange(C)
    idx_c = idx[:, None]                                    # (B,1)
    abs_pos = idx_c - ((idx_c - kpos[None, :]) % C)         # (B,C)
    valid = (abs_pos >= 0) & (abs_pos <= idx_c)   # >=0: slot written
    if window is not None:
        valid &= abs_pos > idx_c - window
    scale = 1.0 / jnp.sqrt(cfg.dh).astype(jnp.float32)
    KV = cfg.n_kv_heads
    G = cfg.n_heads // KV
    qg = q.reshape(B, 1, KV, G, cfg.dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        new_k.astype(jnp.float32)) * scale
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, new_v.astype(jnp.float32))
    out = out.reshape(B, 1, cfg.q_dim).astype(x.dtype) @ p["wo"]
    return out, {"k": new_k, "v": new_v}


def _decode_slot(page_rows: jax.Array, L: jax.Array, N: int, psz: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Pool row and in-page offset of each lane's new token at position
    ``L``: (page_rows[lane, L // psz], L % psz).  Unassigned pages and
    inactive lanes (L < 0) route to row N, out of bounds, so a
    ``mode="drop"`` scatter skips them."""
    P = page_rows.shape[1]
    pi = jnp.clip(L // psz, 0, P - 1)
    page = jnp.take_along_axis(page_rows, pi[:, None], axis=1)[:, 0]  # (B,)
    page = jnp.where((page < 0) | (L < 0) | (L // psz >= P), N, page)
    return page, jnp.clip(L % psz, 0, psz - 1)


def attention_decode_paged(p, x: jax.Array, pool: Dict[str, jax.Array],
                           page_rows: jax.Array, lengths: jax.Array,
                           cfg: ModelConfig, *,
                           window: Optional[int] = None
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against a paged KV cache.

    x (B,1,d).  pool["k"/"v"]: shared page pools (N, psz, KV, dh) — every
    lane's K/V lives in pool pages, so memory scales with tokens actually
    cached rather than lanes * max-context.  ``page_rows`` (B, P) int32 maps
    each lane's logical page p to a pool row (-1 = unassigned);
    ``lengths`` (B,) is each lane's current context length (the write
    position for the new token).  Inactive lanes signal with a negative
    length: their write is routed out of bounds and dropped.

    The gathered per-lane view is a *linear* cache (position t at row
    t // psz, offset t % psz), so with identical inputs the output matches
    :func:`attention_decode` on a ring cache of span P * psz exactly —
    the paged/dense differential tests rely on this.
    """
    B, S, _ = x.shape
    assert S == 1
    N, psz, KV, dh = pool["k"].shape
    P = page_rows.shape[1]
    L = lengths.astype(jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, jnp.maximum(L, 0).reshape(B, 1))
    page, off = _decode_slot(page_rows, L, N, psz)
    new_k = pool["k"].at[page, off].set(
        k[:, 0].astype(pool["k"].dtype), mode="drop")
    new_v = pool["v"].at[page, off].set(
        v[:, 0].astype(pool["v"].dtype), mode="drop")
    # gather each lane's pages into a linear (B, P*psz, KV, dh) view;
    # unassigned rows gather page 0 (garbage) and are masked below
    rows = jnp.where(page_rows < 0, 0, page_rows)
    gk = new_k[rows].reshape(B, P * psz, KV, dh)
    gv = new_v[rows].reshape(B, P * psz, KV, dh)
    kpos = jnp.arange(P * psz)
    valid = kpos[None, :] <= L[:, None]                     # (B, C)
    if window is not None:
        valid &= kpos[None, :] > L[:, None] - window
    scale = 1.0 / jnp.sqrt(cfg.dh).astype(jnp.float32)
    G = cfg.n_heads // KV
    qg = q.reshape(B, 1, KV, G, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        gk.astype(jnp.float32)) * scale
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, gv.astype(jnp.float32))
    out = out.reshape(B, 1, cfg.q_dim).astype(x.dtype) @ p["wo"]
    return out, {"k": new_k, "v": new_v}


def resolve_decode_impl(cfg: ModelConfig, page_size: int, *,
                        window: Optional[int] = None,
                        pools_sharded: bool = False) -> str:
    """The decode attention :func:`paged_decode_step` takes:
    ``paged_kernel`` (the Pallas kernel reading live pages in place,
    ``kernels/paged_attention.py``) on TPU outside ``force_unroll`` when
    the head dim fills the 128 lanes, a page's ``page_size · KV`` rows
    fill whole 8-row tiles, the pools are not sharded and there is no
    sliding window; else ``gather`` (:func:`attention_decode_paged`)."""
    if (jax.default_backend() == "tpu" and not unroll_scans()
            and cfg.dh % 128 == 0 and (page_size * cfg.n_kv_heads) % 8 == 0
            and not pools_sharded and window is None):
        return "paged_kernel"
    return "gather"


def attention_decode_paged_in_place(p, x: jax.Array,
                                    pools: Dict[str, jax.Array],
                                    layer: jax.Array, page_rows: jax.Array,
                                    lengths: jax.Array, cfg: ModelConfig
                                    ) -> Tuple[jax.Array, Tuple]:
    """One-token decode that reads the paged K/V in place and writes
    nothing.

    ``pools["k"/"v"]``: the stacked pools (n_layers, N, psz, KV, dh) of
    the whole layer stack, read at ``layer`` by the paged-attention kernel
    (only each lane's live pages, no gather, no per-layer slice).  Returns
    the attention output and this token's ``(k_new, v_new)`` (B, KV, dh)
    for :func:`write_decode_tokens` to store after the layer scan.  Same
    semantics as :func:`attention_decode_paged`, whose pool it leaves
    unwritten."""
    from repro.kernels.ops import paged_attention as _paged
    B = x.shape[0]
    L = lengths.astype(jnp.int32)
    dt = pools["k"].dtype
    q, k, v = _project_qkv(p, x, cfg, jnp.maximum(L, 0).reshape(B, 1))
    KV = cfg.n_kv_heads
    k_new, v_new = k[:, 0].astype(dt), v[:, 0].astype(dt)
    out = _paged(q.reshape(B, KV, cfg.n_heads // KV, cfg.dh), pools["k"],
                 pools["v"], k_new, v_new, layer, page_rows, L)
    out = out.reshape(B, 1, cfg.q_dim).astype(x.dtype) @ p["wo"]
    return out, (k_new, v_new)


def write_decode_tokens(pools: Dict[str, jax.Array], k_new: jax.Array,
                        v_new: jax.Array, page_rows: jax.Array,
                        lengths: jax.Array) -> Dict[str, jax.Array]:
    """Store every layer's new token (k_new/v_new (n_layers, B, KV, dh))
    in the stacked pools at each lane's (page, offset), one scatter per
    pool; inactive lanes' writes are dropped, as in
    :func:`attention_decode_paged`."""
    n_layers, N, psz = pools["k"].shape[:3]
    page, off = _decode_slot(page_rows, lengths.astype(jnp.int32), N, psz)
    layer = jnp.arange(n_layers)[:, None]
    return {name: pools[name].at[layer, page[None], off[None]].set(
                new, mode="drop")
            for name, new in (("k", k_new), ("v", v_new))}


def attention_prefill_paged(p, x: jax.Array, pool: Dict[str, jax.Array],
                            page_rows: jax.Array, base: jax.Array,
                            prompt_len: jax.Array, cfg: ModelConfig, *,
                            window: Optional[int] = None
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Chunked-prefill attention that captures K/V into the page pools.

    x (B,S,d): one prompt chunk covering absolute positions
    [base, base + S) for every lane (``base`` may be a traced scalar, so
    one compilation serves the whole chunk loop).  ``prompt_len`` (B,)
    clips per-lane writes and masks shorter prompts; padding lanes use
    ``prompt_len = 0``.  Writes the chunk's K/V into the pools *first*,
    then attends over the gathered pool view, so earlier chunks of the
    same prompt are visible.
    """
    B, S, _ = x.shape
    N, psz, KV, dh = pool["k"].shape
    P = page_rows.shape[1]
    base = jnp.asarray(base, jnp.int32)
    ap = base + jnp.arange(S, dtype=jnp.int32)              # (S,) abs pos
    q, k, v = _project_qkv(p, x, cfg, jnp.broadcast_to(ap, (B, S)))
    pi = jnp.clip(ap // psz, 0, P - 1)                      # (S,)
    page = page_rows[:, pi]                                 # (B,S)
    in_prompt = ap[None, :] < prompt_len[:, None]           # (B,S)
    page = jnp.where((page < 0) | ~in_prompt
                     | (ap[None, :] // psz >= P), N, page)
    off = jnp.broadcast_to(ap % psz, (B, S))
    new_k = pool["k"].at[page, off].set(
        k.astype(pool["k"].dtype), mode="drop")
    new_v = pool["v"].at[page, off].set(
        v.astype(pool["v"].dtype), mode="drop")
    rows = jnp.where(page_rows < 0, 0, page_rows)
    gk = new_k[rows].reshape(B, P * psz, KV, dh)
    gv = new_v[rows].reshape(B, P * psz, KV, dh)
    kv_len = jnp.minimum(prompt_len, base + S)
    out = sdpa_ref(q, gk, gv, causal=True, window=window,
                   q_offset=base, kv_len=kv_len)
    out = out.reshape(B, S, cfg.q_dim) @ p["wo"]
    return out, {"k": new_k, "v": new_v}


def cross_attention(p, x: jax.Array, enc_kv: Tuple[jax.Array, jax.Array],
                    cfg: ModelConfig) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V (no RoPE)."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.dh)
    k, v = enc_kv
    out = sdpa_ref(q, k, v, causal=False)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"]


def precompute_cross_kv(p, enc_out: jax.Array, cfg: ModelConfig):
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.dh)
    v = (enc_out @ p["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.dh)
    return k, v


def init_kv_cache(cfg: ModelConfig, batch: int, context: int,
                  *, dtype=None) -> Dict[str, jax.Array]:
    """Cache for one layer; ``context`` = full context or window span."""
    span = context if cfg.sliding_window is None else min(context, cfg.sliding_window)
    dt = dtype or cfg.dtype
    shape = (batch, span, cfg.n_kv_heads, cfg.dh)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_page_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                   *, dtype=None) -> Dict[str, jax.Array]:
    """Shared K/V page pool for one layer: (n_pages, page_size, KV, dh)."""
    dt = dtype or cfg.dtype
    shape = (n_pages, page_size, cfg.n_kv_heads, cfg.dh)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
