"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None) -> jax.Array:
    """q (B,S,H,dh); k/v (B,T,KV,dh) grouped-query attention."""
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, dh).astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.float32(dh))
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no visible key are exact zeros (matching the kernel's
    # masked-row semantics), not a softmax average over the -1e30 sentinel
    any_visible = mask.any(axis=-1)                          # (S,)
    probs = jnp.where(any_visible[None, None, None, :, None], probs, 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, dh).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        k_new: jax.Array, v_new: jax.Array, layer,
                        page_rows: jax.Array, lengths: jax.Array) -> jax.Array:
    """q (B,KV,G,dh) decode attention over each lane's first ``lengths``
    pooled positions of ``layer`` (pools (n_layers,N,psz,KV,dh), pages
    ``page_rows`` (B,P)) plus its new token ``k_new``/``v_new`` (B,KV,dh)."""
    B, KV, G, dh = q.shape
    psz, P = k_pool.shape[2], page_rows.shape[1]
    rows = jnp.where(page_rows < 0, 0, page_rows)

    def lane_view(pool, new):
        seq = pool[layer][rows].reshape(B, P * psz, KV, dh)
        return jnp.concatenate([seq, new[:, None]], 1).astype(jnp.float32)

    k, v = lane_view(k_pool, k_new), lane_view(v_pool, v_new)
    pos = jnp.arange(P * psz + 1)
    valid = ((pos[None, :] < lengths[:, None])
             | (pos[None, :] == P * psz))                   # (B, T+1)
    scores = jnp.einsum("bkgd,btkd->bkgt", q.astype(jnp.float32), k)
    scores = scores / jnp.sqrt(jnp.float32(dh))
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", probs, v).astype(q.dtype)


def ssd_scan_ref(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                 Cm: jax.Array, chunk: int = 64) -> jax.Array:
    """Chunked SSD oracle — delegates to the model-layer implementation
    (itself validated against the sequential one-step recurrence)."""
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, dt, A, Bm, Cm, chunk)


def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(x.dtype)
