"""Blocked flash attention for TPU (Pallas), GQA + causal + sliding window.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * the wrapper lays q out as (B, H, S, dh) and K/V as (B, KV, T, dh), so
    every block's last two dims are (rows, dh) — the (8, 128) tiling
    Mosaic requires — and heads are indexed through the grid,
  * the grid is (batch, head, q block, k block); the k-block axis is the
    innermost, sequential reduction, so only one (block_k, dh) K/V tile
    per operand is staged in VMEM at a time (double-buffered by the
    pipeline), whatever the key length,
  * running max/sum live in (block_q, 1) fp32 VMEM scratch across the
    k-block steps; the output block is written once, on the last step,
  * no warp-level shuffles: the reduction happens in-register per block,
    which is the natural systolic-array formulation.

Ragged lengths: S and T need not be block multiples — inputs are padded
up to the block grid and the kernel masks out-of-range k positions
(padded q rows are computed and sliced off).  Rows whose mask admits no
key at all (tiny window + causal corners) produce exact zeros.

Context beyond ~8k per device arrives sequence-sharded; each shard calls
the ring variant (``kernels/ring_attention.py``) which walks the K/V
panels around the ``seq`` mesh axis.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# (batch, head, q block) are independent; the k-block axis carries the
# online-softmax state, so it must run in order on one core
DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _validate_attn_shapes(S: int, T: int, H: int, KV: int,
                          window: Optional[int]) -> None:
    """Reject genuinely unsupported shapes with descriptive errors."""
    if KV <= 0 or H % KV != 0:
        raise ValueError(
            f"GQA requires n_heads divisible by n_kv_heads; got H={H}, "
            f"KV={KV} (H % KV = {H % KV}) — integer grouping would "
            f"silently mis-route queries to the wrong KV head")
    if window is not None:
        if window <= 0:
            raise ValueError(
                f"sliding window must be a positive span, got window="
                f"{window} (every position would be masked)")
        if window > T:
            raise ValueError(
                f"sliding window {window} exceeds the key length T={T}; "
                f"pass window=None for full attention over this context")


def block_sizes(S: int, T: int, block_q: int, block_k: int):
    """Clamp the blocks to the (8-row rounded) lengths and pad S/T up to
    whole blocks: returns (block_q, block_k, S_pad, T_pad)."""
    block_q = min(block_q, -(-S // 8) * 8)
    block_k = min(block_k, -(-T // 8) * 8)
    return (block_q, block_k, -(-S // block_q) * block_q,
            -(-T // block_k) * block_k)


def init_softmax_state(acc_ref, m_ref, l_ref) -> None:
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)


def online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                        q_pos, k_pos, scale: float, causal: bool,
                        window: Optional[int], kv_len: Optional[int]) -> None:
    """Fold one (block_k, dh) K/V tile into the running (acc, m, l) state.

    ``q_pos`` (block_q, 1) and ``k_pos`` (1, block_k) are the positions the
    causal / window masks compare; ``kv_len`` masks a padded key tail
    (``None``: no padding in this tile's panel)."""
    q = q_ref[...].astype(jnp.float32) * scale
    k = k_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
    mask = jnp.ones(s.shape, bool)
    if kv_len is not None:              # padded K/V tail: never attended
        mask &= k_pos < kv_len
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # all-masked rows keep m_new == NEG_INF; exp(NEG_INF - NEG_INF) would
    # be 1 with a finite sentinel, so zero those lanes explicitly
    p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[...].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, kv_len: Optional[int]):
    # q_ref/o_ref: (block_q, dh); k_ref/v_ref: (block_k, dh) — tile ik of
    # this (batch, kv head)'s key panel; scratch acc (block_q, dh), m/l
    # (block_q, 1) carry the online softmax across the k-block axis.
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        init_softmax_state(acc_ref, m_ref, l_ref)

    q_lo, k_lo = iq * block_q, ik * block_k
    live = []
    if causal:          # skip tiles wholly in this q block's future
        live.append(k_lo <= q_lo + block_q - 1)
    if window is not None:   # ... or wholly behind its window
        live.append(k_lo + block_k - 1 > q_lo - window)

    def step():
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                            q_pos=q_pos, k_pos=k_pos, scale=scale,
                            causal=causal, window=window, kv_len=kv_len)

    if live:
        pl.when(functools.reduce(jnp.logical_and, live))(step)
    else:
        step()

    @pl.when(ik == pl.num_programs(3) - 1)
    def _():
        l = l_ref[...]
        # rows with no admissible key (l == 0) are exact zeros, not noise
        o = jnp.where(l > 0.0, acc_ref[...] / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[...] = o.astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def heads_major(x: jax.Array, length: int) -> jax.Array:
    """(B, L, heads, dh) -> (B, heads, length, dh), zero-padding L."""
    return _pad_to(x, 1, length).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B,S,H,dh); k/v (B,T,KV,dh) -> (B,S,H,dh).

    Arbitrary (ragged) S/T are padded up to the block grid; out-of-range
    keys are masked in-kernel and padded q rows sliced off the output.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    _validate_attn_shapes(S, T, H, KV, window)
    G = H // KV
    block_q, block_k, S_pad, T_pad = block_sizes(S, T, block_q, block_k)
    n_k = T_pad // block_k

    def kv_block(i, j):
        # a dead tile (skipped in-kernel) re-uses the last live tile's
        # index, so the pipeline issues no DMA for it
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        if window is not None:
            lo = jnp.maximum(0, i * block_q - window + 1) // block_k
            j = jnp.maximum(j, jnp.minimum(lo, n_k - 1))
        return j

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (dh ** 0.5), causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=T if T < T_pad else None)
    q_spec = pl.BlockSpec((None, None, block_q, dh),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, dh),
                           lambda b, h, i, j: (b, h // G, kv_block(i, j), 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, S_pad // block_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S_pad, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
    )(heads_major(q, S_pad), heads_major(k, T_pad), heads_major(v, T_pad))
    return out.transpose(0, 2, 1, 3)[:, :S]
