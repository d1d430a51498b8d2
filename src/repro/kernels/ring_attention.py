"""Ring attention: flash attention over a sequence-sharded mesh axis.

Each device holds a local q shard and a local K/V panel of the sequence.
The panels rotate around the ``seq`` mesh axis with ``lax.ppermute`` —
the same ring hand-off the pipeline runtime uses: the permute on the
current panel is issued *before* the round's compute, so the collective
has no data dependency on it and XLA overlaps the send/recv with the
flash kernel of the round in flight.

Every round runs a *partial* flash kernel over (local q, visiting K/V
panel) that returns the un-normalized online-softmax state (acc, m, l);
rounds merge states with the standard log-sum-exp combine, and after
P − 1 hand-offs (P = axis size) every device has attended its q shard to
the full global sequence.  The result is token-identical to running the
single-device ``flash_attention`` on the gathered sequence.

Masks are expressed through ``delta = q_start − k_start`` (the offset of
the local q shard against the visiting panel's global origin), the only
dynamic quantity the kernel needs: ``k_global <= q_global`` is exactly
``k_local <= q_local + delta``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (DIM_SEMANTICS, NEG_INF,
                                           block_sizes, heads_major,
                                           init_softmax_state,
                                           online_softmax_step, when_live,
                                           _positions, _validate_attn_shapes)


def _partial_kernel(delta_ref, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                    acc_s, m_s, l_s, *, scale: float, causal: bool,
                    window: Optional[int], block_q: int, block_k: int,
                    kv_len: Optional[int]):
    # delta_ref: (1,) int32 in SMEM — q_start − k_start in global
    # positions.  Outputs are the raw online-softmax state: acc
    # (block_q, dh) fp32, m / l (block_q, 1) fp32.  Rows the mask fully
    # rejects keep m == NEG_INF, l == 0, acc == 0, which the cross-round
    # merge and the final normalization treat as an exact zero
    # contribution.
    iq, ik = pl.program_id(2), pl.program_id(3)
    delta = delta_ref[0]

    @pl.when(ik == 0)
    def _():
        init_softmax_state(acc_s, m_s, l_s)

    # q/k positions on the visiting panel's local axis: k_global <=
    # q_global is exactly k_local <= q_local + delta
    q_lo, k_lo = iq * block_q + delta, ik * block_k

    def step(**masks):       # delta is dynamic, so the tile skip is too
        q_pos, k_pos = _positions(q_lo, k_lo, block_q, block_k)
        online_softmax_step(q_ref, k_ref, v_ref, acc_s, m_s, l_s,
                            q_pos=q_pos, k_pos=k_pos, scale=scale, **masks)

    when_live(step, q_lo, k_lo, block_q=block_q, block_k=block_k,
              causal=causal, window=window, kv_len=kv_len)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _():
        acc_ref[...] = acc_s[...]
        m_ref[...] = m_s[...]
        l_ref[...] = l_s[...]


def _flash_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                   delta: jax.Array, *, kv_len: int, causal: bool,
                   window: Optional[int], block_q: int, block_k: int,
                   interpret: bool) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One panel visit: (acc, m, l) of local q against one K/V panel.

    Heads-major operands: q (B, H, S_pad, dh) and k/v (B, KV, T_pad, dh),
    already padded to whole blocks; ``block_k`` tiles T_pad exactly and
    only the first ``kv_len`` keys of the panel are real."""
    B, H, S_pad, dh = q.shape
    KV, T_pad = k.shape[1], k.shape[2]
    G = H // KV
    kernel = functools.partial(
        _partial_kernel, scale=1.0 / (dh ** 0.5), causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        kv_len=kv_len if kv_len < T_pad else None)
    q_spec = pl.BlockSpec((None, None, block_q, dh),
                          lambda b, h, i, j, d: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, dh),
                           lambda b, h, i, j, d: (b, h // G, j, 0))
    row_spec = pl.BlockSpec((None, None, block_q, 1),
                            lambda b, h, i, j, d: (b, h, i, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, S_pad // block_q, T_pad // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, row_spec, row_spec],
            scratch_shapes=[pltpu.VMEM((block_q, dh), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, S_pad, dh), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, S_pad, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, S_pad, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
    )(jnp.reshape(delta, (1,)).astype(jnp.int32), q, k, v)


def _merge(state, part):
    """Log-sum-exp combine of two online-softmax states.

    Fully-masked states carry m == NEG_INF with acc == 0, l == 0; the
    exp() of a NEG_INF gap underflows to an exact 0 coefficient, so they
    merge as identity elements without special-casing.
    """
    acc_a, m_a, l_a = state
    acc_b, m_b, l_b = part
    m_new = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m_new)
    cb = jnp.exp(m_b - m_new)
    return (acc_a * ca + acc_b * cb, m_new, l_a * ca + l_b * cb)


def ring_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         axis_name: str = "seq", axis_size: int,
                         causal: bool = True, window: Optional[int] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """Sequence-sharded flash attention; call inside ``shard_map``.

    q (B, S/P, H, dh); k/v (B, T/P, KV, dh) — local shards of a sequence
    split over the ``axis_name`` mesh axis of size ``axis_size`` (= P).
    Returns the local (B, S/P, H, dh) output shard, token-identical to
    ``flash_attention`` on the gathered sequence.

    P − 1 ``ppermute`` rounds rotate the K/V panels; each round's
    hand-off is issued before its compute so the collective overlaps the
    kernel (the pipeline runtime's hand-off idiom).  On causally dead
    visits (a panel entirely in this shard's future) every tile skips
    its compute and the visit contributes an all-masked zero state —
    the merge ignores it.
    """
    P = int(axis_size)
    B, S_loc, H, dh = q.shape
    T_loc, KV = k.shape[1], k.shape[2]
    _validate_attn_shapes(S_loc * P, T_loc * P, H, KV, window)
    if P == 1:
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)

    block_q, block_k, S_pad, T_pad = block_sizes(S_loc, T_loc, block_q,
                                                 block_k)
    idx = jax.lax.axis_index(axis_name)
    q_start = idx * S_loc
    perm = [(i, (i + 1) % P) for i in range(P)]

    qh = heads_major(q, S_pad)
    state = (jnp.zeros((B, H, S_pad, dh), jnp.float32),
             jnp.full((B, H, S_pad, 1), NEG_INF, jnp.float32),
             jnp.zeros((B, H, S_pad, 1), jnp.float32))
    k_cur, v_cur = heads_major(k, T_pad), heads_major(v, T_pad)
    for r in range(P):
        if r < P - 1:
            # hand-off overlap: rotate the panel we already consumed a
            # copy of BEFORE this round's kernel — no data dependency,
            # so the collective runs under the compute
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (idx - r) % P               # original owner of k_cur/v_cur
        delta = q_start - src * T_loc
        part = _flash_partial(qh, k_cur, v_cur, delta, kv_len=T_loc,
                              causal=causal, window=window, block_q=block_q,
                              block_k=block_k, interpret=interpret)
        state = _merge(state, part)
        if r < P - 1:
            k_cur, v_cur = k_nxt, v_nxt

    acc, _, l = state
    o = jnp.where(l > 0.0, acc / jnp.where(l > 0.0, l, 1.0), 0.0)
    return o.astype(q.dtype).transpose(0, 2, 1, 3)[:, :S_loc]
