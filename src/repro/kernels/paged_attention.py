"""Paged-attention decode for TPU (Pallas): one new query token per lane
against the K/V that lane has cached in the shared page pools, read in
place.

Layout and data flow:
  * the pools stay where they are, stacked over layers as
    ``(n_layers, N, psz, KV, dh)`` in HBM (``memory_space=ANY``); the
    wrapper views them as ``(n_layers, N, psz·KV, dh)`` rows, which is the
    same memory on the TPU's (8, 128) tiling, so one page is one
    contiguous DMA of ``psz·KV`` rows,
  * the layer index, each lane's page rows and its length are scalar
    prefetch operands: the kernel computes every DMA source from them,
    so no per-layer slice of the stacked pool and no gather is ever
    materialised,
  * the grid walks the lanes; lane ``b`` fetches only its
    ``ceil(L_b / psz)`` live pages, in blocks of ``pages_per_block``,
    double-buffered (the next block's DMAs are in flight while the
    current block is computed).  Lanes with ``L < 0`` (inactive) fetch
    nothing, and so do page rows past the live ones (``-1``),
  * the new token's K/V (``k_new``/``v_new``, this step's, not yet in
    the pool) seeds the online softmax with its own score, so ``L = 0``
    needs no special case and an inactive lane returns its own value row.

Heads: a block's rows are (page, slot, KV head) interleaved, so the
kernel scores every query head against every row in one matmul and masks
the rows of other KV heads.  That is KV× the MXU work of per-head
matmuls, but it needs no relayout of the page in VMEM, and decode is
bound by the pool's bytes, not by the MXU.

Precision: operands in the pool's dtype (bf16 for a bf16 model), scores,
softmax statistics and accumulators in f32; P is cast to V's dtype for
P·V, as in ``flash_attention.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))      # a @ b.T

# pages fetched per block (each block is one wait and one matmul pair)
PAGES_PER_BLOCK = 8


def _kernel(layer_ref, rows_ref, len_ref, q_ref, kn_ref, vn_ref, kp_hbm,
            vp_hbm, o_ref, kbuf, vbuf, sems, *, psz: int, kv: int, g: int,
            pb: int, n_rows: int, scale: float):
    # q_ref/o_ref (H, dh); kn_ref/vn_ref (KV, dh); kp_hbm/vp_hbm the whole
    # pools (n_layers, N, psz·KV, dh); kbuf/vbuf (2, pb·psz·KV, dh)
    b = pl.program_id(0)
    layer = layer_ref[0]
    L = len_ref[b]
    rows = psz * kv
    n_live = jnp.minimum((jnp.maximum(L, 0) + psz - 1) // psz, n_rows)
    n_blk = (n_live + pb - 1) // pb

    @pl.when(b == 0)
    def _():
        # a block's tail past the live pages is never fetched; its rows
        # get p = 0, so they must hold finite values, not stale VMEM
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)

    def page_copies(blk, slot, i):
        pg = blk * pb + i
        row = rows_ref[b * n_rows + jnp.minimum(pg, n_rows - 1)]
        dst = pl.ds(i * rows, rows)
        return (pg < n_live) & (row >= 0), [
            pltpu.make_async_copy(src.at[layer, jnp.maximum(row, 0)],
                                  buf.at[slot, dst], sems.at[j, slot])
            for j, (src, buf) in enumerate(((kp_hbm, kbuf), (vp_hbm, vbuf)))]

    def fetch(blk, slot, start: bool):
        for i in range(pb):
            live, copies = page_copies(blk, slot, i)

            @pl.when(live)
            def _():
                for c in copies:
                    c.start() if start else c.wait()

    H, dh = q_ref.shape
    q = q_ref[...]
    # the new token seeds each query row's online softmax: its own KV
    # head's key and value rows, its score by a row-wise f32 product
    head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // g
    kn, vn = (r[...].astype(jnp.float32) for r in (kn_ref, vn_ref))
    k0 = v0 = jnp.zeros((H, dh), jnp.float32)
    for k in range(kv):
        k0 = jnp.where(head == k, kn[k:k + 1], k0)
        v0 = jnp.where(head == k, vn[k:k + 1], v0)
    m0 = jnp.sum(q.astype(jnp.float32) * k0, axis=-1, keepdims=True) * scale
    l0 = jnp.ones((H, 1), jnp.float32)

    @pl.when(n_blk > 0)
    def _():
        fetch(0, 0, start=True)

    cols = pb * rows
    col = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)
    col_own = (col % kv) == jax.lax.broadcasted_iota(
        jnp.int32, (H, cols), 0) // g
    col_pos = col // kv

    def body(blk, carry):
        m, l, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _():
            fetch(blk + 1, 1 - slot, start=True)

        fetch(blk, slot, start=False)
        s = jax.lax.dot_general(q, kbuf[slot], _NT,
                                preferred_element_type=jnp.float32) * scale
        mask = col_own & (col_pos < L - blk * (pb * psz))
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)         # masked: exp(-1e30 - m) = 0
        v = vbuf[slot]
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), acc

    _, l, acc = jax.lax.fori_loop(0, n_blk, body, (m0, l0, v0))
    o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_attention(q, k_pool, v_pool, k_new, v_new, layer, page_rows,
                    lengths, *, pages_per_block: int = PAGES_PER_BLOCK,
                    interpret: bool = False):
    """Decode attention of one token per lane over its paged K/V.

    q (B, KV, G, dh); k_pool/v_pool (n_layers, N, psz, KV, dh), the
    stacked pools, read at ``layer`` (int32 scalar); k_new/v_new
    (B, KV, dh), this token's K/V in the pools' dtype.  ``page_rows``
    (B, P) int32 maps each lane's logical page to a pool row (-1 =
    unassigned); ``lengths`` (B,) int32 is each lane's cached token count
    (the new token's position), negative for an inactive lane.  Lane b
    attends over its positions [0, L_b) in the pool plus the new token.
    Returns (B, KV, G, dh) in q's dtype."""
    B, KV, G, dh = q.shape
    n_layers, N, psz, _, _ = k_pool.shape
    P = page_rows.shape[1]
    H, rows = KV * G, psz * KV
    pb = min(pages_per_block, P)
    kp = k_pool.reshape(n_layers, N, rows, dh)
    vp = v_pool.reshape(n_layers, N, rows, dh)
    lane = lambda b, *_: (b, 0, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, psz=psz, kv=KV, g=G, pb=pb, n_rows=P,
                          scale=1.0 / (dh ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, dh), lane),
                      pl.BlockSpec((None, KV, dh), lane),
                      pl.BlockSpec((None, KV, dh), lane),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, dh), lane),
            scratch_shapes=[pltpu.VMEM((2, pb * rows, dh), k_pool.dtype),
                            pltpu.VMEM((2, pb * rows, dh), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        # lane 0 clears the V buffers the later lanes rely on
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      page_rows.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(B, H, dh), k_new, v_new, kp, vp)
    return out.reshape(B, KV, G, dh)
