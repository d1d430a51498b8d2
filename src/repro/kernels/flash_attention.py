"""Blocked flash attention for TPU (Pallas), GQA + causal + sliding window,
with its own backward pass.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * the wrapper lays q out as (B, H, S, dh) and K/V as (B, KV, T, dh), so
    every block's last two dims are (rows, dh) — the (8, 128) tiling
    Mosaic requires — and heads are indexed through the grid,
  * the grid is (batch, head, q block, k block); the k-block axis is the
    innermost, sequential reduction, so only one (block_k, dh) K/V tile
    per operand is staged in VMEM at a time (double-buffered by the
    pipeline), whatever the key length,
  * running max/sum live in (block_q, 1) fp32 VMEM scratch across the
    k-block steps; the output block and each row's logsumexp are written
    once, on the last step,
  * no warp-level shuffles: the reduction happens in-register per block,
    which is the natural systolic-array formulation.

Operand dtypes: every matmul takes its operands in the input dtype (bf16
for a bf16 model, so the MXU runs at its bf16 rate; f32 inputs stay f32)
and accumulates in f32.  The softmax statistics, the accumulators and
the scores stay f32; P is cast to V's dtype for P·V, and in the backward
pass P to dO's dtype for dV and dS to the input dtype for dQ and dK.

Backward (``jax.custom_vjp``): the forward saves (q, k, v, o, lse), with
lse the f32 logsumexp of each query row, and the backward recomputes
each tile's probabilities as exp(s - lse).  D = rowsum(dO ∘ O) is
computed once in jnp.  Two kernels on the same tiles, with the same
causal / window tile skipping as the forward:
  * dK/dV: grid (batch, kv head, k block, group member, q block); for
    each K/V tile the last two axes walk the live q tiles of the G query
    heads that share it, accumulating dK and dV in f32 VMEM scratch,
  * dQ: grid (batch, head, q block, k block); for each q tile the last
    axis walks its live K/V tiles, accumulating dQ.

Ragged lengths: S and T need not be block multiples — inputs are padded
up to the block grid and the kernel masks out-of-range k positions
(padded q rows are computed and sliced off; their cotangent is zero).
Rows whose mask admits no key at all (tiny window + causal corners)
produce exact zeros and get zero gradients.

Context beyond ~8k per device arrives sequence-sharded; each shard calls
the ring variant (``kernels/ring_attention.py``) which walks the K/V
panels around the ``seq`` mesh axis.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# (batch, head, q block) are independent; the k-block axis carries the
# online-softmax state, so it must run in order on one core
DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
# dK/dV: (batch, kv head, k block) are independent; (group member, q
# block) carry the dK/dV accumulators
DKV_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary",
                 "arbitrary")

_NT = (((1,), (1,)), ((), ()))      # a @ b.T

# the fastest square tile for forward + backward at S 4096, dh 128, bf16
# on a v5e (PERF.md, section 6); both clamp to shorter sequences
BLOCK_Q = BLOCK_K = 1024


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


def _tile_mask(q_pos, k_pos, *, causal: bool, window: Optional[int],
               kv_len: Optional[int]):
    """(block_q, block_k) mask of admissible (query, key) pairs, or None
    when every pair of the tile is admissible."""
    conds = []
    if kv_len is not None:              # padded K/V tail: never attended
        conds.append(k_pos < kv_len)
    if causal:
        conds.append(k_pos <= q_pos)
    if window is not None:
        conds.append(k_pos > q_pos - window)
    return functools.reduce(jnp.logical_and, conds) if conds else None


def when_live(step, q_lo, k_lo, *, block_q: int, block_k: int,
              causal: bool, window: Optional[int],
              kv_len: Optional[int]) -> None:
    """Run ``step(causal=, window=, kv_len=)`` on the (q block at q_lo,
    k block at k_lo) tile unless it lies wholly in the queries' future
    (causal) or behind their window.  A tile that the diagonal, the
    window's edge or the padded key tail cuts gets those masks; a tile
    whose every pair is admissible gets none (``causal=False,
    window=None, kv_len=None``), which spares most tiles the mask's
    element-wise work."""
    live, whole = [], []
    if causal:
        live.append(k_lo <= q_lo + block_q - 1)
        whole.append(k_lo + block_k - 1 <= q_lo)
    if window is not None:
        live.append(k_lo + block_k - 1 > q_lo - window)
        whole.append(k_lo > q_lo + block_q - 1 - window)
    if kv_len is not None:
        whole.append(k_lo + block_k <= kv_len)
    masked = functools.partial(step, causal=causal, window=window,
                               kv_len=kv_len)
    if not whole:
        masked()
        return
    whole = functools.reduce(jnp.logical_and, whole)
    pl.when(whole)(functools.partial(step, causal=False, window=None,
                                     kv_len=None))
    cut = jnp.logical_not(whole)
    if live:
        cut = functools.reduce(jnp.logical_and, live, cut)
    pl.when(cut)(masked)


def online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *,
                        q_pos, k_pos, scale: float, causal: bool,
                        window: Optional[int], kv_len: Optional[int]) -> None:
    """Fold one (block_k, dh) K/V tile into the running (acc, m, l) state.

    ``q_pos`` (block_q, 1) and ``k_pos`` (1, block_k) are the positions the
    causal / window masks compare; ``kv_len`` masks a padded key tail
    (``None``: no padding in this tile's panel)."""
    s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
    mask = _tile_mask(q_pos, k_pos, causal=causal, window=window,
                      kv_len=kv_len)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if mask is not None:
        # a row with no admissible key yet keeps m_new == NEG_INF, where
        # exp(NEG_INF - NEG_INF) would be 1: zero its masked lanes
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _positions(q_lo, k_lo, block_q: int, block_k: int):
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    return q_pos, k_pos


class _Tiling(NamedTuple):
    """The static part of one attention call: masks and block grid."""
    causal: bool
    window: Optional[int]
    block_q: int
    block_k: int
    kv_len: Optional[int]      # real key count when the panel is padded
    interpret: bool

    def kernel_args(self, dh: int):
        return dict(scale=1.0 / (dh ** 0.5), causal=self.causal,
                    window=self.window, block_q=self.block_q,
                    block_k=self.block_k, kv_len=self.kv_len)

    def live_tiles(self, n_q: int, n_k: int) -> int:
        """Number of (q block, k block) tiles the kernels compute."""
        bq, bk, n = self.block_q, self.block_k, 0
        for i in range(n_q):
            for j in range(n_k):
                n += ((not self.causal or j * bk <= i * bq + bq - 1) and
                      (self.window is None
                       or j * bk + bk - 1 > i * bq - self.window))
        return n

    def kv_block(self, i, j, n_k: int):
        """K/V block index for q block i, k step j: a dead tile (skipped
        in-kernel) re-uses the nearest live tile's index, so the pipeline
        issues no DMA for it."""
        bq, bk = self.block_q, self.block_k
        if self.causal:
            j = jnp.minimum(j, (i * bq + bq - 1) // bk)
        if self.window is not None:
            lo = jnp.maximum(0, i * bq - self.window + 1) // bk
            j = jnp.maximum(j, jnp.minimum(lo, n_k - 1))
        return j

    def q_block(self, j, i, n_q: int):
        """q block index for k block j, q step i (the dK/dV walk): dead
        tiles re-use the nearest live tile's index, as ``kv_block``."""
        bq, bk = self.block_q, self.block_k
        if self.causal:
            i = jnp.maximum(i, jnp.minimum(j * bk // bq, n_q - 1))
        if self.window is not None:
            hi = (j * bk + bk - 2 + self.window) // bq
            i = jnp.minimum(i, jnp.minimum(hi, n_q - 1))
        return i


def _cost(t: _Tiling, B: int, H: int, n_q: int, n_k: int, dh: int,
          matmuls: int, nbytes: int) -> pl.CostEstimate:
    pairs = B * H * t.live_tiles(n_q, n_k) * t.block_q * t.block_k
    return pl.CostEstimate(flops=2 * matmuls * pairs * dh,
                           transcendentals=pairs, bytes_accessed=nbytes)


def _nbytes(*xs) -> int:
    return sum(x.size * x.dtype.itemsize for x in xs)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, kv_len: Optional[int]):
    # q_ref/o_ref: (block_q, dh); k_ref/v_ref: (block_k, dh) — tile ik of
    # this (batch, kv head)'s key panel; lse_ref (block_q, 1); scratch
    # acc (block_q, dh), m/l (block_q, 1) carry the online softmax
    # across the k-block axis.
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        init_softmax_state(acc_ref, m_ref, l_ref)

    q_lo, k_lo = iq * block_q, ik * block_k

    def step(**masks):
        q_pos, k_pos = _positions(q_lo, k_lo, block_q, block_k)
        online_softmax_step(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                            q_pos=q_pos, k_pos=k_pos, scale=scale, **masks)

    when_live(step, q_lo, k_lo, block_q=block_q, block_k=block_k,
              causal=causal, window=window, kv_len=kv_len)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _():
        l = l_ref[...]
        seen = l > 0.0
        safe = jnp.where(seen, l, 1.0)
        # rows with no admissible key (l == 0) are exact zeros, not noise;
        # their lse is never read (every probability of the row is masked)
        o_ref[...] = jnp.where(seen, acc_ref[...] / safe,
                               0.0).astype(o_ref.dtype)
        lse_ref[...] = jnp.where(seen, m_ref[...] + jnp.log(safe), 0.0)


def _forward(q, k, v, t: _Tiling):
    """Heads-major padded q (B, H, S_pad, dh), k/v (B, KV, T_pad, dh) ->
    (o like q, lse (B, H, S_pad, 1) f32)."""
    B, H, S_pad, dh = q.shape
    KV, T_pad = k.shape[1], k.shape[2]
    G = H // KV
    n_q, n_k = S_pad // t.block_q, T_pad // t.block_k
    q_spec = pl.BlockSpec((None, None, t.block_q, dh),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, t.block_k, dh),
                           lambda b, h, i, j: (b, h // G,
                                               t.kv_block(i, j, n_k), 0))
    row_spec = pl.BlockSpec((None, None, t.block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))
    return pl.pallas_call(
        functools.partial(_flash_kernel, **t.kernel_args(dh)),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, S_pad, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t.block_q, dh), jnp.float32),
                        pltpu.VMEM((t.block_q, 1), jnp.float32),
                        pltpu.VMEM((t.block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        cost_estimate=_cost(t, B, H, n_q, n_k, dh, 2,
                            2 * _nbytes(q) + G * n_q * _nbytes(k, v)),
        interpret=t.interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _probs_and_dscores(a_ref, b_ref, lse, d, a_pos, b_pos, *, scale: float,
                       causal: bool, window: Optional[int],
                       kv_len: Optional[int], q_rows: bool):
    """One tile's probabilities P = exp(s - lse) and score cotangents
    dS = P ∘ (dP - D), f32 and laid out as the scores a·bᵀ.

    ``q_rows``: a = (q, dO) and b = (k, v), so the tile is (block_q,
    block_k) and lse/D are (block_q, 1) columns; else a = (k, v) and
    b = (q, dO), the tile is transposed, (block_k, block_q), and lse/D
    are (1, block_q) rows."""
    (x_ref, dx_ref), (y_ref, dy_ref) = a_ref, b_ref
    s = jax.lax.dot_general(x_ref[...], y_ref[...], _NT,
                            preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)
    q_pos, k_pos = (a_pos, b_pos) if q_rows else (b_pos, a_pos)
    mask = _tile_mask(q_pos, k_pos, causal=causal, window=window,
                      kv_len=kv_len)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(dx_ref[...], dy_ref[...], _NT,
                             preferred_element_type=jnp.float32)
    return p, p * (dp - d)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_ref,
               *, scale: float, causal: bool, window: Optional[int],
               block_q: int, block_k: int, kv_len: Optional[int]):
    # the forward's grid: for q tile iq, walk the live K/V tiles ik;
    # lse_ref/d_ref are (block_q, 1) columns
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q_lo, k_lo = iq * block_q, ik * block_k

    def step(**masks):
        q_pos, k_pos = _positions(q_lo, k_lo, block_q, block_k)
        _, ds = _probs_and_dscores(
            (q_ref, do_ref), (k_ref, v_ref), lse_ref[...], d_ref[...],
            q_pos, k_pos, scale=scale, q_rows=True, **masks)
        acc_ref[...] += jnp.dot(ds.astype(k_ref.dtype), k_ref[...],
                                preferred_element_type=jnp.float32)

    when_live(step, q_lo, k_lo, block_q=block_q, block_k=block_k,
              causal=causal, window=window, kv_len=kv_len)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale: float, causal: bool,
                window: Optional[int], block_q: int, block_k: int,
                kv_len: Optional[int]):
    # for K/V tile ik, walk group member g's live q tiles iq; the tile is
    # computed transposed, (block_k, block_q), so dK and dV are plain
    # products with q and dO, and lse_ref/d_ref are (1, block_q) rows
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (iq == 0))
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    q_lo, k_lo = iq * block_q, ik * block_k

    def step(**masks):
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
        pt, dst = _probs_and_dscores(
            (k_ref, v_ref), (q_ref, do_ref), lse_ref[...], d_ref[...],
            k_pos, q_pos, scale=scale, q_rows=False, **masks)
        dv_acc[...] += jnp.dot(pt.astype(do_ref.dtype), do_ref[...],
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(dst.astype(q_ref.dtype), q_ref[...],
                               preferred_element_type=jnp.float32)

    when_live(step, q_lo, k_lo, block_q=block_q, block_k=block_k,
              causal=causal, window=window, kv_len=kv_len)

    @pl.when((g == pl.num_programs(3) - 1) & (iq == pl.num_programs(4) - 1))
    def _():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _backward(t: _Tiling, q, k, v, o, lse, do):
    B, H, S_pad, dh = q.shape
    KV, T_pad = k.shape[1], k.shape[2]
    G = H // KV
    n_q, n_k = S_pad // t.block_q, T_pad // t.block_k
    d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    kw = t.kernel_args(dh)
    nb = _nbytes(q, k, v, do, lse, lse)

    # dQ on the forward's grid; lse and D as (B, H, S_pad, 1) columns
    q_spec = pl.BlockSpec((None, None, t.block_q, dh),
                          lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, t.block_k, dh),
                           lambda b, h, i, j: (b, h // G,
                                               t.kv_block(i, j, n_k), 0))
    col_spec = pl.BlockSpec((None, None, t.block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, col_spec, col_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((t.block_q, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
        cost_estimate=_cost(t, B, H, n_q, n_k, dh, 3, nb + _nbytes(q)
                            + (G * n_q - 1) * _nbytes(k, v)),
        interpret=t.interpret,
    )(q, k, v, do, lse, d[..., None])

    # dK/dV: (batch, kv head, k block, group member, q block); lse and D
    # as (B, H, 1, S_pad) rows
    def q_index(b, h, j, g, i):
        return (b, h * G + g, t.q_block(j, i, n_q), 0)

    def row_index(b, h, j, g, i):
        return (b, h * G + g, 0, t.q_block(j, i, n_q))

    q_spec = pl.BlockSpec((None, None, t.block_q, dh), q_index)
    row_spec = pl.BlockSpec((None, None, 1, t.block_q), row_index)
    kv_spec = pl.BlockSpec((None, None, t.block_k, dh),
                           lambda b, h, j, g, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, KV, n_k, G, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((t.block_k, dh), jnp.float32),
                        pltpu.VMEM((t.block_k, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DKV_SEMANTICS),
        cost_estimate=_cost(t, B, H, n_q, n_k, dh, 4, n_k * nb
                            + _nbytes(k, v)),
        interpret=t.interpret,
    )(q, k, v, do, lse.reshape(B, H, 1, S_pad), d[:, :, None, :])
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, t: _Tiling):
    return _forward(q, k, v, t)[0]


def _attend_fwd(q, k, v, t: _Tiling):
    o, lse = _forward(q, k, v, t)
    return o, (q, k, v, o, lse)


def _attend_bwd(t: _Tiling, res, do):
    return _backward(t, *res, do)


_attend.defvjp(_attend_fwd, _attend_bwd)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

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
                    block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q (B,S,H,dh); k/v (B,T,KV,dh) -> (B,S,H,dh); differentiable.

    Arbitrary (ragged) S/T are padded up to the block grid; out-of-range
    keys are masked in-kernel and padded q rows sliced off the output.
    """
    B, S, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    _validate_attn_shapes(S, T, H, KV, window)
    block_q, block_k, S_pad, T_pad = block_sizes(S, T, block_q, block_k)
    t = _Tiling(causal=causal, window=window, block_q=block_q,
                block_k=block_k, kv_len=T if T < T_pad else None,
                interpret=interpret)
    out = _attend(heads_major(q, S_pad), heads_major(k, T_pad),
                  heads_major(v, T_pad), t)
    return out.transpose(0, 2, 1, 3)[:, :S]
