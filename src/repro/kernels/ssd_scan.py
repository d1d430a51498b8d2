"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid: (batch, head, chunk).  The wrapper lays every operand out heads-major
((B, H, S, ·), so each block's last two dims are (chunk, width) — the
tiling Mosaic requires) and the chunk axis is the innermost, sequential
one: a program sees one ``chunk``-row tile of x / B / C / dt, and the
(head_dim x state) SSM state rides across chunks in a VMEM scratch
buffer.  Each chunk does the quadratic intra-chunk part on the MXU
(chunk x chunk matmul) and one state update — the same decomposition as
the paper's SSD algorithm, re-tiled for VMEM instead of CUDA shared
memory.

The per-chunk prefix sums and the row views of (chunk, 1) columns are
masked sublane / lane reductions rather than ``cumsum`` or transposes of
single columns, which Mosaic does not lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, o_ref, state_ref, *,
                chunk: int):
    # x (Q,P) dt (Q,1) dA (Q,1) b (Q,N) c (Q,N) out (Q,P); scratch (P,N)
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)                     # (Q,P)
    bm = b_ref[...].astype(jnp.float32)                    # (Q,N)
    cm = c_ref[...].astype(jnp.float32)                    # (Q,N)
    dt = dt_ref[...].astype(jnp.float32)                   # (Q,1)
    da = da_ref[...].astype(jnp.float32)                   # (Q,1) negative

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # row views: [j] = sum over rows k of the column, masked
    cum_row = jnp.sum(jnp.where(rows <= cols, da, 0.0), axis=0,
                      keepdims=True)                       # (1,Q) inclusive
    dt_row = jnp.sum(jnp.where(rows == cols, dt, 0.0), axis=0, keepdims=True)
    cum = jnp.sum(jnp.where(rows == cols, cum_row, 0.0), axis=1,
                  keepdims=True)                           # (Q,1)
    last = cum_row[:, chunk - 1:]                          # (1,1)

    # intra-chunk quadratic part
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    decay = jnp.exp(jnp.where(rows >= cols, cum - cum_row, -1e30))
    y = jnp.dot(cb * decay * dt_row, x, preferred_element_type=jnp.float32)
    # contribution of the carried state
    state = state_ref[...]                                 # (P,N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # (Q,N)@(N,P)
    # state update: state * exp(cum_last) + x^T (B * decay_to_end * dt)
    w = jnp.exp(last - cum) * dt                           # (Q,1)
    upd = jax.lax.dot_general(x, bm * w, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P,N)
    state_ref[...] = state * jnp.exp(last) + upd
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> jax.Array:
    """x (B,S,H,P); dt (B,S,H); A (H,); Bm/Cm (B,S,H,N) -> y (B,S,H,P)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    if S % chunk:
        raise ValueError(f"sequence length {S} is not a multiple of the "
                         f"SSD chunk {chunk}")

    def heads_major(a):                       # (B,S,H,·) -> (B,H,S,·)
        return a.transpose(0, 2, 1, 3)

    dt4 = dt.astype(jnp.float32)[..., None]   # (B,S,H,1)
    da4 = dt4 * A.astype(jnp.float32)[:, None]

    def spec(width):
        return pl.BlockSpec((None, None, chunk, width),
                            lambda b, h, c: (b, h, c, 0))

    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, S // chunk),
        in_specs=[spec(P), spec(1), spec(1), spec(N), spec(N)],
        out_specs=spec(P),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(heads_major(x), heads_major(dt4), heads_major(da4), heads_major(Bm),
      heads_major(Cm))
    return heads_major(y)
