"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute with ``interpret=True`` — the
kernel body runs step-by-step on CPU, validating BlockSpec indexing and
the numerical algorithm against ``ref.py``.  Everywhere else the same
call sites compile to Mosaic, so a backend Mosaic cannot target fails
loudly instead of silently interpreting.
"""
from __future__ import annotations

from typing import Optional

import jax

from . import flash_attention as _fa
from . import ring_attention as _ra
from . import rmsnorm as _rn
from . import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_q: int = _fa.BLOCK_Q, block_k: int = _fa.BLOCK_K):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def ring_flash_attention(q, k, v, *, axis_name: str = "seq", axis_size: int,
                         causal: bool = True, window: Optional[int] = None,
                         block_q: int = 128, block_k: int = 128):
    """Sequence-sharded flash attention (call inside shard_map)."""
    return _ra.ring_flash_attention(
        q, k, v, axis_name=axis_name, axis_size=axis_size, causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        interpret=_interpret())


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 64):
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                         interpret=_interpret())


def rmsnorm(x, w, *, eps: float = 1e-6, block_rows: int = 256):
    return _rn.rmsnorm(x, w, eps=eps, block_rows=block_rows,
                       interpret=_interpret())
