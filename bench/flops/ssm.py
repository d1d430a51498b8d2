"""Operation counts of a Mamba2 (SSD) language model, from its shapes.

Counted: every matrix multiplication with a weight (in_proj, out_proj and
the tied head) at 2 FLOPs per multiply-add, forward and backward (x3),
and the chunked SSD terms as the algorithm computes them with one group
(``ngroups`` = 1, so C.B^T is formed once per chunk, not per head).  The
depthwise convolution, norms, gates and the loss are left out, and no
recomputation is counted, so the count is a floor of the work done.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(c: Dict) -> int:
    d, di, n, h = (c["hidden_size"], c["expand"] * c["hidden_size"],
                   c["state_size"], c["n_heads"])
    in_proj = d * (2 * di + 2 * n + h)
    out_proj = di * d
    head = c["vocab_size"] * d
    return c["num_hidden_layers"] * (in_proj + out_proj) + head


def ssd_flops_per_token(c: Dict) -> int:
    """Forward FLOPs per token of one layer's chunked SSD scan."""
    q, n, h, p = c["chunk_size"], c["state_size"], c["n_heads"], c["head_dim"]
    cb = 2 * q * n                 # C.B^T over the chunk, once (one group)
    diag = 2 * q * h * p           # (decay-masked CB) @ x, every head
    states = 2 * n * h * p         # chunk state: B^T x
    off = 2 * n * h * p            # carried state read out: C h
    return cb + diag + states + off


def train_flops_per_token(c: Dict, seq: int) -> float:
    del seq                        # SSD work per token does not grow with S
    return 3.0 * (2 * matmul_params(c)
                  + c["num_hidden_layers"] * ssd_flops_per_token(c))
