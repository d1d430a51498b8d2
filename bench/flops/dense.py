"""Operation and byte counts of a dense GQA decoder, from its shapes.

Training: every weight matrix multiplication (q, k, v, o, the SwiGLU
projections and the head) at 2 FLOPs per multiply-add, forward and
backward (x3), plus causal attention counted at half of the S x S score
and value products.  Norms, RoPE, softmax and the loss are left out, and
no recomputation is counted.

Decode: one step of every active lane reads every weight once (bf16) and
the live keys and values of each lane (not the pages reserved for it).
"""
from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


def layer_matmul_params(c: Dict) -> int:
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * dh, c["num_key_value_heads"] * dh
    return d * q + 2 * d * kv + q * d + 3 * d * c["intermediate_size"]


def matmul_params(c: Dict) -> int:
    """Weights that are multiplied, the head (tied or not) included."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["vocab_size"] * c["hidden_size"])


def weight_bytes(c: Dict) -> int:
    """Bytes a decode step reads of weights: every matrix once, the
    embedding once when tied (it is the head), twice otherwise only for
    the rows gathered, which are few and not counted."""
    norms = c["num_hidden_layers"] * (2 * c["hidden_size"]
                                      + 2 * c["head_dim"]) + c["hidden_size"]
    return BF16 * (matmul_params(c) + norms)


def attn_flops_per_token(c: Dict, context: int) -> int:
    """Forward score and value products of one token over ``context``
    keys, all layers."""
    q = c["num_attention_heads"] * c["head_dim"]
    return c["num_hidden_layers"] * 4 * context * q


def train_flops_per_token(c: Dict, seq: int) -> float:
    causal_half = attn_flops_per_token(c, seq) / 2
    return 3.0 * (2 * matmul_params(c) + causal_half)


def kv_bytes_per_token(c: Dict) -> int:
    return (c["num_hidden_layers"] * 2 * c["num_key_value_heads"]
            * c["head_dim"] * BF16)


def decode_step_cost(c: Dict, contexts: Sequence[int]):
    """(FLOPs, bytes) of one decode step whose active lanes attend over
    ``contexts`` keys each (the new token included)."""
    flops = sum(2 * matmul_params(c) + attn_flops_per_token(c, t)
                for t in contexts)
    nbytes = weight_bytes(c) + sum(contexts) * kv_bytes_per_token(c)
    return float(flops), float(nbytes)
