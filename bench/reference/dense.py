"""Plain float32 dense GQA decoder (Qwen3: RMSNorm, QK-norm, RoPE, SwiGLU,
tied head), written from the published architecture and independent of
the program, in ``jax.numpy`` at the highest matmul precision.

The seed's weights are drawn as the configuration's init recipe states
them (normal, 1/sqrt(fan-in), bf16 storage, embedding std 0.02) with the
same keys, so that the reference starts from the weights the program
serves; it reads none of them from the program.  Layers are made and
applied one at a time, so the float32 model never has to fit at once.

``precision="fp8"`` is the control: the reference computed one step
below the configuration's bf16, every tensor the program holds in bf16
(matmul and attention operands, the residual stream) rounded to
float8_e4m3 with a per-tensor scale.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    """Round to bf16, kept in float32 (an explicit op: XLA may drop a
    convert to bf16 and back as excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _dense(key, d_in: int, d_out: int):
    s = 1.0 / jnp.sqrt(d_in)
    return _bf16(jax.random.normal(key, (d_in, d_out), jnp.float32) * s)


def _layer(key, c: Dict):
    d, dh = c["hidden_size"], c["head_dim"]
    q, kv, ff = (c["num_attention_heads"] * dh,
                 c["num_key_value_heads"] * dh, c["intermediate_size"])
    k1, k2 = jax.random.split(key)
    ka = jax.random.split(k1, 4)
    km = jax.random.split(k2, 3)
    return {"wq": _dense(ka[0], d, q), "wk": _dense(ka[1], d, kv),
            "wv": _dense(ka[2], d, kv), "wo": _dense(ka[3], q, d),
            "w_gate": _dense(km[0], d, ff), "w_up": _dense(km[1], d, ff),
            "w_down": _dense(km[2], ff, d)}


def embedding(c: Dict, key):
    return _bf16(jax.random.normal(jax.random.split(key, 8)[0],
                                   (c["vocab_size"], c["hidden_size"]),
                                   jnp.float32) * 0.02)


def layer_weights(c: Dict, key, i):
    keys = jax.random.split(jax.random.split(key, 8)[1],
                            c["num_hidden_layers"])
    one = jax.lax.dynamic_slice_in_dim(keys, i, 1)
    return jax.tree.map(lambda x: x[0], jax.vmap(lambda k: _layer(k, c))(one))


def fake_fp8(x):
    """Round to float8_e4m3 with a per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def q8(x, precision: str):
    return fake_fp8(x) if precision == "fp8" else x


def matmul(a, w, precision: str):
    return jnp.matmul(q8(a, precision), q8(w, precision), precision=HIGHEST)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(w, x, c: Dict, precision: str):
    """One decoder layer over one sequence x (T, d), causal."""
    T = x.shape[0]
    H, KV, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, pos = c["rms_norm_eps"], jnp.arange(T)
    h = rms(x, eps)
    q = rms(matmul(h, w["wq"], precision).reshape(T, H, dh), eps)
    k = rms(matmul(h, w["wk"], precision).reshape(T, KV, dh), eps)
    v = matmul(h, w["wv"], precision).reshape(T, KV, dh)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    q, k, v = (q8(a, precision) for a in (q, k, v))
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(dh))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", q8(jax.nn.softmax(s, -1), precision), v,
                   precision=HIGHEST).reshape(T, H * dh)
    x = q8(x + matmul(o, w["wo"], precision), precision)
    h = rms(x, eps)
    ff = jax.nn.silu(matmul(h, w["w_gate"], precision)) * matmul(
        h, w["w_up"], precision)
    return q8(x + matmul(ff, w["w_down"], precision), precision)


def logits_at(c: Dict, seed: int, tokens: np.ndarray, where: np.ndarray,
              precision: str = "f32") -> jax.Array:
    """Logits (M, V) of the sequences ``tokens`` (n, T) at the positions
    ``where`` (M, 2) = (sequence, position)."""
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda k: embedding(c, k))(key)

        @jax.jit
        def step(key, i, x):
            w = layer_weights(c, key, i)
            return jax.lax.map(lambda s: layer(w, s, c, precision), x)

        x = emb[jnp.asarray(tokens)]
        for i in range(c["num_hidden_layers"]):
            x = step(key, i, x)
        @jax.jit
        def head(x, emb, w):
            h = rms(x[w[:, 0], w[:, 1]], c["rms_norm_eps"])
            return matmul(h, emb.T, precision)

        return head(x, emb, jnp.asarray(where))
