"""Numerical invariants across the model zoo: SSD chunked==sequential,
MoE dispatch equivalence, decode==prefill consistency, attention paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models.attention import sdpa_chunked, sdpa_ref
from repro.models.common import ModelConfig
from repro.models.moe import init_moe, moe_ffn
from repro.models.ssm import ssd_chunked, ssd_step


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@given(st.integers(min_value=1, max_value=3),
       st.sampled_from([8, 16, 32]),
       st.integers(min_value=1, max_value=3))
@settings(max_examples=10, deadline=None)
def test_ssd_chunked_equals_sequential(B, chunk, H):
    S, P, N = 64, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(B * 100 + chunk + H), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, H, N))
    Cm = jax.random.normal(ks[4], (B, S, H, N))
    y = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    s = jnp.zeros((B, H, P, N))
    outs = []
    for t in range(S):
        s, yt = ssd_step(s, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        outs.append(yt)
    y_seq = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-4)


def test_ssd_chunk_size_invariance():
    B, S, H, P, N = 1, 64, 2, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, H, N))
    Cm = jax.random.normal(ks[4], (B, S, H, N))
    y16 = ssd_chunked(x, dt, A, Bm, Cm, 16)
    y64 = ssd_chunked(x, dt, A, Bm, Cm, 64)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _moe_cfg(E=4, k=2, cf=8.0):
    return ModelConfig(name="t", arch_type="moe", n_layers=1, d_model=16,
                       n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                       n_experts=E, top_k=k, capacity_factor=cf,
                       dtype=jnp.float32)


def test_moe_sort_equals_einsum_dispatch():
    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 16), jnp.float32)
    o1, a1 = moe_ffn(p, x, cfg, dispatch="sort")
    o2, a2 = moe_ffn(p, x, cfg, dispatch="einsum")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4,
                               rtol=1e-4)
    assert abs(float(a1) - float(a2)) < 1e-5


def test_moe_matches_dense_oracle_when_no_drops():
    """With capacity >= all tokens, routed MoE equals the dense weighted
    combination of expert outputs."""
    cfg = _moe_cfg(cf=100.0)
    p = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, 16), jnp.float32)
    out, _ = moe_ffn(p, x, cfg, dispatch="sort")

    # dense oracle: every expert on every token, weighted by router top-k
    from repro.models.layers import swiglu
    xf = x.reshape(-1, 16)
    logits = xf @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / topv.sum(-1, keepdims=True)
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xf, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", swiglu(g, u), p["w_down"])
    w = jnp.zeros((xf.shape[0], cfg.n_experts)).at[
        jnp.arange(xf.shape[0])[:, None], topi].set(topv)
    ref = jnp.einsum("te,ted->td", w, y_all).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-4)


def test_moe_capacity_drops_tokens():
    cfg = _moe_cfg(cf=0.1)   # tiny capacity forces drops, must not crash
    p = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 16), jnp.float32)
    out, aux = moe_ffn(p, x, cfg)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))


def test_moe_grad_flows_through_router():
    cfg = _moe_cfg()
    p = init_moe(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 16), jnp.float32)

    def loss(pp):
        out, aux = moe_ffn(pp, x, cfg)
        return (out ** 2).mean() + 0.01 * aux

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]).sum()) > 0.0
    assert float(jnp.abs(g["w_gate"]).sum()) > 0.0


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@given(st.sampled_from([64, 128, 256]), st.booleans(),
       st.sampled_from([None, 32]))
@settings(max_examples=8, deadline=None)
def test_chunked_attention_equals_ref(S, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(S), 3)
    q = jax.random.normal(ks[0], (2, S, 4, 16))
    k = jax.random.normal(ks[1], (2, S, 2, 16))
    v = jax.random.normal(ks[2], (2, S, 2, 16))
    o = sdpa_chunked(q, k, v, causal=causal, window=window, block_q=32)
    r = sdpa_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# decode == prefill (cache correctness, incl. ring semantics)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen3-4b", "mamba2-370m", "zamba2-1.2b"])
def test_decode_matches_prefill(arch):
    from repro.models import (decode_step, init_decode_state, init_lm,
                              lm_forward)
    cfg = get_config(arch).reduced().with_(dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    params = init_lm(key, cfg)
    T = 12
    toks = jax.random.randint(key, (2, T), 0, cfg.vocab_size)
    full_logits, _ = lm_forward(params, toks, cfg)

    state = init_decode_state(cfg, 2, context=32)
    for t in range(T):
        logits, state = decode_step(params, state, toks[:, t], cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]),
            atol=2e-3, rtol=2e-3)


def test_ring_cache_sliding_window_decode():
    """With a window-sized ring cache, decode must equal full prefill with
    the same sliding window — even past the wrap-around point."""
    from repro.models import decode_step, init_decode_state, init_lm, lm_forward
    W = 8
    cfg = (get_config("qwen3-4b").reduced()
           .with_(dtype=jnp.float32, sliding_window=W))
    key = jax.random.PRNGKey(1)
    params = init_lm(key, cfg)
    T = 20                     # > window: cache wraps
    toks = jax.random.randint(key, (1, T), 0, cfg.vocab_size)
    full_logits, _ = lm_forward(params, toks, cfg, window=W)
    state = init_decode_state(cfg, 1, context=W)   # ring of window size
    for t in range(T):
        logits, state = decode_step(params, state, toks[:, t], cfg, window=W)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, t]),
            atol=2e-3, rtol=2e-3, err_msg=f"t={t}")


def test_whisper_decode_matches_teacher_forcing():
    from repro.models import (encdec_decode_step, init_encdec,
                              init_encdec_decode_state)
    from repro.models.encdec import decode_train, encode
    cfg = get_config("whisper-medium").reduced().with_(dtype=jnp.float32)
    key = jax.random.PRNGKey(2)
    params = init_encdec(key, cfg, max_dec_len=64)
    frames = jax.random.normal(key, (2, cfg.encoder_seq, cfg.d_model))
    T = 6
    toks = jax.random.randint(key, (2, T), 0, cfg.vocab_size)
    enc = encode(params, frames, cfg)
    full = decode_train(params, toks, enc, cfg)
    state = init_encdec_decode_state(params, frames, cfg, context=16)
    for t in range(T):
        logits, state = encdec_decode_step(params, state, toks[:, t], cfg)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, t]),
                                   atol=2e-3, rtol=2e-3)
