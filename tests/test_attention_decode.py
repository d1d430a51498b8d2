"""Differential coverage for the decode-time attention paths.

One harness, three cache layouts, one oracle: ``sdpa_ref`` over the full
token history.  ``attention_decode`` (linear cache, ring cache) and
``attention_decode_paged`` / ``attention_prefill_paged`` must reproduce the
oracle's output token-for-token — the serving engine's paged/dense
differential guarantee (tests/test_serving.py) bottoms out in these
per-layer identities.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (attention_decode, attention_decode_paged,
                                    attention_prefill_paged, init_attention,
                                    init_kv_cache, init_page_pool,
                                    _project_qkv, sdpa_ref)
from repro.models.common import ModelConfig

CFG = ModelConfig(name="t", arch_type="dense", n_layers=1, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64)


def _setup(B, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    p = init_attention(ks[0], CFG)
    xs = jax.random.normal(ks[1], (B, T, CFG.d_model), jnp.float32)
    return p, xs


def _oracle(p, xs, t, *, window=None):
    """Full-history reference output for step t: attend from token t over
    tokens [0, t]."""
    B = xs.shape[0]
    pos = jnp.broadcast_to(jnp.arange(t + 1), (B, t + 1))
    q, k, v = _project_qkv(p, xs[:, :t + 1], CFG, pos)
    out = sdpa_ref(q[:, t:t + 1], k, v, causal=True, window=window,
                   q_offset=t)
    return out.reshape(B, 1, CFG.q_dim) @ p["wo"]


@pytest.mark.parametrize("B,T", [(1, 8), (3, 8)])
def test_linear_cache_decode_matches_full_attention(B, T):
    """attention_decode with a linear cache (C >= T, no wraparound) must
    equal full-context reference attention at every step."""
    p, xs = _setup(B, T)
    cache = init_kv_cache(CFG, B, context=T, dtype=jnp.float32)
    for t in range(T):
        out, cache = attention_decode(p, xs[:, t:t + 1], cache,
                                      jnp.int32(t), CFG)
        ref = _oracle(p, xs, t)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_ring_cache_decode_matches_windowed_attention():
    """With a ring cache of span W and window=W the decode output must
    equal windowed reference attention even after wraparound."""
    B, T, W = 2, 14, 8
    p, xs = _setup(B, T, seed=1)
    cache = init_kv_cache(CFG, B, context=W, dtype=jnp.float32)
    for t in range(T):
        out, cache = attention_decode(p, xs[:, t:t + 1], cache,
                                      jnp.int32(t), CFG, window=W)
        ref = _oracle(p, xs, t, window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_per_lane_cache_index_recycled_slot():
    """Per-lane cache_index: lane 0 restarts a fresh request at position 0
    while lane 1 continues — the recycled lane must see *only* its new
    tokens (no KV leakage from the stale ring content)."""
    B, T = 2, 6
    p, xs = _setup(B, T, seed=2)
    C = 8
    cache = init_kv_cache(CFG, B, context=C, dtype=jnp.float32)
    # warm both lanes with T tokens
    for t in range(T):
        _, cache = attention_decode(p, xs[:, t:t + 1], cache,
                                    jnp.int32(t), CFG)
    # lane 0 recycles: fresh stream ys at positions 0..; lane 1 continues
    ys = jax.random.normal(jax.random.PRNGKey(9), (1, 4, CFG.d_model))
    idx = jnp.array([0, T], jnp.int32)
    for t in range(4):
        x_t = jnp.concatenate([ys[:, t:t + 1], xs[1:2, T % T:T % T + 1]], 0)
        out, cache = attention_decode(p, x_t, cache, idx, CFG)
        # oracle for the recycled lane: attention over ys[:, :t+1] only
        ref0 = _oracle(p, ys, t)
        np.testing.assert_allclose(np.asarray(out[0:1]), np.asarray(ref0),
                                   atol=1e-5, rtol=1e-5)
        idx = idx + 1


@pytest.mark.parametrize("psz", [2, 4])
def test_paged_decode_matches_linear_decode(psz):
    """Same harness, paged layout: attention_decode_paged over scattered
    pool pages must match attention_decode on a linear cache bit-for-bit
    (identical fp32 einsum/softmax over an identical gathered view)."""
    B, T = 2, 8
    P = -(-T // psz)
    p, xs = _setup(B, T, seed=3)
    cache = init_kv_cache(CFG, B, context=P * psz, dtype=jnp.float32)
    pool = init_page_pool(CFG, n_pages=B * P + 3, page_size=psz,
                          dtype=jnp.float32)
    # deliberately non-contiguous, interleaved page assignment
    rows = np.full((B, P), -1, np.int32)
    perm = np.random.default_rng(0).permutation(B * P)
    for i, r in enumerate(perm):
        rows[i % B, i // B] = int(r)
    rows_j = jnp.asarray(rows)
    lengths = jnp.zeros((B,), jnp.int32)
    for t in range(T):
        dense_out, cache = attention_decode(p, xs[:, t:t + 1], cache,
                                            jnp.int32(t), CFG)
        paged_out, pool = attention_decode_paged(p, xs[:, t:t + 1], pool,
                                                 rows_j, lengths, CFG)
        np.testing.assert_allclose(np.asarray(paged_out),
                                   np.asarray(dense_out),
                                   atol=1e-6, rtol=1e-6)
        lengths = lengths + 1


def test_paged_decode_inactive_lane_write_dropped():
    """lengths = -1 marks an inactive lane: its write must be dropped (the
    pool unchanged) and active lanes unaffected."""
    B, psz, P = 2, 4, 2
    p, xs = _setup(B, 4, seed=4)
    pool = init_page_pool(CFG, n_pages=B * P, page_size=psz,
                          dtype=jnp.float32)
    rows = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.array([0, -1], jnp.int32)
    _, pool2 = attention_decode_paged(p, xs[:, 0:1], pool, rows, lengths, CFG)
    # lane 1's pages (rows 2, 3) untouched
    np.testing.assert_array_equal(np.asarray(pool2["k"][2:]),
                                  np.asarray(pool["k"][2:]))
    # lane 0's first page slot 0 written
    assert not np.allclose(np.asarray(pool2["k"][0, 0]), 0.0)


def test_paged_prefill_then_decode_matches_dense():
    """Chunked paged prefill (write-then-attend) + paged decode must
    reproduce the dense one-token-at-a-time decode trajectory, including
    ragged prompt lengths and a traced chunk base."""
    B, psz, P, S = 2, 4, 4, 4           # context 16, chunk 4
    T_prompt = jnp.array([6, 3], jnp.int32)          # ragged prompts
    p, xs = _setup(B, 10, seed=5)
    pool = init_page_pool(CFG, n_pages=B * P, page_size=psz,
                          dtype=jnp.float32)
    rows = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)

    prefill = jax.jit(lambda pool, x, base: attention_prefill_paged(
        p, x, pool, rows, base, T_prompt, CFG))
    outs = []
    for base in range(0, 8, S):                      # 2 chunks, one compile
        o, pool = prefill(pool, xs[:, base:base + S], jnp.int32(base))
        outs.append(o)
    # after prefill, decode one more token per lane at its own length
    lengths = T_prompt
    nxt = jax.random.normal(jax.random.PRNGKey(11), (B, 1, CFG.d_model))
    paged_out, pool = attention_decode_paged(p, nxt, pool, rows, lengths, CFG)

    # dense oracle, per lane: feed its prompt then the same next token
    for lane in range(B):
        L = int(T_prompt[lane])
        seq = jnp.concatenate([xs[lane:lane + 1, :L], nxt[lane:lane + 1]], 1)
        ref = _oracle(p, seq, L)
        np.testing.assert_allclose(np.asarray(paged_out[lane:lane + 1]),
                                   np.asarray(ref), atol=1e-5, rtol=1e-5)
        # the prefill chunk outputs match the oracle at prompt positions
        chunk = jnp.concatenate(outs, 1)             # (B, 8, d)
        for t in range(L):
            ref_t = _oracle(p, xs[lane:lane + 1], t)
            np.testing.assert_allclose(
                np.asarray(chunk[lane:lane + 1, t:t + 1]),
                np.asarray(ref_t), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arch,zero_wo", [("qwen3-4b", True),
                                          ("qwen3-4b", False),
                                          ("arctic-480b", True)])
def test_paged_kernel_decode_step_matches_gather_step(arch, zero_wo,
                                                      monkeypatch):
    """The whole decode step on the kernel path (pools read in place by
    the paged kernel in interpret mode, new tokens written after the layer
    scan) against the gather path, over three rounds that read back what
    the earlier rounds wrote, on lanes at ragged lengths with one
    inactive.  With the attention output projection zeroed every layer
    sees the same input on both paths, so the returned pools must be
    equal bit for bit; with it, the logits of the active lanes agree
    within tolerance.  The MoE case has two layer stacks (one dense
    layer, then two MoE layers), each with its own pools."""
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config(arch).with_(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, head_dim=128,
        d_ff=128, vocab_size=64, dtype=jnp.float32)
    if cfg.n_experts > 1:
        cfg = cfg.with_(n_experts=4, first_k_dense=1)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    if zero_wo:
        for stack in params["stacks"]:
            stack["attn"]["wo"] = jnp.zeros_like(stack["attn"]["wo"])
    B, psz, P, N = 4, 8, 4, 20
    rng = np.random.default_rng(1)
    pools = {"stacks": [{
        name: jnp.asarray(rng.standard_normal((n, N, psz, 2, 128)),
                          jnp.float32) for name in "kv"}
        for _, n in T.build_stacks(cfg)]}
    rows = jnp.asarray(rng.permutation(N)[:B * P].reshape(B, P), jnp.int32)
    lengths = jnp.array([0, 7, 13, -1], jnp.int32)
    token = jnp.array([3, 5, 7, 9], jnp.int32)
    step = {}
    for impl in ("paged_kernel", "gather"):
        monkeypatch.setattr(T, "resolve_decode_impl",
                            lambda *a, impl=impl, **k: impl)
        step[impl] = jax.jit(lambda pl, t, n: T.paged_decode_step(
            params, pl, t, rows, n, cfg)).lower(
                pools, token, lengths).compile()
    got, want = pools, pools
    live = np.asarray(lengths) >= 0
    for _ in range(3):
        lg, got = step["paged_kernel"](got, token, lengths)
        lw, want = step["gather"](want, token, lengths)
        if zero_wo:
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(lg)[live],
                                       np.asarray(lw)[live],
                                       atol=1e-4, rtol=1e-4)
        token = jnp.argmax(lw, axis=-1).astype(jnp.int32)
        lengths = jnp.where(lengths >= 0, lengths + 1, lengths)
