"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ref import flash_attention_ref, rmsnorm_ref, ssd_scan_ref

from conftest import run_subprocess

TOL = {jnp.float32: 3e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,H,KV,dh", [
    (1, 128, 2, 2, 32),     # MHA
    (2, 256, 4, 2, 64),     # GQA 2:1
    (1, 256, 8, 1, 64),     # MQA
    (2, 512, 4, 4, 128),    # MXU-aligned head dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
def test_flash_attention_sweep(B, S, H, KV, dh, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("blocks", [(32, 128), (128, 32), (64, 64)])
def test_flash_attention_block_shapes(blocks):
    bq, bk = blocks
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 32))
    k = jax.random.normal(ks[1], (1, 256, 2, 32))
    v = jax.random.normal(ks[2], (1, 256, 2, 32))
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,T,H,KV,causal,window", [
    (1, 128, 128, 2, 2, True, None),     # causal MHA
    (1, 128, 128, 4, 1, True, 24),       # sliding window, MQA
    (1, 128, 128, 4, 2, True, 80),       # window past a block: whole tiles
    (1, 128, 128, 8, 2, True, None),     # GQA G = 4
    (2, 100, 100, 4, 1, True, None),     # ragged: S/T pad to the blocks
    (2, 96, 80, 4, 2, False, None),      # ragged cross lengths, no mask
])
def test_flash_attention_grads(B, S, T, H, KV, causal, window, dtype):
    """The custom_vjp's dq, dk, dv against jax.grad of sdpa_ref, taken
    in float32 on the same (dtype-rounded) inputs."""
    from repro.models.attention import sdpa_ref
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, S, H, 32), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, 32), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, 32), dtype)
    do = jax.random.normal(ks[3], (B, S, H, 32), dtype)
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, window=window, block_q=32, block_k=32,
        interpret=True), q, k, v)
    _, ref_vjp = jax.vjp(lambda q, k, v: sdpa_ref(
        q, k, v, causal=causal, window=window),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}[dtype]
    for name, got, ref in zip("qkv", vjp(do), ref_vjp(do.astype(jnp.float32))):
        assert got.dtype == dtype, name
        ref = np.asarray(ref)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   atol=tol * np.abs(ref).max(), rtol=0,
                                   err_msg=f"d{name}")


def _qwen_like(n_heads=4, n_kv_heads=2, head_dim=128):
    from repro.configs import get_config
    return get_config("qwen3-4b").with_(
        n_layers=1, d_model=64, n_heads=n_heads, n_kv_heads=n_kv_heads,
        head_dim=head_dim, d_ff=128, vocab_size=64)


def test_attention_auto_resolves_from_backend_shape_and_mesh(monkeypatch):
    """Off TPU the kernel is never taken; on TPU it is, unless the scans
    are unrolled (the dry-run's cost probes), the head dim is off the
    128 lanes or the heads do not split over the model axis."""
    import types
    from repro.models import attention as A
    from repro.models.flags import batch_sharding, force_unroll
    cfg = _qwen_like()
    assert A.resolve_impl(2048, cfg) == "chunked"          # CPU
    assert A.resolve_impl(512, cfg) == "ref"
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert A.resolve_impl(2048, cfg) == "flash"
    assert A.resolve_impl(512, cfg) == "ref"
    with force_unroll():
        assert A.resolve_impl(2048, cfg) == "chunked"
    assert A.resolve_impl(2048, _qwen_like(head_dim=64)) == "chunked"
    mesh = types.SimpleNamespace(shape={"data": 1, "model": 4})
    with batch_sharding(("data",), mesh=mesh):
        assert A.resolve_impl(2048, cfg) == "chunked"       # 2 KV heads
        assert A.resolve_impl(2048, _qwen_like(8, 4)) == "flash"


def test_decode_attention_resolves_from_backend_shape_window_and_pools(
        monkeypatch):
    """The paged decode kernel on TPU at fitting shapes; the gather path
    on CPU, under ``force_unroll``, at a head dim off the 128 lanes, at a
    page of fewer than 8 rows, with a sliding window (the kernel does not
    mask one) and when the pools are sharded over a mesh."""
    from repro.models import attention as A
    from repro.models.flags import force_unroll
    cfg = _qwen_like(8, 4)
    assert A.resolve_decode_impl(cfg, 16) == "gather"          # CPU
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    assert A.resolve_decode_impl(cfg, 16) == "paged_kernel"
    assert A.resolve_decode_impl(_qwen_like(32, 8), 16) == "paged_kernel"
    assert A.resolve_decode_impl(_qwen_like(8, 1), 8) == "paged_kernel"
    with force_unroll():
        assert A.resolve_decode_impl(cfg, 16) == "gather"
    assert A.resolve_decode_impl(_qwen_like(8, 4, 64), 16) == "gather"
    assert A.resolve_decode_impl(_qwen_like(8, 1), 4) == "gather"
    assert A.resolve_decode_impl(cfg, 16, window=512) == "gather"
    assert A.resolve_decode_impl(cfg, 16, pools_sharded=True) == "gather"


def test_paged_decode_step_records_gather_on_cpu_with_a_window():
    """What a server reports of its decode step: the resolved decode
    attention, recorded when the step is traced."""
    from repro.models.attention import recording_attention
    from repro.models.transformer import (init_lm, init_paged_state,
                                          paged_decode_step)
    cfg = _qwen_like(8, 4).with_(n_layers=2)
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: init_paged_state(cfg, 8, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for window in (None, 64):
        with recording_attention() as seen:
            jax.eval_shape(lambda p, pl, t, r, n: paged_decode_step(
                p, pl, t, r, n, cfg, window=window),
                params, pools, i32(2), i32(2, 4), i32(2))
        assert seen == {"gather"}


def _paged_case(rng, dtype, *, B, P, psz, KV, dh, n_layers=2):
    """Random stacked pools with every row filled, and a page table whose
    lanes sit at length 0, psz - 1, psz, mid-page, the cap's last
    position and inactive (-1); each lane holds distinct pool rows for
    the positions up to its new token's, -1 past them."""
    lens = np.array([0, psz - 1, psz, psz + psz // 2 + 1, P * psz - 1, -1]
                    [:B], np.int32)
    pages = [-(-(int(L) + 1) // psz) if L >= 0 else 2 for L in lens]
    N = sum(pages) + 3
    perm = rng.permutation(N)
    rows = np.full((B, P), -1, np.int32)
    at = 0
    for b, n in enumerate(pages):
        rows[b, :n] = perm[at:at + n]
        at += n
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    pools = [normal(n_layers, N, psz, KV, dh) for _ in range(2)]
    return pools, jnp.asarray(rows), jnp.asarray(lens), normal


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("psz", [8, 16])
@pytest.mark.parametrize("G", [1, 4])
def test_paged_attention_sweep(G, psz, dtype):
    """The paged decode kernel (interpret mode, two pages a block so a
    lane spans several blocks) against its oracle, and the in-place
    decode attention built on it against the gather path: the same
    output for every active lane, and the same pool once its new tokens
    are written."""
    from repro.kernels.paged_attention import paged_attention
    from repro.kernels.ref import paged_attention_ref
    from repro.models.attention import (attention_decode_paged,
                                        attention_decode_paged_in_place,
                                        init_attention, write_decode_tokens)
    B, P, KV, dh = 6, 4, 2, 128
    rng = np.random.default_rng(G * 100 + psz)
    (kp, vp), rows, lens, normal = _paged_case(
        rng, dtype, B=B, P=P, psz=psz, KV=KV, dh=dh)
    q, kn, vn = normal(B, KV, G, dh), normal(B, KV, dh), normal(B, KV, dh)
    for layer in (0, 1):
        out = paged_attention(q, kp, vp, kn, vn, jnp.int32(layer), rows,
                              lens, pages_per_block=2, interpret=True)
        ref = paged_attention_ref(q, kp, vp, kn, vn, layer, rows, lens)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])
    # an inactive lane reads nothing: its output is its own value row
    np.testing.assert_array_equal(
        np.asarray(out[-1]), np.asarray(jnp.repeat(vn[-1][:, None], G, 1)))

    cfg = _qwen_like(KV * G, KV).with_(dtype=dtype)
    p = init_attention(jax.random.PRNGKey(G), cfg)
    x = normal(B, 1, cfg.d_model)
    pools = {"k": kp, "v": vp}
    got, (k_new, v_new) = attention_decode_paged_in_place(
        p, x, pools, jnp.int32(1), rows, lens, cfg)
    want, pool1 = attention_decode_paged(
        p, x, {"k": kp[1], "v": vp[1]}, rows, lens, cfg)
    live = np.asarray(lens) >= 0
    scale = float(np.abs(np.asarray(want, np.float32)).max())
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=TOL[dtype] * scale, rtol=TOL[dtype])
    zeros = jnp.zeros_like(k_new)
    written = write_decode_tokens(pools, jnp.stack([zeros, k_new]),
                                  jnp.stack([zeros, v_new]), rows, lens)
    for name in "kv":
        np.testing.assert_array_equal(np.asarray(written[name][1]),
                                      np.asarray(pool1[name]))


@pytest.mark.parametrize("S,impl,recorded", [
    (1024, "auto", {"chunked"}), (16, "auto", {"ref"}),
    (16, "flash", {"flash"})])
def test_attention_records_the_kernel_it_resolves_to(S, impl, recorded):
    from repro.models.attention import (attention, init_attention,
                                        recording_attention)
    cfg = _qwen_like()
    p = init_attention(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((1, S, cfg.d_model), cfg.dtype)
    pos = jax.ShapeDtypeStruct((1, S), jnp.int32)
    with recording_attention() as seen:
        jax.eval_shape(lambda x, pos: attention(p, x, pos, cfg, impl=impl),
                       x, pos)
    assert seen == recorded


@pytest.mark.parametrize("arch,recorded", [
    ("mamba2-370m", set()), ("qwen3-4b", {"chunked"})])
def test_a_models_loss_records_its_layers_attention(arch, recorded):
    """What the trainer's set-up line reports of a model's layers: the
    SSM records no attention (``attn=none``), the decoder the kernel its
    layers resolve to at S 1024 on CPU."""
    from repro.configs import get_config
    from repro.models.attention import recording_attention
    from repro.models.transformer import init_lm, lm_loss
    cfg = get_config(arch).with_(n_layers=2, d_model=64, vocab_size=64)
    if arch == "qwen3-4b":
        cfg = cfg.with_(n_heads=4, n_kv_heads=2, head_dim=32, d_ff=128)
    params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
    with recording_attention() as seen:
        jax.eval_shape(lambda p, t: lm_loss(p, {"tokens": t, "labels": t},
                                            cfg), params, tokens)
    assert seen == recorded


def test_flash_on_a_2x2_mesh_matches_one_device():
    """attention(impl="flash") under a (data 2, model 2) mesh runs the
    kernel in shard_map (batch over data, heads over model); its output
    and gradients match the unsharded kernel."""
    out = run_subprocess("""
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_local_mesh
from repro.models.attention import attention, init_attention
from repro.models.flags import batch_sharding
cfg = get_config("qwen3-4b").with_(n_layers=1, d_model=64, n_heads=4,
                                   n_kv_heads=2, head_dim=32, d_ff=128,
                                   vocab_size=64, dtype=jnp.float32)
p = init_attention(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model))
pos = jnp.broadcast_to(jnp.arange(64), (4, 64))
def loss(p, x):
    return jnp.sum(attention(p, x, pos, cfg, impl="flash") ** 2)
one = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(p, x)
mesh = make_local_mesh(model=2)
def sharded(p, x):
    with batch_sharding(("data",), mesh=mesh):
        return jax.value_and_grad(loss, argnums=(0, 1))(p, x)
with mesh:
    four = jax.jit(sharded)(p, x)
    text = str(jax.make_jaxpr(sharded)(p, x))
gaps = [float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
              / np.max(np.abs(np.asarray(b))))
        for a, b in zip(jax.tree.leaves(four), jax.tree.leaves(one))]
print(json.dumps({"mesh": dict(mesh.shape), "gaps": gaps,
                  "shard_map": "shard_map" in text}))
""", devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["mesh"] == {"data": 2, "model": 2}
    assert res["shard_map"]
    assert max(res["gaps"]) < 1e-5, res["gaps"]
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 64, 1, 4, 8, 16),
    (2, 128, 2, 8, 16, 32),
    (1, 256, 4, 64, 128, 64),   # production-like dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, H, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, H, N), dtype)
    out = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    ref = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol * 40, rtol=tol)


@pytest.mark.parametrize("shape", [(4, 128), (2, 37, 256), (1, 8, 8, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, shape, dtype)
    w = jax.random.normal(k2, shape[-1:], dtype)
    out = rmsnorm(x, w, interpret=True)
    ref = rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_ssd_kernel_in_model_block():
    """ssm_block(use_kernel=True) must match the jnp path."""
    from repro.configs import get_config
    from repro.models.ssm import init_ssm, ssm_block
    cfg = get_config("mamba2-370m").reduced().with_(ssm_chunk=16)
    p = init_ssm(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model),
                          jnp.float32)
    y0 = ssm_block(p, x, cfg, use_kernel=False)
    y1 = ssm_block(p, x, cfg, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-4,
                               rtol=2e-4)
