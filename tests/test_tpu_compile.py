"""v5e compile rehearsals: the Pallas kernels at real widths, compiled for a
described (not attached) TPU v5e by the installed TPU compiler.

Nothing runs, so these say nothing about results or speed; they catch
what interpret mode cannot — block shapes off the (8, 128) tiling and
kernels that need more VMEM than a TPU core has.  Each case asserts the
kernel survived as a Mosaic ``tpu_custom_call`` in the compiled HLO.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and the test workers all import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.ring_attention import ring_flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip, so
    # keep it out of any persistent cache while this module runs
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_qwen3_4b_width(one_chip):
    x = _sds((8, 2048, 2560), jnp.bfloat16, one_chip)
    w = _sds((2560,), jnp.bfloat16, one_chip)
    _assert_kernel(jax.jit(lambda x, w: rmsnorm(x, w)).lower(x, w).compile())


@pytest.mark.parametrize("seq", [4096, 32768])
def test_flash_attention_qwen3_4b_heads(one_chip, seq):
    q = _sds((1, seq, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, seq, 8, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True)
                       ).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


def test_ring_attention_4_chips_32k_per_shard(topo):
    mesh = jax.sharding.Mesh(topo.devices, ("seq",))
    shard = NamedSharding(mesh, P(None, "seq"))
    n = len(topo.devices)
    q = _sds((1, n * 32768, 32, 128), jnp.bfloat16, shard)
    kv = _sds((1, n * 32768, 8, 128), jnp.bfloat16, shard)
    fn = jax.shard_map(
        lambda q, k, v: ring_flash_attention(q, k, v, axis_name="seq",
                                             axis_size=n, causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False)
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    _assert_kernel(compiled)
    assert "collective-permute" in compiled.as_text()


def test_ssd_scan_mamba2_370m_widths(one_chip):
    B, S, H, Pd, N = 1, 2048, 32, 64, 128
    x = _sds((B, S, H, Pd), jnp.bfloat16, one_chip)
    dt = _sds((B, S, H), jnp.float32, one_chip)
    A = _sds((H,), jnp.float32, one_chip)
    bc = _sds((B, S, H, N), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda *a: ssd_scan(*a, chunk=64)
                       ).lower(x, dt, A, bc, bc).compile()
    _assert_kernel(compiled)
