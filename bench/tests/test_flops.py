"""Operation and byte counts against counts made by hand from the
published shapes."""
from __future__ import annotations

from bench.harness import registry


def _cfg(name):
    return registry.config(registry.load_spec(), name)


def test_mamba2_370m_counts():
    fl, c = registry.flops("ssm"), _cfg("mamba2-370m")
    # in_proj 1024 x (2*2048 + 2*128 + 32), out_proj 2048 x 1024, 48
    # layers, tied head 50280 x 1024
    assert fl.matmul_params(c) == 48 * (1024 * 4384 + 2048 * 1024) \
        + 50280 * 1024 == 367_632_384
    # C.B^T once (2*64*128), diag 2*64*32*64, states and read-out
    # 2*128*32*64 each
    assert fl.ssd_flops_per_token(c) == 16_384 + 262_144 + 2 * 524_288
    assert fl.train_flops_per_token(c, 2048) == 3 * (
        2 * 367_632_384 + 48 * 1_327_104) == 2_396_897_280


def test_qwen3_4b_counts():
    fl, c = registry.flops("dense"), _cfg("qwen3-4b")
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 \
        + 3 * 2560 * 9728
    assert fl.layer_matmul_params(c) == per_layer == 100_925_440
    # 3.63B outside the embedding, 4.02B with the tied embedding
    assert fl.matmul_params(c) == 36 * per_layer + 151_936 * 2560 \
        == 4_022_272_000
    # bf16 weights: every matrix and norm once (8,044,936,192 B is what
    # the program's parameters hold on the chip)
    assert fl.weight_bytes(c) == 8_044_936_192
    assert fl.kv_bytes_per_token(c) == 36 * 2 * 8 * 128 * 2 == 147_456
    flops, nbytes = fl.decode_step_cost(c, [100, 200])
    assert flops == 2 * 2 * 4_022_272_000 + 36 * 4 * 300 * 4096
    assert nbytes == 8_044_936_192 + 300 * 147_456
    assert fl.train_flops_per_token(c, 2048) == 3 * (
        2 * 4_022_272_000 + 36 * 4 * 2048 * 4096 / 2)
