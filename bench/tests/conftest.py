"""Shared pieces of the benchmark's CPU tests: tiny configurations of
each architecture and the cells' traffic cut to match, so that a whole
driver run (program, window, reference) fits a test."""
from __future__ import annotations

import importlib.util
import pathlib
import sys

import pytest

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(CHECKOUT))
sys.path.insert(0, str(CHECKOUT / "src"))

from bench.harness import registry  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def bench_run():
    """``bench/run.py`` as a module (it is a script, not a package)."""
    spec = importlib.util.spec_from_file_location(
        "bench_run", CHECKOUT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def measure(cell: str, config, traffic, driver, seed: int, seconds: float):
    """One run of ``driver`` through the harness on the CPU, the chip
    check skipped."""
    import jax
    spec = registry.load_spec()
    return bench_run().measure(
        spec, registry.cell(spec, cell), config, traffic, driver, seed=seed,
        seconds=seconds, traced=False, devices=jax.devices()[:1],
        peaks=CPU_PEAKS, t_start=0.0)


def tiny_ssm():
    c = registry.config(registry.load_spec(), "mamba2-370m")
    c.update(program_overrides={"n_layers": 2, "d_model": 64,
                                "ssm_state": 16, "ssm_chunk": 16,
                                "vocab_size": 256},
             hidden_size=64, num_hidden_layers=2, state_size=16, n_heads=2,
             vocab_size=256, chunk_size=16)
    t = registry.traffic("train-seq2k")
    t.update(seq_len=64, global_batch=4)
    return c, t


def tiny_dense():
    c = registry.config(registry.load_spec(), "qwen3-4b")
    c.update(program_overrides={"tie_embeddings": True, "n_layers": 2,
                                "d_model": 128, "n_heads": 4,
                                "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                                "vocab_size": 512},
             hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, intermediate_size=256,
             vocab_size=512)
    t = registry.traffic("serve-chat-steady")
    t.update(rate_per_s=6.0,
             prompt={"median": 40, "sigma": 0.8, "min": 4, "max": 100},
             output={"median": 8, "sigma": 0.7, "min": 2, "max": 24},
             engine={"page_size": 16, "n_pages": 64, "decode_slots": 4,
                     "max_context": 128, "prefill_batch": 2,
                     "prefill_chunk": 32},
             trace={"start_frac": 0.2, "seconds": 1.0})
    return c, t


@pytest.fixture
def spec():
    return registry.load_spec()
