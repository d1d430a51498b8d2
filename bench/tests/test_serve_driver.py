"""The serving driver at a tiny size on the CPU: the schedule is the same
work for every seed, a sound run is correct, and a served token altered
where the engine produces it makes ``correct`` false."""
from __future__ import annotations

import jax
import numpy as np

from bench.harness import gen, registry
from conftest import measure, tiny_dense


def test_schedule_is_the_same_work_for_every_seed():
    """Every seed sends the same lengths at the same times; the seed
    draws the tokens."""
    t = registry.traffic("serve-chat-steady")
    a = gen.request_schedule(t, 30.0, 1, 1000)
    b = gen.request_schedule(t, 30.0, 2**31 + 9, 1000)
    assert len(a) == len(b) == round(t["rate_per_s"] * 30.0)
    assert [(d, len(p), o) for d, p, o in a] == [(d, len(p), o)
                                                 for d, p, o in b]
    assert a[0][0] == 0.0 and max(d for d, *_ in a) < 30.0
    assert [p for _, p, _ in a] != [p for _, p, _ in b]
    lens = np.array([len(p) for _, p, _ in a])
    assert lens.min() >= t["prompt"]["min"] and lens.max() <= t["prompt"]["max"]
    assert abs(np.median(lens) - t["prompt"]["median"]) <= 16


def _measure(driver):
    c, t = tiny_dense()
    return measure("qwen3-4b.serve-chat-steady", c, t, driver, seed=3,
                   seconds=2.0)


def test_sound_run_is_correct():
    line = _measure(registry.driver("serve"))
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 12
    m = line["metrics"]
    assert m["ttft_mean_ms"]["value"] > 0 and m["tpot_p90_ms"]["value"] > 0


def test_altered_token_is_not_correct():
    """Every third decode round, every lane's next token is replaced by
    another one where the engine produces it."""
    drv = registry.driver("serve")
    build = drv.build

    def broken_build(*a, **k):
        cfg, engine = build(*a, **k)
        decode, calls = engine._decode, [0]

        def altered(*args):
            logits, pools = decode(*args)
            calls[0] += 1
            if calls[0] % 3 == 0:
                top = jax.numpy.argmax(logits, axis=-1)
                rows = jax.numpy.arange(logits.shape[0])
                logits = logits.at[rows, (top + 1) % logits.shape[-1]].set(
                    1e9)
            return logits, pools

        engine._decode = altered
        return cfg, engine

    drv.build = broken_build
    line = _measure(drv)
    assert not line["correct"], line["checks"]


def test_control_fails_the_limit():
    """The fp8 reference put in the program's place: at each position of
    four sequences, the token it puts first lies further below the float32
    reference's best than the cell's limit allows.  8 layers at width 512:
    at the tiny width the control's rounding reads under the limit (it
    reads 1.01-1.29 at the cell's own size, PERF.md)."""
    import jax.numpy as jnp
    from bench.harness import compare
    c, t = tiny_dense()
    c.update(hidden_size=512, num_hidden_layers=8, num_attention_heads=8,
             num_key_value_heads=2, head_dim=64, intermediate_size=1024,
             vocab_size=16384)
    ref = registry.reference("dense")
    rng = np.random.default_rng(4)
    toks = rng.integers(0, c["vocab_size"], (4, 256)).astype(np.int32)
    where = np.array([(i, p) for i in range(4) for p in range(16, 256)],
                     np.int32)
    f32 = ref.logits_at(c, 4, toks, where)
    fp8 = ref.logits_at(c, 4, toks, where, precision="fp8")
    gap = compare.widest_logit_gap(f32, jnp.argmax(fp8, axis=-1))
    assert gap > t["limits"]["served_logit_gap"], gap


def _tracer(rounds: int, prefills: int):
    import types
    return types.SimpleNamespace(rounds=[[100, 200]] * rounds,
                                 n_prefill=prefills,
                                 decode_program="jit_step")


def test_decode_program_is_the_one_run_once_a_round():
    """Of the programs named after the decode function, the one that ran
    exactly once per traced round; another count, or two, is an error."""
    import pytest
    drv = registry.driver("serve")
    c, _ = tiny_dense()
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    tr = {"modules": {"jit_step(7)": {"n": 5.0, "s": 0.5},
                      "jit_step(9)": {"n": 2.0, "s": 0.2},
                      "jit_argmax(3)": {"n": 5.0, "s": 0.01}}}
    r = drv.decode_roofline(c, _tracer(5, 2), peaks, tr)
    assert r["decode_module"] == "jit_step(7)" and r["decode_device_s"] == 0.5
    assert r["decode_least_s"] > 0
    with pytest.raises(ValueError, match="decode program"):
        drv.decode_roofline(c, _tracer(4, 2), peaks, tr)   # none ran 4 times
    tr["modules"]["jit_step(9)"]["n"] = 5.0
    with pytest.raises(ValueError, match="decode program"):
        drv.decode_roofline(c, _tracer(5, 5), peaks, tr)   # two ran 5 times
