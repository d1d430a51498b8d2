"""The trace reduction: by hand on made-up events, and on a small trace
recorded on a TPU v5e (``sample.xplane.pb`` beside this file: a jitted
loop of matmuls run three times in ``bench.window``, each run in a
``bench.step`` span followed by 20 ms of host work in a
``bench.host_work`` span)."""
from __future__ import annotations

import pathlib

import pytest

from bench.harness import trace

SAMPLE = pathlib.Path(__file__).with_name("sample.xplane.pb")


def _events():
    ms = 1_000_000.0
    ops = [(0 * ms, 10 * ms, "%while.1 = (f32[8]{0}) while(...)"),
           (1 * ms, 4 * ms, "%fusion.2 = f32[8,8]{1,0} fusion(...)"),
           (5 * ms, 9 * ms, "%fusion.2 = f32[8,8]{1,0} fusion(...)"),
           (12 * ms, 14 * ms, "%all-reduce.3 = f32[8]{0} all-reduce(...)"),
           (13 * ms, 15 * ms, "%fusion.4 = f32[8]{0} fusion(...)"),
           (18 * ms, 20 * ms, "%all-gather.5 = f32[16]{0} all-gather(...)")]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [(0, 15 * ms, "jit_step(1)"),
                    (18 * ms, 20 * ms, "jit_step(1)")]}},
        "spans": [(-1 * ms, 21 * ms, trace.WINDOW),
                  (14.5 * ms, 19 * ms, "bench.host_work"),
                  (10 * ms, 19 * ms, "bench.outer")]}


def test_reduce_by_hand():
    r = trace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.022)
    # busy: [0,10] + [12,15] + [18,20] = 15 ms
    assert r["busy_s"] == pytest.approx(0.015)
    # collectives: [12,14] and [18,20]; [12,13] and [18,20] overlap no
    # other op
    assert r["collective_s"] == pytest.approx(0.004)
    assert r["collective_exposed_s"] == pytest.approx(0.003)
    ops = dict(r["device_ops"])
    assert ops["while.1 f32[8]"] == pytest.approx(0.003)     # 10 - 3 - 4
    assert ops["fusion.2 f32[8,8]"] == pytest.approx(0.007)
    assert r["modules"]["jit_step(1)"]["n"] == 2
    assert r["modules"]["jit_step(1)"]["s"] == pytest.approx(0.017)
    # [-1,0] and [20,21] lie in no span but the window; [10,12] in
    # bench.outer; [15,18] in bench.host_work, the innermost
    (l1, s1), (l2, s2) = r["idle_gaps"][:2]
    assert (l1, l2) == ("bench.host_work", "bench.outer")
    assert (s1, s2) == (pytest.approx(0.003), pytest.approx(0.002))
    assert r["idle_by_host_span"]["bench.outer"] == pytest.approx(0.002)
    assert r["idle_by_host_span"]["no bench span"] == pytest.approx(0.002)


def test_reduce_needs_the_window():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(ValueError):
        trace.reduce(ev)


def test_recorded_tpu_trace():
    r = trace.reduce(trace.load(SAMPLE))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # three runs of one program; in this trace the device clock reads
    # about 0.9 ms behind the host's, so the first run, dispatched 6 us
    # into the window, lands just before it; and three 20 ms host gaps
    # that the reduction pins on the host span around them
    (mod,) = [v for k, v in r["modules"].items() if k.startswith("jit_")]
    assert mod["n"] == 2
    assert r["idle_by_host_span"]["bench.host_work"] >= 3 * 0.019
    assert [g[0] for g in r["idle_gaps"][:3]] == ["bench.host_work"] * 3
    assert r["collective_s"] == 0.0
    assert r["device_ops"][0][1] > 0
