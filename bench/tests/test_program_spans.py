"""The program's host spans against device idle time: worked by hand on
made-up events, and on a small trace recorded on a TPU v5e
(``serve_spans.xplane.pb`` beside this file: the serve cell's engine at
2 layers and width 128 serving two short requests in ``bench.window``,
recorded by ``bench/tools/engine_spans.py --seconds 0.125``, which cuts
the profile to the lines and events the readers use)."""
from __future__ import annotations

import os
import pathlib
import shutil

import pytest

from bench.harness import program_spans, trace

HERE = pathlib.Path(__file__).parent
SAMPLE = HERE / "serve_spans.xplane.pb"
MS = 1_000_000.0


def _events():
    """Window 0-100 ms; device busy 0-2, 30-40, 50-55, 60-65, 90-95 and
    95.005-110 ms.  A round open since before the window, then a round
    5-60 holding page-table work 10-30 (a compile 15-20 inside it) and
    bookkeeping 40-50; a wait 70-80."""
    ops = [(a * MS, b * MS, f"%fusion.{i} = f32[8]{{0}} fusion(...)")
           for i, (a, b) in enumerate([(0, 2), (30, 40), (50, 55), (60, 65),
                                       (90, 95), (95.005, 110)])]
    return {"window": (0.0, 100 * MS), "devices": [ops],
            "events": [(-10 * MS, 3 * MS, "serve.round"),
                       (5 * MS, 60 * MS, "serve.round"),
                       (10 * MS, 30 * MS, "serve.page_table"),
                       (15 * MS, 20 * MS, program_spans.COMPILE),
                       (40 * MS, 50 * MS, "serve.bookkeep"),
                       (70 * MS, 80 * MS, "serve.wait")]}


def test_idle_by_event_by_hand():
    idle = program_spans.idle_by_event(_events())
    # 2-3 the clipped round; 3-5 nothing; 5-10 the round; 10-15 and 20-30
    # page-table work; 15-20 the compile inside it, the innermost; 40-50
    # bookkeeping; 55-60 the round; 65-70 and 80-90 nothing; 70-80 the
    # wait; 95-95.005 a gap between ops
    assert idle["serve.page_table"] == pytest.approx(0.015)
    assert idle[program_spans.COMPILE] == pytest.approx(0.005)
    assert idle["serve.round"] == pytest.approx(0.011)
    assert idle["serve.bookkeep"] == pytest.approx(0.010)
    assert idle["serve.wait"] == pytest.approx(0.010)
    assert idle[None] == pytest.approx(0.017)
    assert idle[trace.SHORT_GAP] == pytest.approx(5e-6)
    assert sum(idle.values()) == pytest.approx(0.068005)
    s = program_spans.shares(idle, 0.1)
    assert s == {"page_table": pytest.approx(15.0),
                 "engine_loop": pytest.approx(21.0),
                 "compile": pytest.approx(5.0)}


def test_no_serve_span_reads_none():
    ev = _events()
    ev["events"] = [e for e in ev["events"] if not e[2].startswith("serve.")]
    assert program_spans.idle_by_event(ev) is None
    ev["window"] = None
    with pytest.raises(ValueError):
        program_spans.idle_by_event(ev)


def _record(path):
    return {"kind": "serve", "trace": trace.reduce(trace.load(path))}


def test_recorded_tpu_trace():
    """On the chip's trace the shares sum to no more than its device idle
    share, every host phase shows, and nothing compiled."""
    ev = program_spans.load(SAMPLE)
    assert {n for *_, n in ev["events"]} >= {
        "serve.round", "serve.admit", "serve.prefill", "serve.decode",
        "serve.page_table", "serve.bookkeep"}
    red = trace.reduce(trace.load(SAMPLE))
    idle_pct = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    s = program_spans.shares(program_spans.idle_by_event(ev),
                             red["window_s"])
    assert s["page_table"] > 0 and s["engine_loop"] > 0
    assert s["compile"] == 0.0
    assert 0.75 * idle_pct <= sum(s.values()) <= idle_pct


def test_read_finds_the_runs_trace(tmp_path):
    """The reader takes the newest profile whose window is the record's;
    a profile without the program's spans reads ``None``; a record with
    no such profile on disk is an error; other records read ``None``."""
    a, b = tmp_path / "a" / "x.xplane.pb", tmp_path / "b" / "x.xplane.pb"
    for src, dst in ((SAMPLE, a), (HERE / "sample.xplane.pb", b)):
        dst.parent.mkdir()
        shutil.copyfile(src, dst)
    os.utime(a, (1e9, 1e9))                  # the other profile is newer
    for group in program_spans.GROUPS:
        got = program_spans.read(_record(SAMPLE), group, root=tmp_path)
        assert got is not None and got >= 0.0
    assert program_spans.read(_record(b), "page_table", root=tmp_path) is None
    rec = _record(SAMPLE)
    rec["trace"] = dict(rec["trace"], window_s=rec["trace"]["window_s"] * 2)
    with pytest.raises(FileNotFoundError):
        program_spans.read(rec, "page_table", root=tmp_path)
    assert program_spans.read({"kind": "train", "trace": rec["trace"]},
                              "page_table", root=tmp_path) is None
    assert program_spans.read({}, "page_table", root=tmp_path) is None
