"""The harness finds every piece of every cell by its name, and
``BENCHMARK.json`` keeps to the shape the harness and its readers rely
on."""
from __future__ import annotations

import json
import re

from bench.harness import registry
from conftest import CHECKOUT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_resolves(spec):
    for w in spec["workloads"]:
        c = registry.config(spec, w["config"])
        t = registry.traffic(w["traffic"])
        drv = registry.driver(t["driver"])
        assert callable(drv.run)
        assert registry.flops(c["arch_type"])
        assert registry.reference(c["arch_type"])
        assert w["chips"] == c["chips"]
        for m in registry.end_to_end_for(spec, w["name"]):
            assert m["name"] == "setup_s" or "workloads" in m
        for m in registry.per_layer_for(spec, w["name"]):
            assert callable(registry.metric_reader(m["name"]).read)


def test_every_cell_reports_enough(spec):
    for w in spec["workloads"]:
        e2e = {m["name"] for m in registry.end_to_end_for(spec, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.per_layer_for(spec, w["name"])


def test_per_layer_metrics_move_a_metric_of_their_cells(spec):
    for m in spec["per_layer"]:
        for cell in m["workloads"]:
            e2e = {x["name"] for x in registry.end_to_end_for(spec, cell)}
            assert m["moves"] in e2e, (m["name"], cell)


def test_names_units_and_files(spec):
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in spec["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads((CHECKOUT / c["file"]).read_text())
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert spec["paths"] == ["bench"]
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= 1


def test_unknown_device_kind_is_an_error():
    import pytest
    assert registry.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        registry.peaks("cpu")


def test_metric_reader_finds_nothing_returns_none():
    for m in registry.load_spec()["per_layer"]:
        assert registry.metric_reader(m["name"]).read({}) is None
