"""Find every piece of a cell by its name in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the traffic file names
its driver; each per-layer metric is a reader of its own.  Each piece is
a file, so a new cell, mix, driver or metric is a new file and never an
edit:

  configuration   bench/configs/<config>.json
  traffic mix     bench/traffic/<traffic>.json
  driver          bench/drivers/<driver>.py        (traffic["driver"])
  metric reader   bench/metrics/<metric>.py
  FLOP counts     bench/flops/<arch_type>.py       (config["arch_type"])
  reference       bench/reference/<arch_type>.py   (config["arch_type"])
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Any, Dict, List

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent


def load_spec(root: pathlib.Path = CHECKOUT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def config(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((CHECKOUT / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(path: pathlib.Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                   .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str) -> ModuleType:
    return _module(BENCH / "drivers" / f"{name}.py")


def flops(arch_type: str) -> ModuleType:
    return _module(BENCH / "flops" / f"{arch_type}.py")


def reference(arch_type: str) -> ModuleType:
    return _module(BENCH / "reference" / f"{arch_type}.py")


def metric_reader(name: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{name}.py")


def end_to_end_for(spec: Dict[str, Any], cell_name: str) -> List[Dict]:
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer_for(spec: Dict[str, Any], cell_name: str) -> List[Dict]:
    """Per-layer metrics this cell reports: those listing it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(spec, cell_name)}
    return [m for m in spec["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]
