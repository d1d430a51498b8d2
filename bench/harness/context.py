"""What a driver is handed, and what it hands back."""
from __future__ import annotations

import dataclasses
import pathlib
import time
from typing import Any, Dict, List


@dataclasses.dataclass
class Context:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Dict[str, Any]
    t_start: float                  # set-up is timed from here
    work_dir: pathlib.Path          # traces; inside the checkout

    def setup_s(self) -> float:
        return time.perf_counter() - self.t_start


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]            # end-to-end metrics by name
    record: Dict[str, Any]           # what the per-layer readers read
    checks: List[Dict[str, Any]]     # {"name", "value", "limit"}
    attempted: int
    failed: int
    memory_peak_bytes: int


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
