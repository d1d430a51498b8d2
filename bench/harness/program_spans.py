"""The serving engine's own host spans against the device's idle time.

The program marks its host phases with ``jax.profiler.TraceAnnotation``s
named ``serve.<phase>`` (``repro/serving/metrics.py``), and JAX marks
each backend compile with a ``backend_compile_and_load`` event.  Both
land on the host plane of the traced run's ``.xplane.pb``, on the same
clock as the device's ops.  This reads them from that file and puts each
stretch of device idle time in ``bench.window`` down to the innermost of
those events covering it: an idle gap is split where events open and
close, and each piece goes to the shortest event around it.  Gaps under
``trace.SHORT_GAP_NS`` lie between the ops of a running program and are
left to no host event.

A per-layer reader sees only the driver's record, so the trace is found
again on disk: the newest ``.xplane.pb`` under the checkout's ``.bench/``
whose window is as long as the record's.  A trace without any
``serve.*`` span (a program without the spans) reads ``None``.
"""
from __future__ import annotations

import functools
import math
import pathlib
from typing import Dict, List, Optional, Tuple

from bench.harness import registry, trace

SPAN = "serve."
COMPILE = "backend_compile_and_load"
# what each share counts: the innermost event's name
GROUPS = {
    "page_table": {"serve.page_table"},
    "engine_loop": {"serve.round", "serve.admit", "serve.prefill",
                    "serve.decode", "serve.bookkeep"},
    "compile": {COMPILE},
}
Event = Tuple[float, float, str]


def load(path: pathlib.Path) -> Dict:
    """The window, each device's ops, and the program's host events
    (``serve.*`` and compiles) of one profile."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: List[List[Event]] = []
    events: List[Event] = []
    window = None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices += [[(e.start_ns, e.end_ns, e.name) for e in line.events]
                        for line in plane.lines if line.name == "XLA Ops"]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN) or e.name == COMPILE:
                        events.append((e.start_ns, e.end_ns, e.name))
                    elif e.name == trace.WINDOW and window is None:
                        window = (e.start_ns, e.end_ns)
    return {"window": window, "devices": devices, "events": events}


def idle_by_event(ev: Dict) -> Optional[Dict[str, float]]:
    """Device idle seconds in the window, averaged over the devices, by
    the innermost program event covering them (``None``: no event;
    ``trace.SHORT_GAP``: a gap between ops).  ``None`` if the trace has
    no ``serve.*`` span."""
    if ev["window"] is None:
        raise ValueError(f"no {trace.WINDOW!r} span in the trace")
    if not any(n.startswith(SPAN) for _, _, n in ev["events"]):
        return None
    lo, hi = ev["window"]
    events = sorted((max(s, lo), min(e, hi), e - s, n)
                    for s, e, n in ev["events"] if e > lo and s < hi)
    out: Dict[Optional[str], float] = {}
    for ops in ev["devices"]:
        busy = trace.merge([(s, e) for s, e, _ in trace._clip(ops, lo, hi)])
        gaps = trace._subtract([(lo, hi)], busy)
        short = sum(e - s for s, e in gaps if e - s < trace.SHORT_GAP_NS)
        out[trace.SHORT_GAP] = out.get(trace.SHORT_GAP, 0.0) + short
        long_gaps = [g for g in gaps if g[1] - g[0] >= trace.SHORT_GAP_NS]
        for name, t in _split(long_gaps, events):
            out[name] = out.get(name, 0.0) + t
    n_dev = max(1, len(ev["devices"]))
    return {k: v / n_dev / 1e9 for k, v in out.items()}


def _split(gaps: List[Tuple[float, float]], events):
    """``(innermost event name or None, ns)`` for the pieces of ``gaps``
    between consecutive event boundaries."""
    points = sorted({p for s, e, *_ in events for p in (s, e)}
                    | {p for g in gaps for p in g})
    active: List = []
    i = j = 0
    for a, b in zip(points, points[1:]):
        while i < len(events) and events[i][0] <= a:
            active.append(events[i])
            i += 1
        active = [x for x in active if x[1] > a]
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        if j < len(gaps) and gaps[j][0] <= a:     # [a, b] lies in the gap
            inner = min(active, key=lambda x: x[2])[3] if active else None
            yield inner, b - a


def shares(idle: Dict[Optional[str], float], window_s: float
           ) -> Dict[str, float]:
    """Percent of the window each group of ``GROUPS`` holds idle."""
    return {g: 100.0 * sum(v for k, v in idle.items() if k in names)
            / window_s for g, names in GROUPS.items()}


def find(window_s: float, root: pathlib.Path) -> Dict:
    """The newest profile under ``root`` whose window lasts
    ``window_s``, loaded."""
    for path in sorted(root.glob("**/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime, reverse=True):
        ev = load(path)
        if ev["window"] is not None and math.isclose(
                (ev["window"][1] - ev["window"][0]) / 1e9, window_s,
                rel_tol=1e-12, abs_tol=0.0):
            return ev
    raise FileNotFoundError(
        f"no profile under {root} with a {window_s!r} s window")


@functools.lru_cache(maxsize=4)
def _shares_of_run(window_s: float, root: str) -> Optional[Dict]:
    idle = idle_by_event(find(window_s, pathlib.Path(root)))
    return None if idle is None else shares(idle, window_s)


def read(record: Dict, group: str,
         root: pathlib.Path = registry.CHECKOUT / ".bench"
         ) -> Optional[float]:
    """Share of a serving run's traced window, in percent, in which the
    device is idle under ``group``'s host events; ``None`` for any other
    run, or for a program without ``serve.*`` spans."""
    tr = record.get("trace")
    if record.get("kind") != "serve" or not tr:
        return None
    got = _shares_of_run(tr["window_s"], str(root))
    return None if got is None else got[group]
