"""Device trace: capture with the JAX profiler, and reduce the
``.xplane.pb`` it writes to busy and idle time, per-op self time,
per-program time, collective intervals and idle gaps.

Host spans are the benchmark's own ``jax.profiler.TraceAnnotation``s,
named ``bench.<what>``.  The span ``bench.window`` marks the traced
window; everything is clipped to it.  Device and host events share the
profile's clock, so a gap on the device is labelled by the innermost
host span that covers its midpoint.
"""
from __future__ import annotations

import contextlib
import pathlib
import re
from typing import Dict, List, Tuple

import jax

WINDOW = "bench.window"
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all")

SHORT_GAP_NS = 10_000.0
SHORT_GAP = "between ops (<10us)"
Interval = Tuple[float, float]


@contextlib.contextmanager
def capture(trace_dir: pathlib.Path):
    """Profile the block; the python tracer stays off (it would slow
    the host), the benchmark's annotations and the runtime's stay on."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_label(name: str) -> str:
    """``%fusion.12 = bf16[8,2048]{...} fusion(...)`` -> ``fusion.12
    bf16[8,2048]``: the op and its first result shape."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + shape.group(1) if shape else "")


def load(path: pathlib.Path) -> Dict:
    """Events of one profile: per device its ops and programs, and the
    benchmark's host spans, each as ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, List]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "async": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async",
                       "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.start_ns, e.end_ns, e.name)
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.end_ns, e.name) for e in line.events
                          if e.name.startswith("bench.")]
    return {"devices": devices, "spans": spans}


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Exclusive time per op label: an op's duration less that of the ops
    nested inside it (a while loop holds the ops of its body)."""
    out: Dict[str, float] = {}
    stack: List[List] = []             # [end, label, duration, child time]

    def pop():
        end, label, dur, child = stack.pop()
        out[label] = out.get(label, 0.0) + dur - child

    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            pop()
        if stack:
            stack[-1][3] += e - s
        stack.append([e, op_label(name), e - s, 0.0])
    while stack:
        pop()
    return out


def _clip(events, lo: float, hi: float):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce(events: Dict, top: int = 10) -> Dict:
    """Busy and idle time of the devices in the traced window, averaged
    over the devices; per-op self time and per-program time; collective
    time and the part of it no other op overlaps; the longest idle gaps,
    each labelled by what the host was doing."""
    win = [(s, e) for s, e, n in events["spans"] if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = win[0]
    window_ns = hi - lo
    spans = [sp for sp in _clip(events["spans"], lo, hi) if sp[2] != WINDOW]
    n_dev = max(1, len(events["devices"]))
    busy = coll = exposed = 0.0
    ops_self: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in events["devices"].values():
        ops = _clip(dev["ops"], lo, hi)
        busy_iv = merge([(s, e) for s, e, _ in ops])
        busy += _length(busy_iv)
        gaps += _subtract([(lo, hi)], busy_iv)
        c_iv = merge([(s, e) for s, e, n in ops + _clip(dev["async"], lo, hi)
                      if _COLLECTIVE.search(n.partition(" = ")[0])])
        other = merge([(s, e) for s, e, n in ops
                       if not _COLLECTIVE.search(n.partition(" = ")[0])])
        coll += _length(c_iv)
        exposed += _length(_subtract(c_iv, other))
        for k, v in self_times(ops).items():
            ops_self[k] = ops_self.get(k, 0.0) + v
        for s, e, n in _clip(dev["modules"], lo, hi):
            m = modules.setdefault(n, [0, 0.0])
            m[0] += 1
            m[1] += e - s

    def label(gap):
        if gap[1] - gap[0] < SHORT_GAP_NS:
            return SHORT_GAP
        mid = (gap[0] + gap[1]) / 2
        inside = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        return (min(inside, key=lambda sp: sp[1] - sp[0])[2] if inside
                else "no bench span")

    by_label: Dict[str, float] = {}
    for g in gaps:
        by_label[label(g)] = by_label.get(label(g), 0.0) + (g[1] - g[0])
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "n_devices": n_dev,
        "collective_s": coll / n_dev / 1e9,
        "collective_exposed_s": exposed / n_dev / 1e9,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(ops_self.items(), key=lambda kv: -kv[1])[:top]],
        "modules": {k: {"n": v[0] / n_dev, "s": v[1] / n_dev / 1e9}
                    for k, v in modules.items()},
        "idle_by_host_span": {k: v / n_dev / 1e9 for k, v in
                              sorted(by_label.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[label(g), (g[1] - g[0]) / 1e9] for g in longest],
    }
