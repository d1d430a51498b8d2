"""Host-side serving telemetry: per-request latency accounting, running
queue/occupancy values, host phases and counters, aggregated into a
JSON-able summary (the schema ``benchmarks/bench_serve.py`` writes to
``BENCH_serve.json``).

Host phases are spans: :meth:`ServeMetrics.span` opens a
``jax.profiler.TraceAnnotation`` named ``serve.<phase>``, which lands on
the host plane of the same profile as the device's ops and so shares its
clock, and adds the phase's own wall time (less that of the spans nested
inside it) and call count to ``phase_s`` / ``phase_n``.  A span's stats
are host values only: no span reads the device or adds a sync.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[i]


@dataclasses.dataclass
class RequestMetrics:
    """Latency record of one served request (seconds on the engine's
    clock)."""

    rid: str
    arrival_s: float                         # when the request was due
    admitted_s: Optional[float] = None       # when it got a lane and pages
    prompt_tokens: int = 0
    new_tokens: int = 0
    first_token_s: Optional[float] = None    # absolute time of first token
    finish_s: Optional[float] = None
    deadline_ms: float = 0.0                 # 0 = no per-token SLO attached

    @property
    def ttft_ms(self) -> Optional[float]:
        """Due time to first token."""
        if self.first_token_s is None:
            return None
        return (self.first_token_s - self.arrival_s) * 1e3

    @property
    def wait_ms(self) -> Optional[float]:
        """Due time to admission."""
        if self.admitted_s is None:
            return None
        return (self.admitted_s - self.arrival_s) * 1e3

    @property
    def tok_ms(self) -> Optional[float]:
        """Mean per-token decode latency after the first token."""
        if (self.finish_s is None or self.first_token_s is None
                or self.new_tokens <= 1):
            return None
        return ((self.finish_s - self.first_token_s)
                / (self.new_tokens - 1)) * 1e3


class _Span:
    """One open phase: a profiler annotation plus its own wall time."""

    __slots__ = ("metrics", "phase", "note", "t0", "nested")

    def __init__(self, metrics: "ServeMetrics", phase: str, stats):
        self.metrics, self.phase = metrics, phase
        self.note = jax.profiler.TraceAnnotation("serve." + phase, **stats)

    def __enter__(self):
        self.note.__enter__()
        self.metrics._open.append(self)
        self.nested = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        m = self.metrics
        m._open.pop()
        if m._open:
            m._open[-1].nested += dt
        m.phase_s[self.phase] = m.phase_s.get(self.phase, 0.0) + dt \
            - self.nested
        m.phase_n[self.phase] = m.phase_n.get(self.phase, 0) + 1
        self.note.__exit__(*exc)

    def set(self, **stats) -> None:
        """Attach host values known only at the end of the phase."""
        self.note.set_metadata(**stats)


@dataclasses.dataclass
class ServeMetrics:
    """Aggregated over one engine run."""

    requests: List[RequestMetrics] = dataclasses.field(default_factory=list)
    # running values over the engine loop's iterations
    samples: int = 0
    queue_depth_max: int = 0
    page_occupancy_mean: float = 0.0
    page_occupancy_max: float = 0.0
    decode_steps: int = 0
    prefill_chunks: int = 0
    wall_s: float = 0.0
    # host phases: own seconds and calls per ``serve.<phase>`` span
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    phase_n: Dict[str, int] = dataclasses.field(default_factory=dict)
    # decode attention the decode step resolved to when it was traced
    # (``paged_kernel`` or ``gather``; None before any decode round)
    decode_attn: Optional[str] = None
    # mean over decode rounds of the pages the paged kernel fetches (each
    # stepping lane's ceil(L / page size)) over the pages the gather path
    # reads (every lane's reserved pages)
    live_page_share: float = 0.0
    host_syncs: int = 0                      # device -> host reads
    compiles: int = 0                        # backend compiles in run()
    compile_s: float = 0.0
    _open: List[_Span] = dataclasses.field(default_factory=list, repr=False)

    def span(self, phase: str, **stats) -> _Span:
        """Context manager timing one ``serve.<phase>``; ``stats`` are
        host values attached to the profiler event."""
        return _Span(self, phase, stats)

    def sample(self, queue_depth: int, occupancy: float) -> None:
        """One engine-loop iteration's queue length and page occupancy."""
        self.samples += 1
        self.queue_depth_max = max(self.queue_depth_max, queue_depth)
        self.page_occupancy_max = max(self.page_occupancy_max, occupancy)
        self.page_occupancy_mean += ((occupancy - self.page_occupancy_mean)
                                     / self.samples)

    def decode_round(self, live_pages: int, reserved_pages: int) -> None:
        """One decode round: count it and fold its live page share into
        the running mean."""
        self.decode_steps += 1
        self.live_page_share += ((live_pages / reserved_pages
                                  - self.live_page_share) / self.decode_steps)

    def on_duration(self, event: str, duration: float, **_) -> None:
        """``jax.monitoring`` duration listener: counts backend compiles."""
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def summary(self) -> Dict:
        done = [r for r in self.requests if r.finish_s is not None]
        ttfts = sorted(r.ttft_ms for r in done if r.ttft_ms is not None)
        toks = sorted(r.tok_ms for r in done if r.tok_ms is not None)
        waits = sorted(r.wait_ms for r in self.requests
                       if r.wait_ms is not None)
        total_new = sum(r.new_tokens for r in done)
        return {
            "requests": len(self.requests),
            "completed": len(done),
            "new_tokens": total_new,
            "decode_steps": self.decode_steps,
            "prefill_chunks": self.prefill_chunks,
            "wall_s": self.wall_s,
            "tok_per_s": (total_new / self.wall_s if self.wall_s else 0.0),
            "ttft_ms_p50": _pct(ttfts, 0.5),
            "ttft_ms_p99": _pct(ttfts, 0.99),
            "tok_ms_p50": _pct(toks, 0.5),
            "tok_ms_p99": _pct(toks, 0.99),
            "queue_wait_ms_p50": _pct(waits, 0.5),
            "queue_wait_ms_p90": _pct(waits, 0.9),
            "queue_depth_max": self.queue_depth_max,
            "page_occupancy_mean": self.page_occupancy_mean,
            "page_occupancy_max": self.page_occupancy_max,
            "decode_attn": self.decode_attn,
            "live_page_share": self.live_page_share,
            "host_syncs": self.host_syncs,
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "phase_s": dict(self.phase_s),
            "phase_n": dict(self.phase_n),
        }
