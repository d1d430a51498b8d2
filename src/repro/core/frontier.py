"""Throughput-vs-memory plan frontier (DESIGN.md §6).

The paper's headline evaluation presents throughput as a *function of the
per-device memory budget*.  ``GalvatronOptimizer.sweep_budgets`` produces a
:class:`PlanFrontier`: one (budget, plan, predicted throughput) point per
swept budget, all searched in ~one pass by running the stage DP with a
budget axis.  The frontier serializes to JSON (consumed by
``launch/search.py`` and ``benchmarks/bench_frontier.py``) and exposes the
knee points — the budgets where predicted throughput actually improves,
i.e. where buying more memory buys speed.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

from .plan import ParallelPlan

GB = 1024 ** 3


@dataclasses.dataclass
class FrontierPoint:
    """One swept budget: the best plan found under it (None if everything
    OOMs) and its predicted throughput (samples/s; 0.0 when infeasible)."""

    budget_bytes: float
    plan: Optional[ParallelPlan]
    predicted_throughput: float = 0.0

    @property
    def feasible(self) -> bool:
        return self.plan is not None

    def to_json(self) -> Dict:
        return {
            "budget_bytes": self.budget_bytes,
            "budget_gb": self.budget_bytes / GB,
            "predicted_throughput": self.predicted_throughput,
            "plan": self.plan.to_json() if self.plan is not None else None,
        }

    @staticmethod
    def from_json(d: Dict) -> "FrontierPoint":
        plan = (ParallelPlan.from_json(d["plan"])
                if d.get("plan") is not None else None)
        return FrontierPoint(
            budget_bytes=d["budget_bytes"],
            plan=plan,
            predicted_throughput=d.get("predicted_throughput", 0.0),
        )


@dataclasses.dataclass
class PlanFrontier:
    """The whole budget sweep, sorted by budget ascending.

    ``quant_bytes`` records the DP quantization grid the sweep ran on —
    a serial ``optimize()`` reproduces a point byte-for-byte only on the
    same grid.  ``search_stats`` is the aggregated engine telemetry
    (cache hits/misses summed across parallel workers); like
    ``ParallelPlan.search_stats`` it is excluded from equality.
    """

    points: List[FrontierPoint]
    quant_bytes: float = 0.0
    search_stats: Optional[Dict[str, float]] = dataclasses.field(
        default=None, compare=False)

    def __post_init__(self):
        self.points = sorted(self.points, key=lambda p: p.budget_bytes)

    # ---- queries --------------------------------------------------------
    def budgets(self) -> List[float]:
        return [p.budget_bytes for p in self.points]

    def throughputs(self) -> List[float]:
        return [p.predicted_throughput for p in self.points]

    def feasible_points(self) -> List[FrontierPoint]:
        return [p for p in self.points if p.feasible]

    def plan_at(self, budget_bytes: float) -> Optional[ParallelPlan]:
        """Best known plan fitting under ``budget_bytes``: the highest-
        throughput feasible point whose swept budget is <= the query (plans
        found under a smaller budget remain valid under a larger one).
        This is the incremental answer for budgets between swept points —
        no re-search needed."""
        best: Optional[FrontierPoint] = None
        for p in self.points:
            if p.budget_bytes <= budget_bytes and p.feasible:
                if (best is None
                        or p.predicted_throughput > best.predicted_throughput):
                    best = p
        return best.plan if best is not None else None

    def knee_points(self) -> List[FrontierPoint]:
        """Pareto knees: feasible points whose predicted throughput strictly
        exceeds every smaller budget's — the budgets where extra memory
        actually converts into speed."""
        out: List[FrontierPoint] = []
        seen_best = 0.0
        for p in self.points:
            if p.feasible and p.predicted_throughput > seen_best:
                out.append(p)
                seen_best = p.predicted_throughput
        return out

    def summary(self) -> str:
        rows = []
        for p in self.points:
            if p.feasible:
                rows.append(f"{p.budget_bytes / GB:7.1f} GB  "
                            f"{p.predicted_throughput:10.2f} samples/s  "
                            f"{p.plan.summary()}")
            else:
                rows.append(f"{p.budget_bytes / GB:7.1f} GB        OOM")
        return "\n".join(rows)

    # ---- (de)serialization ----------------------------------------------
    def to_json(self) -> Dict:
        knees = {id(p) for p in self.knee_points()}
        return {
            "quant_bytes": self.quant_bytes,
            "points": [dict(p.to_json(), knee=(id(p) in knees))
                       for p in self.points],
            "search_stats": self.search_stats,
        }

    def dumps(self) -> str:
        return json.dumps(self.to_json(), indent=2)

    @staticmethod
    def from_json(d: Dict) -> "PlanFrontier":
        return PlanFrontier(
            points=[FrontierPoint.from_json(p) for p in d["points"]],
            quant_bytes=d.get("quant_bytes", 0.0),
            search_stats=d.get("search_stats"),
        )

    @staticmethod
    def loads(s: str) -> "PlanFrontier":
        return PlanFrontier.from_json(json.loads(s))
