"""Plan verifier (repro.analysis.plan_lint): structured loading errors,
format-version policy, and property-based fuzzing over random degree
tuples (hypothesis when installed, the deterministic shim otherwise).

Also pins the satellite error-handling contract: ``ParallelPlan.from_json``
raises :class:`PlanFormatError` naming the offending field (never a bare
``KeyError``), and ``runtime/plan_bridge.py`` wraps uncompilable schedule
combos in a structured ``DiagnosticError``."""
import json
import pathlib

import pytest

from hypothesis import given, settings, strategies as st

from repro.analysis import (DiagnosticError, detect_format_version,
                            load_plan_file, load_plan_json, verify_plan,
                            verify_plan_json)
from repro.core import (PLAN_FORMAT_VERSION, ParallelPlan, PlanFormatError,
                        Strategy, enumerate_strategies)

REPO = pathlib.Path(__file__).resolve().parent.parent
EXAMPLE_PLANS = sorted((REPO / "examples" / "plans").glob("*.plan.json"))


def error_rules(diags):
    return sorted({d.rule for d in diags if d.severity == "error"})


def make_plan(n_devices=8, pp=2, layers=8, schedule="1f1b", m=4, V=1,
              batch=32, strategy=None):
    group = n_devices // pp
    s = strategy or enumerate_strategies(group)[0]
    per = layers // pp
    return ParallelPlan(
        n_devices=n_devices, pp_degree=pp,
        partition=[per] * (pp - 1) + [layers - per * (pp - 1)],
        strategies=[s] * layers, global_batch=batch, n_micro=m,
        schedule=schedule, vpp_degree=V)


# ---------------------------------------------------------------------------
# clean plans certify
# ---------------------------------------------------------------------------

def test_valid_plan_has_no_errors():
    diags = verify_plan(make_plan())
    assert error_rules(diags) == []


@pytest.mark.parametrize("path", EXAMPLE_PLANS, ids=lambda p: p.name)
def test_checked_in_example_plans_certify(path):
    plan, report = load_plan_file(str(path))
    assert report.ok
    assert plan.n_devices >= 1
    assert detect_format_version(json.loads(path.read_text())) == \
        PLAN_FORMAT_VERSION


def test_example_plan_artifacts_exist():
    # CI lints these; losing them silently would hollow the lint job out
    assert EXAMPLE_PLANS, "examples/plans/*.plan.json disappeared"


# ---------------------------------------------------------------------------
# property-based fuzz over random degree tuples
# ---------------------------------------------------------------------------

@settings(max_examples=40)
@given(st.integers(0, 4), st.integers(0, 3), st.integers(1, 4),
       st.integers(0, 4), st.booleans())
def test_fuzz_legal_plans_never_error(log_dev, log_pp, m, strat_i, zb):
    """Any plan built from the real enumeration rules (pp | n_devices,
    per-layer strategies from enumerate_strategies(group), a legal
    schedule) verifies with zero errors."""
    n_devices = 2 ** log_dev
    pp = 2 ** min(log_pp, log_dev)
    group = n_devices // pp
    strategies = enumerate_strategies(group)
    s = strategies[strat_i % len(strategies)]
    schedule = "zb-h1" if (zb and pp > 1 and m >= pp) else "1f1b"
    plan = make_plan(n_devices=n_devices, pp=pp, layers=4 * pp,
                     schedule=schedule, m=m, batch=16 * m, strategy=s)
    diags = verify_plan(plan)
    assert error_rules(diags) == [], [d.format() for d in diags]


@settings(max_examples=40)
@given(st.integers(0, 4), st.integers(0, 4), st.integers(1, 6))
def test_fuzz_wrong_strategy_total_is_always_flagged(log_dev, log_wrong, m):
    """Whenever a layer's degrees don't multiply to the stage group size,
    PLN002 fires — for every random (n_devices, wrong_total) pair."""
    n_devices = 2 ** log_dev
    pp = 2 if n_devices >= 2 else 1
    group = n_devices // pp
    wrong = 2 ** log_wrong
    plan = make_plan(n_devices=n_devices, pp=pp, layers=2 * pp, m=m,
                     batch=8 * m, strategy=Strategy((("dp", wrong),)))
    rules = error_rules(verify_plan(plan))
    assert ("PLN002" in rules) == (wrong != group), rules


@settings(max_examples=30)
@given(st.sampled_from(["gpipe", "1f1b", "1f1b-interleaved", "zb-h1"]),
       st.integers(0, 3), st.integers(1, 8), st.integers(1, 2))
def test_fuzz_schedule_legality_matches_verifier(name, log_pp, m, V):
    """PLN004 fires exactly on the combos schedule_legal rejects."""
    from repro.analysis import schedule_legal
    pp = 2 ** log_pp
    plan = make_plan(n_devices=8 * pp, pp=pp, layers=4 * pp, schedule=name,
                     m=m, V=V, batch=8 * m)
    rules = error_rules(verify_plan(plan))
    assert ("PLN004" in rules) == (not schedule_legal(name, pp, m, V)), \
        (name, pp, m, V, rules)


# ---------------------------------------------------------------------------
# structural rules + version policy
# ---------------------------------------------------------------------------

def test_partition_rules():
    plan = make_plan()
    plan.partition = [3, 4]                      # sums to 7, not 8 layers
    assert "PLN003" in error_rules(verify_plan(plan))
    plan = make_plan()
    plan.partition = [8, 0]
    assert "PLN003" in error_rules(verify_plan(plan))


def test_missing_field_is_a_structured_diagnostic():
    d = make_plan().to_json()
    del d["partition"]
    with pytest.raises(DiagnosticError) as ei:
        load_plan_json(d)
    assert ei.value.rules() == ["PLN009"]
    assert any("partition" in x.location for x in ei.value.diagnostics)


def test_future_version_rejected():
    d = make_plan().to_json()
    d["format_version"] = PLAN_FORMAT_VERSION + 1
    assert error_rules(verify_plan_json(d)) == ["PLN001"]


def test_v0_plans_warn_by_default_and_fail_under_strict():
    d = make_plan().to_json()
    for k in ("format_version", "schedule", "vpp_degree", "est_iter_time",
              "est_throughput", "est_stage_mem", "alpha_t", "alpha_m",
              "searched_by", "search_stats"):
        d.pop(k, None)
    assert detect_format_version(d) == 0
    lax = verify_plan_json(d)
    assert "PLN001" in {x.rule for x in lax if x.severity == "warning"}
    assert "PLN001" not in error_rules(lax)
    assert "PLN001" in error_rules(verify_plan_json(d, strict=True))
    with pytest.raises(DiagnosticError):
        load_plan_json(d, strict=True)
    plan, _ = load_plan_json(d, strict=False)    # lax load still works
    assert (plan.schedule, plan.vpp_degree) == ("1f1b", 1)


def test_not_json_file_is_structured(tmp_path):
    p = tmp_path / "broken.plan.json"
    p.write_text("{not json")
    with pytest.raises(DiagnosticError) as ei:
        load_plan_file(str(p))
    assert ei.value.rules() == ["PLN009"]


# ---------------------------------------------------------------------------
# satellite: from_json / plan_bridge never leak bare KeyError
# ---------------------------------------------------------------------------

def test_from_json_raises_plan_format_error_naming_the_field():
    d = make_plan().to_json()
    del d["n_micro"]
    with pytest.raises(PlanFormatError) as ei:
        ParallelPlan.from_json(d)
    assert ei.value.field == "n_micro"
    assert "n_micro" in str(ei.value)
    # and never a bare KeyError
    with pytest.raises(ValueError):
        ParallelPlan.from_json({})


def test_from_json_rejects_future_version():
    d = make_plan().to_json()
    d["format_version"] = PLAN_FORMAT_VERSION + 5
    with pytest.raises(PlanFormatError) as ei:
        ParallelPlan.from_json(d)
    assert ei.value.field == "format_version"


def test_from_json_names_broken_strategy_entry():
    d = make_plan().to_json()
    d["strategies"][2] = {"levels": "zzz"}
    with pytest.raises(PlanFormatError) as ei:
        ParallelPlan.from_json(d)
    assert "strategies[2]" in ei.value.field


def test_plan_bridge_wraps_uncompilable_schedule():
    from repro.runtime.plan_bridge import schedule_program_from_plan
    plan = make_plan()
    plan.schedule = "1f1b-interleaved"           # vpp_degree stays 1
    with pytest.raises(DiagnosticError) as ei:
        schedule_program_from_plan(plan)
    assert "PLN004" in ei.value.rules()
    # legal plans compile through the bridge, with optional validation
    prog = schedule_program_from_plan(make_plan(), validate=True)
    assert prog.n_stages == 2
