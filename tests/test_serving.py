"""Serving subsystem end-to-end (repro.serving + launch/serve.py).

The load-bearing guarantee: the paged continuous-batching engine is
**token-identical** to the dense-cache greedy reference for a mixed-length
request batch — same params, same prompts, byte-equal generations — while
holding KV for only the tokens actually cached.  On top of that: v3 plan
JSON round-trips with the serving section, PLN010 lints serving fields
against mesh arithmetic, and the SLO-axis search emits plans that certify
and carry self-consistent serving geometry.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.common import ModelConfig

TINY = ModelConfig(name="tiny-serve", arch_type="dense", n_layers=2,
                   d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                   vocab_size=128)


def _mixed_requests(rng, n, *, min_len=1, max_len=10, max_new=(2, 8)):
    from repro.launch.serve import Request
    reqs = []
    for i in range(n):
        plen = int(rng.integers(min_len, max_len + 1))
        prompt = rng.integers(0, TINY.vocab_size, size=plen).tolist()
        reqs.append(Request(i, prompt, int(rng.integers(*max_new))))
    return reqs


# ---------------------------------------------------------------------------
# paged engine == dense reference (the end-to-end differential)
# ---------------------------------------------------------------------------

def test_paged_engine_token_identical_to_dense_reference():
    """Mixed-length prompts, more requests than lanes (slot recycling),
    ragged max_new: every request's generation must equal the dense-cache
    greedy oracle token for token."""
    from repro.launch.serve import serve, serve_paged
    from repro.serving import EngineConfig

    rng = np.random.default_rng(0)
    reqs_paged = _mixed_requests(rng, 7)
    reqs_dense = [dataclasses.replace(r) if dataclasses.is_dataclass(r)
                  else type(r)(r.rid, list(r.prompt), r.max_new)
                  for r in reqs_paged]

    ecfg = EngineConfig(page_size=4, n_pages=24, decode_slots=3,
                        max_context=24, prefill_batch=2, prefill_chunk=4)
    metrics = serve_paged(TINY, reqs_paged, ecfg, seed=0, verbose=False)
    # dense oracle: every lane gets the full context (no paging, no reuse)
    serve(TINY, reqs_dense, batch=3, context=24, seed=0, verbose=False)

    for rp, rd in zip(reqs_paged, reqs_dense):
        assert rp.generated == rd.generated, (
            f"req {rp.rid}: paged {rp.generated} != dense {rd.generated}")
        assert rp.done and rd.done
        assert len(rp.generated) == rp.max_new

    summ = metrics.summary()
    assert summ["completed"] == len(reqs_paged)
    assert summ["new_tokens"] == sum(r.max_new for r in reqs_paged)
    assert summ["decode_steps"] >= 1 and summ["prefill_chunks"] >= 1
    assert 0.0 < summ["page_occupancy_max"] <= 1.0
    assert summ["ttft_ms_p50"] >= 0.0


def test_engine_arrivals_queueing_and_metrics():
    """Requests arriving over time stay queued until their arrival;
    queue-depth and occupancy telemetry reflect the contention."""
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_lm
    from repro.serving import EngineConfig, ServeRequest, ServingEngine

    ecfg = EngineConfig(page_size=4, n_pages=8, decode_slots=2,
                        max_context=16, prefill_batch=2, prefill_chunk=4)
    params = jax.jit(lambda k: init_lm(k, TINY))(jax.random.PRNGKey(0))
    engine = ServingEngine(TINY, params, make_local_mesh(), ecfg)
    reqs = [ServeRequest(rid=f"r{i}", prompt=[3 + i, 5, 7], max_new=3,
                         arrival_s=0.0 if i < 2 else 0.01, deadline_ms=50.0)
            for i in range(5)]
    metrics = engine.run(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.tokens) == 3 for r in reqs)
    summ = metrics.summary()
    assert summ["completed"] == 5
    assert summ["queue_depth_max"] >= 1          # more requests than lanes
    assert 0.0 < metrics.page_occupancy_max <= 1.0
    assert metrics.page_occupancy_mean <= metrics.page_occupancy_max
    # per-request accounting: TTFT recorded before finish
    for rm in metrics.requests:
        assert rm.first_token_s is not None
        assert rm.finish_s >= rm.first_token_s
        assert rm.ttft_ms >= 0.0
        # TTFT counts from the due time; admission comes at or after it
        assert rm.arrival_s in (0.0, 0.01)
        assert rm.admitted_s >= rm.arrival_s
        assert rm.wait_ms >= 0.0
    assert summ["queue_wait_ms_p90"] >= summ["queue_wait_ms_p50"] >= 0.0


def _tiny_engine(ecfg):
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_lm
    from repro.serving import ServingEngine
    params = jax.jit(lambda k: init_lm(k, TINY))(jax.random.PRNGKey(0))
    return ServingEngine(TINY, params, make_local_mesh(), ecfg)


def _host_events(trace_dir):
    """``serve.*`` events of the profile's host threads:
    ``(thread, start_ns, end_ns, name, stats)``."""
    import pathlib
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(trace_dir).glob("**/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(line.name, e.start_ns, e.end_ns, e.name,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith("serve.")]
    return out


def test_engine_spans_and_counters(tmp_path):
    """Under the profiler every host phase appears as a ``serve.*`` span
    on the host plane, page-table work nested in a loop iteration, and
    admission carries its stats; a warm run compiles nothing, reads the
    device, and its phases' own times fit in its wall time."""
    from repro.serving import EngineConfig, ServeRequest
    from repro.serving.metrics import ServeMetrics

    ecfg = EngineConfig(page_size=4, n_pages=8, decode_slots=2,
                        max_context=16, prefill_batch=2, prefill_chunk=4)
    engine = _tiny_engine(ecfg)

    def reqs():
        return [ServeRequest(rid=f"r{i}", prompt=[3 + i, 5, 7, 9, 11],
                             max_new=4, arrival_s=0.0 if i < 2 else 0.5)
                for i in range(4)]

    assert engine.run(reqs()).compiles > 0                 # cold
    engine.metrics = ServeMetrics()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        m = engine.run(reqs())
    finally:
        jax.profiler.stop_trace()

    assert m.compiles == 0 and m.compile_s == 0.0
    assert m.host_syncs > 0
    assert sum(m.phase_s.values()) <= m.wall_s
    phases = {"round", "admit", "prefill", "decode", "page_table",
              "bookkeep", "wait"}
    assert set(m.phase_s) == set(m.phase_n) == phases
    assert m.phase_n["prefill"] == m.prefill_chunks
    assert m.phase_n["decode"] == m.decode_steps
    summ = m.summary()
    assert summ["compiles"] == 0 and summ["host_syncs"] == m.host_syncs
    assert summ["phase_s"] == m.phase_s

    ev = _host_events(tmp_path)
    assert {n for *_, n, _ in ev} == {"serve." + p for p in phases}
    rounds = [(t, s, e) for t, s, e, n, _ in ev if n == "serve.round"]
    for t, s, e, n, _ in ev:
        if n == "serve.page_table":
            assert any(t == rt and rs <= s and e <= re
                       for rt, rs, re in rounds), (s, e)
    admits = [st for *_, n, st in ev if n == "serve.admit"]
    assert sum(st["n"] for st in admits) == 4
    assert all("waited_ms" in st and st["waited_ms"] >= 0 for st in admits)
    assert all({"queue", "lanes"} <= set(st) for *_, n, st in ev
               if n == "serve.round")


def test_host_occupancy_matches_page_table():
    """The page occupancy the engine counts on the host equals the page
    table's own at every loop iteration of a run whose lanes cross page
    boundaries and whose pages are reused by later requests."""
    from repro.serving import EngineConfig, ServeRequest

    ecfg = EngineConfig(page_size=4, n_pages=8, decode_slots=2,
                        max_context=16, prefill_batch=2, prefill_chunk=4)
    engine = _tiny_engine(ecfg)
    seen = []
    sample = engine.metrics.sample

    def checked(queue_depth, occupancy):
        seen.append((occupancy, float(engine.pm.occupancy(engine.state))))
        sample(queue_depth, occupancy)

    engine.metrics.sample = checked
    reqs = [ServeRequest(rid=f"r{i}", prompt=[3 + i, 5, 7], max_new=8)
            for i in range(5)]                  # 3 pages each, 15 > 8 rows
    m = engine.run(reqs)
    assert all(r.done and len(r.tokens) == 8 for r in reqs)
    assert len(seen) == m.samples > 5
    for host, table in seen:
        assert host == pytest.approx(table)
    assert max(h for h, _ in seen) == pytest.approx(6 / 8)
    assert m.page_occupancy_max == pytest.approx(6 / 8)
    assert engine.pages_in_use() == 0


def test_engine_reads_the_device_only_for_argmax():
    """The page table lives on the host: over a run whose lanes cross page
    boundaries and whose pages are reused by later requests, the engine's
    only device-to-host reads are one ``argmax`` per decode round and one
    per prefill chunk."""
    from repro.serving import EngineConfig, ServeRequest

    ecfg = EngineConfig(page_size=4, n_pages=8, decode_slots=2,
                        max_context=16, prefill_batch=2, prefill_chunk=4)
    engine = _tiny_engine(ecfg)
    pm, granted = engine.pm, {"admit": [], "append": []}

    class Recording:
        """Delegates to the engine's page manager; records rows granted."""

        def __getattr__(self, name):
            return getattr(pm, name)

        def _record(self, kind, before, after):
            new = (after.page_rows >= 0) & (before.page_rows < 0)
            granted[kind] += after.page_rows[new].tolist()

        def admit(self, st, slot, prompt_len):
            st2, ok = pm.admit(st, slot, prompt_len)
            self._record("admit", st, st2)
            return st2, ok

        def ensure_append_capacity(self, st, want):
            st2, ok = pm.ensure_append_capacity(st, want)
            self._record("append", st, st2)
            return st2, ok

    engine.pm = Recording()
    reqs = [ServeRequest(rid=f"r{i}", prompt=[3 + i, 5, 7, 9, 11][:3 + i % 3],
                         max_new=8) for i in range(5)]   # 3-5 prompt tokens
    m = engine.run(reqs)
    assert all(r.done and len(r.tokens) == 8 for r in reqs)
    assert m.prefill_chunks > 3                 # 5-token prompts: 2 chunks
    assert m.host_syncs == m.decode_steps + m.prefill_chunks
    assert granted["append"]                    # lanes crossed a page edge
    all_rows = granted["admit"] + granted["append"]
    assert len(all_rows) > ecfg.n_pages         # rows went back and out again
    assert set(all_rows) <= set(range(ecfg.n_pages))
    assert int(pm.used_pages(engine.state)) == 0


def test_summary_reports_decode_attention_and_live_page_share():
    """``decode_attn`` names what the decode step resolved to when it was
    traced (the gather path on CPU), and stays reported by a later run
    with fresh metrics; ``live_page_share`` is the mean over decode rounds
    of each stepping lane's ceil(L / page size) pages over every lane's
    reserved pages, as the page table handed to the step says."""
    from repro.serving import EngineConfig, ServeRequest
    from repro.serving.metrics import ServeMetrics

    ecfg = EngineConfig(page_size=4, n_pages=12, decode_slots=3,
                        max_context=16, prefill_batch=2, prefill_chunk=4)
    engine = _tiny_engine(ecfg)
    decode, shares = engine._decode, []

    def recorded(params, pools, token, page_rows, lengths):
        L = np.maximum(np.asarray(lengths), 0)
        shares.append(np.sum(-(-L // 4)) / np.asarray(page_rows).size)
        return decode(params, pools, token, page_rows, lengths)

    engine._decode = recorded
    for run in range(2):
        shares.clear()
        engine.metrics = ServeMetrics()
        reqs = [ServeRequest(rid=f"r{i}", prompt=[3 + i] * (2 + 3 * i),
                             max_new=6) for i in range(4)]
        summ = engine.run(reqs).summary()
        assert summ["decode_attn"] == "gather", run
        assert summ["decode_steps"] == len(shares) > 0
        assert 0 < summ["live_page_share"] < 1
        assert summ["live_page_share"] == pytest.approx(np.mean(shares))


def test_engine_rejects_oversized_prompt_and_unsupported_arch():
    from repro.launch.mesh import make_local_mesh
    from repro.models import init_lm
    from repro.serving import EngineConfig, ServeRequest, ServingEngine

    ecfg = EngineConfig(page_size=4, n_pages=8, decode_slots=2,
                        max_context=8, prefill_batch=2, prefill_chunk=4)
    params = jax.jit(lambda k: init_lm(k, TINY))(jax.random.PRNGKey(0))
    engine = ServingEngine(TINY, params, make_local_mesh(), ecfg)
    with pytest.raises(ValueError, match="exceeds max_context"):
        engine.run([ServeRequest(rid="big", prompt=list(range(9)),
                                 max_new=2)])
    ssm_cfg = dataclasses.replace(TINY, arch_type="ssm", ssm_state=8)
    with pytest.raises(NotImplementedError, match="paged serving"):
        ServingEngine(ssm_cfg, params, make_local_mesh(), ecfg)


def test_supports_paged_decode_gate_values():
    """MoE decoders pass the gate; SSM/hybrid/enc-dec are gated out —
    the predicate docs/serving.md cross-links."""
    from repro.models.transformer import supports_paged_decode
    moe_cfg = dataclasses.replace(TINY, arch_type="moe", n_experts=4,
                                  top_k=2)
    assert supports_paged_decode(moe_cfg)
    assert not supports_paged_decode(
        dataclasses.replace(TINY, arch_type="ssm", ssm_state=8))
    assert not supports_paged_decode(
        dataclasses.replace(TINY, arch_type="hybrid", ssm_state=8,
                            attn_every=2))
    assert not supports_paged_decode(
        dataclasses.replace(TINY, is_encoder_decoder=True, n_enc_layers=2))


def test_engine_config_validates_geometry():
    from repro.serving import EngineConfig
    with pytest.raises(ValueError, match="multiple"):
        EngineConfig(page_size=16, max_context=40)
    assert EngineConfig(page_size=16, max_context=64).pages_per_slot == 4


# ---------------------------------------------------------------------------
# plan JSON v3: serving section round-trip + lint
# ---------------------------------------------------------------------------

def _serving_plan(**over):
    from repro.core import ParallelPlan, ServingSection, enumerate_strategies
    sv = dict(slo_ms=30.0, page_size=16, max_context=256, decode_batch=8,
              prefill_chunk=32, decode_tp=2, decode_pp=2, prefill_tp=4,
              prefill_pp=1, kv_pool_pages=128)
    sv.update(over)
    s = enumerate_strategies(4)[0]
    return ParallelPlan(
        n_devices=8, pp_degree=2, partition=[4, 4], strategies=[s] * 8,
        global_batch=32, n_micro=4, schedule="1f1b",
        serving=ServingSection(**sv))


def test_v3_serving_roundtrip():
    from repro.core import PLAN_FORMAT_VERSION, ParallelPlan
    plan = _serving_plan()
    d = json.loads(plan.dumps())
    assert d["format_version"] == PLAN_FORMAT_VERSION == 5
    back = ParallelPlan.from_json(d)
    assert back.serving == plan.serving
    assert back.canonical_dumps() == plan.canonical_dumps()


def test_v2_plans_still_load_with_no_serving():
    from repro.core import ParallelPlan
    plan = _serving_plan()
    d = json.loads(plan.dumps())
    del d["serving"]
    d["format_version"] = 2
    back = ParallelPlan.from_json(d)
    assert back.serving is None


def test_detect_format_version_serving():
    from repro.analysis import detect_format_version
    d = json.loads(_serving_plan().dumps())
    assert detect_format_version(d) == 5
    d.pop("format_version")
    # unstamped + default sp_degree/seq_len: the serving section implies v3
    assert detect_format_version(d) == 3


def test_pln010_valid_serving_plan_certifies():
    from repro.analysis import verify_plan
    diags = verify_plan(_serving_plan())
    assert not [d for d in diags if d.severity == "error"], \
        [d.format() for d in diags]


@pytest.mark.parametrize("over,field", [
    (dict(decode_tp=3, decode_pp=2), "decode_tp"),       # 6 does not | 8
    (dict(prefill_tp=5), "prefill_tp"),
    (dict(decode_tp=0), "decode_tp"),
    (dict(page_size=0), "page_size"),
    (dict(max_context=250), "max_context"),              # not page multiple
    (dict(decode_batch=0), "decode_batch"),
    (dict(kv_pool_pages=4), "kv_pool_pages"),            # < decode_batch
    (dict(prefill_chunk=0), "prefill_chunk"),
    (dict(slo_ms=0.0), "slo_ms"),
])
def test_pln010_rejects_bad_serving_fields(over, field):
    from repro.analysis import verify_plan
    diags = verify_plan(_serving_plan(**over))
    errs = [d for d in diags if d.severity == "error" and d.rule == "PLN010"]
    assert errs, f"expected PLN010 error for {over}"
    assert any(field in d.location for d in errs), \
        [d.format() for d in errs]


def test_pln010_warnings():
    from repro.analysis import verify_plan
    # non-power-of-two page size and SLO-exceeding prediction warn
    diags = verify_plan(_serving_plan(page_size=12, max_context=240,
                                      est_tok_ms=45.0))
    warns = [d for d in diags if d.rule == "PLN010"
             and d.severity == "warning"]
    assert {("page_size" in d.location) or ("est_tok_ms" in d.location)
            for d in warns} == {True}
    assert len(warns) == 2


# ---------------------------------------------------------------------------
# SLO-axis search
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def slo_points():
    from repro.core import galvatron_variant, paper_8gpu
    from repro.core.layerspec import dense_layer
    from repro.serving import ServingPlanSearch

    specs = [dense_layer(f"l{i}", 512, 1024, 16, 16, 4096,
                         store_attn_matrix=True) for i in range(8)]
    cfg = galvatron_variant("bmw")
    cfg.batch_grid = [8, 16]
    cfg.n_bins = 64
    cfg.micro_candidates = 2
    search = ServingPlanSearch(specs, paper_8gpu(), config=cfg)
    points, frontier = search.sweep_slos([20.0, 60.0], max_context=512)
    return search, points, frontier


def test_slo_sweep_emits_certifying_v3_plans(slo_points):
    from repro.analysis import verify_plan_json
    search, points, frontier = slo_points
    assert len(points) == 2
    feasible = [p for p in points if p.feasible]
    assert feasible, "no SLO point feasible on the 8-GPU paper cluster"
    for pt in feasible:
        d = json.loads(pt.plan.dumps())
        assert d["format_version"] == 5
        diags = verify_plan_json(d)
        assert not [x for x in diags if x.severity == "error"], \
            [x.format() for x in diags]
        sv = pt.plan.serving
        assert sv.slo_ms == pt.slo_ms
        assert sv.max_context % sv.page_size == 0
        assert sv.kv_pool_pages >= sv.decode_batch
        assert sv.decode_tp * sv.decode_pp <= pt.plan.n_devices
        assert sv.est_tok_per_s > 0


def test_slo_budget_mapping_monotone(slo_points):
    """A looser SLO is a larger per-step byte budget, and the derived
    decode batch never shrinks as the SLO loosens."""
    search, points, frontier = slo_points
    assert points[1].budget_bytes > points[0].budget_bytes
    if points[0].feasible and points[1].feasible:
        assert (points[1].plan.serving.decode_batch
                >= points[0].plan.serving.decode_batch)


def test_serving_stats_exact_vs_heuristic():
    """from_model_config (exact) and from_layer_specs (heuristic from the
    boundary bytes) must agree on the order of magnitude of KV traffic."""
    from repro.configs import get_config
    from repro.configs.specs import layerspecs_for
    from repro.serving import ServingModelStats

    cfg = get_config("qwen3-4b")
    exact = ServingModelStats.from_model_config(cfg)
    heur = ServingModelStats.from_layer_specs(layerspecs_for(cfg, 1024))
    assert exact.param_bytes > 0 and exact.kv_bytes_per_token > 0
    assert heur.kv_bytes_per_token > 0
    ratio = exact.kv_bytes_per_token / heur.kv_bytes_per_token
    assert 0.05 < ratio < 20.0
