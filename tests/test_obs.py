"""Telemetry-layer tests (DESIGN.md §10): span recorder semantics and
export round-trips, metrics registry snapshot/delta/exposition, the
retrace detector, the disabled-tracing overhead bound, the serve
engine's registry-backed stat views, and the exactly-once contract
between recovery-ladder rungs and their counters/trace events.

The overhead test is deterministic by design: instead of racing two
timed solves (noisy on shared CI), it counts the instrument sites a
traced solve actually hits, microbenches the disabled-path cost of one
site (an ``ACTIVE`` lookup + the shared no-op span), and bounds their
product against the solve's wall clock.
"""
import dataclasses
import json
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.psc import PSCConfig, p_spectral_cluster
from repro.graphs import ring_of_cliques, sbm_graph
from repro.grblas import mxm
from repro.obs import (DEFAULT, MetricsRegistry, NULL, TraceConfig, Tracer,
                       roofline_summary, use)
from repro.obs import trace as obs_trace
from repro.obs.retrace import (RetraceDetector, RetraceError,
                               assert_no_retrace)
from repro.serve.psc_engine import ClusterServeEngine
from repro.testing import nan_in_multivector

K = 4
# 2-level continuation ([1.7, 1.5]) — same recipe as tests/test_chaos.py
_KW = dict(k=K, newton_iters=8, tcg_iters=5, p_target=1.5, p_factor=0.85)


@pytest.fixture(scope="module")
def sbm():
    return sbm_graph([30] * K, 0.92, 0.03, seed=0)[0]


# ------------------------------------------------------------ span recorder

def test_span_nesting_and_chrome_round_trip():
    t = {"now": 0.0}
    tr = Tracer(TraceConfig(fence=False, clock=lambda: t["now"]))
    with use(tr):
        with tr.span("root", cat="test", n=4):
            t["now"] += 1.0
            with tr.span("child_a"):
                t["now"] += 0.25
            tr.instant("ping", x=1)
            with tr.span("child_b", note="b"):
                t["now"] += 0.5
            t["now"] += 0.25

    # spans land in exit order; nesting is reconstructed via parent/sid
    assert [s.name for s in tr.spans] == ["child_a", "child_b", "root"]
    root = tr.roots()[0]
    assert root.name == "root" and root.t0 == 0.0 and root.dur == 2.0
    kids = tr.children(root)
    assert [s.name for s in kids] == ["child_a", "child_b"]
    for s in kids:
        assert s.depth == 1 and s.parent == root.sid
        assert root.t0 <= s.t0
        assert s.t0 + s.dur <= root.t0 + root.dur
    assert kids[0].dur == 0.25 and kids[1].dur == 0.5

    # Chrome trace-event JSON: valid (json round-trip), "X" complete
    # events in microseconds, "i" instants, attrs under args
    doc = json.loads(json.dumps(tr.export_chrome()))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"root", "child_a", "child_b"}
    rx = next(e for e in xs if e["name"] == "root")
    assert rx["ts"] == 0.0 and rx["dur"] == 2.0e6
    assert rx["args"] == {"n": 4}
    inst = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert len(inst) == 1
    assert inst[0]["name"] == "ping" and inst[0]["args"] == {"x": 1}
    assert inst[0]["ts"] == 1.25e6          # stamped after child_a closed
    assert doc["otherData"]["dropped"] == 0

    # JSONL: one object per line, spans then events
    lines = [json.loads(ln) for ln in tr.export_jsonl().splitlines()]
    assert [ln["kind"] for ln in lines] == ["span"] * 3 + ["event"]
    assert lines[-1]["parent"] == root.sid


def test_bounded_buffer_drops_past_capacity():
    tr = Tracer(TraceConfig(capacity=4, fence=False))
    with use(tr):
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        for i in range(6):
            tr.instant(f"e{i}")
    assert len(tr.spans) == 4
    assert len(tr.events) == 4
    assert tr.dropped == 6 + 2


@pytest.fixture
def annotations(monkeypatch):
    """Replace ``jax.profiler.TraceAnnotation`` by a recorder of its
    enters and exits, in order."""
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    return log


def _nested_and_misnested(tr):
    with use(tr):
        with tr.span("root"):
            with tr.span("a"):
                pass
            outer, inner = tr.span("b"), tr.span("c")
            outer.__enter__()
            inner.__enter__()
            outer.__exit__(None, None, None)    # b exits while c is open
            inner.__exit__(None, None, None)    # c was unwound with b


def test_annotate_mirrors_each_span_as_a_profiler_annotation(annotations):
    tr = Tracer(TraceConfig(fence=False, annotate=True))
    _nested_and_misnested(tr)
    assert annotations == [("enter", "root"), ("enter", "a"), ("exit", "a"),
                           ("enter", "b"), ("enter", "c"), ("exit", "c"),
                           ("exit", "b"), ("exit", "root")]
    assert sorted(s.name for s in tr.spans) == ["a", "b", "c", "root"]
    assert tr._stack == [] and tr._annotations == []


def test_default_config_opens_no_profiler_annotation(annotations):
    tr = Tracer(TraceConfig(fence=False))
    _nested_and_misnested(tr)
    assert annotations == []
    assert sorted(s.name for s in tr.spans) == ["a", "b", "c", "root"]


def test_null_tracer_is_the_default_and_free():
    assert obs_trace.ACTIVE is NULL
    assert not NULL.enabled
    sp = obs_trace.ACTIVE.span("anything", cat="x", big=1)
    assert sp is obs_trace.NULL_SPAN
    with sp as s:
        assert s.set(a=1) is s
        assert s.fence(42) == 42


def test_session_ownership_nested_calls_share_the_outer_tracer():
    with obs_trace.session(True) as owner:
        assert owner is not None and obs_trace.ACTIVE is owner
        with obs_trace.session(True) as inner:       # nested: reuse outer
            assert inner is None
        with obs_trace.session(None) as off:
            assert off is None
    assert obs_trace.ACTIVE is NULL
    with obs_trace.session(False) as off:
        assert off is None and obs_trace.ACTIVE is NULL


# --------------------------------------------------------- traced pipeline

def test_traced_flat_pipeline_telemetry(sbm):
    cfg = PSCConfig(trace=True, **_KW)
    res = p_spectral_cluster(sbm, cfg)
    tel = res.telemetry
    assert tel is not None and tel.dropped == 0
    assert tel.root().name == "psc"
    ph = tel.phase_breakdown()
    assert {"init", "continuation", "kmeans"} <= set(ph)
    assert tel.coverage() >= 0.8
    # per-p solver levels carry the SolverReport facts
    levels = [s for s in tel.spans if s.name == "solver.level"]
    assert len(levels) == 2                  # the 2-level schedule
    assert all("n_apply" in s.attrs and "fval" in s.attrs for s in levels)
    # untraced run: telemetry is None, result identical
    res2 = p_spectral_cluster(sbm, dataclasses.replace(cfg, trace=None))
    assert res2.telemetry is None
    assert res2.rcut == res.rcut
    assert np.array_equal(np.asarray(res2.labels), np.asarray(res.labels))


def test_disabled_tracing_overhead_within_2pct(sbm):
    """ISSUE-9 acceptance: tracing off must cost the Newton hot loop
    <= 2%.  Deterministic form: (instrument sites a traced solve hits)
    x (measured disabled-path cost per site) <= 2% of the solve."""
    cfg = PSCConfig(trace=True, **_KW)
    t0 = time.perf_counter()
    res = p_spectral_cluster(sbm, cfg)
    wall = time.perf_counter() - t0
    n_sites = len(res.telemetry.spans) + len(res.telemetry.events)
    assert n_sites > 0

    assert obs_trace.ACTIVE is NULL
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with obs_trace.ACTIVE.span("x", cat="t", a=1) as sp:
            sp.fence(None)
    null_cost = (time.perf_counter() - t0) / reps

    budget = 0.02 * wall
    spent = n_sites * null_cost
    assert spent <= budget, (
        f"disabled-path overhead {spent * 1e6:.1f}us "
        f"({n_sites} sites x {null_cost * 1e9:.0f}ns) exceeds 2% of the "
        f"{wall:.2f}s solve ({budget * 1e6:.0f}us)")


def test_roofline_summary_from_mxm_spans():
    W, _ = ring_of_cliques(4, 8)
    X = jnp.asarray(np.random.default_rng(0).standard_normal(
        (W.n_rows, K)), jnp.float32)
    tr = Tracer(TraceConfig())
    with use(tr):
        mxm(W, X)                            # eager: emits grblas.mxm
    spans = [s for s in tr.spans if s.name == "grblas.mxm"]
    assert spans
    s0 = spans[0]
    assert s0.attrs["bytes"] > 0 and s0.attrs["nnz"] == W.nnz
    summ = roofline_summary(spans, peak_gbs=100.0)
    row = summ[s0.attrs["backend"]]
    assert row["calls"] == len(spans)
    assert row["gb_s"] > 0
    assert row["frac_of_peak"] == pytest.approx(row["gb_s"] / 100.0)


# --------------------------------------------------------- metrics registry

def test_metrics_snapshot_delta_and_exposition():
    reg = MetricsRegistry()
    reg.counter("req_total", lane="bucket").inc()
    reg.counter("req_total", lane="solo").inc(2)
    reg.gauge("depth").set(3)
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)

    snap = reg.snapshot()
    assert snap['req_total{lane="bucket"}'] == 1.0
    assert snap['req_total{lane="solo"}'] == 2.0
    assert snap["depth"] == 3.0
    assert snap["lat_s_count"] == 3.0
    assert snap["lat_s_sum"] == pytest.approx(5.55)
    assert snap['lat_s_bucket{le="0.1"}'] == 1.0
    assert snap['lat_s_bucket{le="1.0"}'] == 2.0
    assert snap['lat_s_bucket{le="+Inf"}'] == 3.0

    assert reg.total("req_total") == 3.0
    assert reg.labeled_values("req_total", "lane") == {"bucket": 1.0,
                                                       "solo": 2.0}

    prev = snap
    reg.counter("req_total", lane="solo").inc()
    assert reg.delta(prev) == {'req_total{lane="solo"}': 1.0}

    text = reg.exposition()
    assert "# TYPE req_total counter" in text
    assert "# TYPE depth gauge" in text
    assert "# TYPE lat_s histogram" in text
    assert 'req_total{lane="bucket"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert text.endswith("\n")

    with pytest.raises(TypeError):
        reg.gauge("req_total")               # type conflict is an error
    with pytest.raises(ValueError):
        reg.counter("req_total", lane="bucket").inc(-1)


# ---------------------------------------------------------- serve stat views

def test_engine_stats_and_cache_share_one_registry():
    cfg = PSCConfig(k=K, reorder="none", newton_iters=6, tcg_iters=4)
    eng = ClusterServeEngine(cfg, max_batch=4)
    W, _ = ring_of_cliques(4, 10)
    eng.serve([W])
    eng.serve([W])                           # exact-tier warm hit

    assert eng.cache.metrics is eng.metrics
    assert eng.stats.registry is eng.metrics
    assert eng.stats.n_requests == 2
    assert eng.metrics.value("serve_requests_total") == 2
    assert eng.cache.hits_exact == 1
    assert eng.metrics.value("warm_cache_hits_total", tier="exact") == 1
    assert eng.cache.stats()["misses"] == 1

    # back-compat mutation still lands on the counter
    eng.stats.n_churn += 1
    assert eng.metrics.value("serve_churn_total") == 1

    # failure taxonomy: one family, two views
    eng.stats.record_failure("exception")
    assert eng.stats.n_failed == 1
    assert eng.stats.failures == {"exception": 1}
    d = eng.stats.as_dict()
    assert d["n_failed"] == 1 and d["failures"] == {"exception": 1}
    assert list(d)[:3] == ["n_requests", "n_results", "n_batches"]

    snap = eng.metrics.snapshot()
    assert snap["serve_queue_depth"] == 0.0
    assert snap["serve_batch_occupancy_count"] == 2.0
    text = eng.exposition()
    assert "serve_requests_total 2" in text
    assert 'warm_cache_hits_total{tier="exact"} 1' in text


# ----------------------------------------------------------- retrace detector

def test_retrace_detector_catches_a_bucket_buster():
    # a solver signature no other test uses: the serve memo is global,
    # so this test's compiles must be its own
    cfg = PSCConfig(k=K, reorder="none", newton_iters=5, tcg_iters=3)
    eng = ClusterServeEngine(cfg, max_batch=4)
    Wa, _ = ring_of_cliques(4, 10)           # bucket (64, 512)

    det = RetraceDetector()
    eng.serve([Wa])                          # cold trace
    eng.serve([Wa])                          # warm trace (exact-tier hit)
    per_key = det.serve_buckets()
    assert len(per_key) == 2 and all(v == 1 for v in per_key.values())
    det.assert_at_most(1)

    # steady state: an exact replay compiles nothing
    with assert_no_retrace():
        eng.serve([Wa])

    # the buster: a different (n, nnz) lands in a NEW bucket — that
    # compile is exactly what the steady-state guard must catch
    Wb, _ = ring_of_cliques(4, 6)            # bucket (64, 128)
    with pytest.raises(RetraceError, match="retrace detected"):
        with assert_no_retrace():
            eng.serve([Wb])

    # compiles_total{site=} on DEFAULT moved with the detector
    assert DEFAULT.value("compiles_total", site="serve") >= 3


# --------------------------------------- recovery rungs: exactly-once + ids

def test_rung_counters_fire_exactly_once_and_correlate(sbm):
    """Every RungRecord the ladder produces increments
    ``recovery_rungs_total{rung=}`` exactly once, and the rung's trace
    instant carries the injection id of the fault that triggered it."""
    before = DEFAULT.snapshot()
    tr = Tracer(TraceConfig())
    with use(tr):
        with nan_in_multivector("newton", at_call=1,
                                max_calls=None) as log:
            res = p_spectral_cluster(sbm, PSCConfig(guard=True, **_KW))
    assert res.recovery is not None
    assert res.recovery.final_rung == "driver_switch"
    assert log.count() >= 2 and log.ids == sorted(log.ids)

    fired = {}
    for r in res.recovery.rungs:
        fired[r.rung] = fired.get(r.rung, 0) + 1
    assert fired                             # the ladder actually ran

    d = DEFAULT.delta(before)
    for rung, n in fired.items():
        key = f'recovery_rungs_total{{rung="{rung}"}}'
        assert d.get(key, 0.0) == n, (key, d)
    moved = {k for k in d if k.startswith("recovery_rungs_total")}
    assert moved == {f'recovery_rungs_total{{rung="{r}"}}' for r in fired}

    # fault instants and rung instants share the injection-id timeline
    faults = [e for e in tr.events
              if e["name"] == "fault.nan_in_multivector"]
    assert [e["attrs"]["injection_id"] for e in faults] == log.ids
    assert d.get('fault_injections_total{site="nan_in_multivector"}') \
        == len(log.ids)
    rung_evs = [e for e in tr.events if e["name"] == "recovery.rung"]
    assert len(rung_evs) == len(res.recovery.rungs)
    assert all(e["attrs"]["injection_id"] in log.ids for e in rung_evs)
    # the divergence that started the ladder is on the same timeline
    div = [e for e in tr.events if e["name"] == "solver.divergence"]
    assert div and div[0]["attrs"]["injection_id"] in log.ids
