"""Clustering-as-a-service bench (DESIGN.md §8): the committed evidence
for the serve engine's three contracts, written to BENCH_serve.json at
the repo root by ``make bench-serve``.

  1. trace economy — a mixed-size stream of >= 20 requests compiles
     exactly one trace per (bucket, mode) signature, asserted through
     ``repro.obs.retrace.RetraceDetector`` (which reads the solver
     registry's trace log), with the stream's wall clock broken down
     by serve-layer spans;
  2. warm >= 3x cold — an exact-tier cache hit (solver re-entry at the
     schedule tail) beats the full cold continuation by >= 3x wall
     clock at equal RCut (within 1%), measured steady-state (traces
     primed on separate graphs, per-request time = batch solve time /
     batch size);
  3. churn >= 2x scratch — an ``engine.update`` incremental re-cluster
     of a 1%-edge-churned graph beats a from-scratch cold solve of the
     edited graph by >= 2x within 2% RCut.

Every section raises on a violated bound, so a regression fails the
bench run rather than silently committing worse numbers.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

from repro.core import PSCConfig
from repro.core.solvers import registry
from repro.graphs import ring_of_cliques, sbm_graph
from repro.obs import TraceConfig, Tracer, use as use_tracer
from repro.obs.retrace import RetraceDetector
from repro.serve import ClusterServeEngine, EdgeDelta, apply_edge_delta, \
    bucket_for

K = 4


def _cfg(**kw):
    kw.setdefault("k", K)
    kw.setdefault("reorder", "none")
    kw.setdefault("newton_iters", 20)
    kw.setdefault("tcg_iters", 12)
    kw.setdefault("kmeans_restarts", 4)
    return PSCConfig(**kw)


def _reweighted(W, scale):
    return W.with_vals(np.asarray(W.vals) * scale)


def _serve_traces():
    return sum(1 for t in registry.SOLVER_TRACES if t and t[0] == "serve")


# --------------------------------------------------------------- section 1

def bench_stream(n_requests=24):
    """Mixed-size stream: one compiled trace per bucket, counted."""
    cfg = _cfg()
    Wa, _ = ring_of_cliques(4, 10)                   # bucket (64, 512)
    Wb, _ = ring_of_cliques(4, 6)                    # bucket (64, 128)
    stream = [_reweighted(Wa, 1.0 + 0.01 * i) for i in range(12)]
    stream += [_reweighted(Wb, 1.0 + 0.01 * i) for i in range(8)]
    stream += [sbm_graph([16] * 4, 0.25, 0.02, seed=i)[0] for i in range(4)]
    stream = stream[:n_requests]
    expected = {bucket_for(W, K, "cold").key for W in stream}

    eng = ClusterServeEngine(cfg, max_batch=8)
    det = RetraceDetector()
    tr = Tracer(TraceConfig())
    with use_tracer(tr):
        results = eng.serve(stream)
    # acceptance: exactly one compile per (bucket, solver) memo key —
    # a second compile of ANY serve key is a retrace and raises
    per_key = det.serve_buckets()
    det.assert_at_most(1)
    traces = sum(per_key.values())

    row = {
        "n_requests": len(stream),
        "n_buckets": len(expected),
        "buckets": sorted(str(k) for k in expected),
        "traces_compiled": traces,
        "compiles_per_bucket": {str(k): v for k, v in per_key.items()},
        "engine_traces": eng.stats.traces,
        "n_batches": eng.stats.n_batches,
        "mean_rcut": round(float(np.mean([r.rcut for r in results])), 4),
        "span_s": {name: round(sec, 4)
                   for name, sec in sorted(tr.by_name().items())},
        "one_trace_per_bucket": traces == len(expected)
        and all(v == 1 for v in per_key.values()),
    }
    assert row["one_trace_per_bucket"], row
    return row


# --------------------------------------------------------------- section 2

def bench_warm_vs_cold(n_measure=12, batch=4):
    """Steady-state per-request time: cold continuation vs exact-tier
    warm re-entry, same bucket, traces primed out-of-band."""
    cfg = _cfg()
    eng = ClusterServeEngine(cfg, max_batch=batch)
    primers = [sbm_graph([32] * 4, 0.3, 0.01, seed=100 + i)[0]
               for i in range(batch)]
    measured = [sbm_graph([32] * 4, 0.3, 0.01, seed=i)[0]
                for i in range(n_measure)]
    specs = {bucket_for(W, K, "cold").key for W in primers + measured}
    assert len(specs) == 1, f"measurement must stay in one bucket: {specs}"

    eng.serve(primers)                               # compile cold trace
    cold = eng.serve(measured)
    assert all(r.stats.mode == "cold" and not r.stats.trace_new
               for r in cold)
    eng.serve(primers)                               # compile warm trace
    warm = eng.serve(measured)
    assert all(r.stats.mode == "warm" and r.stats.cache_tier == "exact"
               and not r.stats.trace_new for r in warm)

    cold_s = float(np.mean([r.stats.solve_s / r.stats.batch_size
                            for r in cold]))
    warm_s = float(np.mean([r.stats.solve_s / r.stats.batch_size
                            for r in warm]))
    rel = [abs(w.rcut - c.rcut) / max(c.rcut, 1e-12)
           for c, w in zip(cold, warm)]
    row = {
        "n_measured": n_measure, "batch": batch,
        "bucket": str(next(iter(specs))),
        "cold_s_per_graph": round(cold_s, 4),
        "warm_s_per_graph": round(warm_s, 4),
        "speedup": round(cold_s / warm_s, 2),
        "rcut_rel_diff_max": round(max(rel), 5),
        "warm_ge_3x_at_equal_rcut": cold_s / warm_s >= 3.0
        and max(rel) <= 0.01,
    }
    assert row["warm_ge_3x_at_equal_rcut"], row
    return row


# --------------------------------------------------------------- section 3

def _flip_delta(W, frac, seed):
    rng = np.random.default_rng(seed)
    und = np.flatnonzero(np.asarray(W.rows) < np.asarray(W.cols))
    pick = rng.choice(und, max(1, int(frac * len(und))), replace=False)
    return EdgeDelta(np.asarray(W.rows)[pick], np.asarray(W.cols)[pick],
                     np.zeros(len(pick)))


def bench_churn(frac=0.01):
    """1% edge knockouts on a served SBM: engine.update's incremental
    re-cluster vs a from-scratch cold solve of the edited graph."""
    cfg = _cfg()
    W, _ = sbm_graph([40] * 4, 0.25, 0.02, seed=0)

    eng = ClusterServeEngine(cfg, max_batch=1)
    eng.serve([W])                                   # prime cold + cache
    rid = eng.update(W, _flip_delta(W, frac, seed=1))
    eng.flush().pop(rid)                             # prime the warm trace
    delta = _flip_delta(W, frac, seed=2)
    rid = eng.update(W, delta)
    churn = eng.flush()[rid]
    assert churn.stats.mode == "churn"

    W_new = apply_edge_delta(W, delta).W
    scratch_eng = ClusterServeEngine(cfg, max_batch=1)
    scratch = scratch_eng.serve([W_new])[0]
    assert scratch.stats.mode == "cold" and not scratch.stats.trace_new

    row = {
        "n": W.n_rows, "nnz": W.nnz,
        "edges_flipped": len(delta.rows),
        "churn_s": round(churn.stats.solve_s, 4),
        "scratch_s": round(scratch.stats.solve_s, 4),
        "speedup": round(scratch.stats.solve_s / churn.stats.solve_s, 2),
        "rcut_churn": round(churn.rcut, 4),
        "rcut_scratch": round(scratch.rcut, 4),
        "churn_ge_2x_within_2pct": scratch.stats.solve_s
        >= 2.0 * churn.stats.solve_s
        and churn.rcut <= scratch.rcut * 1.02 + 1e-12,
    }
    assert row["churn_ge_2x_within_2pct"], row
    return row


# ------------------------------------------------------------------- driver

def main(out_path=Path("BENCH_serve.json")):
    payload = {
        "bench": "psc_serve_engine",
        "schema": 2,
        "config": {"k": K, "solver": "newton", "newton_iters": 20,
                   "tcg_iters": 12, "p_target": 1.2},
        "stream": bench_stream(),
        "warm_vs_cold": bench_warm_vs_cold(),
        "churn": bench_churn(),
    }
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    print(json.dumps(payload, indent=2))
    return payload


if __name__ == "__main__":
    main()
