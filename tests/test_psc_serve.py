"""ClusterServeEngine (DESIGN.md §8): bucketing invariants, padded-batch
solve == flat pipeline (label-exact), one compiled trace per bucket
across a mixed request stream, admission/deadline/lane behavior, and the
hierarchy-patch invariants behind incremental re-clustering."""
import dataclasses
import time

import numpy as np
import jax
import pytest

from repro.core import PSCConfig, metrics, p_spectral_cluster
from repro.core.solvers import registry
from repro.grblas.containers import SparseMatrix
from repro.graphs import delaunay_graph, ring_of_cliques, sbm_graph
from repro.serve import (BucketSpec, ClusterServeEngine, assemble_batch,
                         bucket_for, next_pow2)
from repro.serve.bucketing import pad_embeddings


def _cfg(**kw):
    kw.setdefault("k", 4)
    kw.setdefault("reorder", "none")
    kw.setdefault("newton_iters", 20)
    kw.setdefault("tcg_iters", 12)
    kw.setdefault("kmeans_restarts", 4)
    return PSCConfig(**kw)


def _reweighted(W, scale):
    """Same pattern, distinct quantized weights (a fresh fingerprint)."""
    return W.with_vals(np.asarray(W.vals) * scale)


# ------------------------------------------------------------ bucketing unit

def test_next_pow2():
    assert next_pow2(1) == 1
    assert next_pow2(2) == 2
    assert next_pow2(3) == 4
    assert next_pow2(1025) == 2048
    assert next_pow2(3, floor=64) == 64
    assert next_pow2(0) == 1


def test_bucket_for_lattice_and_floors():
    W, _ = ring_of_cliques(4, 10)          # n=40, nnz=368
    spec = bucket_for(W, 4, "cold")
    assert spec == BucketSpec(n=64, nnz=512, k=4, mode="cold")
    assert spec.key == ("serve", "cold", 64, 512, 4)
    # floors dominate tiny graphs
    tiny = SparseMatrix.from_coo([0, 1], [1, 0], [1.0, 1.0], (2, 2))
    spec = bucket_for(tiny, 2, "warm")
    assert (spec.n, spec.nnz) == (64, 128)
    rect = SparseMatrix.from_coo([0], [1], [1.0], (2, 3))
    with pytest.raises(ValueError, match="square"):
        bucket_for(rect, 2, "cold")


def test_padded_coo_contract():
    W, _ = ring_of_cliques(4, 10)
    r, c, v = W.padded_coo(64, 512)
    assert r.shape == c.shape == v.shape == (512,)
    # real prefix is the graph's own COO
    np.testing.assert_array_equal(r[:W.nnz], np.asarray(W.rows))
    np.testing.assert_array_equal(v[:W.nnz], np.asarray(W.vals))
    # pads are exactly (0, 0, 0.0) — the PR-5 soundness contract
    assert (r[W.nnz:] == 0).all() and (c[W.nnz:] == 0).all()
    assert (v[W.nnz:] == 0.0).all()
    with pytest.raises(ValueError):
        W.padded_coo(32, 512)              # n does not fit
    with pytest.raises(ValueError):
        W.padded_coo(64, 256)              # nnz does not fit


def test_assemble_batch_shapes_and_mask():
    Wa, _ = ring_of_cliques(4, 10)         # n=40
    Wb, _ = ring_of_cliques(4, 6)          # n=24
    spec = BucketSpec(n=64, nnz=512, k=4, mode="cold")
    batch = assemble_batch([Wa, Wb], spec)
    assert batch.rows.shape == batch.cols.shape == batch.vals.shape \
        == (2, 512)
    assert batch.mask.shape == (2, 64)
    assert batch.n_real == (40, 24)
    np.testing.assert_array_equal(batch.mask[0], (np.arange(64) < 40))
    np.testing.assert_array_equal(batch.mask[1], (np.arange(64) < 24))


def test_pad_embeddings_validation():
    spec = BucketSpec(n=64, nnz=128, k=4, mode="warm")
    U = np.ones((40, 4))
    out = pad_embeddings([U], spec)
    assert out.shape == (1, 64, 4)
    assert (out[0, 40:] == 0.0).all()
    with pytest.raises(ValueError):
        pad_embeddings([np.ones((40, 3))], spec)      # wrong k
    with pytest.raises(ValueError):
        pad_embeddings([np.ones((100, 4))], spec)     # does not fit n


# --------------------------------------------------- pad invariance vs flat

@pytest.mark.parametrize("solver", ["newton", "scf"])
@pytest.mark.parametrize("flat_backend", ["coo", "sellcs"])
def test_bucketed_solve_matches_flat(solver, flat_backend):
    """A padded, vmapped bucket solve returns the SAME labels and RCut
    as the flat pipeline on the bare graph — across both bucketable
    drivers and against flat solves on both the coo and the SELL-C-σ
    backend (the latter exercising the Alg-1 (nnz, k) multivalue path
    under the default hvp_mode="graphblas")."""
    W, _ = ring_of_cliques(4, 10)
    if flat_backend == "sellcs":
        W = SparseMatrix.from_coo(W.rows, W.cols, W.vals,
                                  (W.n_rows, W.n_cols), build_sellcs=True)
    cfg = _cfg(solver=solver, backend=flat_backend)
    flat = p_spectral_cluster(W, cfg)

    eng = ClusterServeEngine(dataclasses.replace(cfg, backend="auto"))
    res = eng.serve([W])[0]
    np.testing.assert_array_equal(res.labels, np.asarray(flat.labels))
    assert res.rcut == pytest.approx(flat.rcut, rel=1e-9)
    assert res.stats.lane == "bucket"
    assert res.stats.mode == "cold"
    assert res.stats.bucket == ("serve", "cold", 64, 512, 4)


# ------------------------------------------------- the bucket lane's layout

def _loop_ops(jaxpr, depth=0, out=None):
    """(while-nesting depth, primitive name, input shapes) of every
    equation, descending into every sub-jaxpr (jit, scan, while, cond)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out.append((depth, name,
                    [tuple(getattr(v.aval, "shape", ())) for v in eqn.invars]))
        inner = depth + (name == "while")
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                    _loop_ops(sub.jaxpr, inner, out)
    return out


def _bucket_solver_ops(n, nnz, **kw):
    """The cold bucket solver of an (n, nnz, k 4) bucket, traced at a
    batch of 8 from abstract shapes (no compile, nothing runs)."""
    from repro.serve import psc_engine

    spec = BucketSpec(n=n, nnz=nnz, k=4, mode="cold")
    solver, _ = psc_engine._bucket_solver(spec, _cfg(**kw))
    idx = jax.ShapeDtypeStruct((8, nnz), np.int32)
    jaxpr = jax.make_jaxpr(solver)(
        idx, idx, jax.ShapeDtypeStruct((8, nnz), np.float32),
        jax.ShapeDtypeStruct((8, n), np.float32),
        jax.ShapeDtypeStruct((8, n, 4), np.float32))
    return psc_engine._bucket_layout(spec, _cfg(**kw)), _loop_ops(jaxpr.jaxpr)


def _vertex_loop_ops(ops, n, nnz):
    """Names of the loop-body equations whose inputs carry the vertex
    axis (n) or the edge axis (nnz): the p-Laplacian's work.  The
    Newton step's own k x k algebra (the QR retraction's diagonal read)
    is not the layout's and stays out."""
    return {name for depth, name, shapes in ops if depth >= 1
            and any(d in (n, nnz) for shape in shapes for d in shape)}


@pytest.mark.parametrize("hvp_mode", ["graphblas", "matrix_free"])
def test_dense_bucket_loops_have_no_gather_or_scatter(hvp_mode):
    """At n_b 128 (the stream cell's bucket) the Newton and tCG loops
    run on the dense tile: no gather and no scatter-add over the vertex
    or edge axis in any while body (the tile's one scatter-add sits
    outside, once per launch).  Under "graphblas" the tCG body holds no
    transcendental over the (B, k, n, n) tile: W-hat is built once per
    Newton step."""
    layout, ops = _bucket_solver_ops(128, 2048, hvp_mode=hvp_mode)
    assert layout == "dense"
    assert "while" in {name for _, name, _ in ops}
    assert not (_vertex_loop_ops(ops, 128, 2048)
                & {"gather", "scatter-add", "scatter"})
    assert [name for depth, name, _ in ops
            if depth == 0 and name == "scatter-add"] == ["scatter-add"]
    tile = (8, 4, 128, 128)
    tcg_pows = [shapes for depth, name, shapes in ops
                if depth == 2 and name == "pow" and tile in shapes]
    assert (tcg_pows == []) == (hvp_mode == "graphblas")


def test_bucket_above_cutoff_traces_coo():
    """Above DENSE_MAX_N the bucket lane keeps the COO segment path."""
    from repro.grblas.containers import DENSE_MAX_N

    n, nnz = 2 * DENSE_MAX_N, 16384
    layout, ops = _bucket_solver_ops(n, nnz)
    assert layout == "coo"
    assert {"gather", "scatter-add"} <= _vertex_loop_ops(ops, n, nnz)
    assert not any(len(shapes) and len(shapes[0]) == 4
                   for _, _, shapes in ops)


def test_bucket_launch_counter_and_span_name_the_layout():
    """One ``serve_bucket_launches_total{layout=...}`` count and one
    ``serve.bucket_solve`` span ``layout`` per launch: dense for a Newton
    bucket, coo for the scf step."""
    from repro.obs import TraceConfig, Tracer, use

    W, _ = ring_of_cliques(4, 10)
    tr = Tracer(TraceConfig())
    with use(tr):
        eng = ClusterServeEngine(_cfg(newton_iters=4, tcg_iters=3),
                                 max_batch=2)
        done = eng.serve([_reweighted(W, 1.0 + 0.01 * i) for i in range(3)])
        scf = ClusterServeEngine(_cfg(solver="scf", scf_sweeps=2))
        scf.serve([W])
    assert all(r.ok for r in done) and eng.stats.n_batches == 2
    launches = eng.metrics.labeled_values("serve_bucket_launches_total",
                                          "layout")
    assert launches == {"dense": 2}
    assert scf.metrics.labeled_values("serve_bucket_launches_total",
                                      "layout") == {"coo": 1}
    spans = [s.attrs["layout"] for s in tr.spans
             if s.name == "serve.bucket_solve"]
    assert spans == ["dense", "dense", "coo"]
    assert 'serve_bucket_launches_total{layout="dense"} 2' in \
        eng.exposition()


def test_solo_lane_matches_flat_exactly():
    """Below-threshold bucket cap forces the solo lane, which IS the
    flat pipeline — bit-identical result."""
    W, _ = ring_of_cliques(4, 10)
    cfg = _cfg()
    flat = p_spectral_cluster(W, cfg)
    eng = ClusterServeEngine(cfg, max_bucket_n=16)
    res = eng.serve([W])[0]
    assert res.stats.lane == "solo"
    np.testing.assert_array_equal(res.labels, np.asarray(flat.labels))
    assert res.rcut == flat.rcut
    assert eng.stats.n_solo == 1


def test_unbucketable_solver_routes_solo():
    W, _ = ring_of_cliques(4, 10)
    cfg = _cfg(solver="inverse_power", p_target=1.2, ipm_iters=40)
    eng = ClusterServeEngine(cfg)
    res = eng.serve([W])[0]
    assert res.stats.lane == "solo"
    acc = len(np.unique(res.labels))
    assert acc == 4


# --------------------------------------------------------- trace accounting

def test_one_trace_per_bucket_mixed_stream():
    """>= 20 mixed-size cold requests over two buckets compile exactly
    two serve traces (one per bucket), observable both through the
    registry trace log and EngineStats."""
    Wa, _ = ring_of_cliques(4, 10)         # bucket (64, 512)
    Wb, _ = ring_of_cliques(4, 6)          # bucket (64, 128)
    # unique solver signature so this test owns its trace keys
    cfg = _cfg(solver="scf", scf_sweeps=7, grad_tol=1.07e-5)
    eng = ClusterServeEngine(cfg, max_batch=8)
    rids = []
    for i in range(12):
        rids.append(eng.submit(_reweighted(Wa, 1.0 + 0.01 * i)))
    for i in range(8):
        rids.append(eng.submit(_reweighted(Wb, 1.0 + 0.01 * i)))
    assert len(rids) == 20

    def serve_traces():
        return sum(1 for t in registry.SOLVER_TRACES
                   if t and t[0] == "serve" and 1.07e-5 in t)

    before = serve_traces()
    done = eng.flush()
    assert len(done) == 20
    assert serve_traces() - before == 2     # one per bucket, ever
    assert eng.stats.traces == 2
    assert eng.stats.n_batches == 3         # ceil(12/8) + ceil(8/8)
    # only the compiling batch of each bucket reports trace_new
    new_flags = [done[r].stats.trace_new for r in rids]
    assert sum(new_flags) == 8 + 8          # first batch of each bucket
    # a second wave on fresh weights warm-hits the pattern tier: the
    # only new compile is the warm-mode signature, once
    more = [eng.submit(_reweighted(Wa, 2.0 + 0.01 * i)) for i in range(8)]
    done = eng.flush()
    assert serve_traces() - before == 3
    assert eng.stats.traces == 3
    assert all(done[r].stats.mode == "warm" for r in more)


# ----------------------------------------------------------- warm-start path

def test_warm_exact_hit_reproduces_labels():
    W, _ = ring_of_cliques(4, 10)
    cfg = _cfg()
    eng = ClusterServeEngine(cfg)
    cold = eng.serve([W])[0]
    assert cold.stats.mode == "cold" and cold.stats.cache_tier is None
    warm = eng.serve([W])[0]
    assert warm.stats.mode == "warm"
    assert warm.stats.cache_tier == "exact"
    assert warm.stats.bucket[1] == "warm"   # separate trace signature
    np.testing.assert_array_equal(warm.labels, cold.labels)
    assert warm.rcut == pytest.approx(cold.rcut, rel=1e-9)
    assert eng.cache.hits_exact == 1


def test_warm_pattern_tier_on_reweighted_graph():
    W, _ = ring_of_cliques(4, 10)
    eng = ClusterServeEngine(_cfg())
    cold = eng.serve([W])[0]
    res = eng.serve([_reweighted(W, 1.5)])[0]
    assert res.stats.mode == "warm"
    assert res.stats.cache_tier == "pattern"
    # uniform scaling preserves the optimal partition
    np.testing.assert_array_equal(res.labels, cold.labels)
    assert eng.cache.hits_pattern == 1


# ----------------------------------------------------- queueing + admission

def test_poll_respects_deadline_and_batch_trigger():
    W, _ = ring_of_cliques(4, 10)
    eng = ClusterServeEngine(_cfg(), max_batch=4, max_wait_s=3600.0)
    rid = eng.submit(W)
    assert eng.poll() == {}                 # not due: queue open
    # a full bucket launches regardless of the deadline
    more = [eng.submit(_reweighted(W, 1.0 + 0.01 * i)) for i in range(3)]
    done = eng.poll()
    assert set(done) == {rid, *more}
    assert done[rid].stats.batch_size == 4
    # deadline expiry launches a partial batch
    late = eng.submit(_reweighted(W, 9.0))
    assert late not in eng.poll()
    done = eng.poll(now=time.monotonic() + 3601.0)
    assert late in done and done[late].stats.batch_size == 1


def test_flush_drains_and_take_pops():
    W, _ = ring_of_cliques(4, 10)
    eng = ClusterServeEngine(_cfg(), max_batch=8, max_wait_s=3600.0)
    rids = [eng.submit(_reweighted(W, 1.0 + 0.01 * i)) for i in range(3)]
    done = eng.flush()
    assert set(done) == set(rids)
    first = eng.take(rids[0])
    assert first.req_id == rids[0]
    with pytest.raises(KeyError):
        eng.take(rids[0])
    assert eng.stats.n_requests == 3 and eng.stats.n_results == 3


def test_serve_returns_submission_order():
    Wa, _ = ring_of_cliques(4, 10)
    Wb, _ = ring_of_cliques(4, 6)
    eng = ClusterServeEngine(_cfg())
    out = eng.serve([Wa, Wb, _reweighted(Wa, 1.1)])
    assert [r.stats.n for r in out] == [40, 24, 40]
    assert [r.req_id for r in out] == sorted(r.req_id for r in out)


def test_engine_rejects_reordering_config():
    with pytest.raises(ValueError, match="reorder"):
        ClusterServeEngine(_cfg(reorder="rcm"))


# ----------------------------------------- hierarchy patching (churn lane)

def test_patch_hierarchy_invariants():
    """Patching after a localized edge edit keeps the multilevel
    invariants — partition of unity per prolongator, finest volume/count
    conservation per level — and reuses aggregates away from the edit."""
    from repro.multilevel import build_hierarchy, patch_hierarchy
    from repro.serve import EdgeDelta, apply_edge_delta

    W, _ = delaunay_graph(9, seed=3)                 # n=512, local edits
    hier = build_hierarchy(W, coarse_size=64, max_levels=4)
    assert hier.n_levels >= 3

    rng = np.random.default_rng(0)
    i = rng.integers(0, W.n_rows, 3)
    j = (i + 1 + rng.integers(0, W.n_rows - 1, 3)) % W.n_rows
    delta = EdgeDelta(i, j, np.full(3, 2.0))         # mostly insertions
    d = apply_edge_delta(W, delta)
    assert d.pattern_changed

    patched, records = patch_hierarchy(hier, d.W, d.touched)
    assert patched.n_levels == hier.n_levels
    assert len(records) == hier.n_levels - 1
    total_vol = float(np.sum(np.asarray(patched.levels[0].vol)))
    n0 = W.n_rows
    for lvl in range(patched.n_levels - 1):
        P = patched.prolongators[lvl]
        fine, coarse = patched.levels[lvl], patched.levels[lvl + 1]
        assert P.n_rows == fine.W.n_rows and P.n_cols == coarse.W.n_rows
        # partition of unity: every fine vertex in exactly one aggregate
        rows = np.asarray(P.rows)
        assert len(rows) == fine.W.n_rows
        np.testing.assert_array_equal(np.sort(rows), np.arange(P.n_rows))
        assert np.all(np.asarray(P.vals) == 1.0)
        # conservation of finest mass
        assert float(np.sum(np.asarray(coarse.vol))) \
            == pytest.approx(total_vol, rel=1e-9)
        assert int(np.sum(np.asarray(coarse.counts))) == n0
        assert records[lvl]["n_dirty"] <= fine.W.n_rows
    # locality at the finest level: 3 edited edges dissolve only the
    # distance-1 aggregates (the fraction shrinks as graphs grow; at
    # coarser levels the closure legitimately covers more of the graph)
    assert records[0]["n_kept_aggregates"] >= 0.8 * records[0]["n_coarse"]
    assert records[0]["n_dirty"] < 0.2 * W.n_rows


def test_patch_hierarchy_empty_seed_reuses_everything():
    """A weights-only delta (empty dirty seed) keeps every aggregate:
    only the Galerkin products rebuild."""
    from repro.multilevel import build_hierarchy, patch_hierarchy

    W, _ = delaunay_graph(9, seed=3)
    hier = build_hierarchy(W, coarse_size=64, max_levels=4)
    W2 = W.with_vals(np.asarray(W.vals) * 1.7)
    patched, records = patch_hierarchy(hier, W2, np.empty(0, np.int64))
    for lvl, rec in enumerate(records):
        assert rec["n_rematched"] == 0
        assert rec["n_kept_aggregates"] == rec["n_coarse"]
        np.testing.assert_array_equal(
            np.asarray(patched.prolongators[lvl].rows),
            np.asarray(hier.prolongators[lvl].rows))
        np.testing.assert_array_equal(
            np.asarray(patched.prolongators[lvl].cols),
            np.asarray(hier.prolongators[lvl].cols))
    # Galerkin weights track the rescaling
    assert float(np.sum(np.asarray(patched.coarsest.W.vals))) \
        == pytest.approx(1.7 * float(np.sum(np.asarray(hier.coarsest.W.vals))),
                         rel=1e-6)


def test_engine_update_churn_close_to_scratch():
    """engine.update() on a previously served graph takes the churn
    path and lands within 2% RCut of a from-scratch solve of the edited
    graph (the serve_bench acceptance bound)."""
    from repro.serve import EdgeDelta, apply_edge_delta

    W, _ = sbm_graph([40, 40, 40, 40], 0.25, 0.02, seed=0)
    cfg = _cfg()
    eng = ClusterServeEngine(cfg)
    eng.serve([W])                                    # prime the cache

    rng = np.random.default_rng(1)
    und = np.asarray(W.rows) < np.asarray(W.cols)
    ei = np.flatnonzero(und)
    pick = rng.choice(ei, max(1, int(0.01 * len(ei))), replace=False)
    delta = EdgeDelta(np.asarray(W.rows)[pick], np.asarray(W.cols)[pick],
                      np.zeros(len(pick)))            # 1% edge knockouts
    rid = eng.update(W, delta)
    res = eng.flush()[rid]
    assert res.stats.mode == "churn"
    assert eng.stats.n_churn == 1

    W_new = apply_edge_delta(W, delta).W
    scratch = p_spectral_cluster(W_new, cfg)
    assert res.rcut <= scratch.rcut * 1.02 + 1e-12
