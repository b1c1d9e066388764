"""The solver-driver registry (core.solvers, DESIGN.md §7): dispatch +
config-time validation rules, newton ≡ scf ≡ inverse_power cluster
equivalence where all drivers converge, per-level V-cycle solver choice,
the pmulti-removal absence pin, and driver source purity (no scipy, no
raw segment_sum — every driver consumes the same api.mxm rings)."""
import warnings
from pathlib import Path

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import PSCConfig, metrics, p_spectral_cluster, solvers
from repro.core.solvers import (SolverReport, SolverState,
                                SolverUnavailableError)
from repro.graphs import (delaunay_graph, gaussian_blobs_knn,
                          ring_of_cliques, sbm_graph)

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from repro._vendor.minihypothesis import given, settings, strategies as st

SOLVERS = ("newton", "scf", "inverse_power")


def _cfg(solver, **kw):
    base = dict(k=4, p_target=1.4, newton_iters=15, tcg_iters=10,
                kmeans_restarts=4, seed=0, scf_sweeps=10, ipm_iters=100)
    base.update(kw)
    return PSCConfig(solver=solver, **base)


# ----------------------------------------------------------- dispatch rules

def test_registry_has_all_three_drivers():
    reg = solvers.registered_solvers()
    assert set(SOLVERS) <= set(reg)
    for name in SOLVERS:
        s = solvers.resolve_solver(name)
        assert s.name == name and callable(s.minimize_at_p)


def test_unknown_solver_raises_loudly():
    with pytest.raises(SolverUnavailableError, match="registered"):
        solvers.resolve_solver("does_not_exist")
    # SolverUnavailableError IS a ValueError: config-time validation
    # surfaces it through the same except clause
    assert issubclass(SolverUnavailableError, ValueError)
    with pytest.raises(SolverUnavailableError):
        PSCConfig(solver="does_not_exist")


def test_p_range_validation_at_config_time():
    # p outside (1, 2] used to produce NaNs deep in the Newton loop —
    # now a clear ValueError at construction
    with pytest.raises(ValueError, match="supported range"):
        PSCConfig(p_target=2.5)
    with pytest.raises(ValueError, match="supported range"):
        PSCConfig(p_target=1.0)            # newton's range is OPEN at 1
    with pytest.raises(ValueError, match="supported range"):
        PSCConfig(p_target=0.5, solver="inverse_power")
    with pytest.raises(ValueError, match="p_factor"):
        PSCConfig(p_factor=1.0)            # schedule would never descend
    # the inverse-power driver registers the wider CLOSED range [1, 2]:
    # the p → 1 sparsest-cut end is reachable
    assert PSCConfig(p_target=1.0, solver="inverse_power").p_target == 1.0
    ipm = solvers.resolve_solver("inverse_power")
    newton = solvers.resolve_solver("newton")
    assert ipm.supports_p(1.0) and not newton.supports_p(1.0)
    assert all(solvers.resolve_solver(s).supports_p(1.4) for s in SOLVERS)


def test_driver_contract_report_fields():
    W, _ = ring_of_cliques(3, 8)
    U0 = jnp.linalg.qr(jnp.ones((W.n_rows, 3)) +
                       jnp.arange(W.n_rows * 3.).reshape(W.n_rows, 3))[0]
    for name in SOLVERS:
        cfg = _cfg(name, k=3, ipm_iters=30, scf_sweeps=4)
        rep = solvers.minimize_at_p(W, U0, 1.5, cfg)
        assert isinstance(rep, SolverReport)
        assert rep.U.shape == (W.n_rows, 3)
        assert np.isfinite(rep.fval)
        assert rep.n_apply > 0 and rep.iters > 0
        assert rep.n_hvp == rep.n_apply    # back-compat alias


# ------------------------------------------------- solver equivalence suite

def test_equivalence_planted_sbm():
    """All drivers land the SAME clusters on a planted SBM (and all
    recover the planted partition exactly)."""
    W, truth = sbm_graph([30, 30, 30, 30], p_in=0.5, p_out=0.03, seed=5)
    labels = {}
    for name in SOLVERS:
        res = p_spectral_cluster(W, _cfg(name))
        labels[name] = res.labels
        assert metrics.clustering_accuracy(res.labels, truth, 4) == 1.0, name
    for name in ("scf", "inverse_power"):
        assert metrics.clustering_accuracy(
            labels[name], labels["newton"], 4) == 1.0, name


def test_equivalence_ring_of_cliques():
    W, truth = ring_of_cliques(4, 10)
    for name in SOLVERS:
        res = p_spectral_cluster(W, _cfg(name, ipm_iters=80))
        acc = metrics.clustering_accuracy(res.labels, truth, 4)
        assert acc == 1.0, f"{name}: accuracy {acc}"


def test_equivalence_delaunay():
    """No planted truth: drivers must agree on the overwhelming majority
    of nodes and land comparable RCut (boundary nodes of a mesh
    partition legitimately wiggle between near-degenerate optima)."""
    W, _ = delaunay_graph(8, seed=0)
    res = {name: p_spectral_cluster(W, _cfg(name)) for name in SOLVERS}
    r_newton = res["newton"].rcut
    for name in ("scf", "inverse_power"):
        agree = metrics.clustering_accuracy(
            res[name].labels, np.asarray(res["newton"].labels), 4)
        assert agree >= 0.85, f"{name}: agreement {agree}"
        assert res[name].rcut <= r_newton * 1.15 + 1e-9, \
            f"{name}: rcut {res[name].rcut} vs newton {r_newton}"


def test_inverse_power_reaches_p_one():
    """The regime Newton cannot reach: a full continuation down to the
    sparsest-cut limit p = 1 still recovers the planted clusters."""
    W, truth = ring_of_cliques(4, 10)
    res = p_spectral_cluster(W, _cfg("inverse_power", p_target=1.0,
                                     ipm_iters=80))
    assert res.p_path[-1] == 1.0
    assert metrics.clustering_accuracy(res.labels, truth, 4) == 1.0
    assert all(np.isfinite(v) for v in res.fvals)


# ------------------------------------------------------ pipeline threading

def test_vcycle_per_level_solver_choice():
    """Cheap SCF sweeps on the coarse level, Newton refinement on top —
    the per-level split the V-cycle exists for."""
    from repro.multilevel import MultilevelConfig

    W, truth = gaussian_blobs_knn(120, 4, seed=1)   # 480 nodes: coarsens
    ml = MultilevelConfig(coarse_size=64, max_levels=6, coarse_solver="scf")
    res = p_spectral_cluster(W, _cfg("newton", newton_iters=10, tcg_iters=8,
                                     multilevel=ml, scf_sweeps=8))
    assert metrics.clustering_accuracy(res.labels, truth, 4) >= 0.95
    assert res.levels and all(r["solver"] == "newton" for r in res.levels)
    # refinement can take its own driver too
    ml2 = MultilevelConfig(coarse_size=64, max_levels=6,
                           coarse_solver="scf", refine_solver="scf")
    res2 = p_spectral_cluster(W, _cfg("newton", multilevel=ml2, scf_sweeps=8))
    assert metrics.clustering_accuracy(res2.labels, truth, 4) >= 0.95
    assert res2.levels and all(r["solver"] == "scf" for r in res2.levels)


def test_partition_threads_solver():
    from repro.graphs.partition import partition

    W, _ = gaussian_blobs_knn(40, 2, seed=3)
    labels, info = partition(W, 2, solver="scf", multilevel=False)
    sizes = info["sizes"]
    assert sum(sizes) == W.n_rows and min(sizes) > 0
    assert np.isfinite(info["rcut"])


def test_pmulti_shim_is_gone():
    """The one-release deprecation window closed: core.pmulti no longer
    exists, and its replacement — the registry's inverse_power driver
    entered at a single p — covers the historical behavior (pinned in
    DESIGN.md §3's migration table)."""
    with pytest.raises(ImportError):
        from repro.core import pmulti  # noqa: F401
    import repro.core as core

    assert not hasattr(core, "p_multi")
    # the replacement path delivers the same clusters the shim did
    W, truth = ring_of_cliques(4, 10)
    cfg = PSCConfig(k=4, p_target=1.2, seed=0, solver="inverse_power",
                    ipm_iters=60)
    from repro.core import lobpcg

    _, U2 = lobpcg.smallest_eigvecs(W, 4, seed=0)
    rep = solvers.minimize_at_p(W, U2, 1.2, cfg)
    from repro.core.psc import discretize

    import jax

    labels = np.asarray(discretize(rep.U, 4, jax.random.PRNGKey(0)))
    assert metrics.clustering_accuracy(labels, truth, 4) == 1.0


def test_scf_continuation_hits_one_trace():
    """PR-3's one-trace-per-schedule contract, for free via the registry
    memo: the SCF reweighting jit serves every p level (and repeat
    runs) from one trace."""
    W, _ = ring_of_cliques(3, 8)
    cfg = _cfg("scf", k=3, scf_sweeps=4, kmeans_iters=10, kmeans_restarts=2)

    def scf_traces():
        return sum(1 for k_ in solvers.SOLVER_TRACES if k_[0] == "scf")

    p_spectral_cluster(W, cfg)          # warm the memo
    before = scf_traces()
    res = p_spectral_cluster(W, cfg)
    assert len(res.p_path) >= 3
    assert scf_traces() == before       # fully cached across the schedule


# --------------------------------------------------------- property checks

@given(seed=st.integers(min_value=0, max_value=10_000),
       p=st.floats(min_value=float(np.float32(1.05)), max_value=2.0,
                   width=32))
@settings(max_examples=8, deadline=None)
def test_property_scf_driver_well_posed(seed, p):
    """Over random planted patterns and random p: the SCF driver returns
    finite, orthonormal iterates and does not increase the functional
    recorded by the newton driver's own evaluation."""
    from repro.core import plap

    W, _ = sbm_graph([12, 12, 12], p_in=0.6, p_out=0.08, seed=seed)
    rng = np.random.default_rng(seed)
    U0 = jnp.linalg.qr(jnp.asarray(
        rng.standard_normal((W.n_rows, 3)), jnp.float32))[0]
    cfg = _cfg("scf", k=3, scf_sweeps=6)
    rep = solvers.minimize_at_p(W, U0, float(p), cfg)
    U = np.asarray(rep.U)
    assert np.isfinite(U).all() and np.isfinite(rep.fval)
    np.testing.assert_allclose(U.T @ U, np.eye(3), atol=1e-4)
    f0 = float(plap.value(W, U0, float(p), cfg.eps))
    assert rep.fval <= f0 * 1.05 + 1e-6


@given(seed=st.integers(min_value=0, max_value=10_000))
@settings(max_examples=5, deadline=None)
def test_property_drivers_agree_on_planted_blobs(seed):
    W, truth = gaussian_blobs_knn(18, 3, seed=seed)
    res_n = p_spectral_cluster(W, _cfg("newton", k=3, newton_iters=10,
                                       tcg_iters=8, seed=seed))
    res_s = p_spectral_cluster(W, _cfg("scf", k=3, seed=seed))
    acc_n = metrics.clustering_accuracy(res_n.labels, truth, 3)
    acc_s = metrics.clustering_accuracy(res_s.labels, truth, 3)
    # well-separated blobs: both drivers recover the planted structure
    assert acc_n >= 0.9 and acc_s >= 0.9


# ------------------------------------------------------------ source purity

def test_no_scipy_or_raw_segment_sum_in_drivers():
    """Every driver consumes the unified api.mxm rings: no scipy and no
    raw segment_sum anywhere in core/solvers/ — enforced by the pscheck
    hot-purity / api-boundary rules (repro.analysis, DESIGN.md §11)."""
    from repro import analysis

    pkg = Path(__file__).resolve().parent.parent / "src/repro/core/solvers"
    assert len(sorted(pkg.glob("*.py"))) >= 5   # __init__, registry, 3 drivers
    analysis.assert_clean([pkg], rules=["hot-purity", "api-boundary"])
    # the drivers reach the algebra through the plap/lobpcg layers (which
    # route api.mxm), never a private reduction
    assert "plap" in (pkg / "newton.py").read_text()
    assert "lobpcg" in (pkg / "scf.py").read_text()
    assert "plap" in (pkg / "inverse_power.py").read_text()
