"""SELL-C-σ layout invariants, auto-dispatch policy, reorder metrics,
and the memoized Newton-step trace contract (psc continuation must not
re-trace per p level)."""
import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp
import pytest

from repro.grblas import (
    Descriptor,
    BackendUnavailableError,
    SELLCS_AUTO_THRESHOLD,
    SparseMatrix,
    mxm,
    reals_ring,
)
from repro.grblas import api
from repro.grblas.containers import SELLCS_MAX_RUNS, sell_width_classes


def _rand(n=120, density=0.06, seed=0, **kw):
    A = sp.random(n, n, density=density,
                  random_state=np.random.RandomState(seed), format="coo")
    A = A + A.T
    return SparseMatrix.from_scipy(A, **kw)


def _skewed(n=400, hub_deg=60, seed=0, **kw):
    """Background degree ~4 plus a few hub rows — ELL fill blows past the
    auto threshold."""
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, n, 2 * n)
    cols = rng.integers(0, n, 2 * n)
    hub_cols = rng.integers(0, n, 3 * hub_deg)
    hub_rows = np.repeat(np.arange(3), hub_deg)
    r = np.concatenate([rows, cols, hub_rows, hub_cols])
    c = np.concatenate([cols, rows, hub_cols, hub_rows])
    keep = r != c
    v = np.ones(keep.sum())
    return SparseMatrix.from_coo(r[keep], c[keep], v, (n, n), **kw)


@pytest.mark.parametrize("n,C,sigma", [(120, 8, 16), (97, 16, None),
                                       (33, 8, 8), (8, 32, None)])
def test_layout_shape_invariants(n, C, sigma):
    # build_ell forced: the fill-ratio invariant below compares against it
    M = _rand(n=n, build_ell=True, build_sellcs=True, sell_c=C,
              sell_sigma=sigma)
    assert M.sell_n_pad % M.sell_c == 0 and M.sell_n_pad >= n
    stored = 0
    for r, cols_r in enumerate(M.sell_cols):
        rows_r, w = cols_r.shape
        assert rows_r % M.sell_c == 0 and w >= 1
        assert M.sell_vals[r].shape == (rows_r, w)
        assert M.sell_row0[r] == (0 if r == 0 else
                                  M.sell_row0[r - 1]
                                  + M.sell_cols[r - 1].shape[0])
        stored += rows_r * w
    # per-slice padding can never store more than global-max padding
    # would over the same n_pad rows (phantom rows are the C-alignment)
    assert (M.sellcs_fill_ratio()
            <= M.ell_fill_ratio() * M.sell_n_pad / n + 1e-9)
    assert stored == round(M.sellcs_fill_ratio() * M.nnz)
    # the permutation round-trips: perm[inv[o]] == o for every row
    perm, inv = np.asarray(M.sell_perm), np.asarray(M.sell_inv)
    assert (perm[inv] == np.arange(n)).all()


def test_sigma_windows_sort_locally_only():
    """σ bounds how far a row may travel: with σ == C == n/4 each window
    permutes internally, so permuted position // σ == original // σ."""
    M = _rand(n=128, build_sellcs=True, sell_c=32, sell_sigma=32)
    inv = np.asarray(M.sell_inv)
    assert (inv // 32 == np.arange(128) // 32).all()


def test_w_align_merges_runs_and_stays_equivalent():
    """Width classes round slice widths up: no more runs than the tight
    widths, every width at or above its slice's widest row and at most
    a class step above it, the same product, and reorder rebuilds them
    by the same rule."""
    M = _skewed(build_sellcs=True, sell_c=8)
    assert len(M.sell_cols) <= SELLCS_MAX_RUNS
    widths = [c.shape[1] for c in M.sell_cols]
    assert widths == sorted(widths, reverse=True)      # global σ-sort
    deg = np.bincount(np.asarray(M.rows), minlength=M.n_rows)
    slice_max = np.zeros(M.sell_n_pad, np.int64)
    slice_max[:M.n_rows] = deg[np.asarray(M.sell_perm)[:M.n_rows]]
    slice_max = slice_max.reshape(-1, M.sell_c).max(axis=1)
    run_of_slice = np.repeat(widths, [c.shape[0] // M.sell_c
                                      for c in M.sell_cols])
    assert (run_of_slice >= slice_max).all()
    assert (run_of_slice <= 2 * np.maximum(slice_max, 1)).all()
    X = jnp.ones((M.n_rows, 3), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(mxm(M, X, desc=Descriptor(backend="sellcs"))),
        np.asarray(mxm(M, X, desc=Descriptor(backend="coo"))),
        rtol=1e-4, atol=1e-4)
    from repro.graphs import reorder
    M2 = reorder(M, "degree")[0]
    assert [c.shape for c in M2.sell_cols] == [c.shape for c in M.sell_cols]


@pytest.mark.parametrize("widths,exact", [
    (np.arange(1, 41), True),                 # 40 widths: kept exact
    (np.arange(1, 42), False),                # 41: one bit coarser
    (np.arange(1, 1025), False),
    (np.array([3, 3, 7, 1000, 2 ** 30]), True)])
def test_sell_width_classes_rule(widths, exact):
    got = sell_width_classes(widths)
    assert bool((got == widths).all()) is exact
    assert len(np.unique(got)) <= SELLCS_MAX_RUNS
    # monotone (runs stay contiguous under the global sort), never
    # below a width, and less than one power-of-two step above it
    order = np.argsort(widths, kind="stable")
    assert (np.diff(got[order]) >= 0).all()
    assert (got >= widths).all() and (got < 2 * widths).all()


def test_auto_build_and_auto_dispatch_on_skew():
    W = _skewed(build_ell=True)          # build_sellcs unset -> auto
    assert W.ell_fill_ratio() > SELLCS_AUTO_THRESHOLD
    assert W.sell_cols is not None, "auto-build should trigger on skew"
    X = jnp.ones((W.n_rows, 4), jnp.float32)
    assert api.available_backends(W, X)[0] == "sellcs"
    want = np.asarray(W.to_dense()) @ np.asarray(X)
    np.testing.assert_allclose(np.asarray(mxm(W, X)), want,
                               rtol=1e-4, atol=1e-4)


def test_auto_build_skips_dead_ell_on_skew():
    """With build_ell unset, the skew regime must not allocate the
    (n, hub_degree) ELL blocks that auto-dispatch would never use —
    it builds the sliced layout instead.  build_ell=True forces ELL."""
    W = _skewed()                        # both build flags on auto
    assert W.ell_cols is None and W.sell_cols is not None
    X = jnp.ones((W.n_rows, 4), jnp.float32)
    np.testing.assert_allclose(np.asarray(mxm(W, X)),
                               np.asarray(W.to_dense()) @ np.asarray(X),
                               rtol=1e-4, atol=1e-4)
    assert _skewed(build_ell=True).ell_cols is not None
    # low-skew graphs keep ELL under the same auto default
    assert _rand().ell_cols is not None


def test_auto_defers_to_ell_on_low_fill():
    M = _rand(build_sellcs=True)         # uniform degrees: low ELL fill
    assert M.ell_fill_ratio() <= SELLCS_AUTO_THRESHOLD
    X = jnp.ones((M.n_rows, 4), jnp.float32)
    order = api.available_backends(M, X)
    assert "sellcs" not in order and order[0] == "ell"
    # ...but naming it explicitly always executes
    got = mxm(M, X, desc=Descriptor(backend="sellcs"))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(M.to_dense()) @ np.asarray(X),
                               rtol=1e-4, atol=1e-4)


def test_rectangular_matrices_never_build_sellcs():
    """The layout shares one permutation across row and column space, so
    it is square-only: explicit build raises, auto skips silently."""
    with pytest.raises(ValueError, match="square"):
        SparseMatrix.from_coo([0, 1], [2, 0], [1.0, 1.0], (2, 4),
                              build_sellcs=True)
    # wide matrix with a hub row: auto must not trip the skew trigger
    r = np.zeros(40, np.int64)
    c = np.arange(40, dtype=np.int64)
    M = SparseMatrix.from_coo(r, c, np.ones(40), (8, 40))
    assert M.sell_cols is None
    x = jnp.ones(40, jnp.float32)
    np.testing.assert_allclose(np.asarray(mxm(M, x)),
                               np.asarray(M.to_dense()) @ np.asarray(x))


def test_empty_matrix_supports_named_sellcs():
    M = SparseMatrix.from_coo([], [], [], (4, 4), build_sellcs=True)
    assert M.sell_cols is not None
    X = jnp.ones((4, 3), jnp.float32)
    got = mxm(M, X, desc=Descriptor(backend="sellcs"))
    np.testing.assert_allclose(np.asarray(got), np.zeros((4, 3)))
    got1 = mxm(M.with_vals(M.vals), X, desc=Descriptor(backend="sellcs"))
    np.testing.assert_allclose(np.asarray(got1), np.zeros((4, 3)))


def test_named_sellcs_without_layout_raises():
    M = _rand(build_sellcs=False)
    X = jnp.ones((M.n_rows, 4), jnp.float32)
    with pytest.raises(BackendUnavailableError):
        mxm(M, X, desc=Descriptor(backend="sellcs"))


def test_with_vals_scalar_and_1d_inputs():
    M = _rand(build_sellcs=True, sell_c=8)
    rng = np.random.default_rng(1)
    newv = jnp.asarray(rng.standard_normal(M.nnz), jnp.float32)
    Wv = M.with_vals(newv)
    x = jnp.asarray(rng.standard_normal(M.n_rows), jnp.float32)
    want = np.asarray(Wv.to_dense()) @ np.asarray(x)
    got = np.asarray(mxm(Wv, x, reals_ring, desc=Descriptor(backend="sellcs")))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_ell_builds_in_target_dtype():
    M = _rand(build_ell=True, dtype=jnp.float64)
    assert M.ell_vals.dtype == jnp.float64
    M32 = _rand(build_ell=True, dtype=jnp.float32)
    assert M32.ell_vals.dtype == jnp.float32
    assert M32.ell_cols.dtype == jnp.int32


def test_fill_ratio_accessors_per_layout():
    M = _rand(build_ell=True, build_bsr=True, block_size=16,
              build_sellcs=True)
    assert M.ell_fill_ratio() >= 1.0
    assert M.bsr_fill_ratio() >= 1.0
    assert 1.0 <= M.sellcs_fill_ratio() <= M.ell_fill_ratio()
    assert np.isnan(_rand(build_ell=False).ell_fill_ratio())
    # deprecated alias still reports the BSR number
    assert M.fill_ratio == M.bsr_fill_ratio()


def test_reorder_reduces_bandwidth_and_preserves_matrix():
    from repro.graphs import bandwidth, delaunay_graph, reorder

    W, _ = delaunay_graph(8, seed=0, locality_order=False)
    W2, perm, inv = reorder(W, "rcm")
    assert bandwidth(W2) < bandwidth(W)
    assert (perm[inv] == np.arange(W.n_rows)).all()
    D, D2 = np.asarray(W.to_dense()), np.asarray(W2.to_dense())
    np.testing.assert_allclose(D2, D[np.ix_(perm, perm)], rtol=1e-6)


def test_reorder_preserves_built_layouts():
    from repro.graphs import reorder

    M = _rand(build_ell=True, build_bsr=True, block_size=16,
              build_sellcs=True, sell_c=8, sell_sigma=16)
    M2, _, _ = reorder(M, "degree")
    assert M2.ell_cols is not None and M2.bsr_blocks is not None
    assert M2.sell_cols is not None
    assert (M2.sell_c, M2.sell_sigma) == (M.sell_c, M.sell_sigma)
    assert M2.block_size == M.block_size


def test_newton_continuation_traces_once():
    """The memoized jitted Newton step must serve every p level of the
    continuation (and repeat runs) from ONE trace on the jnp paths."""
    from repro.core import psc
    from repro.graphs import ring_of_cliques

    W, _ = ring_of_cliques(3, 8)
    cfg = psc.PSCConfig(k=3, p_target=1.4, newton_iters=3, tcg_iters=4,
                        kmeans_restarts=2, kmeans_iters=10, seed=1)
    before = len(psc._NEWTON_TRACES)
    res = psc.p_spectral_cluster(W, cfg)
    assert len(res.p_path) >= 3          # several continuation levels...
    traced = len(psc._NEWTON_TRACES) - before
    assert traced <= 1                   # ...but at most one fresh trace
    psc.p_spectral_cluster(W, cfg)       # repeat run: fully cached
    assert len(psc._NEWTON_TRACES) - before == traced
    fn, _ = psc._jitted_minimize(cfg, 1.4, W,
                                 jnp.zeros((W.n_rows, cfg.k), jnp.float32))
    cache_size = getattr(fn, "_cache_size", lambda: None)()
    if cache_size is not None:           # jax.jit cache stats, if exposed
        assert cache_size == 1


def _lfr(n=4096, k_max=256, seed=0):
    """A seeded LFR-style planted partition (power-law degrees, tau1 2,
    mean degree 20, mu 0.2, 4 communities) from the benchmark's own
    generator: hubs of up to ``k_max`` neighbours."""
    import sys
    from pathlib import Path

    bench = str(Path(__file__).resolve().parents[1] / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import graphs_lfr

    r, c, v, truth = graphs_lfr.lfr_coo(n, 4, 2.0, 20.0, k_max, 0.2, seed)
    return SparseMatrix.from_coo(r, c, v, (n, n)), truth


@pytest.mark.parametrize("n,k_max,seed", [(4096, 256, 0),
                                          (16384, 1024, 1)])
def test_lfr_width_classes_bound_runs(n, k_max, seed):
    """On the normal path (from_coo defaults) a power-law graph builds
    SELL-C-σ only, in at most SELLCS_MAX_RUNS width runs, at most a
    quarter above nnz in stored slots."""
    W, _ = _lfr(n=n, k_max=k_max, seed=seed)
    assert W.ell_cols is None and W.sell_cols is not None
    assert len(W.sell_cols) <= SELLCS_MAX_RUNS
    assert W.sellcs_fill_ratio() <= 1.25
    tight = np.unique(np.bincount(np.asarray(W.rows), minlength=W.n_rows)
                      [np.asarray(W.sell_perm)].reshape(-1, W.sell_c)
                      .max(axis=1))
    assert len(tight) > SELLCS_MAX_RUNS      # the rule had work to do


@pytest.mark.parametrize("ring", ["reals", "plap_apply", "plap_hvp",
                                  "multivalue"])
def test_lfr_sellcs_rings_match_coo(ring):
    """Each ring the flat solve dispatches, on the width-class layout
    against the coo backend."""
    from repro.grblas.semiring import (plap_edge_semiring,
                                       plap_hvp_edge_semiring)

    W, _ = _lfr()
    rng = np.random.default_rng(3)
    n, k = W.n_rows, 4
    X = jnp.asarray(rng.standard_normal((n, k)), jnp.float32)
    E = jnp.asarray(rng.standard_normal((n, k)), jnp.float32)
    what = jnp.asarray(rng.uniform(0.5, 1.5, (W.nnz, k)), jnp.float32)
    p, eps = 1.4, 1e-8
    ops = {"reals": lambda d: mxm(W, X, reals_ring, desc=d),
           "plap_apply": lambda d: mxm(W, X, plap_edge_semiring(p, eps),
                                       desc=d),
           "plap_hvp": lambda d: mxm(W, (X, E),
                                     plap_hvp_edge_semiring(p, eps), desc=d),
           "multivalue": lambda d: mxm(W.with_vals(what), X, reals_ring,
                                       desc=d)}
    got = np.asarray(ops[ring](Descriptor(backend="auto")))
    want = np.asarray(ops[ring](Descriptor(backend="coo")))
    assert api.available_backends(W, X)[0] == "sellcs"
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("seed", [0, 1])
def test_lfr_flat_auto_solve_matches_coo(seed):
    """A whole flat solve (stage 1, one continuation level, k-means) on
    the width-class SELL-C-σ layout (backend auto) against the same solve
    on coo: F_p, RCut and the labels up to a permutation of the clusters.
    The Newton budget is short on purpose: on these graphs the
    trust-region path amplifies the last-bit differences of two
    summation orders (float64 included), so the two solves drift apart
    by 1e-5 in F_p after 30 Hessian applies and by 1e-3 after 200."""
    from itertools import permutations

    from repro.core import PSCConfig, p_spectral_cluster

    W, _ = _lfr(seed=seed)
    out = {}
    for backend in ("auto", "coo"):
        cfg = PSCConfig(k=4, p_target=1.8, newton_iters=3, tcg_iters=4,
                        backend=backend, seed=0)
        out[backend] = p_spectral_cluster(W, cfg)
    a, b = out["auto"], out["coo"]
    assert a.hvp_counts == b.hvp_counts
    np.testing.assert_allclose(a.init_rcut, b.init_rcut, rtol=1e-6)
    np.testing.assert_allclose(a.fvals[-1], b.fvals[-1], rtol=1e-5)
    np.testing.assert_allclose(a.rcut, b.rcut, rtol=1e-5)
    agree = max(int(np.sum(a.labels == np.asarray(perm)[b.labels]))
                for perm in permutations(range(4)))
    assert agree == W.n_rows
