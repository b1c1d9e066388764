"""Backend equivalence: every backend the Descriptor can name must agree
with the COO reference — across rings, p values, and asymmetric as well
as symmetric matrices.  This is the numerics contract of the dispatch
table: "auto" may pick any capable backend, so they must all be
interchangeable to tolerance (1e-5 for f32 kernel paths)."""
import numpy as np
import scipy.sparse as sp
import jax.numpy as jnp
import pytest

from repro.grblas import (
    Descriptor,
    EdgeSemiring,
    SparseMatrix,
    boolean_ring,
    max_times_ring,
    min_plus_ring,
    mxm,
    mxv,
    plap_edge_semiring,
    plap_hvp_edge_semiring,
    reals_ring,
)

BS = 16
PS = [1.2, 1.5, 2.0]


def _graph(symmetric: bool, n=96, density=0.08, seed=0, dtype=jnp.float32):
    A = sp.random(n, n, density=density,
                  random_state=np.random.RandomState(seed), format="coo")
    if symmetric:
        A = A + A.T
    return SparseMatrix.from_scipy(A, build_bsr=True, block_size=BS,
                                   dtype=dtype, build_sellcs=True,
                                   sell_c=8, sell_sigma=32)


def _X(M, k=4, seed=1, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((M.n_rows, k)), dtype)


REALS_DESCRIPTORS = [
    Descriptor(backend="coo"),
    Descriptor(backend="ell"),
    Descriptor(backend="sellcs"),                      # sliced gather (CPU)
    Descriptor(backend="sellcs", interpret=True),      # Pallas interpreter
    Descriptor(backend="bsr_pallas"),                  # jnp blocked ref (CPU)
    Descriptor(backend="bsr_pallas", interpret=True),  # Pallas interpreter
]

EDGE_DESCRIPTORS = [
    Descriptor(backend="ell"),                         # padded-row slot folds
    Descriptor(backend="edge_pallas"),
    Descriptor(backend="edge_pallas", interpret=True),
    Descriptor(backend="sellcs"),
    Descriptor(backend="sellcs", interpret=True),
]


@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_reals_ring_backends_agree(symmetric):
    M = _graph(symmetric)
    X = _X(M)
    want = np.asarray(M.to_dense()) @ np.asarray(X)     # dense oracle
    for desc in REALS_DESCRIPTORS:
        got = np.asarray(mxm(M, X, desc=desc))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"backend={desc.backend} "
                                           f"interpret={desc.interpret}")


@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_reals_ring_as_edge_semiring(symmetric):
    """A generic edge-semiring that ignores the destination endpoint must
    reproduce the plain ring on the COO path (the ring-extension is
    conservative)."""
    M = _graph(symmetric)
    X = _X(M)
    ring = EdgeSemiring(base=reals_ring,
                        edge_mul=lambda w, x_src, x_dst: w * x_src,
                        name="reals_as_edge")
    got = np.asarray(mxm(M, X, ring))
    want = np.asarray(mxm(M, X, desc=Descriptor(backend="coo")))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_plap_apply_backends_agree(symmetric, p):
    M = _graph(symmetric)
    X = _X(M)
    ring = plap_edge_semiring(p, eps=1e-6)
    want = np.asarray(mxm(M, X, ring, desc=Descriptor(backend="coo")))
    for desc in EDGE_DESCRIPTORS:
        got = np.asarray(mxm(M, X, ring, desc=desc))
        np.testing.assert_allclose(
            got, want, rtol=2e-4, atol=1e-5,
            err_msg=f"p={p} backend={desc.backend} "
                    f"interpret={desc.interpret}")


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_plap_hvp_backends_agree(symmetric, p):
    M = _graph(symmetric)
    rng = np.random.default_rng(2)
    U = jnp.asarray(np.linalg.qr(rng.standard_normal((M.n_rows, 3)))[0],
                    jnp.float32)
    Eta = jnp.asarray(rng.standard_normal((M.n_rows, 3)) * 0.1, jnp.float32)
    ring = plap_hvp_edge_semiring(p, eps=1e-6)
    want = np.asarray(mxm(M, (U, Eta), ring, desc=Descriptor(backend="coo")))
    for desc in EDGE_DESCRIPTORS:
        got = np.asarray(mxm(M, (U, Eta), ring, desc=desc))
        np.testing.assert_allclose(
            got, want, rtol=2e-4, atol=1e-5,
            err_msg=f"p={p} backend={desc.backend} "
                    f"interpret={desc.interpret}")


@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_generic_rings_match_dense_oracle(symmetric):
    """(min,+), (max,*), boolean: COO (the only capable layout) vs dense."""
    M = _graph(symmetric, dtype=jnp.float64)
    dense = np.asarray(M.to_dense())
    rng = np.random.default_rng(3)
    x = np.abs(rng.standard_normal(M.n_rows)) + 0.1

    got = np.asarray(mxv(M, jnp.asarray(x), min_plus_ring))
    want = np.full(M.n_rows, np.inf)
    for i in range(M.n_rows):
        nz = dense[i] != 0
        if nz.any():
            want[i] = np.min(dense[i][nz] + x[nz])
    np.testing.assert_allclose(got, want, rtol=1e-10)

    got = np.asarray(mxv(M, jnp.asarray(x), max_times_ring))
    want = np.full(M.n_rows, -np.inf)
    for i in range(M.n_rows):
        nz = dense[i] != 0
        if nz.any():
            want[i] = np.max(dense[i][nz] * x[nz])
    np.testing.assert_allclose(got, want, rtol=1e-10)

    xb = x > 1.0
    got = np.asarray(mxv(M, jnp.asarray(xb), boolean_ring))
    np.testing.assert_array_equal(got, (dense != 0) @ xb)


@pytest.mark.parametrize("symmetric", [True, False],
                         ids=["symmetric", "asymmetric"])
def test_with_vals_multivalues_on_sellcs(symmetric):
    """Alg-1's materialized W-hat ((nnz, k) multivalues on the fixed
    pattern) must execute identically on the sliced layout: with_vals
    re-scatters the packed slice values on-device."""
    M = _graph(symmetric)
    X = _X(M)
    rng = np.random.default_rng(7)
    mv = jnp.asarray(rng.standard_normal((M.nnz, X.shape[1])), jnp.float32)
    Wv = M.with_vals(mv)
    assert Wv.sell_cols is not None
    want = np.asarray(mxm(Wv, X, desc=Descriptor(backend="coo")))
    got = np.asarray(mxm(Wv, X, desc=Descriptor(backend="sellcs")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("method", ["rcm", "degree"])
def test_reorder_round_trip_labels_invariant(method):
    """PSCConfig.reorder must be invisible to callers: identical labels
    (same vertex ids), identical cut metrics."""
    from repro.core import metrics
    from repro.core.psc import PSCConfig, p_spectral_cluster
    from repro.graphs import ring_of_cliques

    W, _ = ring_of_cliques(4, 12)
    kw = dict(k=4, p_target=1.6, newton_iters=4, tcg_iters=5,
              kmeans_restarts=3, kmeans_iters=20, seed=0)
    base = p_spectral_cluster(W, PSCConfig(**kw))
    perm = p_spectral_cluster(W, PSCConfig(reorder=method, **kw))
    assert metrics.clustering_accuracy(base.labels, perm.labels, 4) == 1.0
    np.testing.assert_allclose(perm.rcut, base.rcut, rtol=1e-4)
    np.testing.assert_allclose(perm.ncut, base.ncut, rtol=1e-4)


def test_plap_hot_loop_matches_through_bsr_descriptor():
    """Acceptance pin: the Newton hot-loop ops under
    Descriptor(backend=..., interpret=True) match the COO reference to
    1e-5 when driven through core.plap."""
    from repro.core import plap

    M = _graph(True)
    rng = np.random.default_rng(5)
    U = jnp.asarray(np.linalg.qr(rng.standard_normal((M.n_rows, 3)))[0],
                    jnp.float32)
    Eta = jnp.asarray(rng.standard_normal((M.n_rows, 3)) * 0.1, jnp.float32)
    kernel_desc = Descriptor(backend="edge_pallas", interpret=True)
    coo = Descriptor(backend="coo")
    for p in PS:
        g0 = np.asarray(plap.euc_grad(M, U, p, 1e-6, desc=coo))
        g1 = np.asarray(plap.euc_grad(M, U, p, 1e-6, desc=kernel_desc))
        np.testing.assert_allclose(g1, g0, rtol=2e-4, atol=1e-5)
        h0 = np.asarray(plap.hess_eta_matrix_free(M, U, Eta, p, 1e-6,
                                                  desc=coo))
        h1 = np.asarray(plap.hess_eta_matrix_free(M, U, Eta, p, 1e-6,
                                                  desc=kernel_desc))
        np.testing.assert_allclose(h1, h0, rtol=2e-4, atol=1e-5)
