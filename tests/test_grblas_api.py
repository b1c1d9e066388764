"""Unified execution API: Descriptor dispatch, write semantics, fast-path
registry, generic monoid folds, and the deprecated-shim contract."""
import numpy as np
import scipy.sparse as sp
import jax
import jax.numpy as jnp
import pytest

from repro.grblas import (
    BackendUnavailableError,
    Descriptor,
    SparseMatrix,
    available_backends,
    boolean_ring,
    fast_paths,
    min_plus_ring,
    mxm,
    mxv,
    plap_edge_semiring,
    plap_hvp_edge_semiring,
    reals_ring,
    vxm,
)
from repro.grblas.semiring import Semiring


def _sym(n=40, bs=16, density=0.1, dtype=jnp.float64, seed=0):
    A = sp.random(n, n, density=density,
                  random_state=np.random.RandomState(seed), format="coo")
    A = A + A.T
    return A, SparseMatrix.from_scipy(A, build_bsr=True, block_size=bs,
                                      dtype=dtype)


# ------------------------------------------------------------ dispatch rules

@pytest.mark.skipif(jax.default_backend() != "cpu",
                    reason="auto priority order is platform-specific")
def test_auto_prefers_ell_on_cpu():
    _, M = _sym()
    X = jnp.ones((M.n_rows, 3))
    assert available_backends(M, X)[0] == "ell"


def test_auto_falls_back_to_coo_without_ell():
    A, _ = _sym()
    M = SparseMatrix.from_scipy(A, build_ell=False, dtype=jnp.float64)
    X = jnp.ones((M.n_rows, 3))
    assert available_backends(M, X)[0] == "coo"


def test_generic_monoid_never_rides_ell():
    """ELL pads are only add-identities for the reals ring."""
    _, M = _sym()
    x = jnp.ones(M.n_rows)
    names = available_backends(M, x, min_plus_ring)
    assert "ell" not in names
    with pytest.raises(BackendUnavailableError):
        mxv(M, x, min_plus_ring, desc=Descriptor(backend="ell"))


def test_unknown_backend_raises():
    _, M = _sym()
    with pytest.raises(BackendUnavailableError, match="unknown backend"):
        mxv(M, jnp.ones(M.n_rows), desc=Descriptor(backend="csr_gpu"))


def test_named_backend_validates_layout():
    A, _ = _sym()
    M = SparseMatrix.from_scipy(A, dtype=jnp.float64)  # no BSR built
    with pytest.raises(BackendUnavailableError, match="bsr_pallas"):
        mxm(M, jnp.ones((M.n_rows, 2)),
            desc=Descriptor(backend="bsr_pallas"))


def test_dist_requires_mesh():
    _, M = _sym()
    with pytest.raises(BackendUnavailableError):
        mxm(M, jnp.ones((M.n_rows, 2)), desc=Descriptor(backend="dist"))


def test_edge_ring_dispatch_by_kind():
    _, M = _sym(dtype=jnp.float32)
    X = jnp.ones((M.n_rows, 2), jnp.float32)
    ring = plap_edge_semiring(1.5, 1e-6)
    assert "edge_pallas" in available_backends(M, X, ring)
    pair = plap_hvp_edge_semiring(1.5, 1e-6)
    assert "edge_pallas" in available_backends(M, (X, X), pair)
    # a pair ring needs a pair input
    with pytest.raises(BackendUnavailableError):
        mxm(M, X, pair)


# -------------------------------------------------- vxm / transpose semantics

def test_vxm_edge_semiring_multivector_regression():
    """ops.py:82 used `cond and a or b` on arrays -> truth-value crash for
    any 2-D multivector under an edge ring.  The API must broadcast."""
    A, M = _sym()
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.standard_normal((M.n_rows, 4)))
    ring = plap_edge_semiring(1.5, eps=0.0)
    got = vxm(X, M, ring)                       # crashed before the redesign
    # oracle per column: y_j = sum_i w_ij phi(x_j - x_i)
    Wd = np.asarray(M.to_dense())
    xd = np.asarray(X)
    p = 1.5
    want = np.zeros_like(xd)
    for col in range(xd.shape[1]):
        for j in range(M.n_rows):
            d = xd[j, col] - xd[:, col]
            want[j, col] = np.sum(Wd[:, j] * np.abs(d) ** (p - 1) * np.sign(d))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-8, atol=1e-10)


def test_vxm_is_transposed_mxm():
    A = sp.random(30, 50, density=0.1,
                  random_state=np.random.RandomState(3), format="coo")
    M = SparseMatrix.from_scipy(A, dtype=jnp.float64)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(30))
    got = vxm(x, M)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ A.toarray(),
                               rtol=1e-10)
    # vxm flips the descriptor's transpose bit: flipping it twice on a
    # square matrix lands back on plain mxv
    Asq = sp.random(30, 30, density=0.1,
                    random_state=np.random.RandomState(4), format="coo")
    Msq = SparseMatrix.from_scipy(Asq, dtype=jnp.float64)
    got2 = vxm(x, Msq, desc=Descriptor(transpose=True))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(mxv(Msq, x)),
                               rtol=1e-12)


# ------------------------------------------------------------ write semantics

def test_mask_writes_add_identity():
    _, M = _sym()
    x = jnp.asarray(np.random.default_rng(0).standard_normal(M.n_rows))
    keep = np.arange(M.n_rows) % 2 == 0
    y = mxv(M, x, mask=keep)
    full = np.asarray(mxv(M, x))
    np.testing.assert_allclose(np.asarray(y)[keep], full[keep], rtol=1e-12)
    assert np.all(np.asarray(y)[~keep] == 0.0)
    # min-plus identity is +inf, not 0
    ym = mxv(M, jnp.abs(x), min_plus_ring, mask=keep)
    assert np.all(np.isinf(np.asarray(ym)[~keep]))


def test_accum_and_masked_accum():
    _, M = _sym()
    x = jnp.asarray(np.random.default_rng(0).standard_normal(M.n_rows))
    C = jnp.ones(M.n_rows)
    T = np.asarray(mxv(M, x))
    got = np.asarray(mxv(M, x, accum=(jnp.add, C)))
    np.testing.assert_allclose(got, 1.0 + T, rtol=1e-12)
    keep = np.arange(M.n_rows) % 3 == 0
    got2 = np.asarray(mxv(M, x, mask=keep, accum=(jnp.add, C)))
    np.testing.assert_allclose(got2[keep], 1.0 + T[keep], rtol=1e-12)
    np.testing.assert_allclose(got2[~keep], 1.0)   # C kept where masked out


def test_row_mask_broadcasts_over_multivector():
    _, M = _sym()
    X = jnp.asarray(np.random.default_rng(0).standard_normal((M.n_rows, 3)))
    keep = np.arange(M.n_rows) < 10
    Y = np.asarray(mxm(M, X, mask=keep))
    assert np.all(Y[10:] == 0.0) and np.any(Y[:10] != 0.0)


# ----------------------------------------------- fast paths + generic folds

def test_segment_reduce_generic_fold_is_correct():
    """Unregistered monoid: the fold must honour (add, zero) — the old
    code silently used segment_sum."""
    custom = Semiring(add=jnp.minimum, mul=lambda a, b: a + b,
                      zero=jnp.inf, one=0.0, name="unregistered_min_+")
    assert fast_paths(custom).segment is None
    vals = jnp.asarray([3.0, 1.0, 2.0, 5.0])
    segs = jnp.asarray([0, 0, 2, 2])
    got = np.asarray(custom.segment_reduce(vals, segs, 3))
    np.testing.assert_allclose(got, [1.0, np.inf, 2.0])
    # and end-to-end through mxv it matches the registered twin
    _, M = _sym()
    x = jnp.abs(jnp.asarray(np.random.default_rng(0).standard_normal(M.n_rows)))
    got = mxv(M, x, custom, desc=Descriptor(backend="coo"))
    want = mxv(M, x, min_plus_ring, desc=Descriptor(backend="coo"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


def test_reduce_uses_registry_and_generic_fold():
    from repro.grblas import grb_reduce
    a = jnp.asarray(np.random.default_rng(0).standard_normal((6, 4)))
    np.testing.assert_allclose(np.asarray(grb_reduce(a, reals_ring, axis=0)),
                               np.asarray(a).sum(axis=0), rtol=1e-12)
    np.testing.assert_allclose(float(grb_reduce(a, min_plus_ring)),
                               np.asarray(a).min(), rtol=1e-12)
    assert bool(grb_reduce(a > 0, boolean_ring)) == bool((np.asarray(a) > 0).any())
    custom = Semiring(add=jnp.maximum, mul=lambda x, y: x * y,
                      zero=-jnp.inf, one=1.0, name="unregistered_max_x")
    np.testing.assert_allclose(float(grb_reduce(a, custom)),
                               np.asarray(a).max(), rtol=1e-12)


# ------------------------------------------------- multivals + shim contract

def test_with_vals_multivalues_spmm():
    """Alg-1's W-hat: per-column values on the fixed pattern."""
    _, M = _sym()
    rng = np.random.default_rng(4)
    what = jnp.asarray(rng.standard_normal((M.nnz, 3)))
    eta = jnp.asarray(rng.standard_normal((M.n_rows, 3)))
    got = np.asarray(mxm(M.with_vals(what), eta))
    want = np.zeros((M.n_rows, 3))
    np.add.at(want, np.asarray(M.rows),
              np.asarray(what) * np.asarray(eta)[np.asarray(M.cols)])
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)
    # derived layouts are dropped -> COO is the only capable backend
    assert available_backends(M.with_vals(what), eta) == ["coo"]
    # multivalues against a 1-D vector is a dispatch error, not a
    # broadcast crash deep inside the ring
    with pytest.raises(BackendUnavailableError):
        mxv(M.with_vals(what), jnp.ones(M.n_rows))


def test_spgemm_sparse_sparse_mxm():
    """GraphBLAS' general mxm: a SparseMatrix multiplicand dispatches to
    the spgemm backend and the product is a SparseMatrix."""
    rng = np.random.RandomState(11)
    A = sp.random(24, 30, density=0.15, random_state=rng)
    B = sp.random(30, 18, density=0.2, random_state=rng)
    Ma = SparseMatrix.from_scipy(A, dtype=jnp.float64)
    Mb = SparseMatrix.from_scipy(B, dtype=jnp.float64)
    assert available_backends(Ma, Mb) == ["spgemm"]
    C = mxm(Ma, Mb)
    assert isinstance(C, SparseMatrix)
    np.testing.assert_allclose(np.asarray(C.to_dense()), (A @ B).toarray(),
                               rtol=1e-10, atol=1e-12)
    # transpose descriptor: Aᵀ B
    B2 = sp.random(24, 9, density=0.2, random_state=rng)
    Mb2 = SparseMatrix.from_scipy(B2, dtype=jnp.float64)
    Ct = mxm(Ma, Mb2, desc=Descriptor(backend="spgemm", transpose=True))
    np.testing.assert_allclose(np.asarray(Ct.to_dense()),
                               (A.T @ B2).toarray(), rtol=1e-10, atol=1e-12)


def test_spgemm_rejects_nonreals_and_write_semantics():
    rng = np.random.RandomState(12)
    Ma = SparseMatrix.from_scipy(sp.random(10, 10, density=0.3,
                                           random_state=rng))
    Mb = SparseMatrix.from_scipy(sp.random(10, 10, density=0.3,
                                           random_state=rng))
    with pytest.raises(BackendUnavailableError):
        mxm(Ma, Mb, min_plus_ring)
    with pytest.raises(NotImplementedError):
        mxm(Ma, Mb, mask=np.ones(10, bool))
    # dense backends never claim a sparse multiplicand
    names = available_backends(Ma, Mb)
    assert names == ["spgemm"]


def test_deprecated_shims_deleted():
    """The one-release migration window (DESIGN.md §3) is over: the old
    flag-style entry points must be gone, so stale callers fail loudly
    at import instead of silently warning forever."""
    import repro.grblas.ops as grb_ops
    import repro.grblas.dist as grb_dist
    import repro.kernels.bsr_spmm as kb
    import repro.kernels.plap_edge as kp

    for mod, name in ((grb_ops, "mxm"), (grb_ops, "mxv"), (grb_ops, "vxm"),
                      (grb_dist, "dist_mxm"),
                      (kp, "plap_apply"), (kp, "plap_hvp_edge")):
        assert not callable(getattr(mod, name, None)), \
            f"{mod.__name__}.{name} should be deleted"
    # the bsr_spmm package attribute is the impl *module* now, never the
    # deleted shim function
    assert not callable(getattr(kb, "bsr_spmm", None)) or \
        getattr(kb, "bsr_spmm").__class__.__name__ == "module"
    # the replacements exist
    from repro.grblas.api import mxm as api_mxm  # noqa: F401
    assert callable(kb.bsr_spmm_pallas) and callable(kp.plap_apply_pallas)


def test_psc_backend_validated_up_front():
    """A PSCConfig backend that can never serve the edge-ring hot loop
    fails before any eigensolver work, not mid-Newton-iteration."""
    from repro.core.psc import PSCConfig, p_spectral_cluster
    from repro.graphs import ring_of_cliques

    W, _ = ring_of_cliques(3, 6)
    for bad in ("bsr_pallas", "edge_pallas", "dist"):
        with pytest.raises(BackendUnavailableError):
            p_spectral_cluster(W, PSCConfig(k=2, backend=bad))
    # "coo" and "ell" (plap edge kinds on the padded rows) pass
    # validation (full runs exercised elsewhere)
    for good in ("coo", "ell"):
        PSCConfig(k=2, backend=good).validate_backend(W)


def test_dist_rejects_traced_matrix_with_clear_error():
    """Auto-partitioning is host-side numpy; a matrix passed as a jit
    argument must raise an actionable error, not a TracerArrayConversion
    crash deep inside make_row_partition."""
    import jax
    from repro.grblas import backends as _backends

    _, M = _sym()
    X = jnp.ones((M.n_rows, 2))

    class _FakeMesh:
        shape = {"data": 1}

    desc = Descriptor(backend="dist", mesh=_FakeMesh())

    def f(W, X):
        return _backends._REGISTRY["dist"].execute(W, X, reals_ring, desc)

    with pytest.raises(Exception, match="traced SparseMatrix"):
        jax.jit(f)(M, X)


def test_dist_rejects_pad_unsound_edge_rings():
    """The dist path folds the padded-ELL axis with a plain sum, so only
    edge rings whose multiply annihilates pad zeros may ride it; generic
    edge closures must stay on COO even when a mesh is present."""
    from repro.grblas import EdgeSemiring, plap_edge_semiring
    from repro.grblas import backends as _backends

    _, M = _sym()
    X = jnp.ones((M.n_rows, 2))

    class _FakeMesh:
        shape = {"data": 2}

    desc = Descriptor(backend="dist", mesh=_FakeMesh())
    unsound = EdgeSemiring(base=reals_ring,
                           edge_mul=lambda w, xs, xd: jnp.where(w != 0, xs, 1.0),
                           name="pad_unsound_edge")
    assert not _backends._REGISTRY["dist"].supports(M, X, unsound, desc)
    assert _backends._REGISTRY["dist"].supports(
        M, X, plap_edge_semiring(1.5, 1e-8), desc)


def test_plap_hot_path_has_no_raw_segment_sum():
    """Acceptance pin: core/plap.py routes every SpMM-shaped reduction
    through grblas.api — no direct jax.ops.segment_sum in the hot path.
    Enforced by the pscheck api-boundary rule (repro.analysis)."""
    from pathlib import Path

    from repro import analysis
    from repro.core import plap

    analysis.assert_clean([Path(plap.__file__)],
                          rules=["api-boundary", "hot-purity"])
    assert "api.mxm" in Path(plap.__file__).read_text()
