"""The skewed-degree configuration's float64 p = 2 basis (block LOBPCG)
against ``psc.p2_basis`` (dense ``eigh`` at this size) on an LFR-style
graph of 4,096 vertices."""
import numpy as np
import pytest

from harness import graphs_lfr as L
from references import psc, psc_lfr


def test_p2_basis_agrees_with_dense_eigh():
    n, k = 4096, 4
    r, c, v, _ = L.lfr_coo(n, 4, 2.0, 20.0, 256, 0.2, 1)
    w, V = psc_lfr.p2_basis(r, c, v, n, k)
    w_ref, V_ref = psc.p2_basis(r, c, v, n, k)
    np.testing.assert_allclose(w, w_ref, rtol=1e-8, atol=1e-10)
    cosines = np.linalg.svd(V.T @ V_ref, compute_uv=False)
    assert cosines.min() > 1 - 1e-8            # the same subspace
    np.testing.assert_allclose(V.T @ V, np.eye(k), atol=1e-10)
    for p in (2.0, 1.4):
        np.testing.assert_allclose(psc.fval(r, c, v, V, p, 1e-8),
                                   psc.fval(r, c, v, V_ref, p, 1e-8),
                                   rtol=1e-7)


def test_exports_what_the_checks_use():
    for name in ("fval", "rcut", "spmm", "p2_basis", "ortho_err",
                 "rel_err", "scalar_rel_err", "BF16", "F64"):
        assert hasattr(psc_lfr, name), name


def test_p2_basis_raises_where_it_does_not_converge():
    n, k = 4096, 4
    r, c, v, _ = L.lfr_coo(n, 4, 2.0, 20.0, 256, 0.2, 1)
    with pytest.raises(psc_lfr.ReferenceNotConverged):
        psc_lfr.p2_basis(r, c, v, n, k, max_iters=2)
