"""LOBPCG iterative path vs scipy's sparse eigensolver (n > 1024 so the
dense-eigh fallback is NOT taken)."""
import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla
import pytest

from repro.core import lobpcg
from repro.graphs import delaunay_graph


def test_smallest_eigvecs_match_scipy():
    W, _ = delaunay_graph(11, seed=0)          # n=2048 -> iterative path
    assert W.n_rows > 1024
    k = 4
    evals, evecs = lobpcg.smallest_eigvecs(W, k, seed=0, max_iters=300,
                                           tol=1e-7)
    # scipy reference on the same Laplacian
    rows = np.asarray(W.rows); cols = np.asarray(W.cols)
    vals = np.asarray(W.vals)
    A = sp.coo_matrix((vals, (rows, cols)), shape=(W.n_rows, W.n_rows))
    L = sp.diags(np.asarray(A.sum(axis=1)).ravel()) - A.tocsr()
    ref = np.sort(spla.eigsh(L, k=k, sigma=-1e-3, which="LM",
                             return_eigenvectors=False))
    np.testing.assert_allclose(np.asarray(evals), ref, atol=1e-4)
    # residuals small: ||L v - lambda v||
    V = np.asarray(evecs)
    R = L @ V - V * np.asarray(evals)[None, :]
    assert np.linalg.norm(R, axis=0).max() < 1e-3


def test_eigvec_orthonormal():
    W, _ = delaunay_graph(11, seed=1)
    _, evecs = lobpcg.smallest_eigvecs(W, 3, seed=1, max_iters=200)
    G = np.asarray(evecs.T @ evecs)
    np.testing.assert_allclose(G, np.eye(3), atol=1e-5)


def _dot_precisions(jaxpr) -> list:
    """The ``precision`` of every dot_general in a jaxpr, sub-jaxprs
    (loop bodies, pjit calls) included."""
    import jax

    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    out += _dot_precisions(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    out += _dot_precisions(sub)
    return out


def _laplacian(seed=0):
    W, _ = delaunay_graph(11, seed=seed)
    return W, lobpcg.laplacian_matvec(W)


def test_lobpcg_rayleigh_ritz_runs_at_highest_precision(monkeypatch):
    """Every dense product of the host-loop solver's step (Rayleigh-Ritz
    T = S^T A S, the Ritz vectors S V and the P block) is a HIGHEST
    precision dot: one bfloat16 pass loses stage 1 on the TPU."""
    import functools

    import jax
    import jax.numpy as jnp

    steps = []
    real_jit = jax.jit

    def spy(fun, **kw):
        if getattr(fun, "__name__", "") == "step":
            steps.append(fun)
        return real_jit(fun, **kw)

    monkeypatch.setattr(jax, "jit", spy)
    W, mv = _laplacian()
    X0 = jax.random.normal(jax.random.PRNGKey(0), (W.n_rows, 8), jnp.float32)
    lobpcg.lobpcg(mv, X0, 4, max_iters=2)
    monkeypatch.setattr(jax, "jit", real_jit)
    assert len(steps) == 1
    for with_p in (False, True):
        jaxpr = jax.make_jaxpr(functools.partial(steps[0], with_p=with_p))(
            X0, X0).jaxpr
        precs = _dot_precisions(jaxpr)
        assert len(precs) >= 3
        highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        assert all(p == highest for p in precs), precs


def test_lobpcg_fixed_keeps_default_precision():
    """The serve engine's traceable solver is left at the default matmul
    precision: its compiled bucket programs stay what they were."""
    import jax
    import jax.numpy as jnp

    W, mv = _laplacian()
    X0 = jax.random.normal(jax.random.PRNGKey(0), (W.n_rows, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda X: lobpcg.lobpcg_fixed(mv, X, 4, iters=3))(X0).jaxpr
    precs = _dot_precisions(jaxpr)
    assert len(precs) >= 6 and all(p is None for p in precs), precs
