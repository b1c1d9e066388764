"""p-Laplacian functional: closed-form grad/HVP vs jax autodiff oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plap
from repro.graphs import ring_of_cliques, gaussian_blobs_knn

PS = [2.0, 1.7, 1.3, 1.1]


@pytest.fixture(scope="module")
def setup():
    W, _ = gaussian_blobs_knn(15, 3, seed=3)
    rng = np.random.default_rng(0)
    n, k = W.n_rows, 3
    U = jnp.asarray(np.linalg.qr(rng.standard_normal((n, k)))[0])
    eta = jnp.asarray(rng.standard_normal((n, k)) * 0.1)
    return W, U, eta


@pytest.mark.parametrize("p", PS)
def test_grad_matches_autodiff(setup, p):
    W, U, _ = setup
    eps = 1e-6
    f = plap.autodiff_value(W, p, eps)
    want = jax.grad(f)(U)
    got = plap.euc_grad(W, U, p, eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("mode", ["graphblas", "matrix_free"])
def test_hvp_matches_autodiff(setup, p, mode):
    W, U, eta = setup
    eps = 1e-6
    want = plap.autodiff_hvp(W, U, eta, p, eps)
    fn = (plap.hess_eta_graphblas if mode == "graphblas"
          else plap.hess_eta_matrix_free)
    got = fn(W, U, eta, p, eps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-8)


@pytest.mark.parametrize("mode", ["graphblas", "matrix_free"])
def test_newton_driver_hvp_is_the_mode_hvp(setup, mode):
    from repro.core.solvers.newton import ehvp_at

    W, U, eta = setup
    fn = (plap.hess_eta_graphblas if mode == "graphblas"
          else plap.hess_eta_matrix_free)
    got = ehvp_at(W, 1.4, 1e-7, mode, None)(U)(eta)
    want = fn(W, U, eta, 1.4, 1e-7)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hvp_paths_agree(setup):
    W, U, eta = setup
    a = plap.hess_eta_graphblas(W, U, eta, 1.4, 1e-7)
    b = plap.hess_eta_matrix_free(W, U, eta, 1.4, 1e-7)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-9)


def test_p2_recovers_linear_rayleigh(setup):
    """At p=2 (eps=0), F_2(u) = u^T L u / (2... ) — check against dense L."""
    W, U, _ = setup
    L = np.diag(np.asarray(W.row_sums())) - np.asarray(W.to_dense())
    val = float(plap.value(W, U, 2.0, 0.0))
    Un = np.asarray(U)
    want = sum(Un[:, l] @ L @ Un[:, l] / (Un[:, l] @ Un[:, l])
               for l in range(U.shape[1]))
    np.testing.assert_allclose(val, want, rtol=1e-8)


def test_constant_vector_is_nullvector(setup):
    W, _, _ = setup
    ones = jnp.ones((W.n_rows, 1)) / np.sqrt(W.n_rows)
    for p in PS:
        assert float(plap.value(W, ones, p, 0.0)) < 1e-12
        # eps-smoothing leaves an O(eps^{p/2} * sum(w))-scale bias; shrink
        # eps (x64 active in tests) and allow the residual scale
        g = plap.euc_grad(W, ones, p, 1e-12)
        assert float(jnp.linalg.norm(g)) < 1e-5
