"""The p-Laplacian on the dense layout (the serve bucket lane's path):
value, gradient and both Hessian applies against the COO path and the
autodiff oracle, on a Girvan–Newman-like graph padded into a bucket."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plap
from repro.grblas.api import Descriptor, available_backends
from repro.grblas.containers import SparseMatrix
from repro.grblas.semiring import plap_edge_semiring, reals_ring
from repro.graphs import sbm_graph

COO = Descriptor(backend="coo")
DENSE = Descriptor(backend="dense")
EPS = 1e-6
N_BUCKET, NNZ_BUCKET, K = 128, 2048, 4


def _relerr(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.fixture(scope="module")
def bucket():
    """A 4 x 25-vertex planted partition (p_in 0.5, p_out 0.04: degree
    about 15.5, mostly inside the group) padded like a serve request:
    100 real vertices in a 128-vertex bucket, pad edges (0, 0, 0.0)."""
    G, _ = sbm_graph([25] * 4, 0.5, 0.04, seed=7)
    assert G.nnz <= NNZ_BUCKET
    rows, cols, vals = G.padded_coo(N_BUCKET, NNZ_BUCKET)
    W = SparseMatrix(n_rows=N_BUCKET, n_cols=N_BUCKET, nnz=NNZ_BUCKET,
                     rows=jnp.asarray(rows), cols=jnp.asarray(cols),
                     vals=jnp.asarray(vals, jnp.float32))
    rng = np.random.default_rng(3)
    n = G.n_rows
    U = np.zeros((N_BUCKET, K), np.float32)
    U[:n] = np.linalg.qr(rng.standard_normal((n, K)))[0]
    eta = np.zeros((N_BUCKET, K), np.float32)
    eta[:n] = 0.1 * rng.standard_normal((n, K))
    return W, W.with_dense(), jnp.asarray(U), jnp.asarray(eta), n


@pytest.mark.parametrize("p", [2.0, 1.5, 1.2])
def test_dense_plap_matches_coo_and_autodiff(bucket, p):
    W, Wd, U, eta, n = bucket
    # value and gradient against the COO segment path
    f_c, g_c = plap.value_and_grad(W, U, p, EPS, desc=COO)
    f_d, g_d = plap.value_and_grad(Wd, U, p, EPS, desc=DENSE)
    assert float(f_d) == pytest.approx(float(f_c), rel=2e-6)
    assert _relerr(g_d, g_c) < 5e-6
    # both Hessian applies against the COO path and the autodiff oracle
    want = plap.autodiff_hvp(W, U.astype(jnp.float64),
                             eta.astype(jnp.float64), p, EPS)
    for mode, fn in (("graphblas", plap.hess_eta_graphblas),
                     ("matrix_free", plap.hess_eta_matrix_free)):
        h_c = fn(W, U, eta, p, EPS, desc=COO)
        h_d = fn(Wd, U, eta, p, EPS, desc=DENSE)
        h_at = plap.hvp_at(Wd, U, p, EPS, mode, desc=DENSE)(eta)
        assert _relerr(h_d, h_c) < 5e-6, mode
        assert _relerr(h_d, want) < 5e-6, mode
        np.testing.assert_array_equal(np.asarray(h_at), np.asarray(h_d))
        # pad rows stay exact zeros
        assert not np.asarray(h_d)[n:].any(), mode
    assert not np.asarray(g_d)[n:].any()


def test_dense_alg1_operands_are_dense_multivalues(bucket):
    """Alg. 1's W-hat on the dense layout is a (k, n, n) tile that only
    the dense backend can execute, and that ``to_dense`` refuses (k
    matrices, no COO values); its row sums D match the COO path."""
    W, Wd, U, _, _ = bucket
    D_c, _ = plap.build_alg1_operands(W, U, 1.5, EPS, desc=COO)
    D_d, what = plap.build_alg1_operands(Wd, U, 1.5, EPS, desc=DENSE)
    assert what.shape == (K, N_BUCKET, N_BUCKET)
    assert _relerr(D_d, D_c) < 2e-6
    Wh = Wd.with_vals(what)
    assert Wh.vals is None and Wh.dense.shape == what.shape
    assert available_backends(Wh, U, reals_ring) == ["dense"]
    with pytest.raises(ValueError, match="multivalues"):
        Wh.to_dense()


def test_auto_never_picks_dense_without_the_layout(bucket):
    W, Wd, U, _, _ = bucket
    ring = plap_edge_semiring(1.5, EPS)
    assert "dense" not in available_backends(W, U, ring)
    assert available_backends(Wd, U, ring)[0] == "dense"
    # a COO-shaped with_vals drops the (now stale) dense layout
    assert Wd.with_vals(Wd.vals * 2.0).dense is None


@pytest.mark.parametrize("shape", [(N_BUCKET,), (N_BUCKET, K)])
def test_dense_reals_spmm_matches_coo(bucket, shape):
    from repro.grblas import api

    W, Wd, _, _, _ = bucket
    X = jnp.asarray(np.random.default_rng(5).standard_normal(shape),
                    jnp.float32)
    got = api.mxm(Wd, X, desc=DENSE)
    want = api.mxm(W, X, desc=COO)
    assert got.shape == want.shape
    assert _relerr(got, want) < 1e-6


def test_dense_layout_traces_under_vmap(bucket):
    """with_dense inside a vmapped, jitted function (how the bucket lane
    builds it): one tile per lane."""
    W, _, U, _, _ = bucket

    def f(vals, U):
        Wl = SparseMatrix(n_rows=W.n_rows, n_cols=W.n_cols, nnz=W.nnz,
                          rows=W.rows, cols=W.cols, vals=vals).with_dense()
        return plap.value(Wl, U, 1.5, EPS, desc=DENSE)

    vals = jnp.stack([W.vals, 2.0 * W.vals])
    out = jax.jit(jax.vmap(f))(vals, jnp.stack([U, U]))
    np.testing.assert_allclose(np.asarray(out[1]), 2.0 * np.asarray(out[0]),
                               rtol=1e-6)
