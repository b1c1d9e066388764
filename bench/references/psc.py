"""Plain reference for p-spectral clustering outputs.

Straight from the published definitions, on host COO triples that the
benchmark built itself; it imports nothing of the program.  The same
formulas run in two precisions:

  * ``F64``: numpy float64, the reference every check compares with;
  * ``BF16``: jax.numpy in bfloat16 on the default device, the control
    that stands in for the program and has to fail the checks.

For one column u of U, with W symmetric (both directions stored) and
s(x) = (x^2 + eps)^(p/2):

    F(u) = A(u) / B(u),  A(u) = 1/2 sum_e w_e s(u_i - u_j),
                         B(u) = sum_i s(u_i);      F_p(U) = sum_l F(u^l)
    RCut(C) = sum_a cut(C_a, not C_a) / |C_a|   (directed edge sums)

The p = 2 start: the k smallest eigenvectors of L = D - W, in float64
(dense ``eigh`` for small graphs, shift-invert ``eigsh`` for large ones).

SpMM rings, per column (row i, column j of stored edge e):

    reals        y_i = sum_e w_e x_j
    multivalue   y_i = sum_e w_{e,l} x_j           (Alg. 1's W-hat)
    plap_apply   y_i = sum_e w_e phi(x_i - x_j),   phi(x) = (x^2+eps)^((p-2)/2) x
    plap_hvp     y_i = sum_e w_e phi'(u_i - u_j) (eta_i - eta_j)
"""
from __future__ import annotations

import numpy as np


class _F64:
    name = "f64"

    @staticmethod
    def arr(x):
        return np.asarray(x, np.float64)

    @staticmethod
    def segsum(vals, rows, n):
        vals = np.asarray(vals)
        if vals.ndim == 1:
            return np.bincount(rows, weights=vals, minlength=n)
        return np.stack([np.bincount(rows, weights=vals[:, l], minlength=n)
                         for l in range(vals.shape[1])], axis=1)

    xp = np

    @staticmethod
    def host(x):
        return np.asarray(x, np.float64)


class _BF16:
    name = "bf16"

    @staticmethod
    def arr(x):
        import jax.numpy as jnp

        return jnp.asarray(np.asarray(x), jnp.bfloat16)

    @staticmethod
    def segsum(vals, rows, n):
        import jax

        return jax.ops.segment_sum(vals, rows, n)

    @property
    def xp(self):
        import jax.numpy as jnp

        return jnp

    @staticmethod
    def host(x):
        return np.asarray(np.asarray(x).astype(np.float32), np.float64)


F64 = _F64()
BF16 = _BF16()


def _smooth_power(xp, x, p, eps):
    return (x * x + eps) ** (p / 2.0)


def fval(rows, cols, vals, U, p, eps, prec=F64) -> float:
    xp = prec.xp
    U, w = prec.arr(U), prec.arr(vals)
    d = U[rows] - U[cols]
    A = 0.5 * xp.sum(w[:, None] * _smooth_power(xp, d, p, eps), axis=0)
    B = xp.sum(_smooth_power(xp, U, p, eps), axis=0)
    return float(prec.host(xp.sum(A / B)))


def rcut(rows, cols, vals, labels, k, prec=F64) -> float:
    xp = prec.xp
    labels = np.asarray(labels).astype(np.int64)
    n = len(labels)
    w = prec.arr(vals)
    crossing = prec.arr((labels[rows] != labels[cols]).astype(np.float64))
    out = prec.segsum(w * crossing, rows, n)             # cut weight per row
    per = prec.segsum(out, labels, k) if prec is not F64 else \
        np.bincount(labels, weights=out, minlength=k)
    sizes = np.bincount(labels, minlength=k).astype(np.float64)
    ratio = per / prec.arr(np.maximum(sizes, 1.0))
    return float(prec.host(xp.sum(ratio)))


def spmm(ring, rows, cols, vals, X, n, p=None, eps=None, prec=F64):
    """One SpMM under ``ring``: "reals", "multivalue" (vals (nnz, k)),
    "plap_apply" or "plap_hvp" (X = (U, Eta))."""
    w = prec.arr(vals)
    if ring == "plap_hvp":
        U, E = prec.arr(X[0]), prec.arr(X[1])
        d = U[rows] - U[cols]
        x2e = d * d + eps
        dphi = x2e ** ((p - 2.0) / 2.0) + (p - 2.0) * d * d * \
            x2e ** ((p - 4.0) / 2.0)
        contrib = w[:, None] * dphi * (E[rows] - E[cols])
    else:
        X = prec.arr(X)
        if ring == "reals":
            contrib = w[:, None] * X[cols]
        elif ring == "multivalue":
            contrib = w * X[cols]
        elif ring == "plap_apply":
            d = X[rows] - X[cols]
            contrib = w[:, None] * (d * d + eps) ** ((p - 2.0) / 2.0) * d
        else:
            raise ValueError(f"unknown ring {ring!r}")
    return prec.host(prec.segsum(contrib, rows, n))


def p2_basis(rows, cols, vals, n, k):
    """(eigenvalues, eigenvectors) of the k smallest eigenpairs of the
    graph Laplacian D - W, in float64."""
    import scipy.sparse as sp

    A = sp.csr_matrix((np.asarray(vals, np.float64), (rows, cols)),
                      shape=(n, n))
    L = sp.diags(np.asarray(A.sum(axis=1)).ravel()) - A
    if n <= 4096:
        w, V = np.linalg.eigh(L.toarray())
        return w[:k], V[:, :k]
    from scipy.sparse.linalg import eigsh

    # L + 1e-3 I is positive definite: its LU is exact, and the
    # eigenvalues nearest -1e-3 are the smallest of L
    w, V = eigsh(L.tocsc(), k=k, sigma=-1e-3, which="LM",
                 v0=np.ones(n) / np.sqrt(n))
    order = np.argsort(w)
    return w[order], V[:, order]


def ortho_err(U) -> float:
    """max |U^T U - I| of an embedding, in float64."""
    U = np.asarray(U, np.float64)
    G = U.T @ U
    return float(np.max(np.abs(G - np.eye(G.shape[0]))))


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-300))


def scalar_rel_err(got: float, want: float) -> float:
    return abs(float(got) - float(want)) / max(abs(float(want)), 1e-300)
