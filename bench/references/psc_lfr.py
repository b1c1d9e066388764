"""Plain reference of the skewed-degree configuration (``sbm_hub_4x32k``).

F_p, RCut, the SpMM rings and the error measures are ``psc.py``'s,
imported unchanged.  Only the float64 p = 2 basis differs: ``psc.py``
factors L + 1e-3 I for shift-invert ``eigsh``, and on a power-law graph
with shuffled ids that LU fills in (minutes at 2^14 vertices).  Here the
k smallest eigenpairs of L = D - W come from block LOBPCG in float64
(scipy), with the Jacobi preconditioner D^-1, started from the constant
vector and seeded random columns: no factorization, and nothing of the
planted partition.  Like ``psc.py`` it imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from references.psc import (BF16, F64, fval, ortho_err, rcut,  # noqa: F401
                            rel_err, scalar_rel_err, spmm)

# LOBPCG block: k plus this many guard columns, which speed up the
# convergence of the k-th pair when the spectrum above it is dense
GUARD = 4
MAX_ITERS = 200
TOL = 1e-6
# the most the basis's span may stray from the k smallest eigenvectors'
# (the Davis-Kahan sin-theta bound, estimated from the residuals and the
# gap to the first guard Ritz value): F_p of the basis then moves by
# about as much in relative terms, 30 times under the room of the
# fval_ratio limit (0.93 sound, 0.97 limit, 1.0 under p2_start)
SPAN_TOL = 1e-3


class ReferenceNotConverged(RuntimeError):
    pass


def p2_basis(rows, cols, vals, n, k, max_iters: int = MAX_ITERS,
             seed: int = 0):
    """(eigenvalues, eigenvectors) of the k smallest eigenpairs of the
    graph Laplacian D - W, in float64 (block LOBPCG, module docstring).
    Raises ``ReferenceNotConverged`` when, after ``max_iters``, the
    residuals ||L V - V diag(w)||_F over the gap to the next Ritz value
    (a bound on the sine of the angle between the span of V and that of
    the k smallest eigenvectors) exceed ``SPAN_TOL``."""
    import scipy.sparse as sp
    from scipy.sparse.linalg import LinearOperator, lobpcg

    A = sp.csr_matrix((np.asarray(vals, np.float64), (rows, cols)),
                      shape=(n, n))
    deg = np.asarray(A.sum(axis=1)).ravel()
    L = (sp.diags(deg) - A).tocsr()
    inv_deg = 1.0 / np.maximum(deg, 1e-12)
    M = LinearOperator((n, n), matvec=lambda x: inv_deg * x.ravel(),
                       matmat=lambda X: inv_deg[:, None] * X,
                       dtype=np.float64)
    X0 = np.random.default_rng(seed).standard_normal((n, k + GUARD))
    X0[:, 0] = 1.0
    w_all, V_all = lobpcg(L, X0, M=M, largest=False, tol=TOL,
                          maxiter=max_iters)
    order = np.argsort(w_all)
    w, V = w_all[order[:k]], V_all[:, order[:k]]
    res = float(np.linalg.norm(L @ V - V * w))
    gap = float(w_all[order[k]] - w[-1])
    if not res <= SPAN_TOL * gap:
        raise ReferenceNotConverged(
            f"the float64 p = 2 basis did not converge in {max_iters} "
            f"LOBPCG iterations: residual {res:.3g} over gap {gap:.3g} "
            f"is above {SPAN_TOL}")
    return w, V
