"""Blocked LOBPCG for the smallest-k eigenpairs of the graph Laplacian.

Used for the p=2 starting point of the continuation (classical spectral
clustering): the paper initializes GrB-pGrass from the linear (p=2)
eigenvectors, then tracks them as p decreases.

Pure-JAX implementation: Rayleigh-Ritz over the [X, W, P] block with a
Jacobi (diagonal) preconditioner and Householder-QR orthonormalization.
A dense jnp.linalg.eigh fallback handles tiny graphs.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.grblas.containers import DENSE_MAX_N, SparseMatrix
from repro.grblas import api
from repro.grblas.api import Descriptor


def laplacian_matvec(W: SparseMatrix, normalized: bool = False,
                     desc: Optional[Descriptor] = None) -> Callable:
    """Returns X -> L X with L = D - W (or I - D^-1/2 W D^-1/2).

    The inner SpMM routes through the unified API; ``desc`` selects the
    backend (auto: ELL/COO on CPU, Pallas BSR on TPU, dist with a mesh).
    """
    deg = W.row_sums()
    if normalized:
        dinv = jnp.where(deg > 0, jax.lax.rsqrt(jnp.maximum(deg, 1e-12)), 0.0)

        def mv(X):
            DX = dinv[:, None] * X if X.ndim == 2 else dinv * X
            WX = api.mxm(W, DX, desc=desc)
            return X - (dinv[:, None] * WX if X.ndim == 2 else dinv * WX)
    else:
        def mv(X):
            WX = api.mxm(W, X, desc=desc)
            return (deg[:, None] * X if X.ndim == 2 else deg * X) - WX
    return mv


def _ortho(X):
    """Householder QR orthonormalization.

    (A Cholesky-QR variant with jitter silently turns rank-deficient
    blocks into zero columns whose Rayleigh quotient is a spurious 0,
    hijacking the smallest-k Ritz selection — caught by
    tests/test_lobpcg.py; plain QR keeps the basis full rank.)"""
    Q, _ = jnp.linalg.qr(X)
    return Q


def lobpcg(matvec: Callable, X0: jnp.ndarray, k: int,
           precond_diag: Optional[jnp.ndarray] = None,
           max_iters: int = 200, tol: float = 1e-6) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Smallest-k eigenpairs of the SPSD operator ``matvec``.

    X0: (n, m) initial block with m >= k.  Returns (evals (k,), evecs (n,k)).
    Host loop with jitted body (graph eigenproblems here are O(1e6) max
    on CPU; the TPU path distributes the inner SpMM via grblas.dist).
    """
    n, m = X0.shape
    X = _ortho(X0)
    P = jnp.zeros_like(X)
    pinv = None
    if precond_diag is not None:
        pinv = jnp.where(jnp.abs(precond_diag) > 1e-12, 1.0 / precond_diag, 1.0)

    @partial(jax.jit, static_argnames=("with_p",))
    def step(X, P, with_p):
        AX = matvec(X)
        rho = jnp.sum(X * AX, axis=0)          # Rayleigh quotients
        R = AX - X * rho
        resnorm = jnp.linalg.norm(R, axis=0)
        if pinv is not None:
            R = pinv[:, None] * R
        # basis: [X, R(, P)], orthonormalized jointly (first iteration
        # has no P block — a zero block degrades the Ritz basis)
        blocks = [X, R] + ([P] if with_p else [])
        S = _ortho(jnp.concatenate(blocks, axis=1))
        AS = matvec(S)
        T = S.T @ AS
        T = 0.5 * (T + T.T)
        evals, V = jnp.linalg.eigh(T)
        Xn = S @ V[:, :m]
        # P = component of the update living outside the X block
        Pn = S[:, m:] @ V[m:, :m]
        return Xn, Pn, evals[:m], resnorm

    evals = jnp.zeros(m)
    for it in range(max_iters):
        X, P, evals, resnorm = step(X, P, it > 0)
        if float(jnp.max(resnorm[:k])) < tol:
            break
    return evals[:k], X[:, :k]


def lobpcg_fixed(matvec: Callable, X0: jnp.ndarray, k: int,
                 iters: int = 20,
                 precond_diag: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-iteration LOBPCG: the fully traceable (jit/vmap-able)
    variant of :func:`lobpcg` — no host convergence loop, no float()
    synchronization, a static ``iters`` trip count.

    This is the serve engine's batched inner eigensolver (DESIGN.md §8):
    one bucket of padded graphs runs this under ``jax.vmap`` inside a
    single compiled trace, warm-started from the previous embedding.
    Exact-zero rows of ``X0`` stay exactly zero through every step
    (matvec on isolated pad rows is 0, Householder reflectors never mix
    exact-zero rows), which is what makes bucket padding sound for the
    whole eigensolve, not just the SpMM.

    Same Rayleigh-Ritz body as :func:`lobpcg`; the first iteration runs
    without the P block (a zero block degrades the Ritz basis), the
    remaining ``iters - 1`` run inside one ``lax.fori_loop``.
    """
    n, m = X0.shape
    X = _ortho(X0)
    pinv = None
    if precond_diag is not None:
        pinv = jnp.where(jnp.abs(precond_diag) > 1e-12,
                         1.0 / precond_diag, 1.0)

    def step(X, P, with_p: bool):
        AX = matvec(X)
        rho = jnp.sum(X * AX, axis=0)
        R = AX - X * rho
        if pinv is not None:
            R = pinv[:, None] * R
        blocks = [X, R] + ([P] if with_p else [])
        S = _ortho(jnp.concatenate(blocks, axis=1))
        AS = matvec(S)
        T = S.T @ AS
        T = 0.5 * (T + T.T)
        evals, V = jnp.linalg.eigh(T)
        return S @ V[:, :m], S[:, m:] @ V[m:, :m], evals[:m]

    X, P, evals = step(X, jnp.zeros_like(X), False)

    def body(_, carry):
        X, P, _ = carry
        return step(X, P, True)

    X, P, evals = jax.lax.fori_loop(0, max(int(iters) - 1, 0), body,
                                    (X, P, evals))
    return evals[:k], X[:, :k]


def smallest_eigvecs(W: SparseMatrix, k: int, normalized: bool = False,
                     seed: int = 0, max_iters: int = 200,
                     tol: float = 1e-6,
                     desc: Optional[Descriptor] = None,
                     X0: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Smallest-k eigenpairs of the graph Laplacian of W.

    ``desc`` steers the inner Laplacian SpMM (must be a backend capable
    of the reals ring; the tiny-graph dense-eigh path ignores it).
    ``X0`` (n, >=1) warm-starts the LOBPCG block: its columns seed the
    search subspace (padded to block width with random vectors) — the
    SCF driver restarts each reweighted eigensolve from the previous
    sweep's eigenvectors this way.  The dense exact path ignores it."""
    n = W.n_rows
    if n <= DENSE_MAX_N:  # dense exact path for tiny graphs
        L = jnp.diag(W.row_sums()) - W.to_dense()
        if normalized:
            d = jnp.maximum(W.row_sums(), 1e-12)
            dih = jax.lax.rsqrt(d)
            L = dih[:, None] * L * dih[None, :]
        evals, evecs = jnp.linalg.eigh(L)
        return evals[:k], evecs[:, :k]
    mv = laplacian_matvec(W, normalized, desc=desc)
    m = min(max(2 * k, k + 4), n)
    key = jax.random.PRNGKey(seed)
    rand = jax.random.normal(key, (n, m), jnp.float32)
    if X0 is not None:
        warm = X0 if X0.ndim == 2 else X0[:, None]
        X0 = rand.astype(warm.dtype).at[:, : min(warm.shape[1], m)].set(
            warm[:, : min(warm.shape[1], m)])
    else:
        X0 = rand
    # seed the constant vector (known nullvector) for fast convergence
    X0 = X0.at[:, 0].set(1.0)
    deg = W.row_sums()
    return lobpcg(mv, X0, k, precond_diag=jnp.maximum(deg, 1e-6),
                  max_iters=max_iters, tol=tol)
