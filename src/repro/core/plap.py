"""The p-Laplacian functional F_p, its Euclidean gradient and Hessian apply.

For one eigenvector column u with graph weights W (symmetric):

    A(u) = 1/2 sum_ij w_ij s(u_i - u_j)       s(x) = (x^2+eps)^{p/2}
    B(u) = sum_i s(u_i)                        (= ||u||_p^p, smoothed)
    F(u) = A(u) / B(u)          F_p(U) = sum_l F(u^l)

Closed forms (derived; pinned to jax autodiff in tests/test_plap.py):

    grad A   = p * Delta_p u              (Delta_p u)_i = sum_j w_ij phi(u_i-u_j)
    grad B   = p * phi(u)
    grad F   = (p/B) [Delta_p u - F * phi(u)]

    Hess A   = p [diag(W-hat 1) - W-hat]   w-hat_ij = w_ij phi'(u_i-u_j)
    Hess B   = p diag(phi'(u))
    Hess F @ eta = (1/B) Hess A eta - (F/B) Hess B eta
                   - (1/B^2)[gA (gB.eta) + gB (gA.eta)] + (2F/B^2) gB (gB.eta)

Every SpMM-shaped reduction routes through the unified GraphBLAS API
(grblas.api.mxm) under a Descriptor — backend="auto" serves the Newton
hot loop from the fused Pallas kernels when the BSR layout is built (on
TPU), the SELL-C-σ sliced gather path when that layout is built (the
skewed-degree scaling regime, DESIGN.md §5), the dense backend when a
caller built the dense (n, n) layout (the serve bucket lane: every edge
sum is then a (k, n, n) elementwise fold), and the COO/ELL gather paths
otherwise; there are no raw jax.ops.segment_sum calls left in the hot
path.

Two HVP implementations:
  * hess_eta_graphblas  — Algorithm-1-faithful: materialize D[l] and the
    off-diagonal W-hat[l] (multivalues on W's fixed pattern, via
    W.with_vals), then mxm + eWiseApply per column (the paper's Alg. 1),
    plus the rank-one quotient corrections as dot/axpy vector ops.
  * hess_eta_matrix_free — TPU-adapted: one fused SpMM under the
    pair-edge-semiring, no W-hat materialization (DESIGN.md §2,
    adaptation 4).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.grblas.containers import SparseMatrix
from repro.grblas import api
from repro.grblas import ops as grb
from repro.grblas.api import Descriptor
from repro.grblas.semiring import (plap_edge_semiring,
                                   plap_hvp_edge_semiring, reals_ring)
from repro.core import phi as PHI

_AUTO = Descriptor()


class PLapParts(NamedTuple):
    A: jnp.ndarray      # (k,) numerators
    B: jnp.ndarray      # (k,) denominators
    F: jnp.ndarray      # (k,) Rayleigh quotients
    dpu: jnp.ndarray    # (n,k) Delta_p u per column
    phi_u: jnp.ndarray  # (n,k)


def _edge_diffs(W: SparseMatrix, U: jnp.ndarray) -> jnp.ndarray:
    """d_e = u_i - u_j per nnz edge (directed; W stores both (i,j),(j,i)),
    (nnz, k).  On the dense layout every (i, j) pair, (k, n, n): an
    absent edge carries w 0 in ``_edge_weights``."""
    if W.dense is not None:
        Ut = U.T
        return Ut[:, :, None] - Ut[:, None, :]
    return U[W.rows] - U[W.cols]


def _edge_weights(W: SparseMatrix) -> jnp.ndarray:
    """w_e laid out to broadcast against ``_edge_diffs``."""
    if W.dense is not None:
        return W.dense[None]
    return W.vals[:, None]


def _edge_total(W: SparseMatrix, x: jnp.ndarray) -> jnp.ndarray:
    """Per-column sum of an ``_edge_diffs``-shaped array, (k,)."""
    if W.dense is not None:
        return jnp.sum(x, axis=(1, 2))
    return jnp.sum(x, axis=0)


def parts(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float,
          desc: Optional[Descriptor] = None) -> PLapParts:
    """All shared quantities for value/grad: one edge pass for the scalar
    energies + one edge-semiring SpMM for Delta_p u (the kernel-served op)."""
    d = _edge_diffs(W, U)                                    # (nnz, k)
    w = _edge_weights(W)
    A = 0.5 * _edge_total(W, w * PHI.p_power(d, p, eps))     # (k,)
    B = jnp.sum(PHI.p_power(U, p, eps), axis=0)              # (k,)
    dpu = api.mxm(W, U, plap_edge_semiring(p, eps), desc=desc or _AUTO)
    return PLapParts(A=A, B=B, F=A / B, dpu=dpu, phi_u=PHI.phi(U, p, eps))


def value(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float = 1e-9,
          desc: Optional[Descriptor] = None) -> jnp.ndarray:
    pr = parts(W, U, p, eps, desc)
    return jnp.sum(pr.F)


def euc_grad(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float = 1e-9,
             desc: Optional[Descriptor] = None) -> jnp.ndarray:
    """EucGrad: (p/B)[Delta_p u - F phi(u)] columnwise. (n,k)."""
    pr = parts(W, U, p, eps, desc)
    return (p / pr.B) * (pr.dpu - pr.F * pr.phi_u)


def value_and_grad(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float = 1e-9,
                   desc: Optional[Descriptor] = None):
    pr = parts(W, U, p, eps, desc)
    g = (p / pr.B) * (pr.dpu - pr.F * pr.phi_u)
    return jnp.sum(pr.F), g


# ---------------------------------------------------------------- HVP paths

def hessian_weights(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float):
    """w-hat_e = w_e phi'(u_i - u_j) per edge and column. (nnz,k); on the
    dense layout (k, n, n) dense multivalues (``W.with_vals``)."""
    d = _edge_diffs(W, U)
    return _edge_weights(W) * PHI.phi_prime(d, p, eps)


def build_alg1_operands(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float,
                        desc: Optional[Descriptor] = None):
    """The paper's Algorithm-1 inputs: per column l,
       D[l] = diag(Hess A^l) / p   (vector)  and
       H[l] = off-diagonal W-hat^l (multivalues on W's pattern).
    Returned stacked over columns: D (n,k), What_vals (nnz,k) — or
    (k,n,n) on the dense layout.
    D is the W-hat row sums — mxv with the ones multivector."""
    what = hessian_weights(W, U, p, eps)                     # (nnz,k)
    Wh = W.with_vals(what)
    D = api.mxm(Wh, jnp.ones_like(U), reals_ring,
                desc=_multival_desc(Wh, U, desc))
    return D, what


def _multival_desc(Wh: SparseMatrix, U, desc: Optional[Descriptor]):
    """The caller's descriptor for the materialized-multivalue SpMMs —
    degraded to auto when the named backend can't execute (nnz, k)
    multivalues (e.g. edge_pallas, which is hot-loop-only), so a pinned
    "coo"/"sellcs" really does control the whole Alg-1 HVP."""
    return api.capable_desc(Wh, reals_ring, desc, k=U.shape[-1],
                            dtype=U.dtype)


def hess_eta_graphblas(W: SparseMatrix, U: jnp.ndarray, eta: jnp.ndarray,
                       p: float, eps: float = 1e-9,
                       desc: Optional[Descriptor] = None) -> jnp.ndarray:
    """Algorithm-1-faithful HVP (materialized W-hat), full quotient rule.

    Per column l (all fused):
      1. v  = mxm(What[l], eta, reals_ring)        [Alg.1 line 7]
      2. w  = eWiseApply(eta, D[l], mul)           [Alg.1 line 8]
      3. hA = p * (w - v)                          [Alg.1 line 9 + scale]
    then the rank-one quotient corrections (vector dots / axpys).
    The materialized multivalues run the COO backend — or the SELL-C-σ
    layout when built: with_vals re-scatters the packed slice values
    on-device, so Alg-1's W-hat SpMM stays on the sliced layout too —
    or the dense backend on the dense layout, as (k, n, n) multivalues.
    ``desc`` steers ``parts`` and, when its backend can execute
    multivalues (coo / sellcs / dense), the W-hat SpMMs as well;
    hot-loop-only backends (edge_pallas) degrade those two ops to "auto".
    """
    return _hvp_operator(W, U, p, eps, "graphblas", desc)(eta)


def hess_eta_matrix_free(W: SparseMatrix, U: jnp.ndarray, eta: jnp.ndarray,
                         p: float, eps: float = 1e-9,
                         desc: Optional[Descriptor] = None) -> jnp.ndarray:
    """TPU-adapted HVP: one fused pair-edge-semiring SpMM, nothing
    materialized.  Hess A @ eta per column
        = p * sum_j w-hat_ij (eta_i - eta_j)
    with w-hat computed per edge inside the ring (Pallas kernel when the
    BSR layout is built on TPU; COO segment path otherwise)."""
    return _hvp_operator(W, U, p, eps, "matrix_free", desc)(eta)


def hvp_at(W: SparseMatrix, U: jnp.ndarray, p: float, eps: float = 1e-9,
           mode: str = "graphblas",
           desc: Optional[Descriptor] = None):
    """eta -> Hess F_p(U) @ eta at a fixed U: ``hess_eta_graphblas``
    (``mode`` "graphblas") or ``hess_eta_matrix_free`` ("matrix_free")
    for a caller that applies the Hessian many times at one point (the
    tCG loop of one Newton step).  What depends on U alone — ``parts``
    and, for "graphblas", Algorithm 1's D and W-hat — is built once,
    here, behind an optimization barrier: without it XLA sinks the W-hat
    elementwise chain back into the product's fusion inside the tCG
    loop, and recomputes it at every tCG step."""
    return _hvp_operator(W, U, p, eps, mode, desc,
                         keep=jax.lax.optimization_barrier)


def _hvp_operator(W, U, p, eps, mode, desc, keep=lambda ops: ops):
    pr = parts(W, U, p, eps, desc)
    if mode == "graphblas":
        pr, (D, what_vals) = keep(
            (pr, build_alg1_operands(W, U, p, eps, desc)))
        Wh = W.with_vals(what_vals)

        def apply(eta):
            # lines 6-9 of Algorithm 1, k columns fused through one SpMM:
            v = api.mxm(Wh, eta, reals_ring,
                        desc=_multival_desc(Wh, eta, desc))
            w = grb.e_wise_apply(eta, D, jnp.multiply)
            hA_eta = p * grb.e_wise_apply(w, v, jnp.subtract)  # Hess A @ eta
            return _quotient_correct(pr, U, eta, hA_eta, p, eps)
    else:
        pr = keep(pr)

        def apply(eta):
            hA_eta = p * api.mxm(W, (U, eta), plap_hvp_edge_semiring(p, eps),
                                 desc=desc or _AUTO)
            return _quotient_correct(pr, U, eta, hA_eta, p, eps)
    return apply


def _quotient_correct(pr: PLapParts, U, eta, hA_eta, p, eps):
    """Assemble Hess F @ eta from Hess A @ eta + quotient-rule terms."""
    gA = p * pr.dpu                                   # grad A (n,k)
    gB = p * pr.phi_u                                 # grad B (n,k)
    hB_eta = p * PHI.phi_prime(U, p, eps) * eta       # Hess B diag apply
    gB_eta = jnp.sum(gB * eta, axis=0)                # (k,)
    gA_eta = jnp.sum(gA * eta, axis=0)
    B, F = pr.B, pr.F
    return (hA_eta / B
            - (F / B) * hB_eta
            - (gA * gB_eta + gB * gA_eta) / (B * B)
            + (2.0 * F / (B * B)) * gB * gB_eta)


# ------------------------------------------------------------- autodiff oracle

def autodiff_value(W: SparseMatrix, p: float, eps: float):
    """F_p as a closure for jax.grad / jvp-of-grad oracles in tests."""
    def f(U):
        d = U[W.rows] - U[W.cols]
        A = 0.5 * jnp.sum(W.vals[:, None] * PHI.p_power(d, p, eps), axis=0)
        B = jnp.sum(PHI.p_power(U, p, eps), axis=0)
        return jnp.sum(A / B)
    return f


def autodiff_hvp(W: SparseMatrix, U, eta, p: float, eps: float = 1e-9):
    f = autodiff_value(W, p, eps)
    return jax.jvp(jax.grad(f), (U,), (eta,))[1]
