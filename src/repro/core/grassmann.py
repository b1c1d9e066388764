"""Riemannian trust-region Newton on the Grassmann manifold Gr(k, n).

This is the in-JAX replacement for the ROPTLIB subset the paper uses:
Newton's method on Gr(k,n) with a truncated conjugate-gradient (Steihaug
tCG) inner solver, under a trust region for global convergence
(Absil, Baker & Gallivan, "Trust-region methods on Riemannian
manifolds", 2007 — the solver ROPTLIB's RTRNewton implements).

Representation: a point is an orthonormal U in R^{n x k} (U^T U = I_k);
the tangent space is {xi : U^T xi = 0}.

  proj_U(Z)  = Z - U (U^T Z)               (Euclidean-metric projection)
  rgrad      = proj_U(egrad)
  rhess(eta) = proj_U( ehess(eta) - eta (U^T egrad) )   (Gr correction)
  retract    = qf(U + eta)                 (thin-QR retraction)

Everything is jit-able; the outer loop is lax.while_loop so the whole
optimizer runs on-device (and distributes when the callbacks shard).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


def proj(U, Z):
    return Z - U @ (U.T @ Z)


def retract_qr(U, eta):
    Q, R = jnp.linalg.qr(U + eta)
    # fix sign so retraction is continuous (diag(R) > 0)
    sgn = jnp.sign(jnp.diag(R))
    sgn = jnp.where(sgn == 0, 1.0, sgn)
    return Q * sgn[None, :]


def inner(a, b):
    return jnp.sum(a * b)


class RTRState(NamedTuple):
    U: jnp.ndarray
    fval: jnp.ndarray
    grad: jnp.ndarray
    gradnorm: jnp.ndarray
    radius: jnp.ndarray
    it: jnp.ndarray
    n_hvp: jnp.ndarray  # Hessian-apply count (the paper's scaling unit)


class RTRResult(NamedTuple):
    U: jnp.ndarray
    fval: jnp.ndarray
    gradnorm: jnp.ndarray
    iters: jnp.ndarray
    n_hvp: jnp.ndarray


def _tcg(U, grad, hvp, radius, tcg_iters: int, kappa=0.1, theta=1.0):
    """Steihaug-Toint truncated CG for the trust-region subproblem.

    min_eta <grad,eta> + 1/2 <eta, H eta>   s.t. ||eta|| <= radius,
    eta in T_U.  Returns (eta, n_hvp_used).
    """
    eta0 = jnp.zeros_like(grad)
    r0 = grad
    d0 = -r0
    r0r0 = inner(r0, r0)
    norm_g = jnp.sqrt(r0r0)
    stop_tol = norm_g * jnp.minimum(kappa, norm_g ** theta)

    def boundary_point(eta, d):
        """tau >= 0 with ||eta + tau d|| = radius."""
        dd = inner(d, d)
        ed = inner(eta, d)
        ee = inner(eta, eta)
        disc = jnp.sqrt(jnp.maximum(ed * ed + dd * (radius ** 2 - ee), 0.0))
        tau = (-ed + disc) / jnp.maximum(dd, 1e-30)
        return eta + tau * d

    class C(NamedTuple):
        eta: jnp.ndarray
        r: jnp.ndarray
        d: jnp.ndarray
        rr: jnp.ndarray
        k: jnp.ndarray
        done: jnp.ndarray
        n_hvp: jnp.ndarray

    def cond(c: C):
        return jnp.logical_and(c.k < tcg_iters, jnp.logical_not(c.done))

    def body(c: C):
        Hd = proj(U, hvp(c.d))
        dHd = inner(c.d, Hd)
        alpha = c.rr / jnp.where(dHd == 0, 1e-30, dHd)
        eta_next = c.eta + alpha * c.d
        hit_boundary = jnp.logical_or(dHd <= 0,
                                      jnp.sqrt(inner(eta_next, eta_next)) >= radius)
        eta_b = boundary_point(c.eta, c.d)
        r_next = c.r + alpha * Hd
        rr_next = inner(r_next, r_next)
        small = jnp.sqrt(rr_next) <= stop_tol
        beta = rr_next / jnp.where(c.rr == 0, 1e-30, c.rr)
        d_next = -r_next + beta * c.d
        eta_out = jnp.where(hit_boundary, eta_b, eta_next)
        done = jnp.logical_or(hit_boundary, small)
        return C(eta=eta_out, r=r_next, d=d_next, rr=rr_next,
                 k=c.k + 1, done=done, n_hvp=c.n_hvp + 1)

    init = C(eta=eta0, r=r0, d=d0, rr=r0r0, k=jnp.array(0),
             done=jnp.array(False), n_hvp=jnp.array(0))
    out = jax.lax.while_loop(cond, body, init)
    return out.eta, out.n_hvp


def rtr_minimize(f: Callable, egrad: Callable, ehvp_at: Callable,
                 U0: jnp.ndarray,
                 max_iters: int = 50, tcg_iters: int = 25,
                 grad_tol: float = 1e-6, radius0: float = 0.5,
                 radius_max: float = 4.0) -> RTRResult:
    """Trust-region Newton on Gr(k,n).

    f(U) -> scalar; egrad(U) -> (n,k); ehvp_at(U) -> (eta -> (n,k)), the
    Euclidean HVP at U.  ehvp_at is called once per Newton step, outside
    the tCG loop, so what the Hessian apply builds from U alone can be
    built once per step (``plap.hvp_at``).
    """

    def rgrad(U):
        return proj(U, egrad(U))

    def rhess(U, g_e, ehvp_U, eta):
        # Grassmann Hessian: proj( ehvp - eta (U^T egrad) )
        return proj(U, ehvp_U(eta) - eta @ (U.T @ g_e))

    def cond(s: RTRState):
        return jnp.logical_and(s.it < max_iters, s.gradnorm > grad_tol)

    def body(s: RTRState):
        g_e = egrad(s.U)
        g = proj(s.U, g_e)
        ehvp_U = ehvp_at(s.U)
        hvp = lambda eta: rhess(s.U, g_e, ehvp_U, eta)
        eta, used = _tcg(s.U, g, hvp, s.radius, tcg_iters)
        U_try = retract_qr(s.U, eta)
        f_try = f(U_try)
        # actual vs predicted reduction
        Heta = proj(s.U, hvp(eta))
        pred = -(inner(g, eta) + 0.5 * inner(eta, Heta))
        ared = s.fval - f_try
        rho = ared / jnp.where(jnp.abs(pred) < 1e-30, 1e-30, pred)
        accept = rho > 0.05
        U_new = jnp.where(accept, U_try, s.U)
        f_new = jnp.where(accept, f_try, s.fval)
        shrink = rho < 0.25
        grow = jnp.logical_and(rho > 0.75,
                               jnp.sqrt(inner(eta, eta)) > 0.9 * s.radius)
        radius = jnp.where(shrink, 0.25 * s.radius,
                           jnp.where(grow, jnp.minimum(2.0 * s.radius, radius_max),
                                     s.radius))
        g_new = proj(U_new, egrad(U_new))
        return RTRState(U=U_new, fval=f_new, grad=g_new,
                        gradnorm=jnp.linalg.norm(g_new),
                        radius=radius, it=s.it + 1,
                        n_hvp=s.n_hvp + used + 1)

    g0 = rgrad(U0)
    s0 = RTRState(U=U0, fval=f(U0), grad=g0, gradnorm=jnp.linalg.norm(g0),
                  radius=jnp.array(radius0), it=jnp.array(0),
                  n_hvp=jnp.array(0))
    out = jax.lax.while_loop(cond, body, s0)
    return RTRResult(U=out.U, fval=out.fval, gradnorm=out.gradnorm,
                     iters=out.it, n_hvp=out.n_hvp)
