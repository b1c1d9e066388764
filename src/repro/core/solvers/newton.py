"""Newton driver: trust-region Newton + truncated CG on Gr(k,n) — the
paper's solver, moved out of core.psc behind the registry contract.

The per-p minimization is one jitted function, memoized per execution
signature with ``p`` as a *traced* scalar wherever the backend allows
(every jnp path), so the p-continuation loop hits one trace for the
whole schedule instead of re-tracing per level.  Pallas kernel paths
bake (p, eps) into the kernel as static arguments, so there the memo
key includes p (trace per level, cached across runs) — the probe lives
on the backend registry (Backend.static_ring_params), surfaced here
through ``registry.backend_bakes_ring_params``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import plap
from repro.core.grassmann import rtr_minimize
from repro.core.solvers import registry
from repro.core.solvers.registry import SolverReport, register_solver


def _needs_static_p(cfg, W, U0) -> bool:
    """Would the backend serving the Newton hot loop (plap apply + the
    configured HVP ring) bake (p, eps) into a Pallas kernel?"""
    from repro.grblas.semiring import (plap_edge_semiring,
                                       plap_hvp_edge_semiring)

    probe = jax.ShapeDtypeStruct((W.n_rows, U0.shape[-1]), U0.dtype)
    probes = [(plap_edge_semiring(2.0, cfg.eps), probe)]
    if cfg.hvp_mode == "matrix_free":
        probes.append((plap_hvp_edge_semiring(2.0, cfg.eps), (probe, probe)))
    return registry.backend_bakes_ring_params(cfg, W, probes)


def _jitted_minimize(cfg, p, W, U0):
    """The jitted per-p trust-region minimization, memoized per
    (backend, interpret, hvp_mode, eps, iteration budget[, p]).  W rides
    along as a pytree argument, so one cached callable serves every
    graph of matching layout signature."""
    static_p = float(p) if _needs_static_p(cfg, W, U0) else None
    key = ("newton", cfg.backend, cfg.interpret, cfg.hvp_mode, cfg.eps,
           cfg.newton_iters, cfg.tcg_iters, cfg.grad_tol, static_p)

    def build():
        desc = cfg.descriptor()
        eps, hvp_mode = cfg.eps, cfg.hvp_mode
        newton_iters, tcg_iters, grad_tol = (cfg.newton_iters, cfg.tcg_iters,
                                             cfg.grad_tol)

        def run(W, U0, p_run):
            registry.mark_trace(key)
            f = lambda U: plap.value(W, U, p_run, eps, desc=desc)
            g = lambda U: plap.euc_grad(W, U, p_run, eps, desc=desc)
            if hvp_mode == "graphblas":
                h = lambda U, eta: plap.hess_eta_graphblas(W, U, eta, p_run,
                                                           eps, desc=desc)
            else:
                h = lambda U, eta: plap.hess_eta_matrix_free(W, U, eta, p_run,
                                                             eps, desc=desc)
            return rtr_minimize(f, g, lambda U: partial(h, U), U0,
                                max_iters=newton_iters,
                                tcg_iters=tcg_iters, grad_tol=grad_tol)

        if static_p is None:
            return jax.jit(run)
        return jax.jit(lambda W, U0: run(W, U0, static_p))

    return registry.memoized(key, build), static_p


@register_solver("newton", p_min=1.0, p_max=2.0, p_min_open=True,
                 description="trust-region Newton + tCG on Gr(k,n) "
                             "(the paper's driver)")
def newton_minimize_at_p(state) -> SolverReport:
    cfg, W, U0 = state.cfg, state.W, state.U
    fn, static_p = _jitted_minimize(cfg, state.p, W, U0)
    if static_p is not None:
        res = fn(W, U0)
    else:
        # p rides in U0's dtype so float64 pipelines keep the
        # full-precision continuation values
        res = fn(W, U0, jnp.asarray(state.p, U0.dtype))
    return SolverReport(U=res.U, fval=float(res.fval),
                        n_apply=int(res.n_hvp), iters=int(res.iters),
                        converged=bool(res.gradnorm <= cfg.grad_tol))
