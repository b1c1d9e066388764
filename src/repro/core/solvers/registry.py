"""Solver-driver registry for the nonlinear eigenproblem (DESIGN.md §7).

The p-spectral pipeline factors into three layers: the algebra
(grblas.api.mxm under a Descriptor), the *driver* that minimizes the
p-Rayleigh functional at one continuation level, and the continuation /
discretization shell around it (core.psc).  This module owns the middle
layer's dispatch — the solver analogue of ``grblas/backends.py``:

  * ``register_solver`` / ``resolve_solver`` — a name-keyed registry of
    ``Solver`` entries; unknown names raise ``SolverUnavailableError``
    (a ValueError, so config-time validation surfaces it loudly).
  * the driver contract — ``SolverState`` in (graph, warm-start U, p,
    config), ``SolverReport`` out (U, fval, operator-apply count,
    iteration count, converged flag).  Every driver consumes the same
    ``api.mxm`` rings; where two drivers converge they must land the
    same clusters (pinned by tests/test_solver_registry.py).
  * per-driver applicability — each entry declares its supported p
    range; ``validate_config`` checks ``p_target`` AND every value of
    the continuation schedule against it at config-construction time,
    so a p outside the driver's regime is a clear ValueError instead of
    NaNs deep in a minimization loop.
  * the p-continuation loop (``p_continuation`` / ``p_schedule``) and
    the trace-memo scaffolding (``memoized`` / ``mark_trace`` /
    ``SOLVER_TRACES``), hoisted out of core.psc so every driver gets
    PR-3's one-trace-per-schedule behavior for free: a driver builds
    its jitted step once per execution signature (p traced on jnp
    backends, static only where a Pallas kernel bakes ring params) and
    the whole schedule replays the cached callable.

Registered drivers (imported by ``core.solvers.__init__``):

  name           p range    regime
  newton         (1, 2]     trust-region Newton + tCG on Gr(k,n) — the
                            paper's driver (moved from core.psc)
  scf            (1, 2]     self-consistent field: linear eigenproblems
                            on the IRLS-reweighted graph (Upadhyaya,
                            Jarlebring & Tudisco, arXiv:2111.09750)
  inverse_power  [1, 2]     one eigenvector at a time with deflation,
                            p → 1 sparsest-cut end (Hein & Bühler) —
                            subsumes the old core.pmulti loop

A new driver is one ``register_solver`` call, not another private loop
welded into the pipeline.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp

from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace


class SolverUnavailableError(ValueError):
    """The requested solver is not registered (or cannot run here)."""


@dataclasses.dataclass(frozen=True)
class SolverState:
    """Input contract of one per-p minimization: minimize F_p over
    Gr(k,n) starting from the warm-start iterate ``U`` (orthonormal
    columns), reading execution knobs (backend descriptor, iteration
    budgets, eps) from ``cfg`` (a PSCConfig-shaped object)."""

    W: object                   # SparseMatrix (duck-typed: no psc import)
    U: jnp.ndarray              # (n, k) warm start, orthonormal columns
    p: float
    cfg: object                 # PSCConfig


@dataclasses.dataclass(frozen=True)
class SolverReport:
    """Output contract: the minimizer plus the paper's accounting units."""

    U: jnp.ndarray              # (n, k) iterate (orthonormal columns)
    fval: float                 # F_p at U
    n_apply: int                # operator applies (HVPs / SpMM sweeps) —
                                # the paper's scaling unit
    iters: int                  # outer iterations the driver ran
    converged: bool

    @property
    def n_hvp(self):
        """Back-compat alias: pre-registry callers read RTRResult.n_hvp."""
        return self.n_apply


@dataclasses.dataclass(frozen=True)
class Solver:
    name: str
    minimize_at_p: Callable     # (SolverState) -> SolverReport
    p_min: float
    p_max: float
    p_min_open: bool = True     # True: p must be > p_min (Newton needs
                                # the C^2 interior); False: p_min itself
                                # is reachable (the p→1 driver)
    description: str = ""

    def supports_p(self, p: float) -> bool:
        lo_ok = (p > self.p_min) if self.p_min_open else (p >= self.p_min)
        return lo_ok and p <= self.p_max

    def p_range_str(self) -> str:
        return f"{'(' if self.p_min_open else '['}{self.p_min}, {self.p_max}]"


_REGISTRY: Dict[str, Solver] = {}


def register_solver(name: str, *, p_min: float, p_max: float,
                    p_min_open: bool = True, description: str = ""):
    """Decorator: register ``fn`` as the minimize_at_p hook of ``name``."""

    def deco(fn):
        _REGISTRY[name] = Solver(name=name, minimize_at_p=fn, p_min=p_min,
                                 p_max=p_max, p_min_open=p_min_open,
                                 description=description)
        return fn

    return deco


def registered_solvers() -> Dict[str, Solver]:
    return dict(_REGISTRY)


def resolve_solver(name: str) -> Solver:
    solver = _REGISTRY.get(name)
    if solver is None:
        raise SolverUnavailableError(
            f"unknown solver {name!r}; registered: {sorted(_REGISTRY)}")
    return solver


def validate_config(cfg) -> Solver:
    """Config-time applicability check (called from PSCConfig.__post_init__):
    resolve the named driver, then verify the continuation schedule —
    p_target and every p the schedule will visit — sits inside its
    supported range.  A violation is a clear ValueError here, not NaNs
    deep in the minimization loop."""
    solver = resolve_solver(cfg.solver)
    if not (0.0 < cfg.p_factor < 1.0):
        raise ValueError(
            f"p_factor={cfg.p_factor} must lie in (0, 1): the continuation "
            f"schedule p_t = max(p_target, 2.0 * factor^t) must descend")
    ranges = {s.name: s.p_range_str() for s in _REGISTRY.values()}
    if not solver.supports_p(cfg.p_target):
        raise ValueError(
            f"p_target={cfg.p_target} outside solver {solver.name!r} "
            f"supported range {solver.p_range_str()}; per-driver ranges: "
            f"{ranges}")
    for p in p_schedule(cfg):
        if not solver.supports_p(p):
            raise ValueError(
                f"continuation schedule visits p={p} outside solver "
                f"{solver.name!r} supported range {solver.p_range_str()}; "
                f"per-driver ranges: {ranges}")
    return solver


# --- continuation scaffolding (hoisted from core.psc) ---------------------

def p_schedule(cfg) -> list:
    """The continuation schedule p_t = max(p_target, 2.0 * factor^t),
    t >= 1 — shared by the flat pipeline, the multilevel V-cycle and
    config validation."""
    ps, p = [], 2.0
    while True:
        p = max(cfg.p_target, p * cfg.p_factor)
        ps.append(p)
        if p <= cfg.p_target:
            return ps


def minimize_at_p(W, U0, p, cfg) -> SolverReport:
    """One continuation level under the driver ``cfg.solver`` names."""
    solver = resolve_solver(cfg.solver)
    return solver.minimize_at_p(SolverState(W=W, U=U0, p=p, cfg=cfg))


def p_continuation(W, U0, cfg):
    """Run the whole p schedule, warm-starting each level from the last.

    Returns (U, p_path, fvals, applies, reports) — the per-level records
    the pipeline stores in PSCResult (``reports`` is the full
    SolverReport per level, threaded into ``PSCResult.reports`` so the
    serve engine and benchmarks can meter convergence without re-running
    the solve).  Drivers are resolved once; every level replays the
    driver's memoized jitted step (one trace per execution signature,
    not per level — see ``memoized``)."""
    solver = resolve_solver(cfg.solver)
    U = U0
    p_path: List[float] = []
    fvals: List[float] = []
    applies: List[int] = []
    reports: List[SolverReport] = []
    for p in p_schedule(cfg):
        with _obs_trace.ACTIVE.span("solver.level", cat="solver",
                                    solver=solver.name, p=float(p)) as sp:
            rep = solver.minimize_at_p(SolverState(W=W, U=U, p=p, cfg=cfg))
            sp.fence(rep.U)
            sp.set(fval=float(rep.fval), n_apply=int(rep.n_apply),
                   iters=int(rep.iters), converged=bool(rep.converged))
        U = rep.U
        p_path.append(p)
        fvals.append(float(rep.fval))
        applies.append(int(rep.n_apply))
        reports.append(rep)
    return U, p_path, fvals, applies, reports


def warm_start(W, U0, cfg, p_final: Optional[float] = None,
               steps: int = 1):
    """Warm entry point of the driver contract (DESIGN.md §8): enter the
    continuation at its END instead of replaying the whole p schedule.

    ``U0`` is a previous solve's embedding (the Grassmann formulation
    makes any orthonormal (n, k) a feasible restart point); the driver
    runs only the last ``steps`` schedule values, ending at ``p_final``
    (default ``cfg.p_target``).  This is the repeat-tenant path the
    serve layer's warm cache feeds: a good U converges in a few sweeps
    of SCF or a couple of Newton steps, skipping the p=2 eigensolve and
    the descent from p=2 entirely.

    Returns the same (U, p_path, fvals, applies, reports) tuple as
    ``p_continuation``."""
    solver = resolve_solver(cfg.solver)
    p_end = cfg.p_target if p_final is None else float(p_final)
    if not solver.supports_p(p_end):
        raise ValueError(
            f"warm start at p={p_end} outside solver {solver.name!r} "
            f"supported range {solver.p_range_str()}")
    tail = [p for p in p_schedule(cfg) if p >= p_end][-max(int(steps), 1):]
    if not tail or tail[-1] != p_end:
        tail = (tail + [p_end])[-max(int(steps), 1):]
    U = U0
    p_path: List[float] = []
    fvals: List[float] = []
    applies: List[int] = []
    reports: List[SolverReport] = []
    for p in tail:
        with _obs_trace.ACTIVE.span("solver.level", cat="solver",
                                    solver=solver.name, p=float(p),
                                    warm=True) as sp:
            rep = solver.minimize_at_p(SolverState(W=W, U=U, p=p, cfg=cfg))
            sp.fence(rep.U)
            sp.set(fval=float(rep.fval), n_apply=int(rep.n_apply),
                   iters=int(rep.iters), converged=bool(rep.converged))
        U = rep.U
        p_path.append(p)
        fvals.append(float(rep.fval))
        applies.append(int(rep.n_apply))
        reports.append(rep)
    return U, p_path, fvals, applies, reports


# --- trace-memo scaffolding (hoisted from core.psc, PR-3) ------------------

_TRACE_CACHE: Dict[tuple, Callable] = {}
SOLVER_TRACES: List[tuple] = []   # one entry appended per *trace*; tests
                                  # assert a continuation doesn't grow it
TRACE_LISTENERS: List[Callable] = []   # extra per-compile hooks (key) -> None


def memoized(key: tuple, build: Callable) -> Callable:
    """The compiled callable for ``key``, building on first use.

    ``build()`` returns the jitted callable; its traced body should call
    ``mark_trace(key)`` so retraces are observable.  Keys are
    per-driver execution signatures — (driver name, backend, interpret,
    eps, iteration budget[, static p]) — so one cached callable serves
    every graph of matching layout signature across the whole
    continuation schedule and across runs."""
    fn = _TRACE_CACHE.get(key)
    if fn is None:
        fn = build()
        _TRACE_CACHE[key] = fn
    return fn


def mark_trace(key: tuple) -> None:
    """Record a trace event (call from inside the traced function: jit
    replays are silent, only fresh traces append).  Each fresh trace
    also bumps ``compiles_total{site=<key head>}`` on the DEFAULT
    metrics registry and stamps a ``compile`` instant on the active
    span timeline — obs.retrace builds its detector on this."""
    SOLVER_TRACES.append(key)
    site = str(key[0]) if key else "?"
    _obs_metrics.DEFAULT.counter("compiles_total", site=site).inc()
    _obs_trace.ACTIVE.instant("compile", site=site, key=str(key))
    for fn in TRACE_LISTENERS:
        fn(key)


def backend_bakes_ring_params(cfg, W, probes) -> bool:
    """Would the backend serving these (ring, X-probe) combinations bake
    the ring's (p, eps) into a Pallas kernel as static arguments?  Then
    p cannot be a tracer and the driver's memo key must include it
    (trace per level, cached across runs).  Each backend answers for
    the implementation it actually runs under this descriptor on this
    platform (``Backend.static_ring_params``); the jnp paths keep the
    traced-p single trace.  ``probes`` is a list of (ring, X) with X a
    ShapeDtypeStruct or a tuple of them (pair rings)."""
    from repro.grblas import backends as _backends

    desc = cfg.descriptor()
    for ring, X in probes:
        try:
            be = _backends.select_backend(W, X, ring, desc)
        except _backends.BackendUnavailableError:
            continue    # validate_backend already raised for real runs
        if be.static_ring_params(desc):
            return True
    return False
