"""Faults planted in the program where it produces its answer, for the
checks that must read them as not correct (``bench/tests``) and for the
readings that set each limit's upper end (``bench/tools/readings.py``).

  none            nothing planted
  answer_altered  the embedding gets 0.5 added to one row as it leaves
                  each solver step (flat, multilevel) or the batched
                  bucket solve (serve)
  half_batch      half of each batch is left out and the rest stands in
                  for it: every sparse product keeps the even rows and
                  doubles them; every bucket launch leaves the second
                  half of its lanes unsolved (zero embeddings)
  lane_swap       every bucket launch hands each request the result
                  (labels, embedding, RCut) of the next lane's request
  p2_start        every solver step returns the embedding it was given
                  (the answer stays at its p = 2 start, or its
                  prolonged coarse start)

``plant(fault)`` returns a function that takes the fault out again.
"""
from __future__ import annotations

FAULTS = ("none", "answer_altered", "half_batch", "lane_swap", "p2_start")


def plant(fault: str):
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault == "none":
        return lambda: None
    import dataclasses

    import jax.numpy as jnp
    import numpy as np
    from repro.core.solvers import registry
    from repro.grblas import api
    from repro.serve import psc_engine

    Engine = psc_engine.ClusterServeEngine
    drivers = registry._REGISTRY
    saved_drivers = dict(drivers)
    saved = [(api, "mxm", api.mxm),
             (Engine, "_solve_bucket", Engine._solve_bucket),
             (Engine, "_run_bucket", Engine._run_bucket),
             (psc_engine, "_make_level_step", psc_engine._make_level_step)]
    solve_bucket = Engine._solve_bucket

    def patch_drivers(wrap):
        # every continuation level, warm start and V-cycle refinement
        # runs through a registered driver's minimize_at_p
        for name, solver in saved_drivers.items():
            drivers[name] = dataclasses.replace(
                solver, minimize_at_p=wrap(solver.minimize_at_p))

    def patch_bucket(edit):
        def bucket(self, pends, spec):
            U, new, secs = solve_bucket(self, pends, spec)
            U = np.array(U)
            edit(U, len(pends))
            return U, new, secs

        Engine._solve_bucket = bucket

    if fault == "answer_altered":
        def altering(minimize):
            def altered(state):
                rep = minimize(state)
                return dataclasses.replace(rep, U=rep.U.at[0].add(0.5))

            return altered

        def edit(U, live):
            U[:, 0, :] += 0.5

        patch_drivers(altering)
        patch_bucket(edit)
    elif fault == "half_batch":
        mxm = api.mxm

        def half(A, X, *args, **kw):
            Y = mxm(A, X, *args, **kw)
            if not hasattr(Y, "ndim") or Y.ndim == 0:
                return Y
            keep = (jnp.arange(Y.shape[0]) % 2 == 0).reshape(
                (-1,) + (1,) * (Y.ndim - 1))
            return jnp.where(keep, 2.0 * Y, 0.0)

        def edit(U, live):
            U[(live + 1) // 2:] = 0.0

        api.mxm = half
        patch_bucket(edit)
    elif fault == "lane_swap":
        run_bucket = Engine._run_bucket

        def swapped(self, pends):
            run_bucket(self, pends)
            ids = [p.req_id for p in pends if p.req_id in self._results]
            res = [self._results[i] for i in ids]
            for i, r in zip(ids, res[1:] + res[:1]):
                self._results[i] = dataclasses.replace(r, req_id=i)

        Engine._run_bucket = swapped
    elif fault == "p2_start":
        from repro.core import plap

        def unchanged(minimize):
            def same(state):
                fval = plap.value(state.W, state.U, state.p, state.cfg.eps)
                return registry.SolverReport(U=state.U, fval=float(fval),
                                             n_apply=0, iters=0,
                                             converged=False)

            return same

        def same_level(cfg):
            def step(W, mask, U, p):
                return U, jnp.zeros((), U.dtype)

            return step

        patch_drivers(unchanged)
        # the bucket lanes' scan over the p schedule (built on a
        # bucket's first launch, so plant before the engine warms up)
        psc_engine._make_level_step = same_level

    def undo():
        for owner, name, value in saved:
            setattr(owner, name, value)
        drivers.clear()
        drivers.update(saved_drivers)

    return undo
