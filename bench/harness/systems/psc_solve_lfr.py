"""System under test: one skewed-degree graph (an LFR-style planted
partition, ``harness/graphs_lfr.py``), clustered whole by
``repro.core.p_spectral_cluster`` on the layout ``from_coo`` builds by
default, SELL-C-σ for such a graph.  The window, the checks and the
per-op comparison are ``psc_solve``'s; set-up builds this graph, and
``layer_run`` adds the layout the program built and, in traced runs,
the device time of the Newton driver's own Hessian-vector product."""
from __future__ import annotations

import statistics
import time

from harness import graphs_lfr
from harness import spec as S
from harness import traffic as T
from harness import tracing

_base = S.system("psc_solve")
window = _base.window
end_to_end = _base.end_to_end
check = _base.check

# HVPs the long probe program chains beyond the short one's single HVP
PROBE_APPLIES = 20
# each probe program runs this many times in the profiled session
PROBE_ROUNDS = 3
PROBE_SPAN = "bench.hvp_probe"


def _layout_span(tracer):
    """The attrs of the program's ``grblas.build_sellcs`` span, or None
    when the program recorded none."""
    for s in tracer.spans:
        if s.name == "grblas.build_sellcs":
            return dict(s.attrs)
    return None


def refusal(runs: int, bound) -> str | None:
    """Why set-up refuses a SELL-C-σ layout of ``runs`` width runs from
    a program whose own bound on them is ``bound`` (None where it has
    none), or None.  Every run is one slot loop in each compiled
    program, and without a bound this graph builds hundreds, whose
    compiles alone outlast a run: such a program fails at once."""
    if bound is None:
        return (f"the program built {runs} SELL-C-σ width runs for this "
                f"graph and bounds none (no SELLCS_MAX_RUNS)")
    if runs > bound:
        return (f"the program built {runs} SELL-C-σ width runs for this "
                f"graph, above its own bound {bound}")
    return None


def setup(ctx) -> dict:
    import jax
    from repro.core import p_spectral_cluster
    from repro.grblas import SparseMatrix, containers
    from repro.obs import trace as obs_trace

    conf = ctx.config
    if S.arrivals(ctx.traffic["arrivals"]).LOOP != "closed":
        raise S.SpecError(f"psc_solve_lfr runs a closed loop, not "
                          f"{ctx.traffic['arrivals']!r}")
    t0 = time.perf_counter()
    n, (rows, cols, vals), _ = graphs_lfr.lfr_graph(conf["graph"])
    build = obs_trace.Tracer()
    with obs_trace.use(build):
        W = SparseMatrix.from_coo(rows, cols, vals, (n, n))
        jax.block_until_ready(W)
    build_s = time.perf_counter() - t0
    layout = _layout_span(build)
    runs = len(W.sell_cols or ())
    ctx.info("layout", n=n, nnz=int(len(rows)), sell_runs=runs,
             sellcs_fill=W.sellcs_fill_ratio(), ell_built=W.ell_cols
             is not None, build_sellcs_span=layout)
    if W.sell_cols is not None:
        why = refusal(runs, getattr(containers, "SELLCS_MAX_RUNS", None))
        if why:
            raise S.SpecError(why)

    pcfg = _base.psc_config(conf, ctx.traffic, conf["warmup_seed"])
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        warm = p_spectral_cluster(W, pcfg)
        jax.block_until_ready(warm.U)
    dispatched = _base._dispatched(tracer)
    ctx.info("setup", n=n, nnz=int(len(rows)), k=conf["k"],
             graph_build_s=build_s, dispatched=dispatched,
             warmup_rcut=warm.rcut, warmup_init_rcut=warm.init_rcut,
             warmup_hvps=int(sum(warm.hvp_counts)))
    return {"W": W, "coo": (rows, cols, vals), "n": n, "pcfg": pcfg,
            "dispatched": dispatched, "graph_build_s": build_s,
            "layout": layout}


def device_busy_in(trace: dict, name: str) -> list:
    """Device-busy seconds inside each host span called ``name`` of a
    ``trace_reduce.load_xplane`` trace, in order (first device plane,
    as ``trace_reduce`` reads it); [] without a device plane."""
    import trace_reduce

    planes = [v for _, v in sorted(trace["device"].items()) if v]
    if not planes:
        return []
    ops = [(s, s + d) for s, d, _ in planes[0]]
    out = []
    for lo, d, span in sorted(trace["host"]):
        if span != name:
            continue
        hi = lo + d
        busy = trace_reduce.union([(max(a, lo), min(b, hi)) for a, b in ops
                                   if min(b, hi) > max(a, lo)])
        out.append(1e-9 * sum(b - a for a, b in busy))
    return out


def hvp_probe(ctx, st) -> dict | None:
    """Device seconds of one Hessian-vector product of the timed solve:
    the Newton driver's own HVP (``newton.ehvp_at``, as ``rtr_minimize``
    gets it: built once at U, applied to a chain of directions), at the
    timed descriptor and sizes.  Two programs, of 1 and of 1 +
    ``PROBE_APPLIES`` chained HVPs, run ``PROBE_ROUNDS`` times each under
    the profiler; an HVP is the difference of their median device-busy
    seconds over ``PROBE_APPLIES``.  None where the program has no such
    entry point; ``hvp_s`` None where the trace has no device plane."""
    import jax
    import jax.numpy as jnp
    import trace_reduce

    try:
        from repro.core.solvers.newton import ehvp_at
    except ImportError:
        return None
    W, n, k, pcfg = st["W"], st["n"], ctx.config["k"], st["pcfg"]
    desc = pcfg.descriptor()
    r = T.rng(ctx.seed, 3)
    U = jnp.asarray(r.standard_normal((n, k)), jnp.float32)
    E = jnp.asarray(r.standard_normal((n, k)), jnp.float32)
    p = jnp.float32(pcfg.p_target)

    def chain(W, U, E, p, reps):
        hvp = ehvp_at(W, p, pcfg.eps, pcfg.hvp_mode, desc)(U)

        def step(_, x):
            y = hvp(x)
            return y * jax.lax.rsqrt(jnp.sum(y * y) + 1e-30)
        return jax.lax.fori_loop(0, reps, step, E)

    prog = jax.jit(chain, static_argnames="reps")
    counts = (1, 1 + PROBE_APPLIES)
    for reps in counts:
        jax.block_until_ready(prog(W, U, E, p, reps=reps))
    prof = tracing.Profile()
    prof.start()
    try:
        with tracing.annotate(trace_reduce.WINDOW_SPAN):
            for _ in range(PROBE_ROUNDS):
                for reps in counts:
                    with tracing.annotate(f"{PROBE_SPAN}.{reps}"):
                        jax.block_until_ready(prog(W, U, E, p, reps=reps))
        prof.stop()
        trace = (trace_reduce.load_xplane(prof.path) if prof.path
                 else {"device": {}, "host": []})
    finally:
        prof.close()
    short = device_busy_in(trace, f"{PROBE_SPAN}.1")
    long_ = device_busy_in(trace, f"{PROBE_SPAN}.{1 + PROBE_APPLIES}")
    hvp_s = None
    if short and long_:
        hvp_s = (statistics.median(long_)
                 - statistics.median(short)) / PROBE_APPLIES
    return {"n": n, "nnz": len(st["coo"][0]), "k": k, "hvp_s": hvp_s,
            "short_s": short, "long_s": long_}


def layer_run(ctx, st, rec) -> dict:
    run = _base.layer_run(ctx, st, rec)
    run["sell_layout"] = st["layout"]
    run["hvp_probe"] = probe = hvp_probe(ctx, st)
    ctx.info("hvp_probe", **(probe or {}))
    return run
