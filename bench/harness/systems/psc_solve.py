"""System under test: one graph, clustered whole by
``repro.core.p_spectral_cluster`` (flat, or down the multilevel
V-cycle).  Solves run back to back in a window of whole solves."""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from harness import graphs as G
from harness import spec as S
from harness import traffic as T
from harness import tracing
from harness.window import whole_solve_window

# the ring each per-op check drives, by the ring kind the timed path
# dispatched (grblas.dispatch instants of the warm-up solve)
RING_OF_KIND = {"reals_+x": "reals", "plap_apply": "plap_apply",
                "plap_hvp": "plap_hvp"}


def _dispatched(tracer) -> dict:
    """{ring kind: sorted backends} the traced solve dispatched."""
    seen = {}
    for e in tracer.events:
        if e["name"] == "grblas.dispatch":
            a = e["attrs"]
            seen.setdefault(a["ring"], set()).add(a["backend"])
    for s in tracer.spans:
        if s.name == "grblas.mxm":
            seen.setdefault(s.attrs["ring"], set()).add(s.attrs["backend"])
    return {k: sorted(v) for k, v in sorted(seen.items())}


def psc_config(conf: dict, traffic: dict, seed: int):
    from repro.core import PSCConfig

    ml = None
    if traffic.get("route") == "multilevel":
        from repro.multilevel import MultilevelConfig

        ml = MultilevelConfig(**traffic.get("multilevel", {}))
    return PSCConfig(k=conf["k"], p_target=conf["p_target"],
                     p_factor=conf["p_factor"],
                     newton_iters=conf["newton_iters"],
                     tcg_iters=conf["tcg_iters"], backend=conf["backend"],
                     seed=seed, multilevel=ml)


def setup(ctx) -> dict:
    import jax
    from repro.core import p_spectral_cluster
    from repro.grblas import SparseMatrix
    from repro.obs import trace as obs_trace

    conf, gconf = ctx.config, ctx.config["graph"]
    if S.arrivals(ctx.traffic["arrivals"]).LOOP != "closed":
        raise S.SpecError(f"psc_solve runs a closed loop, not "
                          f"{ctx.traffic['arrivals']!r}")
    t0 = time.perf_counter()
    n, (rows, cols, vals) = G.delaunay_coo(gconf["log2_n"], gconf["seed"])
    W = SparseMatrix.from_coo(rows, cols, vals, (n, n))
    jax.block_until_ready(W)
    build_s = time.perf_counter() - t0

    pcfg = psc_config(conf, ctx.traffic, conf["warmup_seed"])
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        warm = p_spectral_cluster(W, pcfg)
        jax.block_until_ready(warm.U)
    ctx.info("setup", n=n, nnz=int(len(rows)), k=conf["k"],
             graph_build_s=build_s, dispatched=_dispatched(tracer),
             warmup_rcut=warm.rcut, warmup_init_rcut=warm.init_rcut,
             ell_fill=W.ell_fill_ratio())
    return {"W": W, "coo": (rows, cols, vals), "n": n, "pcfg": pcfg,
            "dispatched": _dispatched(tracer), "graph_build_s": build_s}


def window(ctx, st) -> dict:
    import jax
    from repro.core import p_spectral_cluster

    seeds = T.derived_seeds(ctx.seed, 4096)
    tracers = []

    def solve(i):
        cfg = dataclasses.replace(st["pcfg"], seed=seeds[i])
        if not ctx.trace:
            res = p_spectral_cluster(st["W"], cfg)
            jax.block_until_ready(res.U)
            return res
        tr = tracing.annotating_tracer()
        with tracing.use_tracer(tr), tracing.annotate("bench.solve"):
            res = p_spectral_cluster(st["W"], cfg)
            jax.block_until_ready(res.U)
        tracers.append(tr)
        return res

    rec = whole_solve_window(solve, ctx.seconds, time.perf_counter)
    rec["seeds"] = seeds[:len(rec["results"])]
    rec["tracers"] = tracers
    return rec


def end_to_end(ctx, st, rec) -> dict:
    return {"solve_s": rec["solve_s"]}


def _claims(ctx, res):
    """What the timed solve claims, or the control's stand-in for it:
    the reference in bfloat16 in the program's place."""
    U = np.asarray(res.U, np.float64)
    claim = {"U": U, "fval": res.fvals[-1], "rcut": res.rcut}
    if ctx.control:
        ref, (rows, cols, vals) = ctx.ref, ctx.state["coo"]
        p, eps = res.p_path[-1], ctx.state["pcfg"].eps
        claim["U"] = ref.BF16.host(ref.BF16.arr(U))
        claim["fval"] = ref.fval(rows, cols, vals, U, p, eps, ref.BF16)
        claim["rcut"] = ref.rcut(rows, cols, vals, res.labels,
                                 ctx.config["k"], ref.BF16)
    return claim


def _per_op(ctx, st):
    """Each ring the timed path dispatched, through ``grblas.mxm`` with
    the timed descriptor at the timed sizes, against the reference.
    Returns ({ring: rel err}, number of rings run on a backend the timed
    path did not use)."""
    import jax
    import jax.numpy as jnp
    from repro.grblas import mxm
    from repro.grblas.semiring import (plap_edge_semiring,
                                       plap_hvp_edge_semiring, reals_ring)
    from repro.obs import trace as obs_trace

    ref, W, (rows, cols, vals) = ctx.ref, st["W"], st["coo"]
    n, k, pcfg = st["n"], ctx.config["k"], st["pcfg"]
    p, eps = pcfg.p_target, pcfg.eps
    r = T.rng(ctx.seed, 2)
    X = r.standard_normal((n, k)).astype(np.float32)
    E = r.standard_normal((n, k)).astype(np.float32)
    what = r.uniform(0.5, 1.5, (len(rows), k)).astype(np.float32)
    desc = pcfg.descriptor()
    rings = {"reals": (lambda W, X, E, wv: mxm(W, X, reals_ring, desc=desc),
                       X, vals),
             "plap_apply": (lambda W, X, E, wv: mxm(
                 W, X, plap_edge_semiring(p, eps), desc=desc), X, vals),
             "plap_hvp": (lambda W, X, E, wv: mxm(
                 W, (X, E), plap_hvp_edge_semiring(p, eps), desc=desc),
                 (X, E), vals),
             "multivalue": (lambda W, X, E, wv: mxm(
                 W.with_vals(wv), X, reals_ring, desc=desc), X, what)}
    kinds = st["dispatched"]
    wanted = [RING_OF_KIND[kd] for kd in kinds if kd in RING_OF_KIND]
    if pcfg.hvp_mode == "graphblas" and "reals" in wanted:
        wanted.append("multivalue")
    out, used = {}, {}
    for name in wanted:
        fn, arg, wv = rings[name]
        tr = obs_trace.Tracer()
        with obs_trace.use(tr):
            got = jax.jit(fn)(W, jnp.asarray(X), jnp.asarray(E),
                              jnp.asarray(what))
            got = np.asarray(got)
        used[name] = sorted({b for bs in _dispatched(tr).values()
                             for b in bs})
        if ctx.control:
            got = ref.spmm(name, rows, cols, wv, arg, n, p, eps, ref.BF16)
        want = ref.spmm(name, rows, cols, wv, arg, n, p, eps)
        out[name] = ref.rel_err(got, want)
    timed = {b for bs in kinds.values() for b in bs}
    mismatch = sum(1 for name in used if not set(used[name]) <= timed)
    ctx.info("per_op", backends=used, timed_backends=sorted(timed))
    return out, mismatch


def check(ctx, st, rec) -> dict:
    """Every timed solve against the reference on the fixed graph: the
    F_p and RCut it reports, orthonormality, k non-empty clusters, and
    the F_p its embedding reaches against the float64 p = 2 start; then
    each ring the timed path dispatched, once per run."""
    ref, (rows, cols, vals) = ctx.ref, st["coo"]
    k, eps = ctx.config["k"], st["pcfg"].eps
    worst = {"fval_rel_err": 0.0, "rcut_rel_err": 0.0, "ortho_err": 0.0,
             "empty_clusters": 0, "fval_ratio": 0.0}
    _, U2 = ref.p2_basis(rows, cols, vals, st["n"], k)

    for i, res in enumerate(rec["results"]):
        c = _claims(ctx, res)
        U = np.asarray(res.U, np.float64)
        labels = np.asarray(res.labels)
        f_ref = ref.fval(rows, cols, vals, U, res.p_path[-1], eps)
        r_ref = ref.rcut(rows, cols, vals, labels, k)
        bad = (labels.min() < 0) or (labels.max() >= k)
        empty = k - len(np.unique(labels)) + int(bad)
        p = res.p_path[-1]
        vals_i = {"fval_rel_err": ref.scalar_rel_err(c["fval"], f_ref),
                  "rcut_rel_err": ref.scalar_rel_err(c["rcut"], r_ref),
                  "ortho_err": ref.ortho_err(c["U"]),
                  "empty_clusters": empty,
                  "fval_ratio": ref.fval(rows, cols, vals, c["U"], p, eps)
                  / ref.fval(rows, cols, vals, U2, p, eps)}
        for key, v in vals_i.items():
            worst[key] = max(worst[key], v) if v == v else float("nan")
        ctx.info("solve", i=i, seed=rec["seeds"][i],
                 seconds=rec["spans"][i][1] - rec["spans"][i][0],
                 rcut=res.rcut, rcut_ref=r_ref, init_rcut=res.init_rcut,
                 fval=res.fvals[-1], fval_ref=f_ref, p=res.p_path[-1],
                 hvps=int(sum(res.hvp_counts)), **{
                     k2: v2 for k2, v2 in vals_i.items()})
    per_op, mismatch = _per_op(ctx, st)
    for name, v in per_op.items():
        worst[f"mxm_{name}_rel_err"] = v
    worst["per_op_backend_mismatch"] = mismatch
    return {"checks": worst, "attempted": len(rec["results"]), "failed": 0}


def layer_run(ctx, st, rec) -> dict:
    solves = []
    for res, tr in zip(rec["results"], rec["tracers"]):
        spans = {}
        for s in tr.spans:
            spans[s.name] = spans.get(s.name, 0.0) + s.dur
        iters = [r.iters for r in (res.reports or [])]
        solves.append({"spans": spans, "hvps": int(sum(res.hvp_counts)),
                       "iters": iters, "rcut": res.rcut,
                       "init_rcut": res.init_rcut})
    return {"solves": solves, "n": st["n"], "nnz": len(st["coo"][0]),
            "k": ctx.config["k"], "graph_build_s": st["graph_build_s"]}
