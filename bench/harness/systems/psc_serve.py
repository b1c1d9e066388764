"""System under test: ``repro.serve.ClusterServeEngine`` fed an open
loop of small planted-partition graphs through ``submit``/``poll``."""
from __future__ import annotations

import itertools
import math
import time

import numpy as np

from harness import graphs as G
from harness import spec as S
from harness import traffic as T
from harness import tracing
from harness.window import generator_lags, latencies, open_loop, percentile


def _graph(conf, n, r):
    rows, cols, vals, truth = G.planted_sbm_coo(
        n, conf["blocks"], conf["z_in"], conf["z_out"], r)
    return rows, cols, vals, truth


def _matrix(coo, n):
    from repro.grblas import SparseMatrix

    rows, cols, vals, _ = coo
    return SparseMatrix.from_coo(rows, cols, vals, (n, n))


def _engine(conf):
    from repro.core import PSCConfig
    from repro.serve import ClusterServeEngine

    pcfg = PSCConfig(**conf["psc"])
    return ClusterServeEngine(pcfg, **conf["engine"])


def setup(ctx) -> dict:
    import jax

    conf = ctx.config
    arrivals = S.arrivals(ctx.traffic["arrivals"])
    if arrivals.LOOP != "open":
        raise S.SpecError(f"psc_serve needs an open loop, not "
                          f"{ctx.traffic['arrivals']!r}")
    params = dict(ctx.traffic)
    if ctx.rate is not None:
        params["rate_per_s"] = ctx.rate
    rate = params.get("rate_per_s")
    due, sizes = arrivals.schedule(params, conf, ctx.seconds, ctx.seed)
    t0 = time.perf_counter()
    coos = [_graph(conf, n, T.rng(ctx.seed, 2, i))
            for i, n in enumerate(sizes)]
    mats = [_matrix(c, n) for c, n in zip(coos, sizes)]
    jax.block_until_ready(mats)
    build_s = time.perf_counter() - t0

    # warm-up: one graph of every size, drawn from the configuration's
    # own seed, through a throw-away engine (each launch pads to the
    # full batch, so this compiles each bucket's one program)
    warm = [_matrix(_graph(conf, n, T.rng(conf["warmup_seed"], 3, n)), n)
            for n in conf["vertex_counts"]]
    results = _engine(conf).serve(warm)
    ctx.info("setup", requests=len(sizes), rate_per_s=rate,
             sizes={n: sizes.count(n) for n in conf["vertex_counts"]},
             nnz={n: int(m.nnz) for n, m in zip(conf["vertex_counts"], warm)},
             graph_build_s=build_s,
             warmup_ok=all(r.ok for r in results),
             buckets=sorted({str(r.stats.bucket) for r in results}))
    return {"due": due, "sizes": sizes, "coos": coos, "mats": mats,
            "graph_build_s": build_s, "engine": _engine(conf)}


def window(ctx, st) -> dict:
    eng, mats = st["engine"], st["mats"]
    got = {}
    tracer = tracing.annotating_tracer() if ctx.trace else None
    drain = float(ctx.traffic.get("drain_s", 60.0))

    def submit(i):
        return eng.submit(mats[i])

    def poll():
        new = {rid: eng.take(rid) for rid in eng.poll()}
        got.update(new)
        return new

    def sleep(s):
        if ctx.trace:
            with tracing.annotate("bench.wait"):
                time.sleep(s)
        else:
            time.sleep(s)

    if tracer is None:
        rec = open_loop(st["due"], submit, poll, time.perf_counter, sleep,
                        drain_s=drain)
    else:
        with tracing.use_tracer(tracer):
            rec = open_loop(st["due"], submit, poll, time.perf_counter,
                            sleep, drain_s=drain)
    rec["rids"] = rec.pop("ids")
    rec["results"] = got
    rec["tracer"] = tracer
    return rec


def _failed(res) -> bool:
    return (res is None or not res.ok or res.stats.degrade != 0
            or res.stats.retries != 0)


def end_to_end(ctx, st, rec) -> dict:
    res = [rec["results"].get(r) for r in rec["rids"]]
    lat = [math.inf if _failed(x) else l
           for x, l in zip(res, latencies(rec))]
    ok = sum(1 for x in res if not _failed(x))
    return {"graphs_per_s": ok / rec["window_s"],
            "latency_p95_s": percentile(lat, 95)}


def _agreement(labels, truth, k) -> float:
    best = 0
    for perm in itertools.permutations(range(k)):
        best = max(best, int(np.sum(np.asarray(perm)[labels] == truth)))
    return best / len(truth)


def _fval_ratio(ref, coo, U, p, eps, k):
    """F_p of the served embedding over F_p of the float64 p = 2
    eigenvectors of the same graph, both at the request's p."""
    rows, cols, vals, _ = coo
    _, U2 = ref.p2_basis(rows, cols, vals, len(U), k)
    return ref.fval(rows, cols, vals, U, p, eps) / \
        ref.fval(rows, cols, vals, U2, p, eps)


def check(ctx, st, rec) -> dict:
    """Every request of the window against its own graph's reference:
    answered, orthonormal, k non-empty clusters, the RCut it reports,
    and the F_p its embedding reaches against the p = 2 start."""
    ref, k = ctx.ref, ctx.config["psc"]["k"]
    eps = st["engine"].cfg.eps
    worst = {"failed_requests": 0, "ortho_err": 0.0, "empty_clusters": 0,
             "rcut_rel_err": 0.0, "fval_ratio": 0.0}
    agree = []
    for i, rid in enumerate(rec["rids"]):
        res = rec["results"].get(rid)
        if _failed(res):
            worst["failed_requests"] += 1
            continue
        rows, cols, vals, truth = st["coos"][i]
        labels = np.asarray(res.labels)
        U = np.asarray(res.U, np.float64)
        claimed = res.rcut
        if ctx.control:
            U = ref.BF16.host(ref.BF16.arr(U))
            claimed = ref.rcut(rows, cols, vals, labels, k, ref.BF16)
        bad = (labels.min() < 0) or (labels.max() >= k) or \
            len(labels) != st["sizes"][i]
        vals_i = {"ortho_err": ref.ortho_err(U),
                  "empty_clusters": k - len(np.unique(labels)) + int(bad),
                  "rcut_rel_err": ref.scalar_rel_err(
                      claimed, ref.rcut(rows, cols, vals, labels, k)),
                  "fval_ratio": _fval_ratio(ref, st["coos"][i], U,
                                            res.stats.p_final, eps, k)}
        agree.append(_agreement(labels, truth, k))
        for key, v in vals_i.items():
            worst[key] = max(worst[key], v) if v == v else float("nan")
    lat = latencies(rec)
    quarter = max(rec["due"][-1], 1e-9) / 4.0 if rec["due"] else 1.0
    by_q = [[l for t, l in zip(rec["due"], lat) if int(t // quarter) == q
             or (q == 3 and t >= 4 * quarter)] for q in range(4)]
    ctx.info("results", requests=len(rec["rids"]),
             latency_median_by_quarter=[percentile(v, 50) for v in by_q],
             gen_lag_max_s=max(generator_lags(rec), default=None),
             planted_agreement_min=min(agree) if agree else None,
             planted_agreement_mean=float(np.mean(agree)) if agree else None)
    return {"checks": worst, "attempted": len(rec["rids"]),
            "failed": worst["failed_requests"]}


def layer_run(ctx, st, rec) -> dict:
    eng = st["engine"]
    res = [r for r in rec["results"].values() if r is not None and r.ok]
    bucket = [r for r in res if r.stats.lane == "bucket"]
    launches = int(eng.stats.n_batches)
    spans = []
    if rec["tracer"] is not None:
        spans = tracing.span_durations(rec["tracer"], "serve.bucket_solve")
    return {"graph_build_s": st["graph_build_s"],
            "bucket_solve_spans": spans,
            "bucket_results": len(bucket), "launches": launches,
            "max_batch": eng.max_batch,
            "gen_lags": generator_lags(rec)}
