"""Smoke run of the p-spectral clustering main path on one TPU chip.

    python chip_smoke.py                 # phases (a)-(d) on one chip
    python chip_smoke.py --four-chips    # halo-exchange SpMM on four chips
    python chip_smoke.py --rehearse      # tiny sizes, any platform

Phases, each through the entry points a user calls
(``p_spectral_cluster``, ``grblas.mxm``, ``ClusterServeEngine``):

  (a) flat solve of delaunay_r17, ``backend="auto"``: ELL carries the
      reals ring (LOBPCG init) and the Newton edge rings;
  (b) a 2^17-vertex planted SBM with hub rows (ELL fill > 4), so
      ``auto`` builds and selects the SELL-C-σ layout;
  (c) multilevel solve of the weighted planted SBM at n = 2^19;
  (d) the clustering serve engine: one bucketed batch of 8 graphs, a
      warm repeat and a churn update.

Each phase prints one JSON line (graph, n, nnz, k, the grblas backend
each ring resolved to, set-up / compile / solve seconds, compile
counts, peak device bytes, and its checks against a reference).  The
last line is ``{"ok": true, "device": {...}}`` only when every phase
passed on a TPU.  The run fails (exit 1, no such line) on any other
platform, on a Pallas call in interpret mode, a recovery-ladder rung, a
backend fallback, or a serve result that is not ok or came back
degraded or retried.

Each timed step reports its wall clock (ended with
``block_until_ready``), its compile seconds (jax's lowering and backend
compile events inside the step) and their difference as run seconds,
which still hold jax tracing and host work.  The persistent
compilation cache follows ``repro.launch.compile_cache``.
``--rehearse`` runs every phase at a tiny size on whatever platform jax
has and ends with a ``rehearsal`` line instead of ``ok``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# phase sizes: the full run, and the tiny rehearsal
FULL = {"delaunay_r": 17, "hub_block": 2 ** 15, "hubs": 64, "hub_deg": 128,
        "ml_block": 2 ** 17, "serve_graphs": 8}
TINY = {"delaunay_r": 10, "hub_block": 1024, "hubs": 8, "hub_deg": 128,
        "ml_block": 4096, "serve_graphs": 8}

MXM_RTOL = 1e-4          # auto (or dist) vs coo, max-abs error / max-abs
RCUT_TOL = 0.05          # solve vs coo solve: rcut <= ref * (1 + tol)
PLANTED_TOL = 0.02       # planted-partition families: rcut <= planted*(1+tol)
P_TARGET = 1.4
# SELL-C-σ slice widths rounded up to a multiple of 8: each width run
# is one slot loop in every compiled program that applies the matrix,
# so compile time grows with the run count; on the hub graph of phase b
# this keeps 7 runs for 18% more stored slots
SELL_W_ALIGN = 8
# refine only the finest V-cycle level: every refined level compiles
# its own Newton step (tens of seconds each on the TPU), and the third
# level (~29% of the vertices) carries SELL-C-σ with over a hundred
# width runs, a compile of minutes
REFINE_TOP_FRAC = 0.6


class SmokeFailure(Exception):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------ instrumentation

class CompileClock:
    """Sums jax's lowering and backend-compile event durations (seconds;
    trace events nest, so they are left out) and counts backend
    compiles; phases read deltas."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, duration, **_):
        if name in self.EVENTS:
            self.seconds += duration
            if name == self.EVENTS[-1]:
                self.backend_compiles += 1


class PallasGuard:
    """Records every ``pallas_call`` and refuses interpret mode: a smoke
    run must not mistake the Pallas interpreter for the chip."""

    def __init__(self):
        from jax.experimental import pallas as pl

        self.calls = []
        real = pl.pallas_call

        def guarded(kernel, *args, **kwargs):
            name = getattr(kernel, "__name__", None) or getattr(
                getattr(kernel, "func", None), "__name__", "?")
            interp = bool(kwargs.get("interpret", False))
            self.calls.append(name)
            if interp:
                raise SmokeFailure(f"pallas_call {name} ran with interpret=True")
            return real(kernel, *args, **kwargs)

        pl.pallas_call = guarded


class Phase:
    """One phase: a tracer installed as the active span recorder, the
    compile clock and retrace detector bookmarked, and the JSON line it
    prints."""

    def __init__(self, ctx, name, **info):
        from repro.obs import trace as obs_trace
        from repro.obs.retrace import RetraceDetector

        self.ctx = ctx
        self.line = {"phase": name, **info}
        self.tracer = obs_trace.Tracer(obs_trace.TraceConfig(capacity=1 << 20))
        self._use = obs_trace.use(self.tracer)
        self.retrace = RetraceDetector()
        self.checks = {}

    def __enter__(self):
        self._use.__enter__()
        self.t0 = time.perf_counter()
        self.c0 = self.ctx.clock.seconds
        self.b0 = self.ctx.clock.backend_compiles
        return self

    def timed(self, key, fn):
        """Run ``fn`` to completion (block_until_ready on its result);
        store wall, compile and run seconds under ``key``."""
        import jax

        c0, t0 = self.ctx.clock.seconds, time.perf_counter()
        out = fn()
        jax.block_until_ready(_arrays(out))
        wall = time.perf_counter() - t0
        comp = self.ctx.clock.seconds - c0
        self.line[f"{key}_wall_s"] = wall
        self.line[f"{key}_compile_s"] = comp
        self.line[f"{key}_run_s"] = wall - comp
        return out

    def reference(self):
        """Context in which reference computations run untraced, so the
        phase's ``backends`` report only the path under test."""
        from repro.obs import trace as obs_trace

        return obs_trace.use(obs_trace.NULL)

    def check(self, name, value, limit, ok):
        self.checks[name] = {"value": value, "limit": limit, "ok": bool(ok)}
        _check(ok, f"{self.line['phase']}: check {name} failed "
                   f"(value {value}, limit {limit})")

    def __exit__(self, exc_type, exc, tb):
        self._use.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            return False
        self.line["backends"] = _backends_used(self.tracer)
        bad = [e["name"] for e in self.tracer.events
               if e["name"] in ("grblas.fallback", "solver.divergence")
               or e["name"].startswith(("recovery.", "fault."))]
        bad += [s.name for s in self.tracer.spans
                if s.name.startswith("recovery.")]
        _check(not bad, f"{self.line['phase']}: fallback or recovery "
                        f"events fired: {sorted(set(bad))}")
        self.line["compiles_memo"] = sum(self.retrace.compiles().values())
        self.line["compiles_xla"] = self.ctx.clock.backend_compiles - self.b0
        self.line["compile_s"] = self.ctx.clock.seconds - self.c0
        self.line["phase_wall_s"] = time.perf_counter() - self.t0
        self.line["peak_bytes_in_use"] = self.ctx.peak_bytes()
        self.line["pallas_calls"] = len(self.ctx.pallas.calls)
        self.line["checks"] = self.checks
        self.ctx.emit(self.line)
        return False


def _arrays(out):
    """The device arrays a phase step returned (results carry theirs in
    ``U``), for block_until_ready."""
    import jax

    items = out if isinstance(out, (list, tuple)) else [out]
    vals = [getattr(x, "U", x) for x in items]
    return [v for v in vals if isinstance(v, jax.Array)]


def _backends_used(tracer):
    """{ring kind: [backend, ...]} from the grblas.mxm spans (eager
    calls) and grblas.dispatch instants (calls traced under jit)."""
    seen = {}
    for s in tracer.spans:
        if s.name == "grblas.mxm":
            seen.setdefault(s.attrs.get("ring"), set()).add(s.attrs["backend"])
    for e in tracer.events:
        if e["name"] == "grblas.dispatch":
            a = e["attrs"]
            seen.setdefault(a.get("ring"), set()).add(a["backend"])
    return {str(k): sorted(v) for k, v in sorted(seen.items(), key=str)}


class Context:
    def __init__(self, jax, out_path):
        self.jax = jax
        self.device = jax.devices()[0]
        self.clock = CompileClock(jax)
        self.pallas = PallasGuard()
        self.out_path = out_path

    def peak_bytes(self):
        stats = self.device.memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def emit(self, line):
        text = json.dumps(line, default=_json_default)
        print(text, flush=True)
        if self.out_path:
            with open(self.out_path, "a") as f:
                f.write(text + "\n")


def _json_default(v):
    try:
        return v.item()
    except AttributeError:
        return str(v)


# ---------------------------------------------------------------- references

def _rel_err(a, b):
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _mxm_checks(ph, W, rings=("reals", "plap_apply", "plap_hvp"), seed=0,
                k=4):
    """The named rings through ``backend="auto"`` against the coo
    backend on the same device, at highest matmul precision.  The side
    under test is one jitted call; the coo side runs op by op, so its
    scatter program compiles once for all rings, not once per ring.  A
    ring that auto resolves to coo fails: the check would compare coo
    with itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.grblas import Descriptor, mxm
    from repro.grblas.semiring import (plap_edge_semiring,
                                       plap_hvp_edge_semiring, reals_ring)
    from repro.obs import trace as obs_trace

    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((W.n_rows, k)), jnp.float32)
    E = jnp.asarray(rng.standard_normal((W.n_rows, k)), jnp.float32)
    auto, coo = Descriptor(backend="auto"), Descriptor(backend="coo")
    table = {"reals": (reals_ring, X),
             "plap_apply": (plap_edge_semiring(P_TARGET, 1e-8), X),
             "plap_hvp": (plap_hvp_edge_semiring(P_TARGET, 1e-8), (X, E))}
    with jax.default_matmul_precision("highest"):
        for name in rings:
            ring, arg = table[name]
            tracer = obs_trace.Tracer()
            with obs_trace.use(tracer):
                got = jax.jit(lambda W, a: mxm(W, a, ring, desc=auto))(W, arg)
            used = sorted({b for bs in _backends_used(tracer).values()
                           for b in bs})
            _check(used and "coo" not in used,
                   f"{ph.line['phase']}: mxm {name} under auto ran on "
                   f"{used}, nothing to check against coo")
            with ph.reference():
                want = mxm(W, arg, ring, desc=coo)
            err = _rel_err(got, want)
            ph.check(f"mxm_{name}_{'+'.join(used)}_vs_coo_rel_err", err,
                     MXM_RTOL, err <= MXM_RTOL)


def _planted_rcut(W, truth, k):
    from repro.core import metrics

    return float(metrics.rcut(W, truth, k))


def _hub_sbm(block, hubs, hub_deg, seed=0):
    """Weighted planted SBM (4 blocks) plus ``hubs`` hub rows: each hub
    is a vertex of its own block joined to ``hub_deg`` random vertices
    of that block, so the degree distribution is skewed (ELL fill far
    over 4) while the planted partition stays the cut to find.  O(nnz)
    host work."""
    import numpy as np
    from repro.graphs import sbm_graph_sparse
    from repro.grblas import SparseMatrix

    W0, truth = sbm_graph_sparse([block] * 4, deg_in=16, deg_out=4, w_in=2.0,
                                 w_out=1.0, seed=seed, build_ell=False,
                                 build_sellcs=False)
    r, c, v = (np.asarray(a) for a in W0.host_coo())
    rng = np.random.default_rng(seed + 1)
    b = rng.integers(0, 4, hubs)
    hub = b * block + rng.integers(0, block, hubs)
    hr = np.repeat(hub, hub_deg)
    hc = np.repeat(b * block, hub_deg) + rng.integers(0, block,
                                                      hubs * hub_deg)
    keep = hr != hc
    rows = np.concatenate([r, hr[keep], hc[keep]])
    cols = np.concatenate([c, hc[keep], hr[keep]])
    vals = np.concatenate([v, np.full(2 * keep.sum(), 2.0)])
    n = 4 * block
    _, first = np.unique(rows.astype(np.int64) * n + cols, return_index=True)
    W = SparseMatrix.from_coo(rows[first], cols[first], vals[first], (n, n),
                              sell_w_align=SELL_W_ALIGN)
    return W, truth


# -------------------------------------------------------------------- phases

def phase_flat(ctx, sz):
    from repro.core import PSCConfig, p_spectral_cluster
    from repro.graphs import delaunay_graph

    r = sz["delaunay_r"]
    t0 = time.perf_counter()
    W, _ = delaunay_graph(r, seed=0)
    ctx.jax.block_until_ready(W.vals)
    setup = time.perf_counter() - t0
    with Phase(ctx, "a_flat", graph=f"delaunay_r{r}", n=W.n_rows,
               nnz=W.nnz, k=4, ell_fill=W.ell_fill_ratio(),
               setup_s=setup) as ph:
        _mxm_checks(ph, W)
        cfg = PSCConfig(k=4, p_target=P_TARGET, newton_iters=10, tcg_iters=8,
                        seed=0)
        res = ph.timed("solve", lambda: p_spectral_cluster(W, cfg))
        with ph.reference():
            ref = ph.timed("coo_solve", lambda: p_spectral_cluster(
                W, dataclasses.replace(cfg, backend="coo")))
        _check(res.recovery is None, "a_flat: recovery ran")
        ph.line.update(rcut=res.rcut, init_rcut=res.init_rcut,
                       rcut_coo=ref.rcut, p_path=res.p_path,
                       applies=res.hvp_counts)
        ph.check("rcut_vs_coo_solve", res.rcut, ref.rcut * (1 + RCUT_TOL),
                 res.rcut <= ref.rcut * (1 + RCUT_TOL))
    used = set(ph.line["backends"].get("plap_apply", []))
    _check(used == {"ell"}, f"a_flat: plap_apply ran on {used}, not ell")


def phase_sellcs(ctx, sz):
    from repro.core import PSCConfig, p_spectral_cluster

    t0 = time.perf_counter()
    W, truth = _hub_sbm(sz["hub_block"], sz["hubs"], sz["hub_deg"])
    ctx.jax.block_until_ready(W.vals)
    setup = time.perf_counter() - t0
    with Phase(ctx, "b_sellcs", graph=f"hub_sbm_4x{sz['hub_block']}",
               n=W.n_rows, nnz=W.nnz, k=4, setup_s=setup,
               sellcs_fill=W.sellcs_fill_ratio(),
               sell_runs=len(W.sell_cols or ()),
               ell_built=W.ell_cols is not None) as ph:
        _check(W.sell_cols is not None, "b_sellcs: SELL-C-σ layout not built")
        _mxm_checks(ph, W)
        cfg = PSCConfig(k=4, p_target=P_TARGET, newton_iters=10, tcg_iters=8,
                        kmeans_restarts=4, seed=0)
        res = ph.timed("solve", lambda: p_spectral_cluster(W, cfg))
        _check(res.recovery is None, "b_sellcs: recovery ran")
        planted = _planted_rcut(W, truth, 4)
        ph.line.update(rcut=res.rcut, init_rcut=res.init_rcut,
                       rcut_planted=planted)
        ph.check("rcut_vs_planted", res.rcut, planted * (1 + PLANTED_TOL),
                 res.rcut <= planted * (1 + PLANTED_TOL))
    used = set(ph.line["backends"].get("plap_apply", []))
    _check(used == {"sellcs"}, f"b_sellcs: plap_apply ran on {used}, "
                               f"not sellcs")


def phase_multilevel(ctx, sz):
    from repro.core import PSCConfig, p_spectral_cluster
    from repro.graphs import sbm_graph_sparse
    from repro.multilevel import MultilevelConfig

    b = sz["ml_block"]
    t0 = time.perf_counter()
    W, truth = sbm_graph_sparse([b] * 4, deg_in=16, deg_out=4, w_in=2.0,
                                w_out=1.0, seed=0)
    ctx.jax.block_until_ready(W.vals)
    gen = time.perf_counter() - t0
    with Phase(ctx, "c_multilevel", graph=f"sbm_weighted_4x{b}",
               n=W.n_rows, nnz=W.nnz, k=4, setup_generate_s=gen) as ph:
        cfg = PSCConfig(k=4, p_target=P_TARGET, newton_iters=10,
                        tcg_iters=8, kmeans_restarts=4, seed=0,
                        multilevel=MultilevelConfig(
                            refine_top_frac=REFINE_TOP_FRAC))
        res = ph.timed("solve", lambda: p_spectral_cluster(W, cfg))
        _check(res.recovery is None, "c_multilevel: recovery ran")
        coarsen = sum(s.dur for s in ph.tracer.spans
                      if s.name == "multilevel.coarsen")
        planted = _planted_rcut(W, truth, 4)
        ph.line.update(setup_coarsen_s=coarsen, setup_s=gen + coarsen,
                       rcut=res.rcut, init_rcut=res.init_rcut,
                       rcut_planted=planted,
                       levels=[(lv["n"], lv["nnz"], lv["p"])
                               for lv in res.levels])
        ph.check("rcut_vs_planted", res.rcut, planted * (1 + PLANTED_TOL),
                 res.rcut <= planted * (1 + PLANTED_TOL))


def phase_serve(ctx, sz):
    import numpy as np
    from repro.core import PSCConfig
    from repro.core.metrics import clustering_accuracy
    from repro.graphs import sbm_graph
    from repro.serve import ClusterServeEngine, EdgeDelta

    t0 = time.perf_counter()
    pairs = [sbm_graph([32] * 4, 0.3, 0.01, seed=s)
             for s in range(sz["serve_graphs"])]
    graphs = [g for g, _ in pairs]
    setup = time.perf_counter() - t0
    cfg = PSCConfig(k=4, newton_iters=20, tcg_iters=12, kmeans_restarts=4)
    with Phase(ctx, "d_serve", graph=f"{len(graphs)}x sbm_4x32",
               n=graphs[0].n_rows, nnz=graphs[0].nnz, k=4,
               setup_s=setup) as ph:
        eng = ClusterServeEngine(cfg, max_batch=8, cache_capacity=32)
        cold = ph.timed("batch", lambda: eng.serve(graphs))
        warm = ph.timed("warm", lambda: eng.serve([graphs[0]])[0])
        W = graphs[0]
        rng = np.random.default_rng(7)
        und = np.flatnonzero(np.asarray(W.rows) < np.asarray(W.cols))
        pick = rng.choice(und, max(len(und) // 100, 1), replace=False)
        delta = EdgeDelta(np.asarray(W.rows)[pick], np.asarray(W.cols)[pick],
                          np.full(len(pick), 0.25))

        def churn():
            rid = eng.update(W, delta)
            return eng.flush()[rid]

        ch = ph.timed("churn", churn)
        results = cold + [warm, ch]
        for r in results:
            _check(r.ok, f"d_serve: request {r.req_id} failed: {r.error}")
            _check(r.stats.degrade == 0 and r.stats.retries == 0,
                   f"d_serve: request {r.req_id} degraded/retried")
        st = eng.stats
        _check(st.n_quarantined == 0 and st.n_retried == 0
               and st.n_degraded == 0, f"d_serve: engine stats {st.as_dict()}")
        per_bucket = ph.retrace.serve_buckets()
        ph.line.update(modes=[r.stats.mode for r in results],
                       lanes=sorted({r.stats.lane for r in results}),
                       batches=st.n_batches, buckets=len(per_bucket),
                       rcut=[r.rcut for r in results])
        ph.check("one_trace_per_bucket", sorted(per_bucket.values()), "all 1",
                 per_bucket and all(v == 1 for v in per_bucket.values()))
        agree = clustering_accuracy(warm.labels, cold[0].labels, 4)
        ph.check("warm_repeat_label_agreement", agree, ">= 0.99",
                 agree >= 0.99)
        worst = max(r.rcut / _planted_rcut(g, t, 4)
                    for r, (g, t) in zip(cold, pairs))
        ph.check("rcut_vs_planted_worst_ratio", worst, 1 + PLANTED_TOL,
                 worst <= 1 + PLANTED_TOL)


def phase_four_chips(ctx, sz):
    """Halo-exchange SpMM over a 4-device mesh (dist and dist_sellcs)
    against the one-device coo result of the same op."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.graphs import sbm_graph_sparse
    from repro.grblas import (HALO_FALLBACK_FRAC, Descriptor, SparseMatrix,
                              device_mesh, make_row_partition, mxm)
    from repro.grblas.semiring import plap_edge_semiring, reals_ring

    b = sz["ml_block"]
    t0 = time.perf_counter()
    W0, truth = sbm_graph_sparse([b] * 4, deg_in=16, deg_out=4, w_in=2.0,
                                 w_out=1.0, seed=0, build_ell=False,
                                 build_sellcs=False)
    # shuffle the vertex ids so the cluster-aligned placement is a real
    # permutation (exercising the permute / un-permute around the mesh)
    n = W0.n_rows
    perm = np.random.default_rng(3).permutation(n)
    r, c, v = (np.asarray(a) for a in W0.host_coo())
    W = SparseMatrix.from_coo(perm[r], perm[c], v, (n, n), build_ell=True)
    labels = np.empty(n, np.int64)
    labels[perm] = truth
    # the halo schedule is forced: at deg_out=4 the cut rows per shard
    # pair can exceed HALO_FALLBACK_FRAC of a shard, where "auto" would
    # ship the all-gather instead (reported as auto_mode)
    Ap = make_row_partition(W, 4, assignment=labels, mode="halo")
    Aps = make_row_partition(W, 4, assignment=labels, mode="halo",
                             sellcs=True)
    setup = time.perf_counter() - t0
    mesh = device_mesh(n_shards=4)
    k = 4
    X = jnp.asarray(np.random.default_rng(0).standard_normal((n, k)),
                    jnp.float32)
    wb = Ap.wire_bytes(k)
    with Phase(ctx, "e_dist_halo", graph=f"sbm_weighted_4x{b}", n=n,
               nnz=W.nnz, k=k, setup_s=setup, mode=Ap.mode,
               auto_mode=("halo" if wb["halo_width"]
                          <= HALO_FALLBACK_FRAC * Ap.rows_per_shard
                          else "gather"),
               halo_width=wb["halo_width"], rows_per_shard=Ap.rows_per_shard,
               wire_bytes_halo=wb["halo"], wire_bytes_gather=wb["gather"],
               mesh_devices=[str(d) for d in mesh.devices.flat]) as ph:
        coo = Descriptor(backend="coo")
        rings = {"reals": reals_ring,
                 "plap_apply": plap_edge_semiring(P_TARGET, 1e-8)}
        with jax.default_matmul_precision("highest"):
            # one jitted call each (an eager shard_map runs op by op),
            # timed cold and then warm
            for name, ring in rings.items():
                with ph.reference():
                    f = jax.jit(lambda X: mxm(W, X, ring, desc=coo))
                    want = ph.timed(f"coo_{name}", lambda: f(X))
                    ph.timed(f"coo_{name}_warm", lambda: f(X))
                for be, A in (("dist", Ap), ("dist_sellcs", Aps)):
                    d = Descriptor(backend=be, mesh=mesh)
                    f = jax.jit(lambda X: mxm(A, X, ring, desc=d))
                    got = ph.timed(f"{be}_{name}", lambda: f(X))
                    ph.timed(f"{be}_{name}_warm", lambda: f(X))
                    err = _rel_err(got, want)
                    ph.check(f"{be}_{name}_vs_coo_rel_err", err, MXM_RTOL,
                             err <= MXM_RTOL)
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in mesh.devices.flat]
        ph.line["peak_bytes_per_device"] = peaks
        if all(p is not None for p in peaks):
            ph.check("work_on_every_device", min(peaks[1:]) / peaks[0],
                     "> 0.05", min(peaks[1:]) > 0.05 * peaks[0])


# ---------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device halo-exchange SpMM phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any platform; no ok line")
    ap.add_argument("--out", default=None,
                    help="also append every JSON line to this file")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}: run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: jax found no TPU (platform {platform!r}); "
              f"this run measures the chip and stops here", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} devices, jax has {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    ctx = Context(jax, args.out)
    ctx.emit({"phase": "start", "platform": platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "jax": jax.__version__, "compile_cache": cache_dir,
              "rehearse": args.rehearse})
    sz = TINY if args.rehearse else FULL
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(ctx, sz)
        else:
            for phase in (phase_flat, phase_sellcs, phase_multilevel,
                          phase_serve):
                phase(ctx, sz)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    total = time.perf_counter() - t0
    if args.rehearse:
        ctx.emit({"rehearsal": "passed", "total_s": total, "device": device})
        return 0
    ctx.emit({"phase": "end", "total_s": total,
              "pallas_calls": len(ctx.pallas.calls)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
