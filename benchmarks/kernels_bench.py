"""Kernel microbenchmarks: jnp/XLA-CPU wall time of each kernel's ref
path (us/call) + the BSR fill ratio the TPU kernel would pay.
(Pallas interpret-mode timing is not meaningful as a device proxy; the
bsr-interpret row below is recorded only so the backend-descriptor
trajectory has every dispatch path on it.  TPU wall time comes from the
roofline analysis.)

Also sweeps the unified-API backend descriptor (coo / ell / sellcs /
bsr_pallas-ref / bsr_pallas-interpret / edge coo vs ref) on one
synthetic graph and emits BENCH_backends.json at the repo root so later
PRs have a perf trajectory for the dispatch table, plus the SELL-C-σ
sweep (C x sigma x reorder vs coo/ell, skewed-degree + delaunay) into
BENCH_sellcs.json, plus the flat-vs-multilevel V-cycle sweep
(131k-524k-node graphs, DESIGN.md §6) into BENCH_multilevel.json, plus
the solver-driver sweep (graph × p × {newton, scf, inverse_power},
DESIGN.md §7) into BENCH_solvers.json.  ``make bench-kernels``
regenerates all of them; ``make bench-multilevel`` / ``make
bench-solvers`` rerun just their own sweep (the multilevel one solves
big graphs end to end — the long pole).

The distributed sweep (halo exchange vs all-gather, shards × k ×
placement, DESIGN.md §4) lives in ``sweep_dist`` and emits
BENCH_dist.json; it needs a multi-device platform, so it has its own
entry point: ``make bench-dist`` (forces 8 host devices).
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs import delaunay_graph, reorder, sbm_graph
from repro.grblas import Descriptor, SparseMatrix, mxm, plap_edge_semiring
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.flash_attention import flash_attention

_ROOT = Path(__file__).resolve().parent.parent


def _time(f, *a, reps=5):
    r = f(*a)
    jax.block_until_ready(r)
    t0 = time.time()
    for _ in range(reps):
        r = f(*a)
    jax.block_until_ready(r)
    return (time.time() - t0) / reps * 1e6


def sweep_backends(r=10, k=4, out_path=None):
    """Time one SpMM per backend descriptor on a delaunay graph."""
    W, _ = delaunay_graph(r, seed=0, build_bsr=True, block_size=128,
                          build_sellcs=True)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((W.n_rows, k)), jnp.float32)
    ring = plap_edge_semiring(1.4, 1e-8)

    cases = [
        ("reals", "coo", Descriptor(backend="coo")),
        ("reals", "ell", Descriptor(backend="ell")),
        ("reals", "sellcs", Descriptor(backend="sellcs")),
        ("reals", "bsr_ref", Descriptor(backend="bsr_pallas")),
        ("reals", "bsr_interpret",
         Descriptor(backend="bsr_pallas", interpret=True)),
        ("plap_edge", "coo", Descriptor(backend="coo")),
        ("plap_edge", "sellcs", Descriptor(backend="sellcs")),
        ("plap_edge", "edge_ref", Descriptor(backend="edge_pallas")),
    ]
    entries = []
    # minimum-traffic byte model of one SpMM (same model the grblas
    # dispatch spans attach — obs.trace.roofline_summary uses it): the
    # achieved-GB/s column turns wall_us into roofline fractions
    from repro.grblas.api import _traffic_bytes

    nbytes = _traffic_bytes(W, k)
    for ring_name, label, desc in cases:
        rg = ring if ring_name == "plap_edge" else None
        if rg is None:
            fn = jax.jit(lambda u, d=desc: mxm(W, u, desc=d))
        else:
            fn = jax.jit(lambda u, d=desc: mxm(W, u, rg, desc=d))
        reps = 2 if "interpret" in label else 5
        us = _time(fn, X, reps=reps)
        entries.append({"ring": ring_name, "backend": label,
                        "wall_us": round(us, 1),
                        "achieved_gb_s": round(nbytes / (us * 1e-6) / 1e9,
                                               3)})
    payload = {
        "schema": 2,
        "graph": f"delaunay_r{r}", "n": W.n_rows, "nnz": W.nnz, "k": k,
        "traffic_bytes_per_spmm": int(nbytes),
        "bsr_fill_ratio": round(W.bsr_fill_ratio(), 2),
        "ell_fill_ratio": round(W.ell_fill_ratio(), 2),
        "sellcs_fill_ratio": round(W.sellcs_fill_ratio(), 2),
        "platform": jax.default_backend(),
        "entries": entries,
    }
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


# ------------------------------------------------------ SELL-C-σ sweep

def _skewed_sbm(seed=0, **kw):
    """SBM with a tiny hub block: ~16 rows of degree ~200 over a ~deg-8
    background — the power-law-ish regime where full ELL pads every row
    to the hub width (fill >> 4x)."""
    W, _ = sbm_graph([4000, 16], p_in=0.002, p_out=0.05, seed=seed,
                     build_ell=True, **kw)   # force ELL: it IS the baseline
    return W


def _rebuild(W: SparseMatrix, C, sigma, method):
    """Build the sweep variant: same graph, explicit SELL params, then an
    optional bandwidth-reducing relabel (which preserves the params)."""
    W2 = SparseMatrix.from_coo(
        np.asarray(W.rows), np.asarray(W.cols), np.asarray(W.vals),
        (W.n_rows, W.n_cols), build_ell=True, build_sellcs=True,
        sell_c=C, sell_sigma=sigma)
    if method != "none":
        W2, _, _ = reorder(W2, method=method)
    return W2


def sweep_sellcs(k=4, out_path=None, reps=20):
    """sellcs x {C, sigma, reorder} against coo / full-ELL, on a
    skewed-degree SBM and a delaunay triangulation (reals ring — the
    layout-bound op; the edge kinds share the same gather pattern)."""
    rng = np.random.default_rng(0)
    graphs = [
        ("sbm_skew", _skewed_sbm(seed=0)),
        ("delaunay_r13", delaunay_graph(13, seed=0)[0]),
    ]
    payload = {"schema": 2, "platform": jax.default_backend(), "k": k,
               "graphs": []}
    for name, W in graphs:
        X = jnp.asarray(rng.standard_normal((W.n_rows, k)), jnp.float32)
        entry = {
            "graph": name, "n": W.n_rows, "nnz": W.nnz,
            "ell_fill_ratio": round(W.ell_fill_ratio(), 2),
            "baselines": [], "sellcs": [],
        }
        for label, desc in (("coo", Descriptor(backend="coo")),
                            ("ell", Descriptor(backend="ell"))):
            us = _time(jax.jit(lambda u, d=desc: mxm(W, u, desc=d)), X,
                       reps=reps)
            entry["baselines"].append({"backend": label,
                                       "wall_us": round(us, 1)})
        sell_desc = Descriptor(backend="sellcs")
        for C in (16, 32, 64):
            for sigma_name, sigma in (("C", C), ("8C", 8 * C), ("n", None)):
                for method in ("none", "rcm"):
                    Ws = _rebuild(W, C, sigma, method)
                    us = _time(
                        jax.jit(lambda u, M=Ws: mxm(M, u, desc=sell_desc)),
                        X, reps=reps)
                    entry["sellcs"].append({
                        "C": C, "sigma": sigma_name, "reorder": method,
                        "wall_us": round(us, 1),
                        "fill_ratio": round(Ws.sellcs_fill_ratio(), 3),
                    })
        best = min(entry["sellcs"], key=lambda e: e["wall_us"])
        ell_us = next(b["wall_us"] for b in entry["baselines"]
                      if b["backend"] == "ell")
        entry["best_sellcs"] = best
        entry["speedup_vs_ell"] = round(ell_us / best["wall_us"], 2)
        payload["graphs"].append(entry)
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


# --------------------------------------------------- distributed SpMM sweep

def sweep_dist(out_path=None, shards=(4, 8), ks=(1, 8, 16, 32), reps=16):
    """Halo-exchange vs all-gather distributed SpMM (grblas.dist):
    shards × k × placement on a cluster-aligned SBM and a delaunay
    triangulation, plus the per-shard SELL-C-σ layout on the same plan.

    Wire bytes are the analytic per-call volumes of the static plans
    (RowPartitionedMatrix.wire_bytes — the collectives move exactly the
    planned rows); wall clock is measured over the forced host-device
    mesh, and every path is pinned against the coo result.  Needs a
    multi-device platform: ``make bench-dist`` forces 8 host devices.
    """
    from repro.graphs import sbm_graph_sparse
    from repro.grblas import HALO_FALLBACK_FRAC, make_row_partition

    n_dev = len(jax.devices())
    if n_dev < max(shards):
        raise RuntimeError(
            f"sweep_dist needs >= {max(shards)} devices, found {n_dev}: run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(`make bench-dist`)")

    def _tmed(f, X, reps=reps):
        """Median-of-reps: the host-device collectives are noisy."""
        r = f(X)
        jax.block_until_ready(r)
        ts = []
        for _ in range(reps):
            t0 = time.time()
            r = f(X)
            jax.block_until_ready(r)
            ts.append(time.time() - t0)
        return float(np.median(ts) * 1e6)

    rng = np.random.default_rng(0)
    # the communication term dominates when avg degree is small relative
    # to the shard count (per-shard flops ~ (nnz/S)·k vs gather copy
    # n·k), so the sweep uses the sparse-degree regime the halo targets
    Wsbm, truth = sbm_graph_sparse([16384] * 4, deg_in=8.0, deg_out=0.8,
                                   seed=0, build_ell=True)
    Wdel, _ = delaunay_graph(15, seed=0)
    graphs = [
        # aligned = the planted clusters; delaunay's natural order is
        # its own locality-aligned placement (contiguous row blocks)
        ("sbm4_65k", Wsbm, truth),
        ("delaunay_r15", Wdel, None),
    ]
    payload = {"schema": 2,
               "platform": jax.default_backend(), "n_devices": n_dev,
               "halo_note": "wire bytes analytic per call; self-chunks and "
                            "own shards excluded on both schedules",
               "graphs": []}
    for name, W, aligned in graphs:
        entry = {"graph": name, "n": W.n_rows, "nnz": W.nnz, "entries": []}
        for S in shards:
            mesh = jax.make_mesh((int(S),), ("data",))
            d = Descriptor(backend="dist", mesh=mesh)
            ds = Descriptor(backend="dist_sellcs", mesh=mesh)
            for placement in ("aligned", "shuffled"):
                asg = aligned if placement == "aligned" else \
                    rng.permutation(W.n_rows)
                halo = make_row_partition(W, S, assignment=asg, mode="halo")
                gath = make_row_partition(W, S, assignment=asg,
                                          mode="gather")
                sell = make_row_partition(W, S, assignment=asg, mode="halo",
                                          sellcs=True)
                # what mode="auto" would have picked — the build-time
                # rule of make_row_partition, derived from the forced
                # halo plan instead of building a fourth partition
                mode_auto = ("halo" if halo.halo_width
                             <= HALO_FALLBACK_FRAC * halo.rows_per_shard
                             else "gather")
                for k in ks:
                    shape = (W.n_rows,) if k == 1 else (W.n_rows, k)
                    X = jnp.asarray(rng.standard_normal(shape), jnp.float32)
                    ref = np.asarray(mxm(W, X))
                    us_h = _tmed(jax.jit(lambda u: mxm(halo, u, desc=d)), X)
                    us_g = _tmed(jax.jit(lambda u: mxm(gath, u, desc=d)), X)
                    us_s = _tmed(jax.jit(lambda u: mxm(sell, u, desc=ds)), X)
                    err = max(
                        float(np.abs(np.asarray(mxm(p, X, desc=dd)) - ref).max())
                        for p, dd in ((halo, d), (gath, d), (sell, ds)))
                    wb = halo.wire_bytes(k=k)
                    entry["entries"].append({
                        "shards": int(S), "placement": placement, "k": k,
                        "mode_auto": mode_auto,
                        "halo_width": wb["halo_width"],
                        "halo_rows_true": wb["halo_rows_true"],
                        "wire_bytes_halo": wb["halo"],
                        "wire_bytes_gather": wb["gather"],
                        "wire_ratio": round(wb["halo"] / max(wb["gather"], 1),
                                            3),
                        "wall_us_halo": round(us_h, 1),
                        "wall_us_gather": round(us_g, 1),
                        "wall_us_dist_sellcs": round(us_s, 1),
                        "wall_speedup_halo_vs_gather": round(us_g / us_h, 2),
                        "wall_speedup_sellcs_vs_gather": round(us_g / us_s,
                                                               2),
                        "max_abs_err_vs_coo": err,
                    })
        payload["graphs"].append(entry)
    # headline: the acceptance configuration (aligned SBM, 4 shards);
    # both dist flavours ride the same halo plan — sellcs is the faster
    # execution of it (per-slice padding cuts the fold width too)
    head = [e for g in payload["graphs"] if g["graph"] == "sbm4_65k"
            for e in g["entries"]
            if e["shards"] == 4 and e["placement"] == "aligned"
            and e["k"] >= 16]
    payload["headline_sbm4_aligned_4shards"] = [
        {k: e[k] for k in ("k", "wire_ratio", "wall_speedup_halo_vs_gather",
                           "wall_speedup_sellcs_vs_gather")}
        for e in head]
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


# ------------------------------------------------- multilevel V-cycle sweep

def sweep_multilevel(out_path=None, k=4, seed=0):
    """Flat solver vs the multilevel V-cycle (repro.multilevel) across
    hierarchy depths × graph sizes, recording RCut + end-to-end wall
    clock.  Emits BENCH_multilevel.json — the committed evidence for the
    DESIGN.md §6 claim (≥3× end-to-end at ≥100k nodes within 1% RCut).

    Graph families mirror the paper's evaluation: delaunay
    triangulations (delaunay_nXX) and a planted-partition SBM in the
    sparse regime (sbm_graph_sparse — the dense generator is O(n²)).
    The 524k-node delaunay runs flat once for the scaling point; the
    depth sweep lives on the ~131k graphs to keep the bench re-runnable.
    """
    import dataclasses

    from repro.core import PSCConfig, p_spectral_cluster
    from repro.graphs import sbm_graph_sparse
    from repro.multilevel import MultilevelConfig

    base = PSCConfig(k=k, p_target=1.4, newton_iters=15, tcg_iters=12,
                     kmeans_restarts=4, seed=seed, trace=True)

    def _phases(res):
        tel = res.telemetry
        if tel is None:
            return None
        return {name: round(sec, 3)
                for name, sec in sorted(tel.phase_breakdown().items())}
    graphs = [
        ("delaunay_r17", lambda: delaunay_graph(17, seed=seed)[0], (3, 12)),
        # weighted planted partition (w_in > w_out, similarity-graph
        # style): degrees dense enough that no vertex is isolated (an
        # isolated vertex makes RCut trivially 0) and the planted cut is
        # the unambiguous optimum — in the *unit-weight* sparse regime
        # the blocks are locally invisible (no triangles, equal
        # degrees), so any locality-based coarsening — ours or
        # Metis-style — loses them while global eigenvectors keep them;
        # that regime measures generator degeneracy, not solver quality
        ("sbm_131k", lambda: sbm_graph_sparse(
            [32768] * k, deg_in=16.0, deg_out=4.0, w_in=2.0, w_out=1.0,
            seed=seed)[0], (3, 12)),
        ("delaunay_r19", lambda: delaunay_graph(19, seed=seed)[0], (12,)),
    ]
    payload = {"schema": 2, "platform": jax.default_backend(), "k": k,
               "config": {"p_target": base.p_target,
                          "newton_iters": base.newton_iters,
                          "tcg_iters": base.tcg_iters}, "graphs": []}
    for name, make, depths in graphs:
        W = make()
        t0 = time.time()
        rf = p_spectral_cluster(W, base)
        t_flat = time.time() - t0
        entry = {
            "graph": name, "n": W.n_rows, "nnz": W.nnz,
            "flat": {"rcut": float(rf.rcut), "wall_s": round(t_flat, 2),
                     "init_rcut": float(rf.init_rcut),
                     "phase_s": _phases(rf)},
            "vcycle": [],
        }
        for depth in depths:
            cfg = dataclasses.replace(
                base, multilevel=MultilevelConfig(max_levels=depth))
            t0 = time.time()
            rm = p_spectral_cluster(W, cfg)
            t_ml = time.time() - t0
            recs = rm.levels or []
            n_levels = recs[0]["n_levels"] if recs else 1
            entry["vcycle"].append({
                "max_levels": depth, "hierarchy_levels": n_levels,
                "levels_refined": len({r["level"] for r in recs}),
                "phase_s": _phases(rm),
                "rcut": float(rm.rcut), "wall_s": round(t_ml, 2),
                "speedup_vs_flat": round(t_flat / t_ml, 2),
                "rcut_gap_pct": round(
                    (float(rm.rcut) - float(rf.rcut))
                    / max(float(rf.rcut), 1e-12) * 100.0, 3),
            })
        best = max(entry["vcycle"], key=lambda e: e["speedup_vs_flat"])
        entry["best_vcycle"] = best
        payload["graphs"].append(entry)
        print(f"[multilevel] {name}: flat {t_flat:.1f}s rcut={rf.rcut:.5f}; "
              f"best vcycle {best['wall_s']}s ({best['speedup_vs_flat']}x, "
              f"gap {best['rcut_gap_pct']}%)")
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


# --------------------------------------------------- solver-driver sweep

def sweep_solvers(out_path=None, k=4, seed=0):
    """Registry-driver sweep (DESIGN.md §7): graph family × p × solver,
    recording wall clock, RCut and (where a planted truth exists)
    clustering accuracy.  Emits BENCH_solvers.json — the committed
    evidence that the three continuation drivers land equivalent cuts
    and what each costs, plus the p=1.0 sparsest-cut row only the
    inverse-power driver can serve.  ``make bench-solvers`` regenerates.
    """
    from repro.core import PSCConfig, metrics, p_spectral_cluster
    from repro.graphs import gaussian_blobs_knn

    graphs = [
        # second element: planted labels where the family has them
        # (delaunay's is vertex coordinates — no planted truth)
        ("sbm4_120", lambda: sbm_graph([30] * k, p_in=0.5, p_out=0.03,
                                       seed=5)[:2]),
        ("blobs4_480", lambda: gaussian_blobs_knn(120, k, seed=1)[:2]),
        ("delaunay_r10", lambda: (delaunay_graph(10, seed=seed)[0], None)),
    ]
    payload = {"schema": 2, "platform": jax.default_backend(), "k": k,
               "entries": []}
    for name, make in graphs:
        W, truth = make()
        for p_target in (1.4, 1.1, 1.0):
            for solver in ("newton", "scf", "inverse_power"):
                if p_target == 1.0 and solver != "inverse_power":
                    continue        # p=1 is outside newton/scf's open range
                cfg = PSCConfig(k=k, p_target=p_target, newton_iters=15,
                                tcg_iters=10, kmeans_restarts=4, seed=seed,
                                solver=solver, scf_sweeps=10, ipm_iters=100,
                                trace=True)
                t0 = time.time()
                res = p_spectral_cluster(W, cfg)
                wall = time.time() - t0
                tel = res.telemetry
                row = {"graph": name, "n": W.n_rows, "nnz": W.nnz,
                       "p_target": p_target, "solver": solver,
                       "wall_s": round(wall, 2),
                       "phase_s": None if tel is None else
                       {ph: round(sec, 3) for ph, sec
                        in sorted(tel.phase_breakdown().items())},
                       "rcut": round(float(res.rcut), 5),
                       "n_apply": int(sum(res.hvp_counts))}
                if truth is not None:
                    row["accuracy"] = round(float(
                        metrics.clustering_accuracy(res.labels, truth, k)), 4)
                payload["entries"].append(row)
                print(f"[solvers] {name} p={p_target} {solver}: "
                      f"{wall:.1f}s rcut={row['rcut']}"
                      + (f" acc={row.get('accuracy')}" if truth is not None
                         else ""))
    # headline: per (graph, p) the cheapest driver within 2% RCut of the
    # best — what the registry buys over newton-everywhere
    head = []
    seen = {(e["graph"], e["p_target"]) for e in payload["entries"]}
    for g, p in sorted(seen):
        rows = [e for e in payload["entries"]
                if e["graph"] == g and e["p_target"] == p]
        best_rcut = min(e["rcut"] for e in rows)
        ok = [e for e in rows if e["rcut"] <= best_rcut * 1.02 + 1e-9]
        w = min(ok, key=lambda e: e["wall_s"])
        head.append({"graph": g, "p_target": p, "winner": w["solver"],
                     "wall_s": w["wall_s"], "rcut": w["rcut"]})
    payload["headline_cheapest_within_2pct_rcut"] = head
    if out_path is not None:
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    return payload


def main(csv=True):
    lines = []
    W, _ = delaunay_graph(12, seed=0, build_bsr=True, block_size=128)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((W.n_rows, 4)), jnp.float32)
    bsr_ref = Descriptor(backend="bsr_pallas")      # jnp blocked ref on CPU

    lines.append(f"kernel_bsr_spmm_del12,"
                 f"{_time(lambda x: mxm(W, x, desc=bsr_ref), X):.0f},"
                 f"fill_ratio={W.bsr_fill_ratio():.1f}")
    # BSR block-size sweep (EXPERIMENTS.md §Perf-kernels): fill ratio is
    # the HBM-roofline cost multiplier of the MXU-native layout
    for bs in (8, 16, 32, 64):
        Wb, _ = delaunay_graph(12, seed=0, build_bsr=True, block_size=bs)
        lines.append(f"kernel_bsr_fill_bs{bs},0,"
                     f"fill_ratio={Wb.bsr_fill_ratio():.1f}")
    lines.append(
        f"kernel_plap_edge_del12,"
        f"{_time(lambda x: mxm(W, x, plap_edge_semiring(1.4, 1e-9), desc=Descriptor(backend='edge_pallas')), X):.0f},"
        f"nnz={W.nnz}")
    C = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
    lines.append(f"kernel_kmeans_assign_n{W.n_rows},"
                 f"{_time(lambda: kmeans_assign(X, C, use_pallas=False)):.0f},"
                 f"kc=16")
    q = jnp.asarray(rng.standard_normal((1, 8, 1024, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 1024, 64)), jnp.float32)
    lines.append(f"kernel_flash_gqa_s1024,"
                 f"{_time(lambda: flash_attention(q, k, k, use_pallas=False)):.0f},"
                 f"hq=8_hkv=2")

    bench = sweep_backends(out_path=_ROOT / "BENCH_backends.json")
    for e in bench["entries"]:
        lines.append(f"backend_{e['ring']}_{e['backend']}_del10,"
                     f"{e['wall_us']:.0f},n={bench['n']}")
    sell = sweep_sellcs(out_path=_ROOT / "BENCH_sellcs.json")
    for g in sell["graphs"]:
        b = g["best_sellcs"]
        lines.append(f"sellcs_best_{g['graph']},{b['wall_us']:.0f},"
                     f"C={b['C']}_sigma={b['sigma']}_reorder={b['reorder']}"
                     f"_fill={b['fill_ratio']}"
                     f"_speedup_vs_ell={g['speedup_vs_ell']}")
    ml = sweep_multilevel(out_path=_ROOT / "BENCH_multilevel.json")
    for g in ml["graphs"]:
        b = g["best_vcycle"]
        lines.append(f"multilevel_{g['graph']},{b['wall_s']},"
                     f"levels={b['hierarchy_levels']}"
                     f"_speedup_vs_flat={b['speedup_vs_flat']}"
                     f"_rcut_gap_pct={b['rcut_gap_pct']}")
    sol = sweep_solvers(out_path=_ROOT / "BENCH_solvers.json")
    for h in sol["headline_cheapest_within_2pct_rcut"]:
        lines.append(f"solver_winner_{h['graph']}_p{h['p_target']},"
                     f"{h['wall_s']},solver={h['winner']}_rcut={h['rcut']}")
    if csv:
        for line in lines:
            print(line)
    return lines


if __name__ == "__main__":
    main()
