"""Time whole serve-engine bucket launches outside the benchmark window:
full batches of Girvan–Newman graphs (4 groups, by default z_in 13,
z_out 3) of one size, each launch's solve (``ServeStats.solve_s``) and
its requests' stage 3 (``ServeStats.finish_s``), profiler off.

    python bench/tools/bucket_launch.py --n 1024 --launches 3 \\
        --out launch.jsonl

``--z-in 1.5 --z-out 0.45`` gives degree about 2 (nnz 1,992 at n 1024:
the sparsest nnz bucket of its n, 2048).

The first launch compiles and is reported apart (``warm_s``).  Prints one
JSON line per timed launch, then a summary with the engine's
``serve_bucket_launches_total`` by layout (empty where the engine has no
such counter).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--launches", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--z-in", type=float, default=13.0)
    ap.add_argument("--z-out", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import numpy as np
    from harness import graphs as G
    from harness import traffic as T
    from repro.core import PSCConfig
    from repro.grblas import SparseMatrix
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import ClusterServeEngine

    enable_compile_cache()
    n, b = args.n, args.batch

    def graph(i):
        rows, cols, vals, _ = G.planted_sbm_coo(n, 4, args.z_in, args.z_out,
                                                T.rng(args.seed, 7, i))
        return SparseMatrix.from_coo(rows, cols, vals, (n, n))

    graphs = [graph(i) for i in range(b * (args.launches + 1))]
    cfg = PSCConfig(k=4, reorder="none", newton_iters=20, tcg_iters=12,
                    kmeans_restarts=4)
    eng = ClusterServeEngine(cfg, max_batch=b, max_bucket_n=n)
    device = jax.devices()[0]
    lines = []
    t0 = time.perf_counter()
    warm = eng.serve(graphs[:b])
    warm_s = time.perf_counter() - t0
    for i in range(1, args.launches + 1):
        t0 = time.perf_counter()
        res = eng.serve(graphs[i * b:(i + 1) * b])
        wall = time.perf_counter() - t0
        st = [r.stats for r in res]
        lines.append({"what": "launch", "n": n, "nnz": graphs[0].nnz,
                      "bucket": str(st[0].bucket), "ok": all(r.ok for r in res),
                      "solve_s": st[0].solve_s,
                      "finish_s_mean": float(np.mean([s.finish_s for s in st])),
                      "wall_s": wall})
        print(json.dumps(lines[-1]), flush=True)
    solves = [ln["solve_s"] for ln in lines]
    launches = eng.metrics.labeled_values("serve_bucket_launches_total",
                                          "layout")
    lines.append({"what": "summary", "device_kind": device.device_kind,
                  "platform": device.platform, "warm_s": warm_s,
                  "warm_ok": all(r.ok for r in warm),
                  "solve_s_median": float(np.median(solves)),
                  "launches_by_layout": {k: int(v)
                                         for k, v in launches.items()}})
    print(json.dumps(lines[-1]), flush=True)
    with open(args.out, "a") as f:
        for ln in lines:
            f.write(json.dumps(ln) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
