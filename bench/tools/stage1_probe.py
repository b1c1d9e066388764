"""Stage 1 of the flat solve (the program's LOBPCG p = 2 start) on the
delaunay_n17 graph, against scipy's float64 eigensolve, at the default
and at the highest float32 matmul precision.

    python bench/tools/stage1_probe.py --seeds 0,1,2 --repeat 2 \\
        --out stage1.jsonl

For each precision and seed it prints the Rayleigh-Ritz values of the
returned block on the float64 Laplacian and their relative gap to the
reference eigenvalues (one JSON line each).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", default="0,1,2")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--log2-n", type=int, default=17)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import scipy.sparse as sp
    from harness import graphs as G
    from references import psc as R
    from repro.core import lobpcg
    from repro.core.psc import PSCConfig
    from repro.grblas import SparseMatrix
    from repro.grblas import api

    k = 4
    n, (rows, cols, vals) = G.delaunay_coo(args.log2_n, 0)
    A = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    L = sp.diags(np.asarray(A.sum(axis=1)).ravel()) - A
    lam, _ = R.p2_basis(rows, cols, vals, n, k)
    W = SparseMatrix.from_coo(rows, cols, vals, (n, n))
    desc = api.capable_desc(W, desc=PSCConfig(k=k).descriptor(), k=k)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    lines = [{"reference_eigenvalues": lam.tolist()}]
    for rep in range(args.repeat):
        for prec in ("default", "highest"):
            for seed in [int(s) for s in args.seeds.split(",")]:
                t0 = time.perf_counter()
                with jax.default_matmul_precision(prec):
                    _, X = lobpcg.smallest_eigvecs(W, k, seed=seed,
                                                   desc=desc)
                    X = np.asarray(jax.block_until_ready(X), np.float64)
                secs = time.perf_counter() - t0
                Q, _ = np.linalg.qr(X)
                theta = np.sort(np.linalg.eigvalsh(Q.T @ (L @ Q)))
                gap = np.abs(theta[1:] - lam[1:]) / lam[1:]
                lines.append({"repeat": rep, "precision": prec,
                              "seed": seed, "ritz": theta.tolist(),
                              "rel_gap": gap.tolist(),
                              "max_rel_gap": float(gap.max()), "s": secs})
    with open(out, "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
