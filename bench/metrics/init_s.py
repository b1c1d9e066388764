"""Seconds per solve in the fenced ``init`` span: stage 1, the p=2
LOBPCG eigensolve and its k-means (core/lobpcg.py).  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    vals = [s["spans"]["init"] for s in solves if "init" in s["spans"]]
    return sum(vals) / len(vals) if vals else None
