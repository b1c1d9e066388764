"""Seconds per solve in the fenced ``kmeans`` spans: stage 3
(core/kmeans.py) and the cut metrics.  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    vals = [s["spans"]["kmeans"] for s in solves if "kmeans" in s["spans"]]
    return sum(vals) / len(vals) if vals else None
