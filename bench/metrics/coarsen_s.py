"""Seconds per solve in the ``multilevel.coarsen`` span: host
heavy-edge matching, Galerkin products and layout builds
(multilevel/coarsen.py).  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    vals = [s["spans"]["multilevel.coarsen"] for s in solves if "multilevel.coarsen" in s["spans"]]
    return sum(vals) / len(vals) if vals else None
