"""Seconds per solve in the fenced ``multilevel.refine`` spans: prolong
and refine up the V-cycle (multilevel/vcycle.py).  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    vals = [s["spans"]["multilevel.refine"] for s in solves if "multilevel.refine" in s["spans"]]
    return sum(vals) / len(vals) if vals else None
