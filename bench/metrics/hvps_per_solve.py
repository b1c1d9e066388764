"""Hessian applies per solve: the sum of ``PSCResult.hvp_counts`` (a
count).  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    return sum(s["hvps"] for s in solves) / len(solves) if solves else None
