"""Mean RCut of the window's solves (``PSCResult.rcut``): quality, read
beside speed; it decides nothing.  Moves solve_s."""


def read(run):
    solves = run.get("solves") or []
    return sum(s["rcut"] for s in solves) / len(solves) if solves else None
