"""Width runs of the SELL-C-σ layout the program built for the cell's
graph: ``runs`` of its ``grblas.build_sellcs`` span (a count).  Each run
is one slot loop in every program that applies the matrix.  Moves
solve_s."""


def read(run):
    layout = run.get("sell_layout")
    return layout["runs"] if layout and "runs" in layout else None
