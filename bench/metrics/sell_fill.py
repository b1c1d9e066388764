"""Stored slots over nnz of the SELL-C-σ layout the program built
(``grblas.build_sellcs`` span): the padding every apply streams on top
of the matrix (a ratio).  Moves solve_s."""


def read(run):
    layout = run.get("sell_layout")
    if not layout or not layout.get("nnz") or "stored_slots" not in layout:
        return None
    return layout["stored_slots"] / layout["nnz"]
