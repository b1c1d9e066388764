"""Seconds of set-up spent building the cell's graphs: generation on
the host and ``SparseMatrix.from_coo`` layouts on the device (bench
clock).  Moves setup_s."""


def read(run):
    return run.get("graph_build_s")
