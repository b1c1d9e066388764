"""Graph builders owned by the benchmark.

They return host COO triples (rows, cols, vals) with both directions of
every undirected edge stored, sorted by (row, col), no self loops and
no duplicates.  The system under test receives them through
``SparseMatrix.from_coo``; the plain reference reads the same arrays.

``delaunay_coo`` follows the DIMACS10 ``delaunay_nXX`` construction
(Delaunay triangulation of 2^r uniform points in the unit square), with
the points sorted by a 16-bit Morton key first, as the program's own
``graphs.generators.delaunay_graph`` does.  ``planted_sbm_coo`` is the
Girvan-Newman planted partition with a *fixed* number of edges per
block pair (the rounded expectation of the counts), drawn without
replacement, so every graph of one size has exactly the same nnz; its
vertex ids are shuffled.
"""
from __future__ import annotations

import numpy as np


def _finish(r, c, v, n):
    """Both directions, no self loops or duplicates, sorted by (row, col)."""
    keep = r != c
    r, c, v = r[keep], c[keep], v[keep]
    rows = np.concatenate([r, c]).astype(np.int64)
    cols = np.concatenate([c, r]).astype(np.int64)
    vals = np.concatenate([v, v]).astype(np.float64)
    _, idx = np.unique(rows * n + cols, return_index=True)
    return rows[idx], cols[idx], vals[idx]


def _morton(pts):
    xi = (pts[:, 0] * 65535).astype(np.uint64)
    yi = (pts[:, 1] * 65535).astype(np.uint64)

    def spread(a):
        a = (a | (a << np.uint64(8))) & np.uint64(0x00FF00FF)
        a = (a | (a << np.uint64(4))) & np.uint64(0x0F0F0F0F)
        a = (a | (a << np.uint64(2))) & np.uint64(0x33333333)
        a = (a | (a << np.uint64(1))) & np.uint64(0x55555555)
        return a

    return spread(xi) | (spread(yi) << np.uint64(1))


def delaunay_coo(log2_n: int, seed: int):
    """Delaunay triangulation of 2^log2_n uniform points, unit weights."""
    from scipy.spatial import Delaunay

    n = 2 ** int(log2_n)
    pts = np.random.default_rng(seed).random((n, 2))
    pts = pts[np.argsort(_morton(pts), kind="stable")]
    s = Delaunay(pts).simplices
    r = np.concatenate([s[:, 0], s[:, 1], s[:, 2]])
    c = np.concatenate([s[:, 1], s[:, 2], s[:, 0]])
    return n, _finish(r, c, np.ones(len(r)), n)


def _pairs_within(size, m, rng):
    """m distinct unordered pairs {i < j} of range(size)."""
    i, j = np.triu_indices(size, 1)
    idx = rng.choice(len(i), m, replace=False)
    return i[idx], j[idx]


def _pairs_between(size_a, size_b, m, rng):
    idx = rng.choice(size_a * size_b, m, replace=False)
    return idx // size_b, idx % size_b


def sbm_edge_counts(n: int, blocks: int, z_in: float, z_out: float):
    """(block size, undirected edges inside each block, undirected edges
    between each pair of blocks) for expected degrees ``z_in`` inside a
    vertex's own block and ``z_out`` to the other blocks, as Girvan and
    Newman define them: the rounded expectations of their counts."""
    b = n // blocks
    within = int(round(0.5 * z_in * b))
    between = int(round(z_out * b / (blocks - 1)))
    return b, within, between


def planted_sbm_coo(n: int, blocks: int, z_in: float, z_out: float, rng):
    """Planted partition with ``blocks`` equal blocks, fixed edge counts
    and the vertices in an order drawn from ``rng`` (the planted blocks
    are not runs of ids).  Returns (rows, cols, vals, truth)."""
    b, within, between = sbm_edge_counts(n, blocks, z_in, z_out)
    rs, cs = [], []
    for a in range(blocks):
        i, j = _pairs_within(b, within, rng)
        rs.append(a * b + i)
        cs.append(a * b + j)
        for c in range(a + 1, blocks):
            i, j = _pairs_between(b, b, between, rng)
            rs.append(a * b + i)
            cs.append(c * b + j)
    order = rng.permutation(n)                 # planted id -> served id
    r, c = order[np.concatenate(rs)], order[np.concatenate(cs)]
    rows, cols, vals = _finish(r, c, np.ones(len(r)), n)
    truth = np.empty(n, np.int64)
    truth[order] = np.repeat(np.arange(blocks), b)
    return rows, cols, vals, truth
