"""LFR-style planted partition (Lancichinetti, Fortunato and Radicchi,
Phys. Rev. E 78, 046110, 2008), built by the benchmark for the
skewed-degree configuration.

Degrees follow a power law of exponent ``tau1`` between k_min and
``k_max``, with k_min solved so that the mean is ``mean_degree``; each
vertex sends a share ``mu`` of its edges out of its community.  The
communities are ``blocks`` equal runs of vertices (LFR draws their sizes
from a second power law; here they are fixed, so that k matches the
block count).  Edges are drawn by stub matching: internal stubs are
paired inside each community, external stubs across the graph; the
stubs of a pair that is a self loop, a repeated edge or (external) inside
one community are paired again, for a few rounds, and any left then are
dropped.  The vertex ids are shuffled.  Returns sorted symmetric COO triples
(both directions, unit weights, no self loops or duplicates) and the
planted community of each vertex, as the generators of ``graphs.py`` do.
"""
from __future__ import annotations

import numpy as np

from harness.graphs import _finish

# rounds of stub matching: stubs of a rejected pair are paired again
_ROUNDS = 8


def power_law_kmin(tau1: float, mean_degree: float, k_max: float) -> float:
    """k_min of a continuous power law P(k) ~ k^-tau1 on [k_min, k_max]
    whose mean is ``mean_degree`` (bisection; the mean rises with k_min)."""
    def mean(k0):
        k = np.geomspace(k0, k_max, 4097)
        w = k ** -tau1 * k                     # density on a log grid
        return float(np.trapezoid(w * k, np.log(k))
                     / np.trapezoid(w, np.log(k)))

    lo, hi = 1.0, float(mean_degree)
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean(mid) < mean_degree else (lo, mid)
    return 0.5 * (lo + hi)


def degree_sequence(n: int, tau1: float, mean_degree: float, k_max: int,
                    rng) -> np.ndarray:
    """``n`` degrees drawn from the continuous power law (inverse CDF),
    rounded to the nearest integer, at most ``k_max``."""
    k0 = power_law_kmin(tau1, mean_degree, k_max)
    u = rng.random(n)
    a = 1.0 - tau1
    k = (k0 ** a + u * (k_max ** a - k0 ** a)) ** (1.0 / a)
    return np.clip(np.rint(k), 1, k_max).astype(np.int64)


def _pair(stubs, rng):
    """Random pairs of the given stubs (an odd one out is dropped)."""
    stubs = rng.permutation(stubs)
    m = len(stubs) // 2
    return stubs[:m], stubs[m:2 * m]


def _match(stubs, n: int, rng, allowed=None):
    """Pairs of ``stubs`` (vertex ids, one per stub), drawn again for up
    to ``_ROUNDS`` rounds where a pair is a self loop, repeats an edge
    already drawn, or fails ``allowed(i, j)``; stubs still unpaired then
    are dropped."""
    out_i, out_j = [], []
    seen = np.zeros(0, np.int64)
    for _ in range(_ROUNDS):
        if len(stubs) < 2:
            break
        i, j = _pair(stubs, rng)
        key = np.minimum(i, j) * n + np.maximum(i, j)
        ok = (i != j) & ~np.isin(key, seen)
        if allowed is not None:
            ok &= allowed(i, j)
        _, first = np.unique(key, return_index=True)
        once = np.zeros(len(key), bool)
        once[first] = True
        ok &= once
        out_i.append(i[ok])
        out_j.append(j[ok])
        seen = np.union1d(seen, key[ok])
        stubs = np.concatenate([i[~ok], j[~ok]])
    return np.concatenate(out_i), np.concatenate(out_j)


def lfr_coo(n: int, blocks: int, tau1: float, mean_degree: float,
            k_max: int, mu: float, seed: int):
    """(rows, cols, vals, truth) of one LFR-style graph (module docstring)."""
    rng = np.random.default_rng(seed)
    if n % blocks:
        raise ValueError(f"n {n} is not a multiple of blocks {blocks}")
    b = n // blocks
    deg = degree_sequence(n, tau1, mean_degree, k_max, rng)
    ext = rng.binomial(deg, mu)                  # external stubs per vertex
    inner = deg - ext
    comm = np.repeat(np.arange(blocks), b)       # planted community of id
    rs, cs = [], []
    for a in range(blocks):
        ids = np.arange(a * b, (a + 1) * b)
        i, j = _match(np.repeat(ids, inner[ids]), n, rng)
        rs.append(i)
        cs.append(j)
    i, j = _match(np.repeat(np.arange(n), ext), n, rng,
                  allowed=lambda i, j: comm[i] != comm[j])
    rs.append(i)
    cs.append(j)
    order = rng.permutation(n)                   # planted id -> served id
    r, c = order[np.concatenate(rs)], order[np.concatenate(cs)]
    rows, cols, vals = _finish(r, c, np.ones(len(r)), n)
    truth = np.empty(n, np.int64)
    truth[order] = comm
    return rows, cols, vals, truth


def n_components(rows, cols, n: int) -> int:
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    A = sp.csr_matrix((np.ones(len(rows)), (rows, cols)), shape=(n, n))
    return int(connected_components(A, directed=False)[0])


def lfr_graph(gconf: dict):
    """The configuration's graph: ``n``, (rows, cols, vals), truth.
    Raises if the drawn graph is not one connected component or its mean
    degree is more than 3% from the configured one."""
    n = int(gconf["n"])
    rows, cols, vals, truth = lfr_coo(
        n, int(gconf["blocks"]), float(gconf["tau1"]),
        float(gconf["mean_degree"]), int(gconf["k_max"]),
        float(gconf["mu"]), int(gconf["seed"]))
    comps = n_components(rows, cols, n)
    if comps != 1:
        raise ValueError(f"LFR graph has {comps} connected components")
    mean = len(rows) / n
    if abs(mean / float(gconf["mean_degree"]) - 1.0) > 0.03:
        raise ValueError(f"LFR graph's mean degree {mean:.2f} is more than "
                         f"3% from {gconf['mean_degree']}")
    return n, (rows, cols, vals), truth
