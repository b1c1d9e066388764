"""Bandwidth-reducing graph orderings for the SpMM hot loop.

SpMM memory traffic on every layout in grblas.containers is dominated by
the multivector gather, and gather locality is governed by the matrix
bandwidth: after a reverse Cuthill–McKee (RCM) ordering, neighbours of
row i live near i, so the ELL/SELL gather walks X almost sequentially
instead of striding the whole vector.  Degree ordering is the companion
preprocessing for SELL-C-σ: it is the σ=n sort applied to the *graph
itself*, which empties the layout's internal permutation.

The contract is permutation transparency: ``reorder`` returns a new
``SparseMatrix`` over relabeled vertices plus both direction maps, and
callers (core.psc with ``PSCConfig.reorder``) un-permute every row-
indexed output (labels, eigenvectors) before returning, so downstream
code can't observe the relabeling.  Cut metrics are permutation-
invariant by construction (tests/test_grblas_properties.py pins this).

    W2, perm, inv = reorder(W, method="rcm")
    # perm[new] = old,  inv[old] = new,  W2[i, j] == W[perm[i], perm[j]]
    labels_old = labels_new[inv]        # row data back to original ids
"""
from __future__ import annotations

from typing import Tuple

import numpy as np
import scipy.sparse as sp

from repro.grblas.containers import SparseMatrix


def rcm_ordering(W: SparseMatrix) -> np.ndarray:
    """Reverse Cuthill–McKee permutation (perm[new] = old) on the
    symmetrized structure of W."""
    from scipy.sparse.csgraph import reverse_cuthill_mckee

    n = W.n_rows
    A = sp.csr_matrix(
        (np.ones(W.nnz, np.float32),
         (np.asarray(W.rows), np.asarray(W.cols))), shape=(n, W.n_cols))
    return np.asarray(reverse_cuthill_mckee(A, symmetric_mode=False),
                      dtype=np.int64)


def degree_ordering(W: SparseMatrix) -> np.ndarray:
    """Stable descending-degree permutation (perm[new] = old) — the
    global SELL σ-sort expressed as a graph relabeling."""
    deg = np.bincount(np.asarray(W.rows), minlength=W.n_rows)
    return np.argsort(-deg, kind="stable").astype(np.int64)


_ORDERINGS = {"rcm": rcm_ordering, "degree": degree_ordering}


def bandwidth(W: SparseMatrix) -> int:
    """max |i - j| over stored entries — the locality figure RCM reduces."""
    if W.nnz == 0:
        return 0
    return int(np.abs(np.asarray(W.rows, np.int64)
                      - np.asarray(W.cols, np.int64)).max())


def reorder(W: SparseMatrix, method: str = "rcm"
            ) -> Tuple[SparseMatrix, np.ndarray, np.ndarray]:
    """Relabel W's vertices under ``method`` ("rcm" | "degree").

    Returns (W2, perm, inv) with perm[new] = old and inv[old] = new.
    W2 is rebuilt with the same derived layouts (ELL / BSR / SELL-C-σ,
    same parameters) and dtype as W, so a Descriptor that executed on W
    executes on W2.
    """
    if method not in _ORDERINGS:
        raise ValueError(f"unknown reorder method {method!r}; "
                         f"known: {sorted(_ORDERINGS)}")
    perm = _ORDERINGS[method](W)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))

    rows = inv[np.asarray(W.rows, np.int64)]
    cols = inv[np.asarray(W.cols, np.int64)]
    W2 = SparseMatrix.from_coo(
        rows, cols, np.asarray(W.vals), (W.n_rows, W.n_cols),
        build_ell=W.ell_cols is not None,
        build_bsr=W.bsr_blocks is not None,
        block_size=W.block_size or 128,
        dtype=W.vals.dtype,
        build_sellcs=W.sell_cols is not None,
        sell_c=W.sell_c or 32,
        sell_sigma=W.sell_sigma or None)
    return W2, perm, inv
