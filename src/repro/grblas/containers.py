"""Algebraic containers: sparse matrices in JAX-friendly layouts.

A ``SparseMatrix`` carries up to six layouts of the same matrix:

  * COO    (rows, cols, vals)           — construction + segment-sum SpMV
  * CSR    (indptr, cols, vals)         — host-side utilities / export
  * ELL    (ell_cols, ell_vals, pad)    — padded rows, vectorized gather SpMV
  * SELL-C-σ (per-slice padded chunks)  — sliced ELLPACK with σ-window row
                                          sorting: rows are degree-sorted
                                          inside windows of σ rows, cut into
                                          slices of C rows, and each slice is
                                          padded only to its OWN max degree
                                          (Kreutzer/Hager/Wellein/Alappat).
                                          Kills the hub-row blowup of full
                                          ELL on skewed-degree graphs.
  * BSR    (block ptrs/idx, dense tiles)— 128x128 dense tiles for the MXU
                                          Pallas kernel (kernels/bsr_spmm)
  * dense  (n, n) tile                  — the whole matrix as one tile, for
                                          graphs of at most DENSE_MAX_N
                                          vertices (the "dense" backend);
                                          built on device, traceable

All device arrays are static-shaped so every op jits.  Construction is
host-side (numpy/scipy); the resulting container is a pytree of jnp
arrays and can be donated/sharded.

SELL-C-σ storage model
----------------------
The σ-sort produces a row permutation ``sell_perm`` (permuted position →
original row; ``sell_inv`` is its inverse).  Slices of equal padded
width are contiguous after the sort, so the layout is stored as a tuple
of *width runs*: run r holds ``sell_cols[r]`` / ``sell_vals[r]`` of
shape (rows_r, w_r) with rows_r a multiple of C.  Column indices live in
the PERMUTED index space (the executor permutes the multivector once,
streams contiguously, and un-permutes the output — provably transparent
to callers).  Pad entries point at the row itself with value 0, the same
pad-soundness contract as ELL.  ``sell_scatter[r]`` maps each stored
slot back to its COO nnz index (pads → nnz), which is how ``with_vals``
rebuilds the packed values on-device without re-running the host build.
Slice pointers (run row offsets / widths) are static aux metadata, so
every run shape is known at trace time.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

# Auto-build / auto-dispatch threshold: when full-ELL padding would store
# more than this multiple of nnz, from_coo builds the SELL-C-σ layout as
# well and backend auto-selection prefers it over ELL (grblas.backends).
SELLCS_AUTO_THRESHOLD = 4.0

# Most width classes (so width runs, under the global σ-sort) a SELL-C-σ
# layout builds.  Each run is one slot loop in every compiled program
# that applies the matrix: on the TPU a program's compile time grows by
# about a second per run.
SELLCS_MAX_RUNS = 40

# Largest vertex count handled as one dense (n, n) tile: the exact-eigh
# path of lobpcg.smallest_eigvecs and the serve bucket lane's dense
# layout.  One step up (n 2048), a bucket launch's (batch 8, k 4, n, n)
# float32 pairwise tensors would pass 0.5 GB each.
DENSE_MAX_N = 1024


class GraphFingerprint(NamedTuple):
    """Identity of a weighted graph for the serve-layer warm cache
    (DESIGN.md §8): shape, a digest of the sparsity pattern, and a
    digest of the *quantized* weights.  Two graphs with the same pattern
    but different weights share ``pattern_key`` (warm-startable from the
    cached embedding via ``with_vals``) while their full ``key`` differs
    (the cached labels are NOT valid for them)."""

    n: int
    nnz: int
    pattern: str        # blake2b digest of (n, n_cols, rows, cols)
    weights: str        # blake2b digest of round(vals / weight_quant)

    @property
    def key(self) -> tuple:
        return (self.n, self.nnz, self.pattern, self.weights)

    @property
    def pattern_key(self) -> tuple:
        return (self.n, self.nnz, self.pattern)


def sell_width_classes(widths: np.ndarray) -> np.ndarray:
    """Slice widths (>= 1) rounded up to width classes.

    A class keeps the leading ``b`` + 1 bits of a width: w is rounded up
    to a multiple of 2^max(0, floor(log2 w) - b), so widths up to
    2^(b+1) stay exact and each octave above holds at most 2^b classes,
    none more than 1/2^b above the widths it serves.  ``b`` is the
    largest (finest) that leaves at most SELLCS_MAX_RUNS classes among
    the widths given; a layout with that few distinct widths is kept
    exact.  b = 0 (powers of two) gives at most 32 classes to widths
    below 2^31, which a row's degree always is."""
    widths = np.asarray(widths, np.int64)
    if widths.size == 0:
        return widths
    octave = np.floor(np.log2(widths)).astype(np.int64)
    for b in range(int(octave.max()), -1, -1):
        step = np.left_shift(1, np.maximum(octave - b, 0))
        classes = -(-widths // step) * step
        if b == 0 or len(np.unique(classes)) <= SELLCS_MAX_RUNS:
            return classes


def _row_layout(rows, n_rows: int, nnz: int):
    """(counts, pos_in_row) for a (row, col)-sorted COO triple — the
    shared inputs of the ELL and SELL-C-σ builders, computed once per
    construction (two O(nnz) host passes)."""
    counts = np.bincount(rows, minlength=max(n_rows, 1))
    pos_in_row = np.arange(nnz) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    return counts, pos_in_row


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseMatrix:
    n_rows: int
    n_cols: int
    nnz: int
    # COO (always present, sorted by row then col; vals is None only on
    # dense multivalues, see with_vals)
    rows: jnp.ndarray  # (nnz,) int32
    cols: jnp.ndarray  # (nnz,) int32
    vals: jnp.ndarray  # (nnz,) dtype
    # ELL (optional)
    ell_cols: Optional[jnp.ndarray] = None  # (n_rows, max_nnz) int32, pad=row i itself
    ell_vals: Optional[jnp.ndarray] = None  # (n_rows, max_nnz) dtype, pad=0
    # BSR (optional, block = bs x bs dense tiles)
    block_size: int = 0
    bsr_indptr: Optional[np.ndarray] = None   # host (n_row_blocks+1,) — static metadata
    bsr_indices: Optional[jnp.ndarray] = None  # (n_blocks,) int32 col-block ids
    bsr_blocks: Optional[jnp.ndarray] = None   # (n_blocks, bs, bs) dtype
    bsr_row_ids: Optional[jnp.ndarray] = None  # (n_blocks,) int32 row-block ids
    # SELL-C-σ (optional) — see module docstring for the storage model
    sell_c: int = 0                 # slice height C (static)
    sell_sigma: int = 0             # sorting-window size σ (static)
    sell_n_pad: int = 0             # n_rows rounded up to a multiple of C
    sell_row0: Tuple[int, ...] = ()  # static first-row offset of each width run
    sell_perm: Optional[jnp.ndarray] = None     # (n_pad,) int32 pos -> orig row
    sell_inv: Optional[jnp.ndarray] = None      # (n_rows,) int32 orig row -> pos
    sell_cols: Optional[Tuple[jnp.ndarray, ...]] = None  # per run (rows_r, w_r) int32, permuted space
    sell_vals: Optional[Tuple[jnp.ndarray, ...]] = None  # per run (rows_r, w_r[, k]) dtype
    sell_scatter: Optional[Tuple[jnp.ndarray, ...]] = None  # per run (rows_r, w_r) int32 -> nnz idx (pad=nnz)
    # dense (optional): (n_rows, n_cols), or (k, n_rows, n_cols) dense
    # multivalues (with_vals); absent entries are exact zeros
    dense: Optional[jnp.ndarray] = None

    # ---- pytree protocol ----
    def tree_flatten(self):
        children = (self.rows, self.cols, self.vals, self.ell_cols,
                    self.ell_vals, self.bsr_indices, self.bsr_blocks,
                    self.bsr_row_ids, self.sell_perm, self.sell_inv,
                    self.sell_cols, self.sell_vals, self.sell_scatter,
                    self.dense)
        aux = (self.n_rows, self.n_cols, self.nnz, self.block_size,
               None if self.bsr_indptr is None else tuple(self.bsr_indptr.tolist()),
               self.sell_c, self.sell_sigma,
               self.sell_n_pad, self.sell_row0)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (rows, cols, vals, ell_cols, ell_vals, bsr_indices, bsr_blocks,
         bsr_row_ids, sell_perm, sell_inv, sell_cols, sell_vals,
         sell_scatter, dense) = children
        (n_rows, n_cols, nnz, block_size, indptr,
         sell_c, sell_sigma, sell_n_pad, sell_row0) = aux
        return cls(n_rows=n_rows, n_cols=n_cols, nnz=nnz, rows=rows, cols=cols,
                   vals=vals, ell_cols=ell_cols, ell_vals=ell_vals,
                   block_size=block_size,
                   bsr_indptr=None if indptr is None else np.asarray(indptr, np.int64),
                   bsr_indices=bsr_indices, bsr_blocks=bsr_blocks,
                   bsr_row_ids=bsr_row_ids,
                   sell_c=sell_c, sell_sigma=sell_sigma,
                   sell_n_pad=sell_n_pad, sell_row0=sell_row0,
                   sell_perm=sell_perm, sell_inv=sell_inv,
                   sell_cols=sell_cols, sell_vals=sell_vals,
                   sell_scatter=sell_scatter, dense=dense)

    # ---- constructors ----
    @staticmethod
    def from_coo(rows, cols, vals, shape: Tuple[int, int],
                 build_ell: Optional[bool] = None, build_bsr: bool = False,
                 block_size: int = 128, dtype=jnp.float32,
                 build_sellcs: Optional[bool] = None, sell_c: int = 32,
                 sell_sigma: Optional[int] = None) -> "SparseMatrix":
        """``build_sellcs=None`` (auto) builds the SELL-C-σ layout exactly
        when full-ELL padding would exceed SELLCS_AUTO_THRESHOLD x nnz —
        the skewed-degree regime where the hub rows make ELL unusable.
        ``build_ell=None`` (auto) builds ELL except in that same regime:
        allocating the (n, hub_degree) dense blocks only to have every
        dispatch prefer the sliced layout is pure dead storage (~GBs at
        the paper's 8M-node scale).  Pass ``build_ell=True`` to force it
        (e.g. for the "dist" backend, which shards the ELL layout)."""
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        n_rows, n_cols = shape
        nnz = len(vals)

        mat = SparseMatrix(
            n_rows=n_rows, n_cols=n_cols, nnz=nnz,
            rows=jnp.asarray(rows, jnp.int32),
            cols=jnp.asarray(cols, jnp.int32),
            vals=jnp.asarray(vals, dtype),
        )
        counts = pos_in_row = None
        if build_ell is not False or build_sellcs is not False:
            counts, pos_in_row = _row_layout(rows, n_rows, nnz)
            predicted_ell = n_rows * max(int(counts.max()) if nnz else 0, 1)
            ell_blown_up = (nnz > 0
                            and predicted_ell > SELLCS_AUTO_THRESHOLD * nnz)
            if build_sellcs is None:
                # the sliced layout permutes row and column space with ONE
                # permutation, so it only represents square matrices
                build_sellcs = ell_blown_up and n_rows == n_cols
            if build_ell is None:
                build_ell = not (ell_blown_up and build_sellcs)
        if build_ell:
            mat._build_ell(rows, cols, vals, dtype, counts, pos_in_row)
        if build_bsr:
            mat._build_bsr(rows, cols, vals, block_size, dtype)
        if build_sellcs and n_rows > 0:
            mat._build_sellcs(rows, cols, vals, sell_c, sell_sigma, dtype,
                              counts=counts, pos_in_row=pos_in_row)
        return mat

    @staticmethod
    def from_scipy(sp, build_ell: Optional[bool] = None,
                   build_bsr: bool = False,
                   block_size: int = 128, dtype=jnp.float32,
                   build_sellcs: Optional[bool] = None, sell_c: int = 32,
                   sell_sigma: Optional[int] = None) -> "SparseMatrix":
        sp = sp.tocoo()
        return SparseMatrix.from_coo(sp.row, sp.col, sp.data, sp.shape,
                                     build_ell=build_ell, build_bsr=build_bsr,
                                     block_size=block_size, dtype=dtype,
                                     build_sellcs=build_sellcs, sell_c=sell_c,
                                     sell_sigma=sell_sigma)

    # ---- layout builders (host-side) ----
    def _build_ell(self, rows, cols, vals, dtype, counts=None,
                   pos_in_row=None):
        n = self.n_rows
        if counts is None:
            counts, pos_in_row = _row_layout(rows, n, len(rows))
        max_nnz = max(int(counts.max()) if n else 0, 1)
        # allocate in the final on-device dtypes directly: no float64
        # staging array and no full (n, max_nnz) int64 temporary — at
        # 8M-node scale those transients dominated peak host memory.
        ell_cols = np.empty((n, max_nnz), np.int32)
        ell_cols[:] = np.arange(n, dtype=np.int32)[:, None]  # pad = row itself
        ell_vals = np.zeros((n, max_nnz), np.dtype(dtype))
        ell_cols[rows, pos_in_row] = cols
        ell_vals[rows, pos_in_row] = vals
        self.ell_cols = jnp.asarray(ell_cols)
        self.ell_vals = jnp.asarray(ell_vals)
        _obs_metrics.DEFAULT.counter("grblas_layouts_built_total",
                                     layout="ell").inc()

    def _build_bsr(self, rows, cols, vals, bs, dtype):
        n_rb = -(-self.n_rows // bs)
        rb, cb = rows // bs, cols // bs
        keys = rb * n_rb * 0 + rb  # row-block major ordering
        block_key = rb.astype(np.int64) * (-(-self.n_cols // bs)) + cb
        uniq, inv = np.unique(block_key, return_inverse=True)
        n_blocks = len(uniq)
        blocks = np.zeros((n_blocks, bs, bs), np.float64)
        blocks[inv, rows % bs, cols % bs] = vals
        u_rb = (uniq // (-(-self.n_cols // bs))).astype(np.int64)
        u_cb = (uniq % (-(-self.n_cols // bs))).astype(np.int64)
        indptr = np.zeros(n_rb + 1, np.int64)
        np.add.at(indptr, u_rb + 1, 1)
        indptr = np.cumsum(indptr)
        self.block_size = bs
        self.bsr_indptr = indptr
        self.bsr_indices = jnp.asarray(u_cb, jnp.int32)
        self.bsr_blocks = jnp.asarray(blocks, dtype)
        self.bsr_row_ids = jnp.asarray(u_rb, jnp.int32)
        _ = keys
        _obs_metrics.DEFAULT.counter("grblas_layouts_built_total",
                                     layout="bsr").inc()

    def _build_sellcs(self, rows, cols, vals, C: int, sigma: Optional[int],
                      dtype, counts=None, pos_in_row=None):
        """Build the SELL-C-σ layout (``_pack_sellcs``) inside a
        ``grblas.build_sellcs`` span that records what was built."""
        with _obs_trace.ACTIVE.span("grblas.build_sellcs", cat="grblas",
                                    n=self.n_rows, nnz=len(vals)) as sp:
            self._pack_sellcs(rows, cols, vals, C, sigma, dtype, counts,
                              pos_in_row)
            widths = [c.shape[1] for c in self.sell_cols]
            sp.set(runs=len(widths),
                   stored_slots=sum(c.size for c in self.sell_cols),
                   classes=sorted(set(widths), reverse=True))
        _obs_metrics.DEFAULT.counter("grblas_layouts_built_total",
                                     layout="sellcs").inc()

    def _pack_sellcs(self, rows, cols, vals, C: int, sigma: Optional[int],
                     dtype, counts=None, pos_in_row=None):
        """SELL-C-σ: σ-window degree sort, C-row slices, per-slice padding.

        ``sigma=None`` sorts globally (maximum fill reduction; sound
        because the permutation is internal to the layout and undone on
        output).  Slice widths are rounded up to width classes
        (``sell_width_classes``), so a skewed degree distribution builds
        at most SELLCS_MAX_RUNS width runs under the global sort.
        Requires the COO triple sorted by (row, col), which from_coo
        guarantees.
        """
        if self.n_rows != self.n_cols:
            raise ValueError(
                "SELL-C-σ permutes row and column space with one "
                f"permutation and requires a square matrix, got "
                f"({self.n_rows}, {self.n_cols})")
        n = self.n_rows
        nnz = len(vals)
        C = max(int(C), 1)
        if counts is None:
            counts, pos_in_row = _row_layout(rows, n, nnz)
        counts = counts.astype(np.int64)
        sigma_eff = n if sigma is None else max(int(sigma), 1)

        # σ-window stable degree sort (descending): hubs cluster into the
        # same slices so only their slices pay their width.  One
        # vectorized argsort over (n_windows, σ); the pad key -1 sorts
        # after every real degree so trailing pads drop cleanly.
        n_win = -(-n // sigma_eff)
        counts_pad = np.full(n_win * sigma_eff, -1, np.int64)
        counts_pad[:n] = counts
        order_in_win = np.argsort(-counts_pad.reshape(n_win, sigma_eff),
                                  axis=1, kind="stable")
        perm = (order_in_win
                + np.arange(n_win, dtype=np.int64)[:, None] * sigma_eff
                ).reshape(-1)
        perm = perm[perm < n]
        inv = np.empty(n, np.int64)
        inv[perm] = np.arange(n)

        n_slices = max(-(-n // C), 1)
        n_pad = n_slices * C
        deg_p = np.zeros(n_pad, np.int64)
        deg_p[:n] = counts[perm]
        slice_w = deg_p.reshape(n_slices, C).max(axis=1)
        slice_w = sell_width_classes(np.maximum(slice_w, 1))

        # contiguous runs of equal-width slices (slice "pointers")
        run_bounds = np.concatenate(
            [[0], np.flatnonzero(np.diff(slice_w)) + 1, [n_slices]])

        # per-nnz placement: permuted row position, slice, within-row
        # slot.  One stable sort by owning slice; each run's entries are
        # then one contiguous segment (no per-run full-nnz masks — those
        # were O(n_runs x nnz) at the 8M-node scale this layout targets).
        i_nnz = inv[rows]                     # permuted position of each entry
        s_nnz = i_nnz // C                    # owning slice
        cols_p = inv[cols]                    # columns in permuted space
        by_slice = np.argsort(s_nnz, kind="stable")
        s_sorted = s_nnz[by_slice]

        run_cols, run_vals, run_scat, run_row0 = [], [], [], []
        np_dtype = np.dtype(dtype)
        for r in range(len(run_bounds) - 1):
            s0, s1 = int(run_bounds[r]), int(run_bounds[r + 1])
            w = int(slice_w[s0])
            row0 = s0 * C
            rows_r = (s1 - s0) * C
            cp = np.empty((rows_r, w), np.int32)
            cp[:] = (row0 + np.arange(rows_r, dtype=np.int32))[:, None]  # pad=self
            vp = np.zeros((rows_r, w), np_dtype)
            sc = np.full((rows_r, w), nnz, np.int32)                     # pad slot
            seg = by_slice[np.searchsorted(s_sorted, s0, "left"):
                           np.searchsorted(s_sorted, s1, "left")]
            cp[i_nnz[seg] - row0, pos_in_row[seg]] = cols_p[seg]
            vp[i_nnz[seg] - row0, pos_in_row[seg]] = vals[seg]
            sc[i_nnz[seg] - row0, pos_in_row[seg]] = seg
            run_cols.append(jnp.asarray(cp))
            run_vals.append(jnp.asarray(vp))
            run_scat.append(jnp.asarray(sc))
            run_row0.append(int(row0))

        perm_pad = np.zeros(n_pad, np.int64)
        perm_pad[:n] = perm                   # phantom rows read X[0]; their
        self.sell_c = C                       # stored vals are 0 so the
        self.sell_sigma = sigma_eff           # contribution annihilates
        self.sell_n_pad = n_pad
        self.sell_row0 = tuple(run_row0)
        self.sell_perm = jnp.asarray(perm_pad, jnp.int32)
        self.sell_inv = jnp.asarray(inv, jnp.int32)
        self.sell_cols = tuple(run_cols)
        self.sell_vals = tuple(run_vals)
        self.sell_scatter = tuple(run_scat)

    # ---- conveniences ----
    def with_dense(self) -> "SparseMatrix":
        """This matrix with its dense (n, n) layout built on device from
        the COO triple (one scatter-add, traceable: the serve bucket lane
        builds it once per launch inside its jitted solve)."""
        return dataclasses.replace(self, dense=self.to_dense())

    def with_vals(self, vals: jnp.ndarray) -> "SparseMatrix":
        """Same sparsity pattern, new values — GraphBLAS' "new matrix on
        the old structure" (Algorithm 1 builds W-hat this way each Newton
        step).  ``vals`` may be (nnz,) or (nnz, k) *multivalues* (one
        value per stored entry per output column; backends broadcast them
        against an (n, k) multivector).  Derived ELL/BSR/dense layouts
        are dropped (they would be stale), but the SELL-C-σ layout
        survives: its scatter map rebuilds the packed values on-device,
        so the materialized Alg-1 W-hat path runs on the sliced layout
        too.  On a matrix with the dense layout, (k, n, n) ``vals`` are
        dense multivalues (tile l serves output column l): they become
        the new matrix's dense layout, and its COO ``vals`` are None, so
        only the "dense" backend can execute it."""
        if self.dense is not None and vals.ndim == 3:
            return SparseMatrix(n_rows=self.n_rows, n_cols=self.n_cols,
                                nnz=self.nnz, rows=self.rows,
                                cols=self.cols, vals=None, dense=vals)
        m = SparseMatrix(n_rows=self.n_rows, n_cols=self.n_cols,
                         nnz=self.nnz, rows=self.rows, cols=self.cols,
                         vals=vals)
        if self.sell_scatter is not None:
            pad = jnp.zeros((1,) + vals.shape[1:], vals.dtype)
            vext = jnp.concatenate([vals, pad], axis=0)   # slot nnz == pad 0
            m.sell_c = self.sell_c
            m.sell_sigma = self.sell_sigma
            m.sell_n_pad = self.sell_n_pad
            m.sell_row0 = self.sell_row0
            m.sell_perm = self.sell_perm
            m.sell_inv = self.sell_inv
            m.sell_cols = self.sell_cols
            m.sell_scatter = self.sell_scatter
            # gather through the flattened (rows * w,) index: the TPU
            # compiler takes minutes over a 2-D (rows, w) index array
            # with rows between about 2^14 and 2^18, seconds over 1-D
            m.sell_vals = tuple(
                vext[sc.reshape(-1)].reshape(sc.shape + vals.shape[1:])
                for sc in self.sell_scatter)
        return m

    def host_coo(self):
        """Host-side (rows, cols, vals) numpy views of the COO triple —
        the input of every host-side plan builder (row partitioning,
        halo plans, spgemm, reorderings).  Raises for traced containers,
        mirroring the backends' loud traced-operand errors."""
        if isinstance(self.rows, jax.core.Tracer):
            raise TypeError(
                "host_coo() needs concrete arrays; this SparseMatrix is "
                "traced — run host-side plan construction outside jit")
        return (np.asarray(self.rows), np.asarray(self.cols),
                np.asarray(self.vals))

    def fingerprint(self, weight_quant: float = 1e-6) -> GraphFingerprint:
        """Graph identity for the serve-layer warm cache: (n, nnz,
        pattern digest, quantized-weight digest).  The pattern digest
        hashes the sorted COO index arrays (from_coo sorts, so equal
        patterns hash equal regardless of input order); weights are
        quantized to ``weight_quant`` before hashing so bit-level float
        noise does not defeat repeat-tenant detection, while any weight
        change ≥ the quantum lands a distinct fingerprint (pinned by
        tests/test_warm_cache.py).  Host-side: raises on traced
        containers, like every other plan-construction input."""
        rows, cols, vals = self.host_coo()
        h = hashlib.blake2b(digest_size=16)
        h.update(np.int64([self.n_rows, self.n_cols]).tobytes())
        h.update(np.ascontiguousarray(rows, np.int32).tobytes())
        h.update(np.ascontiguousarray(cols, np.int32).tobytes())
        pattern = h.hexdigest()
        hw = hashlib.blake2b(digest_size=16)
        q = np.round(np.asarray(vals, np.float64) / weight_quant)
        # non-finite weights (caught downstream by graphs.validate /
        # serve admission) still need a stable digest: map them onto
        # sentinel quanta instead of tripping the int cast
        if not np.isfinite(q).all():
            q = np.nan_to_num(q, nan=np.iinfo(np.int64).min + 1,
                              posinf=np.iinfo(np.int64).max,
                              neginf=np.iinfo(np.int64).min)
        hw.update(q.astype(np.int64).tobytes())
        return GraphFingerprint(n=self.n_rows, nnz=self.nnz,
                                pattern=pattern, weights=hw.hexdigest())

    def padded_coo(self, n_pad: int, nnz_pad: int):
        """Bucket padding for the serve layer: the COO triple padded to
        static dims (n_pad rows, nnz_pad stored entries) so graphs of
        different sizes share one compiled batched solve (DESIGN.md §8).

        Pad entries are (0, 0, 0.0) — they self-reference an existing
        row with weight zero, so every segment fold adds exact zeros
        (the pad-soundness contract the dist backend established); pad
        ROWS [n_rows, n_pad) carry no entries at all, so they are
        isolated vertices the batched solver masks out.  Returns host
        numpy (rows, cols, vals) ready to stack across a batch."""
        if self.n_rows != self.n_cols:
            raise ValueError("bucket padding is defined for square graphs, "
                             f"got ({self.n_rows}, {self.n_cols})")
        if n_pad < self.n_rows or nnz_pad < self.nnz:
            raise ValueError(
                f"bucket ({n_pad}, {nnz_pad}) smaller than graph "
                f"({self.n_rows}, {self.nnz})")
        rows, cols, vals = self.host_coo()
        pad = nnz_pad - self.nnz
        return (np.concatenate([np.asarray(rows, np.int32),
                                np.zeros(pad, np.int32)]),
                np.concatenate([np.asarray(cols, np.int32),
                                np.zeros(pad, np.int32)]),
                np.concatenate([np.asarray(vals),
                                np.zeros(pad, np.asarray(vals).dtype)]))

    def to_dense(self) -> jnp.ndarray:
        """The (n, n) matrix: the dense layout when it is built, else
        scattered from the COO triple.  Dense multivalues (with_vals of a
        (k, n, n) tile) are k matrices and have no COO values: refused."""
        if self.dense is not None:
            if self.dense.ndim != 2:
                raise ValueError(
                    f"to_dense of (k, n, n) dense multivalues "
                    f"{self.dense.shape}: k matrices, not one")
            return self.dense
        d = jnp.zeros((self.n_rows, self.n_cols), self.vals.dtype)
        return d.at[self.rows, self.cols].add(self.vals)

    def row_degrees(self) -> jnp.ndarray:
        return jax.ops.segment_sum(jnp.ones_like(self.vals), self.rows, self.n_rows)

    def row_sums(self) -> jnp.ndarray:
        return jax.ops.segment_sum(self.vals, self.rows, self.n_rows)

    # ---- layout cost metrics (stored-value inflation vs nnz; 1.0 = no
    # padding waste).  Formerly one ambiguous `fill_ratio` property that
    # documented BSR but was reported for ELL in the benches — now one
    # explicit accessor per layout, all recorded in the bench JSONs.
    def ell_fill_ratio(self) -> float:
        """ELL stored values / nnz (global max-degree row padding)."""
        if self.ell_cols is None:
            return float("nan")
        return float(self.ell_cols.shape[0] * self.ell_cols.shape[1]) / max(self.nnz, 1)

    def bsr_fill_ratio(self) -> float:
        """BSR stored values / nnz (dense-tile zero fill)."""
        if self.bsr_blocks is None:
            return float("nan")
        return float(self.bsr_blocks.size) / max(self.nnz, 1)

    def sellcs_fill_ratio(self) -> float:
        """SELL-C-σ stored values / nnz (per-slice width padding only)."""
        if self.sell_cols is None:
            return float("nan")
        stored = sum(c.shape[0] * c.shape[1] for c in self.sell_cols)
        return float(stored) / max(self.nnz, 1)

    @property
    def fill_ratio(self) -> float:
        """Deprecated alias of :meth:`bsr_fill_ratio` (kept one release;
        use the per-layout accessors)."""
        return self.bsr_fill_ratio()
