"""Backend registry for the unified GraphBLAS execution API.

Every SpMM-shaped operation in the repo flows through one table: a
``Backend`` couples a capability predicate (can this implementation run
this (container layout, ring kind, multivector shape, descriptor)
combination at all?) with an execute function.  ``grblas.api.mxm``
selects from the table — either the backend the Descriptor names
(validated against the predicate, loud error otherwise) or, for
``backend="auto"``, the first capable backend in platform-priority
order.

Registered backends (priority: lower = preferred under "auto"):

  name         layout needed   rings                       cpu  tpu
  dist         ELL / row-part  reals, edge (reals base)      0    0  (needs desc.mesh)
  dist_sellcs  row-part + per- same gates as dist, square    1    1  (needs desc.mesh)
               shard SELL-C-σ  only
  dense        dense tile      reals (incl. (k, n, n) multi-  5    5
                               vals) + plap edge kinds
  edge_pallas  BSR tiles       plap_apply / plap_hvp kinds  61   10
  bsr_pallas   BSR tiles       reals                        60   11
  sellcs       SELL-C-σ        padded-reducer rings (incl.  19   12
                               multivals) + plap edge kinds
  ell          padded ELL      rings with a padded reducer  20   20
                               + plap edge kinds
  coo          COO (always)    any ring, transpose, multivals 30 30
  spgemm       COO (always)    reals, X a SparseMatrix      25   25

"spgemm" is the sparse × *sparse* member of the table — GraphBLAS' mxm
proper: ``api.mxm(A, B)`` with B a SparseMatrix returns the product as
a new SparseMatrix.  It is the only backend claiming a sparse
multiplicand, so its priority never competes; the multilevel subsystem
builds Galerkin coarse operators (Pᵀ W P) through it (DESIGN.md §6).
The result pattern is data-dependent, so execution is host-side (like
every layout build) and traced containers are rejected loudly.

"sellcs" sits above full-ELL in the auto order but *defers* to ELL when
the matrix's ELL fill ratio is under SELLCS_AUTO_THRESHOLD — on low-skew
graphs the two layouts do the same work and ELL has no permute step; on
skewed-degree graphs the sliced layout's per-slice padding is the whole
point (DESIGN.md §5).  Naming backend="sellcs" explicitly always runs.

The Pallas kernels rank first on TPU and last on CPU: their jnp
reference paths exist everywhere (and run under ``desc.interpret``),
but on CPU the gather/segment formulations win.  The SELL-C-σ kernels
are the exception: the TPU compiler refuses them, so "sellcs" runs its
jnp/XLA path on the chip (``sellcs_uses_pallas``).  ``dist`` outranks
everything once a mesh is supplied — the caller asked for sharding.

"dense" runs only on a matrix whose dense layout is built
(``SparseMatrix.with_dense``; no constructor builds it), so "auto" picks
it only where a caller asked for the tile: the serve bucket lane, for
buckets of at most DENSE_MAX_N vertices.

New hardware or layouts are one ``register_backend`` call, not a fifth
parallel entry point (DESIGN.md §3).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.grblas.containers import SELLCS_AUTO_THRESHOLD, SparseMatrix
from repro.grblas.semiring import (
    EdgeSemiring,
    PairEdgeSemiring,
    Semiring,
    fast_paths,
)


class BackendUnavailableError(ValueError):
    """The requested backend cannot execute this operand combination."""


@dataclasses.dataclass(frozen=True)
class Backend:
    name: str
    supports: Callable      # (A, X, ring, desc) -> bool
    execute: Callable       # (A, X, ring, desc) -> jnp.ndarray
    cpu_priority: int       # auto-selection rank off-TPU (lower wins)
    tpu_priority: int       # auto-selection rank on TPU
    # (desc) -> True when the implementation this backend runs under
    # ``desc`` on this platform is a Pallas kernel that bakes the ring's
    # (p, eps) params in as static arguments — callers that jit over a
    # *traced* p (the psc continuation loop) must concretize p first.
    static_ring_params: Callable = lambda desc: False


_REGISTRY: Dict[str, Backend] = {}


def register_backend(name: str, *, cpu_priority: int, tpu_priority: int,
                     supports: Callable,
                     static_ring_params: Callable = lambda desc: False):
    """Decorator: register ``fn`` as the execute hook of backend ``name``."""

    def deco(fn):
        _REGISTRY[name] = Backend(name=name, supports=supports, execute=fn,
                                  cpu_priority=cpu_priority,
                                  tpu_priority=tpu_priority,
                                  static_ring_params=static_ring_params)
        return fn

    return deco


def registered_backends() -> Dict[str, Backend]:
    return dict(_REGISTRY)


def available_backends(A, X, ring, desc) -> list:
    """Names of every backend capable of this operand combination."""
    return [b.name for b in _ordered() if b.supports(A, X, ring, desc)]


def can_execute(A, X, ring, desc) -> bool:
    """Would select_backend succeed?  (Shape-only probe; X may be a
    jax.ShapeDtypeStruct.)  Callers use this to fall back gracefully when
    a descriptor pinned for one ring kind cannot serve another."""
    if desc.backend == "auto":
        return any(b.supports(A, X, ring, desc) for b in _ordered())
    be = _REGISTRY.get(desc.backend)
    return be is not None and be.supports(A, X, ring, desc)


def _ordered():
    on_tpu = jax.default_backend() == "tpu"
    key = (lambda b: b.tpu_priority) if on_tpu else (lambda b: b.cpu_priority)
    return sorted(_REGISTRY.values(), key=key)


def select_backend(A, X, ring, desc) -> Backend:
    """Resolve a Descriptor to one executable backend (or raise loudly)."""
    if desc.backend != "auto":
        be = _REGISTRY.get(desc.backend)
        if be is None:
            raise BackendUnavailableError(
                f"unknown backend {desc.backend!r}; registered: "
                f"{sorted(_REGISTRY)}")
        if not be.supports(A, X, ring, desc):
            raise BackendUnavailableError(
                f"backend {desc.backend!r} cannot execute ring "
                f"{getattr(ring, 'name', ring)!r} on this container "
                f"(layout availability / ring kind / shape mismatch); "
                f"capable backends: {available_backends(A, X, ring, desc)}")
        return be
    for be in _ordered():
        if be.supports(A, X, ring, desc):
            return be
    raise BackendUnavailableError(
        f"no registered backend supports ring "
        f"{getattr(ring, 'name', ring)!r} with this container/descriptor")


# ------------------------------------------------------------------ helpers

def _is_pair(X) -> bool:
    return isinstance(X, (tuple, list))


def _is_sparse(X) -> bool:
    return isinstance(X, SparseMatrix)


def _broadcast_vals(vals, ndim):
    """Lift (nnz,) values to (nnz, 1) against an (n, k) multivector;
    (nnz, k) multivalues (containers.with_vals) pass through."""
    if ndim == 2 and vals.ndim == 1:
        return vals[:, None]
    return vals


def _square(A) -> bool:
    return A.n_rows == A.n_cols


# --------------------------------------------------------------- coo backend

def _coo_supports(A, X, ring, desc):
    if not isinstance(A, SparseMatrix) or _is_sparse(X):
        return False
    if isinstance(ring, PairEdgeSemiring):
        return (_is_pair(X) and len(X) == 2 and _square(A)
                and _vals_match(A, X[0]))
    if isinstance(ring, EdgeSemiring):
        return not _is_pair(X) and _square(A) and _vals_match(A, X)
    return (isinstance(ring, Semiring) and not _is_pair(X)
            and _vals_match(A, X))


def _vals_match(A, X) -> bool:
    """(nnz, k) multivalues (with_vals) only broadcast against an (n, k)
    multivector — reject 1-D inputs at dispatch time, not mid-broadcast.
    A dense-multivalue matrix (with_vals on the dense layout) has no COO
    values at all."""
    if A.vals is None:
        return False
    return A.vals.ndim == 1 or getattr(X, "ndim", 0) == 2


@register_backend("coo", cpu_priority=30, tpu_priority=30,
                  supports=_coo_supports)
def _coo_execute(A, X, ring, desc):
    """Segment reduction over nnz — the reference path for every ring.

    Y[i] = add_j mul(A[i,j], X[j]); transpose swaps the gather/scatter
    index roles (rows <-> cols), which is how vxm rides the same code.
    """
    out_idx, src_idx = (A.cols, A.rows) if desc.transpose else (A.rows, A.cols)
    n_out = A.n_cols if desc.transpose else A.n_rows
    if isinstance(ring, PairEdgeSemiring):
        U, E = X
        vals = _broadcast_vals(A.vals, U.ndim)
        contrib = ring.edge_mul(vals, U[src_idx], U[out_idx],
                                E[src_idx], E[out_idx])
        return ring.base.segment_reduce(contrib, out_idx, n_out)
    vals = _broadcast_vals(A.vals, X.ndim)
    if isinstance(ring, EdgeSemiring):
        contrib = ring.edge_mul(vals, X[src_idx], X[out_idx])
        return ring.base.segment_reduce(contrib, out_idx, n_out)
    contrib = ring.mul(vals, X[src_idx])
    return ring.segment_reduce(contrib, out_idx, n_out)


# --------------------------------------------------------------- ell backend

def _ell_supports(A, X, ring, desc):
    """Padded-ELL is only sound for rings whose pad entries (col=row,
    val=0) contribute the add-identity — the rings with a registered
    ``padded`` fast path (semiring.register_ring_fast_paths), and the
    plap edge kinds, whose multiply annihilates on w=0 (the SELL-C-σ
    gate below)."""
    if not (isinstance(A, SparseMatrix) and A.ell_cols is not None
            and A.vals.ndim == 1 and not _is_sparse(X)
            and not desc.transpose):
        return False
    if isinstance(ring, PairEdgeSemiring):
        return (ring.kind == "plap_hvp" and _square(A) and _is_pair(X)
                and len(X) == 2 and getattr(X[0], "ndim", 0) == 2
                and X[0].shape == X[1].shape)
    if isinstance(ring, EdgeSemiring):
        return (ring.kind == "plap_apply" and _square(A)
                and getattr(X, "ndim", 0) == 2)
    return (isinstance(ring, Semiring) and not _is_pair(X)
            and fast_paths(ring).padded is not None)


@register_backend("ell", cpu_priority=20, tpu_priority=20,
                  supports=_ell_supports)
def _ell_execute(A, X, ring, desc):
    """Padded-ELL: gather (n, max_nnz[, k]) then fold along the pad axis.
    The reals ring and the plap edge kinds fold slot by slot
    (``sellcs_spmm.ref``: ELL is one SELL-C-σ run in row order)."""
    from repro.kernels.sellcs_spmm.ref import (sellcs_plap_apply_ref,
                                               sellcs_plap_hvp_ref, slot_sum)

    if isinstance(ring, PairEdgeSemiring):
        U, E = X
        return sellcs_plap_hvp_ref(A.ell_cols, A.ell_vals, U, E, 0,
                                   *ring.params)
    if isinstance(ring, EdgeSemiring):
        return sellcs_plap_apply_ref(A.ell_cols, A.ell_vals, X, 0,
                                     *ring.params)
    if ring.name == "reals_+x":
        if X.ndim == 1:
            return slot_sum(A.ell_cols, A.ell_vals, lambda c, v: v * X[c])
        return slot_sum(A.ell_cols, A.ell_vals,
                        lambda c, v: v[:, None] * X[c])
    gathered = X[A.ell_cols]                      # (n, m[, k])
    vals = A.ell_vals if X.ndim == 1 else A.ell_vals[..., None]
    contrib = ring.mul(vals, gathered)
    return fast_paths(ring).padded(contrib)


# ------------------------------------------------------------ sellcs backend

def _auto_defers_to_ell(A, X, ring, desc) -> bool:
    """Under "auto", keep low-fill matrices on the plain full-ELL path:
    sellcs only outranks ELL once ELL's padding blowup crosses
    SELLCS_AUTO_THRESHOLD — the skewed-degree regime the sliced layout
    exists for.  A named backend="sellcs" always runs."""
    return (desc.backend == "auto"
            and _ell_supports(A, X, ring, desc)
            and A.ell_fill_ratio() <= SELLCS_AUTO_THRESHOLD)


def _sellcs_supports(A, X, ring, desc):
    if not (isinstance(A, SparseMatrix) and A.sell_cols is not None
            and not desc.transpose):
        return False
    if isinstance(ring, PairEdgeSemiring):
        return (ring.kind == "plap_hvp" and A.vals.ndim == 1 and _square(A)
                and _is_pair(X) and len(X) == 2
                and getattr(X[0], "ndim", 0) == 2
                and X[0].shape == X[1].shape)
    if isinstance(ring, EdgeSemiring):
        # pad entries are (col=self, val=0): sound exactly for edge kinds
        # whose multiply annihilates on w=0 — the known plap kind, not
        # generic closures (same reasoning as the dist backend gate).
        return (ring.kind == "plap_apply" and A.vals.ndim == 1 and _square(A)
                and not _is_pair(X) and getattr(X, "ndim", 0) in (1, 2))
    if not (isinstance(ring, Semiring) and not _is_pair(X)
            and getattr(X, "ndim", 0) in (1, 2)
            and fast_paths(ring).padded is not None
            and _vals_match(A, X)):
        return False
    return not _auto_defers_to_ell(A, X, ring, desc)


def sellcs_uses_pallas(interpret: bool) -> bool:
    """The one rule that picks the SELL-C-σ implementation: the Pallas
    kernels run only in interpreter mode (the CPU numerics pin); on
    every platform, the TPU included, the backend runs the jnp/XLA path.
    The TPU compiler refuses the kernels: Mosaic cannot lower their
    ``jnp.take`` sublane gather ("Shape mismatch in input, indices and
    output"), and their whole-multivector VMEM block outgrows the chip's
    fast memory at real n."""
    return interpret


def sellcs_run(A, X, ring, interpret: bool = False):
    """SELL-C-σ SpMM (shared by the backend and the benchmarks).
    Permute the multivector once (σ-sort order), run one gather+fold
    per width run — Pallas kernel (``sellcs_uses_pallas``) or the jnp
    reference — and un-permute the output.

    ``X`` is a multivector for plain/edge rings, a (U, Eta) pair for the
    "plap_hvp" kind.  (nnz, k) multivalues (with_vals) take the jnp path
    — the Alg-1 materialized W-hat is CPU-bound host-side anyway."""
    from repro.kernels.sellcs_spmm import (
        sellcs_plap_apply_pallas, sellcs_plap_apply_ref,
        sellcs_plap_hvp_pallas, sellcs_plap_hvp_ref,
        sellcs_spmm_pallas, sellcs_spmm_ref)

    use_pallas = sellcs_uses_pallas(interpret)
    C = A.sell_c
    pair = _is_pair(X)
    one_d = False
    if pair:
        U, E = X
        Up, Ep = U[A.sell_perm], E[A.sell_perm]
    else:
        one_d = X.ndim == 1
        Xp = (X[:, None] if one_d else X)[A.sell_perm]

    outs = []
    for r, cols in enumerate(A.sell_cols):
        vals = A.sell_vals[r]
        row0 = A.sell_row0[r]
        if isinstance(ring, PairEdgeSemiring):
            p, eps = ring.params
            if use_pallas:
                Yr = sellcs_plap_hvp_pallas(cols, vals, Up, Ep, C,
                                            slice0=row0 // C, p=float(p),
                                            eps=float(eps),
                                            interpret=interpret)
            else:
                Yr = sellcs_plap_hvp_ref(cols, vals, Up, Ep, row0, p, eps)
        elif isinstance(ring, EdgeSemiring):
            p, eps = ring.params
            if use_pallas:
                Yr = sellcs_plap_apply_pallas(cols, vals, Xp, C,
                                              slice0=row0 // C, p=float(p),
                                              eps=float(eps),
                                              interpret=interpret)
            else:
                Yr = sellcs_plap_apply_ref(cols, vals, Xp, row0, p, eps)
        elif (use_pallas and vals.ndim == 2 and ring.name == "reals_+x"):
            Yr = sellcs_spmm_pallas(cols, vals, Xp, C, slice0=row0 // C,
                                    interpret=interpret)
        elif ring.name == "reals_+x":
            Yr = sellcs_spmm_ref(cols, vals, Xp)
        else:
            vb = vals[..., None] if vals.ndim == 2 else vals
            Yr = fast_paths(ring).padded(ring.mul(vb, Xp[cols]))
        outs.append(Yr)

    Y = jnp.concatenate(outs, axis=0)[A.sell_inv]      # un-permute, drop pads
    return Y[:, 0] if (one_d and not pair) else Y


@register_backend("sellcs", cpu_priority=19, tpu_priority=12,
                  supports=_sellcs_supports,
                  static_ring_params=lambda desc: sellcs_uses_pallas(
                      desc.interpret))
def _sellcs_execute(A, X, ring, desc):
    """Sliced-ELLPACK gather + ring fold over per-width runs; vectorized
    jnp/XLA everywhere, Pallas kernel under ``desc.interpret``.
    The σ permutation is applied to the multivector on the way in and
    inverted on the way out — callers never observe it."""
    return sellcs_run(A, X, ring, interpret=desc.interpret)


# ------------------------------------------------------------- dense backend

def _dense_supports(A, X, ring, desc):
    """The dense tile's absent entries are exact zeros, so, like a pad
    slot, each contributes mul(0, ...): sound for the reals ring and for
    the plap edge kinds, whose multiply annihilates on w = 0.  (k, n, n)
    multivalues serve the reals ring against an (n, k) multivector."""
    if not (isinstance(A, SparseMatrix) and A.dense is not None
            and not _is_sparse(X) and not desc.transpose):
        return False
    multi = A.dense.ndim == 3
    if isinstance(ring, PairEdgeSemiring):
        return (ring.kind == "plap_hvp" and not multi and _square(A)
                and _is_pair(X) and len(X) == 2
                and getattr(X[0], "ndim", 0) == 2
                and X[0].shape == X[1].shape)
    if isinstance(ring, EdgeSemiring):
        return (ring.kind == "plap_apply" and not multi and _square(A)
                and getattr(X, "ndim", 0) == 2)
    if not (isinstance(ring, Semiring) and ring.name == "reals_+x"
            and not _is_pair(X)):
        return False
    if multi:
        return (getattr(X, "ndim", 0) == 2
                and X.shape[1] == A.dense.shape[0])
    return getattr(X, "ndim", 0) in (1, 2)


@register_backend("dense", cpu_priority=5, tpu_priority=5,
                  supports=_dense_supports)
def _dense_execute(A, X, ring, desc):
    """Every (i, j) pair of the tile at once, with no gather or scatter:
    column l of the product is the row sum of a (n, n) slab, laid out
    (k, n, n) so the vertex axis, not k, is minor (a minor k of 4 would
    pad to the TPU's 128 lanes).  Products are elementwise multiplies
    summed in the operands' precision, never a default-precision (one
    bfloat16 pass) MXU matmul; the fold is the reals ring's registered
    dense reducer."""
    fold = fast_paths(getattr(ring, "base", ring)).dense
    M = A.dense
    if isinstance(ring, PairEdgeSemiring):
        Ut, Et = X[0].T, X[1].T
        contrib = ring.edge_mul(M, Ut[:, None, :], Ut[:, :, None],
                                Et[:, None, :], Et[:, :, None])
    elif isinstance(ring, EdgeSemiring):
        Xt = X.T
        contrib = ring.edge_mul(M, Xt[:, None, :], Xt[:, :, None])
    elif X.ndim == 1:
        return fold(M * X[None, :], -1)
    else:
        contrib = (M if M.ndim == 3 else M[None]) * X.T[:, None, :]
    return fold(contrib, -1).T


# -------------------------------------------------------- bsr_pallas backend

def _pad_rows(n_pad_rows, *Xs):
    pad = n_pad_rows - Xs[0].shape[0]
    return [jnp.pad(X, ((0, pad), (0, 0))) if pad else X for X in Xs]


def _bsr_supports(A, X, ring, desc):
    return (isinstance(A, SparseMatrix)
            and A.bsr_blocks is not None
            and A.vals.ndim == 1
            and isinstance(ring, Semiring)
            and not isinstance(ring, (EdgeSemiring, PairEdgeSemiring))
            and ring.name == "reals_+x"
            and not _is_pair(X)
            and getattr(X, "ndim", 0) == 2
            and not desc.transpose)


def bsr_spmm_run(A, X, interpret: bool = False,
                 use_pallas: bool | None = None):
    """BSR SpMM with explicit path control (shared by the backend and the
    deprecated kernel shims).  ``use_pallas=None`` resolves to the
    platform default (Pallas on TPU or under interpret, jnp ref on CPU)."""
    from repro.kernels.bsr_spmm.bsr_spmm import bsr_spmm_pallas
    from repro.kernels.bsr_spmm.ref import bsr_spmm_ref

    bs = A.block_size
    n_rb = len(A.bsr_indptr) - 1
    (Xp,) = _pad_rows(n_rb * bs, X)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" or interpret
    if use_pallas or interpret:
        Y = bsr_spmm_pallas(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids, Xp,
                            n_row_blocks=n_rb, block_size=bs,
                            interpret=interpret)
    else:
        Y = bsr_spmm_ref(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids, Xp,
                         n_row_blocks=n_rb, block_size=bs)
    return Y[: A.n_rows]


@register_backend("bsr_pallas", cpu_priority=60, tpu_priority=11,
                  supports=_bsr_supports)
def _bsr_execute(A, X, ring, desc):
    """128x128 dense-tile SpMM on the MXU (Pallas); jnp blocked ref on CPU.

    ``desc.interpret`` forces the Pallas kernel in interpreter mode —
    the numerics-pinning path used by the backend-equivalence suite.
    """
    return bsr_spmm_run(A, X, interpret=desc.interpret)


# ------------------------------------------------------- edge_pallas backend

def _edge_pallas_supports(A, X, ring, desc):
    if not (isinstance(A, SparseMatrix) and A.bsr_blocks is not None
            and A.vals.ndim == 1 and not desc.transpose and _square(A)):
        return False
    if isinstance(ring, EdgeSemiring) and ring.kind == "plap_apply":
        return not _is_pair(X) and getattr(X, "ndim", 0) == 2
    if isinstance(ring, PairEdgeSemiring) and ring.kind == "plap_hvp":
        return (_is_pair(X) and len(X) == 2 and X[0].ndim == 2
                and X[0].shape == X[1].shape)
    return False


def edge_pallas_run(A, X, ring, interpret: bool = False,
                    use_pallas: bool | None = None):
    """Fused p-Laplacian kernels with explicit path control (shared by
    the backend and the deprecated kernel shims).  ``X`` is a single
    multivector for a "plap_apply" ring, a (U, Eta) pair for
    "plap_hvp"."""
    from repro.kernels.plap_edge.plap_edge import (plap_apply_pallas,
                                                   plap_hvp_pallas)
    from repro.kernels.plap_edge.ref import (plap_apply_ref,
                                             plap_hvp_edge_ref)

    p, eps = ring.params
    bs = A.block_size
    n_rb = len(A.bsr_indptr) - 1
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" or interpret
    if not _is_pair(X):
        (Xp,) = _pad_rows(n_rb * bs, X)
        if use_pallas or interpret:
            Y = plap_apply_pallas(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids,
                                  Xp, n_row_blocks=n_rb, block_size=bs,
                                  p=p, eps=eps, interpret=interpret)
        else:
            Y = plap_apply_ref(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids,
                               Xp, n_rb, bs, p, eps)
    else:
        U, E = X
        Up, Ep = _pad_rows(n_rb * bs, U, E)
        if use_pallas or interpret:
            Y = plap_hvp_pallas(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids,
                                Up, Ep, n_row_blocks=n_rb, block_size=bs,
                                p=p, eps=eps, interpret=interpret)
        else:
            Y = plap_hvp_edge_ref(A.bsr_blocks, A.bsr_indices, A.bsr_row_ids,
                                  Up, Ep, n_rb, bs, p, eps)
    return Y[: A.n_rows]


def _edge_pallas_bakes(desc) -> bool:
    return desc.interpret or jax.default_backend() == "tpu"


@register_backend("edge_pallas", cpu_priority=61, tpu_priority=10,
                  supports=_edge_pallas_supports,
                  static_ring_params=_edge_pallas_bakes)
def _edge_pallas_execute(A, X, ring, desc):
    """Fused p-Laplacian edge-semiring kernels over BSR tiles.

    Claims rings by *kind* ("plap_apply" / "plap_hvp", with (p, eps) in
    ring.params) rather than tracing the edge closure — the kernel IS
    the semiring specialization (DESIGN.md §2, adaptation 4).
    """
    return edge_pallas_run(A, X, ring, interpret=desc.interpret)


# -------------------------------------------------------------- dist backend

def _dist_supports(A, X, ring, desc):
    if desc.mesh is None or desc.transpose or _is_pair(X) or _is_sparse(X):
        return False
    from repro.grblas.dist import RowPartitionedMatrix

    if isinstance(A, RowPartitionedMatrix):
        ok_layout = True
    elif isinstance(A, SparseMatrix):
        ok_layout = A.ell_cols is not None and A.vals.ndim == 1
    else:
        return False
    if isinstance(ring, EdgeSemiring):
        # the dist path folds the padded-ELL axis with an unconditional
        # sum, so pad entries (val=0) must be annihilated by the edge
        # multiply: guaranteed for the known plap kinds
        # (edge_mul(0, ...) == 0), NOT for generic edge closures — those
        # must run the coo backend.  Square-gated like every other
        # edge-ring backend: the shard body reads x_i from the shard's
        # own row block, which only aligns when the row and column
        # spaces (and their paddings) coincide.
        return (ok_layout and _square(A) and ring.base.name == "reals_+x"
                and ring.kind == "plap_apply")
    return (ok_layout and isinstance(ring, Semiring)
            and ring.name == "reals_+x")


def _dist_partition_for(A, desc, *, sellcs: bool):
    """Resolve (and memoize) the row partition of a plain SparseMatrix.

    The memo lives on the container instance and is keyed on
    (shard count, identity of the vals buffer, layout flavour): a caller
    that swaps the value buffers on the same pattern (the Alg-1 Ŵ
    update idiom) must not be served a partition carved from the stale
    ``ell_vals``.  Not pytree state — a matrix that crosses a
    jit/transform boundary re-partitions on the next call — and not
    buildable from traced arrays at all: close over the matrix, or
    pre-build a RowPartitionedMatrix outside the transform.
    """
    from repro.grblas.dist import make_row_partition

    if isinstance(A.ell_cols, jax.core.Tracer):
        raise BackendUnavailableError(
            "dist backend cannot row-partition a traced SparseMatrix "
            "(partitioning is host-side numpy): close over the matrix "
            "instead of passing it as a jit argument, or pre-build a "
            "RowPartitionedMatrix with make_row_partition outside the "
            "transform")
    n_shards = int(desc.mesh.shape[desc.axis])
    cache = getattr(A, "_dist_partitions", None)
    if cache is None:
        cache = {}
        A._dist_partitions = cache  # host-side memo, not pytree state
    key = (n_shards, id(A.ell_vals), sellcs)
    if key not in cache:
        # a matrix has exactly one live ell_vals buffer, so every entry
        # pinning a different one is superseded — evict them all (the
        # Alg-1 Ŵ swap idiom would otherwise accumulate one full
        # partition per Newton step); entries for other shard counts /
        # layouts of the CURRENT buffer stay live
        for stale in [k for k, v in cache.items()
                      if v[0] is not A.ell_vals]:
            del cache[stale]
        # the entry pins the keyed buffer so its id cannot be recycled
        # by the allocator while the memo is alive
        cache[key] = (A.ell_vals,
                      make_row_partition(A, n_shards, sellcs=sellcs))
    return cache[key][1]


@register_backend("dist", cpu_priority=0, tpu_priority=0,
                  supports=_dist_supports)
def _dist_execute(A, X, ring, desc):
    """Row-block sharded SpMM over desc.mesh: shard_map + precomputed
    halo exchange (all_to_all of only the remote rows each shard's
    columns touch), falling back to the full all-gather when the plan
    found the halo denser than HALO_FALLBACK_FRAC of the shard size.

    Accepts a pre-built RowPartitionedMatrix or a plain SparseMatrix —
    see _dist_partition_for for the partition memo contract.
    """
    from repro.grblas.dist import RowPartitionedMatrix, shard_mxm

    if isinstance(A, RowPartitionedMatrix):
        Ap = A
    else:
        Ap = _dist_partition_for(A, desc, sellcs=False)
    return shard_mxm(Ap, X, desc.mesh, axis=desc.axis, ring=ring)


def _dist_sellcs_supports(A, X, ring, desc):
    """Same ring/pad-soundness gates as "dist" (the shard fold sums a
    padded axis unconditionally), plus: square only — the per-shard
    σ-sort shares the halo plan's one-row-space remap — and, for a
    pre-built partition, the DistSellCS slicing must be present."""
    if not _dist_supports(A, X, ring, desc):
        return False
    from repro.grblas.dist import RowPartitionedMatrix

    if isinstance(A, RowPartitionedMatrix):
        return A.sell is not None
    return _square(A)


@register_backend("dist_sellcs", cpu_priority=1, tpu_priority=1,
                  supports=_dist_sellcs_supports)
def _dist_sellcs_execute(A, X, ring, desc):
    """Sharded SELL-C-σ SpMM: the halo-exchange schedule of "dist" with
    each shard running σ-sorted, per-slice-padded width runs over its
    own row block (slice widths maxed across shards so the shard_map
    body stays SPMD-uniform) — the skewed-degree layout advantage under
    a mesh.  A plain SparseMatrix is partitioned with sellcs=True and
    memoized separately from the full-ELL partition.
    """
    from repro.grblas.dist import RowPartitionedMatrix, shard_mxm

    if isinstance(A, RowPartitionedMatrix):
        Ap = A
    else:
        Ap = _dist_partition_for(A, desc, sellcs=True)
    return shard_mxm(Ap, X, desc.mesh, axis=desc.axis, ring=ring,
                     layout="sellcs")


# ------------------------------------------------------------ spgemm backend

def _spgemm_supports(A, X, ring, desc):
    """Sparse × sparse under the reals (+,×) ring.  The output pattern is
    data-dependent, so this is a host-side construction op (like every
    layout build), not a jittable kernel — traced containers are caught
    in execute with an actionable error rather than silently excluded
    here, so a named backend="spgemm" fails loudly."""
    return (isinstance(A, SparseMatrix) and _is_sparse(X)
            and isinstance(ring, Semiring)
            and not isinstance(ring, (EdgeSemiring, PairEdgeSemiring))
            and ring.name == "reals_+x")


@register_backend("spgemm", cpu_priority=25, tpu_priority=25,
                  supports=_spgemm_supports)
def _spgemm_execute(A, B, ring, desc):
    """C = A (*) B (or Aᵀ B under desc.transpose), both sparse, under the
    reals ring — GraphBLAS' general mxm.  Row-expansion SpGEMM: every
    stored A entry (i, j) fans out over B's row j, then duplicate (i, b)
    pairs fold under the add monoid.  O(flops) host work; for the
    partition-of-unity prolongators of the multilevel subsystem (one
    entry per row/column) it degenerates to a linear-time relabel+fold.
    The product comes back as a bare-COO SparseMatrix — derived layouts
    are a consumer decision (a chained triple product should not pay
    ELL/SELL builds on its intermediate): callers that keep the result
    rebuild layouts with ``from_coo`` (multilevel.coarsen does)."""
    import numpy as np

    for arr in (A.rows, A.cols, A.vals, B.rows, B.cols, B.vals):
        if isinstance(arr, jax.core.Tracer):
            raise BackendUnavailableError(
                "spgemm cannot multiply traced SparseMatrix operands (the "
                "output pattern is data-dependent): run it outside jit — "
                "hierarchy construction is host-side setup, not hot-loop "
                "work")
    a_rows = np.asarray(A.cols if desc.transpose else A.rows, np.int64)
    a_cols = np.asarray(A.rows if desc.transpose else A.cols, np.int64)
    a_vals = np.asarray(A.vals)
    n_out = A.n_cols if desc.transpose else A.n_rows
    b_rows = np.asarray(B.rows, np.int64)
    b_cols = np.asarray(B.cols, np.int64)
    b_vals = np.asarray(B.vals)

    # CSR-style row pointers of B (from_coo guarantees row-sorted COO)
    counts = np.bincount(b_rows, minlength=B.n_rows)
    indptr = np.concatenate([[0], np.cumsum(counts)])
    reps = counts[a_cols]                       # fan-out of each A entry
    total = int(reps.sum())
    out_rows = np.repeat(a_rows, reps)
    av = np.repeat(a_vals, reps)
    offs = np.arange(total, dtype=np.int64) - np.repeat(
        np.cumsum(reps) - reps, reps)
    bpos = np.repeat(indptr[a_cols], reps) + offs
    out_cols = b_cols[bpos]
    prod = av * b_vals[bpos]

    # fold duplicates under the add monoid (+)
    key = out_rows * B.n_cols + out_cols
    uniq, inv = np.unique(key, return_inverse=True)
    vals = np.bincount(inv, weights=prod)
    dtype = A.vals.dtype
    return SparseMatrix.from_coo(uniq // B.n_cols, uniq % B.n_cols, vals,
                                 (n_out, B.n_cols), dtype=dtype,
                                 build_ell=False, build_sellcs=False)
