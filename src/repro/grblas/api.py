"""Unified GraphBLAS execution API: descriptor-driven backend dispatch.

One signature for every SpMM-shaped operation in the repo::

    mxm(A, X, ring, *, mask=None, accum=None, desc=None)   # (n,k) or (n,)
    mxv(A, x, ring, ...)                                   # alias of mxm
    vxm(x, A, ring, ...)                                   # transposed mxm

The ``Descriptor`` replaces the old scatter of ``use_ell`` /
``use_pallas`` flags and parallel entry points (ops.mxm,
kernels.bsr_spmm.bsr_spmm, kernels.plap_edge.plap_apply, dist.dist_mxm):

    backend    "auto" | "coo" | "ell" | "sellcs" | "bsr_pallas" |
               "edge_pallas" | "dist" | "dist_sellcs" | "spgemm" | "dense"
    transpose  operate on A^T (COO index-role swap; vxm flips this)
    interpret  run Pallas kernels in interpreter mode (CPU numerics pin)
    mesh/axis  device mesh + axis name for the "dist"/"dist_sellcs"
               backends (halo-exchange row partition, grblas.dist)

"auto" picks the first capable backend in platform-priority order
(grblas.backends): Pallas kernels first on TPU, SELL-C-σ/ELL/COO first
on CPU ("sellcs" outranks full ELL exactly when the ELL fill ratio
crosses SELLCS_AUTO_THRESHOLD — see DESIGN.md §5), "dist" whenever a
mesh is supplied.  A named backend that cannot execute
the operands raises BackendUnavailableError instead of silently falling
back — layout availability (ELL/BSR built?), ring kind, and multivector
shape are all part of the capability check.

Rings: a plain ``Semiring`` multiplies stored values with gathered
multivector entries; an ``EdgeSemiring`` sees both endpoints (the
p-Laplacian apply); a ``PairEdgeSemiring`` sees two multivectors —
pass ``X=(U, Eta)`` — which is the matrix-free Newton HVP.  The Alg-1
materialized path reuses the same API via
``A.with_vals(what_vals)`` (per-column multivalues on A's pattern).
A SparseMatrix multiplicand makes mxm GraphBLAS' general sparse-sparse
product ("spgemm" backend, reals ring): the result is a new
SparseMatrix — the multilevel subsystem's Galerkin triple product
Pᵀ (W P) is two such calls (DESIGN.md §6).

Write semantics (GraphBLAS C⟨M⟩ ⊙= T, simplified to pure outputs):
``accum=(op, C)`` returns op(C, T); ``mask`` (row mask or full-shape)
keeps masked-in entries and writes the ring's add-identity — or, with
accum, C's old value — elsewhere.  See DESIGN.md §3 for the migration
table from the old entry points.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.grblas import backends as _backends
from repro.grblas.semiring import reals_ring
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

# re-exported for callers that catch dispatch failures
BackendUnavailableError = _backends.BackendUnavailableError


@dataclasses.dataclass(frozen=True)
class Descriptor:
    """How to execute one GraphBLAS operation (not what it computes)."""

    backend: str = "auto"
    transpose: bool = False
    interpret: bool = False
    mesh: Any = None            # device mesh: enables the dist backends
    axis: str = "data"          # mesh axis the rows are sharded over

    def transposed(self) -> "Descriptor":
        return dataclasses.replace(self, transpose=not self.transpose)


DEFAULT_DESCRIPTOR = Descriptor()


def mxm(A, X, ring=reals_ring, *, mask=None, accum=None,
        desc: Optional[Descriptor] = None):
    """Sparse x dense multivector (SpMM) under ``ring``.

    X: (n,) or (n, k) — or a pair (U, Eta) for a PairEdgeSemiring — or a
    SparseMatrix, in which case this is GraphBLAS' general sparse-sparse
    mxm (the "spgemm" backend) and the product comes back as a new
    SparseMatrix (host-side construction; the multilevel Galerkin triple
    product Pᵀ (W P) is two such calls).
    """
    desc = DEFAULT_DESCRIPTOR if desc is None else desc
    from repro.grblas.containers import SparseMatrix
    if isinstance(X, SparseMatrix):             # sparse product (spgemm)
        if mask is not None or accum is not None:
            # reject BEFORE dispatch: the SpGEMM is O(flops) host work
            raise NotImplementedError(
                "mask/accum write semantics are defined for dense outputs; "
                "the sparse-sparse product returns a SparseMatrix")
        be = _backends.select_backend(A, X, ring, desc)
        tr = _obs_trace.ACTIVE
        if not tr.enabled:
            return be.execute(A, X, ring, desc)
        with tr.span("grblas.spgemm", cat="grblas", backend=be.name,
                     n=A.n_rows, nnz_a=int(A.nnz), nnz_b=int(X.nnz)):
            return be.execute(A, X, ring, desc)
    be = _backends.select_backend(A, X, ring, desc)
    tr = _obs_trace.ACTIVE
    if not tr.enabled:
        Y = be.execute(A, X, ring, desc)
    else:
        Y = _execute_observed(be, A, X, ring, desc, tr)
    return _finalize(Y, ring, mask, accum)


def _ring_kind(ring) -> str:
    return (getattr(ring, "kind", None) or getattr(ring, "name", None)
            or type(ring).__name__)


def _x_width(X) -> int:
    if isinstance(X, tuple):
        X = X[0]
    shp = getattr(X, "shape", ())
    return int(shp[1]) if len(shp) > 1 else 1


def _traffic_bytes(A, k: int, itemsize: int = 4) -> int:
    """Minimum-traffic SpMM byte model (the memory-roofline denominator
    used by benchmarks/roofline_report.py's dominant-term accounting):
    stream A once (value + column index per nnz), stream the multivector
    in and the product out once.  Real gathers re-read X rows, so
    achieved GB/s against this model is a lower bound."""
    nnz = int(getattr(A, "nnz", 0))
    n_rows = int(getattr(A, "n_rows", 0))
    n_cols = int(getattr(A, "n_cols", n_rows))
    return nnz * (itemsize + 4) + (n_rows + n_cols) * k * itemsize


def _execute_observed(be, A, X, ring, desc, tr):
    """Dispatch accounting when tracing is on.  Inside a jit trace the
    op runs once per *compile*, so wall-clock spans would time the
    tracer — record the dispatch decision (backend, ring kind) as an
    instant instead.  Eager calls get a fenced span carrying shapes,
    nnz, and the byte model (→ achieved GB/s via
    obs.trace.roofline_summary)."""
    kind = _ring_kind(ring)
    if _obs_trace.under_trace(X[0] if isinstance(X, tuple) else X):
        tr.instant("grblas.dispatch", backend=be.name, ring=kind,
                   traced=True)
        return be.execute(A, X, ring, desc)
    k = _x_width(X)
    nnz = int(getattr(A, "nnz", 0))
    with tr.span("grblas.mxm", cat="grblas", backend=be.name, ring=kind,
                 n=int(getattr(A, "n_rows", 0)), k=k, nnz=nnz) as sp:
        Y = be.execute(A, X, ring, desc)
        sp.fence(Y)
        sp.set(bytes=_traffic_bytes(A, k))
    return Y


def mxv(A, x, ring=reals_ring, *, mask=None, accum=None,
        desc: Optional[Descriptor] = None) -> jnp.ndarray:
    """y = A (*) x under ring — grb::mxv (the k=1 column of mxm)."""
    return mxm(A, x, ring, mask=mask, accum=accum, desc=desc)


def vxm(x, A, ring=reals_ring, *, mask=None, accum=None,
        desc: Optional[Descriptor] = None) -> jnp.ndarray:
    """y = x (*) A under ring — grb::vxm = mxm on A^T (descriptor flip)."""
    desc = DEFAULT_DESCRIPTOR if desc is None else desc
    return mxm(A, x, ring, mask=mask, accum=accum, desc=desc.transposed())


def available_backends(A, X, ring=reals_ring,
                       desc: Optional[Descriptor] = None) -> list:
    """Introspection: which backends could run this op (priority order)."""
    return _backends.available_backends(
        A, X, ring, DEFAULT_DESCRIPTOR if desc is None else desc)


def capable_desc(A, ring=reals_ring, desc: Optional[Descriptor] = None, *,
                 k: int = 1, dtype=jnp.float32) -> Optional[Descriptor]:
    """``desc`` if its backend can run an (n, k) multivector under
    ``ring`` on A; None (= auto) otherwise.  Shape-only probe — lets a
    descriptor pinned for one ring kind (e.g. the edge-semiring hot
    loop) degrade gracefully where another ring is needed (e.g. the
    reals-ring initialization)."""
    if desc is None:
        return None
    probe = jax.ShapeDtypeStruct((A.n_rows, k), dtype)
    if _backends.can_execute(A, probe, ring, desc):
        return desc
    if desc.backend != "auto":
        # a pinned backend degrading to auto is a fallback event: count
        # it so a hot loop silently losing its Pallas path is visible
        _obs_metrics.DEFAULT.counter("grblas_fallback_total",
                                     backend=desc.backend,
                                     ring=_ring_kind(ring)).inc()
        _obs_trace.ACTIVE.instant("grblas.fallback", backend=desc.backend,
                                  ring=_ring_kind(ring))
    return None


def _finalize(Y, ring, mask, accum):
    base = getattr(ring, "base", ring)  # edge rings reduce under base
    if mask is not None:
        mask = jnp.asarray(mask)
        while mask.ndim < Y.ndim:      # row mask against a multivector
            mask = mask[..., None]
    if accum is not None:
        op, C = accum
        T = op(C, Y)
        return jnp.where(mask, T, C) if mask is not None else T
    if mask is not None:
        return jnp.where(mask, Y, jnp.asarray(base.zero, Y.dtype))
    return Y
