"""Distributed SpMM: the shard_map analogue of the C++ runtime's
auto-parallelised vxm — now with halo (remote-row) exchange.

Row-block 1-D partition: device d owns rows [d*B, (d+1)*B).  The old
path all-gathered the entire multivector per call, so wire bytes grew
as O(n·k·S) regardless of the partition quality; the paper's
strong-scaling claim rests on communication proportional to the *cut*.
``make_row_partition`` therefore precomputes, per shard, the set of
remote rows its ELL columns actually touch (host-side, from the
pattern), stores a static send plan, and ``shard_mxm`` replaces the
``all_gather`` with one ``all_to_all`` of only those halo rows.  When
the padded halo is so large that it would move more data than the
gather (dense cuts, bad placement), the plan falls back to the gather
at build time — the threshold is ``HALO_FALLBACK_FRAC``.

Graph-aware placement: ``make_row_partition`` can take a clustering
assignment (from repro.core.psc — the paper's own algorithm) to permute
rows so that same-cluster rows land on the same device; the halo then
contains only *cut* rows, which is the framework-level integration of
the paper's balanced-cut objective applied to the machine (DESIGN.md
§4).  Unlike the pre-halo code, the permutation is internal: X arrives
and Y returns in the ORIGINAL row space (the layout permutes on the way
in and un-permutes on the way out, like the SELL-C-σ layout does).

``sellcs=True`` additionally shards the SELL-C-σ layout per row block:
each shard σ-sorts its own rows, slices them into C-row blocks, and
pads per slice — widths are maxed across shards so the shard_map body
stays SPMD-uniform.  That keeps the skewed-degree regime's layout
advantage under a mesh (the "dist_sellcs" backend).

``init_distributed`` / ``device_mesh`` are the multi-process launch
path: a guarded ``jax.distributed.initialize`` (no-op single-process)
plus a 1-D mesh over the global device set.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.grblas.containers import SparseMatrix
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.grblas.semiring import (Semiring, EdgeSemiring, fast_paths,
                                   reals_ring)

# Build-time halo/gather decision: take the halo path only while the
# padded per-pair halo width H stays under this fraction of the shard
# row count R.  Per shard the halo moves (S-1)·H rows vs the gather's
# (S-1)·R, so the fraction is exactly the wire-byte ratio of the two.
HALO_FALLBACK_FRAC = 0.5


@dataclasses.dataclass
class DistSellCS:
    """Per-shard SELL-C-σ slicing of a row partition (SPMD-uniform).

    Every shard σ-sorts its own R rows by degree, slices them into
    C-row blocks, and pads each slice to the *cross-shard* max width of
    that slice index — so all shards share one static set of width runs
    and the shard_map body stays uniform.  Column ids index the shard's
    extended-local vector (locals then halo slots; global x under a
    gather-mode plan), ``own`` holds each packed row's local id (the
    x_i gather for edge kinds), and ``inv`` un-sorts the packed output
    back to local row order.
    """

    run_cols: Tuple[jnp.ndarray, ...]   # per run (S, rows_r, w_r) int32
    run_vals: Tuple[jnp.ndarray, ...]   # per run (S, rows_r, w_r)
    run_own: Tuple[jnp.ndarray, ...]    # per run (S, rows_r) int32 local row
    inv: jnp.ndarray                    # (S, R) int32 local row -> packed pos
    sell_c: int
    n_pad_local: int                    # R rounded up to a multiple of C


class RowPartitionedMatrix:
    """ELL layout split into (n_shards, rows_per_shard, max_nnz) + a
    static halo-exchange plan (see module docstring).

    ``mode`` is decided at build time: "halo" stores column ids remapped
    into each shard's extended-local space [0, R + S·H) plus the send
    plan; "gather" (the fallback) stores global column ids and runs the
    legacy all-gather schedule.
    """

    def __init__(self, ell_cols, ell_vals, n_rows, n_cols, n_shards,
                 perm=None, inv_perm=None, mode="gather", halo_width=0,
                 send_idx=None, halo_rows_true=0, sell=None):
        self.ell_cols = ell_cols    # (S, R, M) int32; extended-local ids in
        self.ell_vals = ell_vals    # (S, R, M)    halo mode, global in gather
        self.n_rows = n_rows        # original (unpadded) row count
        self.n_cols = n_cols
        self.n_shards = n_shards
        self.perm = perm            # (n,) position -> original row, or None
        self.inv_perm = inv_perm    # (n,) original row -> position, or None
        self.mode = mode            # "halo" | "gather"
        self.halo_width = halo_width        # H: padded rows per (dst, src) pair
        self.send_idx = send_idx            # (S, S*H) int32 local rows to ship
        self.halo_rows_true = halo_rows_true  # sum of true (unpadded) needs
        self.sell = sell            # DistSellCS or None

    @property
    def rows_per_shard(self) -> int:
        return self.ell_cols.shape[1]

    def wire_bytes(self, k: int = 1, itemsize: int = 4) -> dict:
        """Analytic per-call communication volume of each schedule.

        The all_to_all self-chunk and the gather's own shard never cross
        the wire, so both counts use (S-1) partners per shard.  These are
        exact for the static plans (the collectives move precisely the
        planned rows) — the quantity BENCH_dist.json records.  On a plan
        that auto-fell back to the gather schedule, "halo" reports what
        the rejected halo WOULD have moved (the basis of the fallback
        decision); on a forced mode="gather" plan no halo was computed
        and "halo" is 0.
        """
        S, R = self.n_shards, self.rows_per_shard
        return {
            "halo": S * (S - 1) * self.halo_width * k * itemsize,
            "gather": S * (S - 1) * R * k * itemsize,
            "halo_rows_true": int(self.halo_rows_true),
            "halo_width": int(self.halo_width),
        }


def _halo_plan(ell_cols: np.ndarray, n_shards: int, R: int):
    """Remote-row needs of each shard, from the partitioned ELL pattern.

    Returns (needed, H, total_true): ``needed[d][s]`` is the sorted array
    of global rows shard d reads from shard s (empty for s == d), H the
    max list length (the static padded width), total_true the sum of all
    list lengths (the unpadded halo volume, for accounting).
    """
    needed = []
    H = 0
    total = 0
    for d in range(n_shards):
        cols_d = np.unique(ell_cols[d])
        owner = cols_d // R
        per_src = []
        for s in range(n_shards):
            rows_s = cols_d[owner == s] if s != d else np.empty(0, np.int64)
            per_src.append(rows_s.astype(np.int64))
            H = max(H, len(rows_s))
            total += len(rows_s)
        needed.append(per_src)
    return needed, H, total


def _remap_local(ell_cols: np.ndarray, needed, n_shards: int, R: int,
                 H: int) -> np.ndarray:
    """Rewrite global column ids into each shard's extended-local space:
    local rows keep [0, R); the h-th row needed from shard s lands at
    R + s*H + h — exactly where the all_to_all deposits it."""
    out = np.empty_like(ell_cols)
    for d in range(n_shards):
        c = ell_cols[d].astype(np.int64)
        o = c // R
        loc = c - d * R
        for s in range(n_shards):
            if s == d:
                continue
            m = o == s
            if not m.any():
                continue
            pos = np.searchsorted(needed[d][s], c[m])
            loc[m] = R + s * H + pos
        out[d] = loc.astype(np.int32)
    return out


def _send_plan(needed, n_shards: int, R: int, H: int) -> np.ndarray:
    """(S, S*H) send plan: row block d of sender s lists the *local* row
    ids s ships to d (pad slots resend row 0 — recipients never read
    them, their remap stops at the true list length)."""
    send = np.zeros((n_shards, n_shards * H), np.int32)
    for d in range(n_shards):
        for s in range(n_shards):
            rows = needed[d][s]
            send[s, d * H:d * H + len(rows)] = rows - s * R
    return send


def _build_dist_sellcs(ell_cols_x: np.ndarray, ell_vals: np.ndarray,
                       counts: np.ndarray, C: int) -> DistSellCS:
    """Per-shard SELL-C slicing of the partitioned ELL arrays.

    ``ell_cols_x`` is already in the execution index space (extended-
    local for halo plans, global for gather plans); ``counts`` holds the
    true per-row entry count (S, R) so pads are dropped, not repacked.
    Widths are maxed across shards per slice index, keeping every run
    shape identical on all shards (the SPMD requirement).
    """
    S, R, M = ell_cols_x.shape
    C = max(int(C), 1)
    n_slices = -(-R // C)
    R_pad = n_slices * C

    orders = np.empty((S, R_pad), np.int64)
    widths = np.empty((S, n_slices), np.int64)
    for d in range(S):
        cnt = np.full(R_pad, -1, np.int64)
        cnt[:R] = counts[d]
        order = np.argsort(-cnt, kind="stable")    # σ = R: whole-shard sort
        orders[d] = order
        widths[d] = np.maximum(
            cnt[order].reshape(n_slices, C).max(axis=1), 1)
    slice_w = widths.max(axis=0)                   # cross-shard max per slice
    run_bounds = np.concatenate(
        [[0], np.flatnonzero(np.diff(slice_w)) + 1, [n_slices]])

    run_cols, run_vals, run_own = [], [], []
    for r in range(len(run_bounds) - 1):
        s0, s1 = int(run_bounds[r]), int(run_bounds[r + 1])
        w = int(slice_w[s0])
        rows_r = (s1 - s0) * C
        cols_r = np.empty((S, rows_r, w), np.int32)
        vals_r = np.zeros((S, rows_r, w), ell_vals.dtype)
        own_r = np.zeros((S, rows_r), np.int32)
        slot = np.arange(w)[None, :]
        for d in range(S):
            sel = orders[d, s0 * C:s1 * C]         # packed rows of this run
            real = sel < R
            safe = np.where(real, sel, 0)
            deg = np.where(real, counts[d][safe], 0)
            keep = slot < deg[:, None]
            cw = ell_cols_x[d][safe, :w] if w <= M else np.pad(
                ell_cols_x[d][safe], ((0, 0), (0, w - M)))
            vw = ell_vals[d][safe, :w] if w <= M else np.pad(
                ell_vals[d][safe], ((0, 0), (0, w - M)))
            own = np.where(real, sel, 0).astype(np.int32)
            cols_r[d] = np.where(keep, cw, own[:, None])
            vals_r[d] = np.where(keep, vw, 0)
            own_r[d] = own
        run_cols.append(jnp.asarray(cols_r))
        run_vals.append(jnp.asarray(vals_r))
        run_own.append(jnp.asarray(own_r))

    inv = np.empty((S, R_pad), np.int64)
    for d in range(S):
        inv[d, orders[d]] = np.arange(R_pad)
    return DistSellCS(run_cols=tuple(run_cols), run_vals=tuple(run_vals),
                      run_own=tuple(run_own),
                      inv=jnp.asarray(inv[:, :R], jnp.int32),
                      sell_c=C, n_pad_local=R_pad)


def make_row_partition(A: SparseMatrix, n_shards: int,
                       assignment: Optional[np.ndarray] = None, *,
                       mode: str = "auto",
                       halo_threshold: float = HALO_FALLBACK_FRAC,
                       sellcs: bool = False,
                       sell_c: int = 32) -> RowPartitionedMatrix:
    """Split A's ELL rows into n_shards contiguous blocks and precompute
    the halo-exchange plan (all host-side).

    If ``assignment`` (a cluster id per row, e.g. from p-spectral
    clustering) is given, rows are permuted so same-cluster rows are
    contiguous — the halo then holds only cut rows.  The permutation is
    internal to the layout: ``shard_mxm`` takes and returns vectors in
    the original row space.

    ``mode``: "auto" builds the halo plan and falls back to the gather
    schedule when the padded halo width exceeds ``halo_threshold * R``
    (it would move more bytes than the gather it replaces); "halo" /
    "gather" force a schedule — the bench uses this to measure both.
    ``sellcs=True`` adds the per-shard SELL-C-σ slicing (DistSellCS).
    """
    assert A.ell_cols is not None, "build_ell=True required"
    if mode not in ("auto", "halo", "gather"):
        raise ValueError(f"mode must be auto|halo|gather, got {mode!r}")
    ell_cols = np.asarray(A.ell_cols)
    ell_vals = np.asarray(A.ell_vals)
    n, m = ell_cols.shape
    square = A.n_rows == A.n_cols
    perm = inv = None
    if assignment is not None:
        if not square:
            raise ValueError(
                "graph-aware placement permutes rows and columns with one "
                "permutation and requires a square operator")
        perm = np.argsort(np.asarray(assignment), kind="stable")
        inv = np.empty_like(perm)
        inv[perm] = np.arange(n)
        # permute rows AND remap column ids into the permuted numbering,
        # so the partitioned operator acts on the permuted vector space
        ell_cols, ell_vals = inv[ell_cols[perm]].astype(np.int32), ell_vals[perm]
    pad = (-n) % n_shards
    if pad:
        # padded rows reference THEMSELVES with weight 0 (no-ops that
        # stay shard-local — referencing column 0, as the pre-halo code
        # did, would drag row 0 into every shard's halo)
        self_cols = np.repeat(np.arange(n, n + pad, dtype=np.int32)[:, None],
                              m, axis=1)
        ell_cols = np.concatenate([ell_cols, self_cols])
        ell_vals = np.concatenate([ell_vals, np.zeros((pad, m), ell_vals.dtype)])
    R = (n + pad) // n_shards
    ell_cols = ell_cols.reshape(n_shards, R, m)
    ell_vals = ell_vals.reshape(n_shards, R, m)

    # true per-row entry counts in partitioned order (pads excluded) —
    # the sellcs slicer sorts on these, not on the padded ELL width
    counts = None
    if sellcs:
        counts = np.bincount(A.host_coo()[0], minlength=n)
        if perm is not None:
            counts = counts[perm]
        counts = np.concatenate(
            [counts, np.zeros(pad, counts.dtype)]).reshape(n_shards, R)

    use_halo = square and n_shards > 1 and mode != "gather"
    H = total = 0
    if use_halo:
        needed, H, total = _halo_plan(ell_cols, n_shards, R)
        if mode == "auto" and H > halo_threshold * R:
            use_halo = False
            # the silent degradation PR 5 added — make it observable:
            # a partition that planned a halo but shipped the gather
            _obs_metrics.DEFAULT.counter("dist_gather_fallback_total").inc()
            _obs_trace.ACTIVE.instant(
                "dist.gather_fallback", n=A.n_rows, n_shards=n_shards,
                halo_width=int(H), rows_per_shard=int(R))
    if use_halo:
        cols_local = _remap_local(ell_cols, needed, n_shards, R, H)
        Ap = RowPartitionedMatrix(
            ell_cols=jnp.asarray(cols_local), ell_vals=jnp.asarray(ell_vals),
            n_rows=A.n_rows, n_cols=A.n_cols, n_shards=n_shards,
            perm=perm, inv_perm=inv, mode="halo", halo_width=H,
            send_idx=jnp.asarray(_send_plan(needed, n_shards, R, H)),
            halo_rows_true=total)
        cols_x = cols_local
    else:
        if mode == "halo":
            raise ValueError(
                "mode='halo' requires a square operator and n_shards > 1 "
                "(the halo plan partitions one row == column space)")
        # an auto fallback keeps the computed (H, total) so wire_bytes
        # still reports what the rejected halo WOULD have moved; a
        # forced mode="gather" never computes the plan (H stays 0)
        Ap = RowPartitionedMatrix(
            ell_cols=jnp.asarray(ell_cols), ell_vals=jnp.asarray(ell_vals),
            n_rows=A.n_rows, n_cols=A.n_cols, n_shards=n_shards,
            perm=perm, inv_perm=inv, mode="gather", halo_width=H,
            halo_rows_true=total)
        cols_x = ell_cols
    if sellcs:
        Ap.sell = _build_dist_sellcs(cols_x, ell_vals, counts, sell_c)
    return Ap


# ----------------------------------------------------------------- execution

# Fault-injection seam (repro.testing.faultinject, DESIGN.md §9): when
# set, the hook rewrites the received halo block inside the shard-mapped
# exchange — fn(recv, Ap) -> recv, jnp ops only (it runs traced).  Used
# by the chaos suite to model corrupted / dropped halo rows; production
# leaves it None.
_HALO_FAULT_HOOK = None


def set_halo_fault_hook(hook) -> None:
    global _HALO_FAULT_HOOK
    _HALO_FAULT_HOOK = hook


def _exchange(Ap: RowPartitionedMatrix, x_local, send_idx, axis: str):
    """The shard-local halo exchange: gather the rows this shard owes
    every peer, one tiled all_to_all, append the received halo."""
    if Ap.halo_width == 0:
        return x_local
    xs = x_local[send_idx]                    # (S*H, k) send buffer
    recv = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0,
                              tiled=True)     # block s = rows from shard s
    if _HALO_FAULT_HOOK is not None:
        recv = _HALO_FAULT_HOOK(recv, Ap)
    return jnp.concatenate([x_local, recv], axis=0)


def shard_mxm(Ap: RowPartitionedMatrix, X: jnp.ndarray, mesh,
              axis: str = "data",
              ring: Semiring | EdgeSemiring = reals_ring,
              layout: str = "ell") -> jnp.ndarray:
    """Distributed SpMM: rows sharded over ``axis``, halo rows exchanged
    per shard (or the full X gathered under a fallback plan).

    The execute hook of the "dist" / "dist_sellcs" backends
    (grblas.backends).  X: (n_cols,) or (n_cols, k) in the ORIGINAL row
    space — any placement permutation is applied internally and the
    output is returned un-permuted (pads sliced first), so dist ==
    single-device numerically for every plan.
    """
    S, R = Ap.n_shards, Ap.rows_per_shard
    if int(mesh.shape[axis]) != S:
        raise ValueError(
            f"partition was built for {S} shards but mesh axis {axis!r} "
            f"has size {int(mesh.shape[axis])}: rebuild with "
            f"make_row_partition(A, {int(mesh.shape[axis])})")
    tr = _obs_trace.ACTIVE
    if tr.enabled and not _obs_trace.under_trace(X):
        k_eff = int(X.shape[1]) if X.ndim > 1 else 1
        wb = Ap.wire_bytes(k_eff)
        wire = int(wb["halo"] if Ap.mode == "halo" else wb["gather"])
        with tr.span("dist.shard_mxm", cat="dist", mode=Ap.mode,
                     n=Ap.n_rows, n_shards=S, k=k_eff,
                     halo_width=int(Ap.halo_width), wire_bytes=wire,
                     layout=layout) as sp:
            out = _shard_mxm_impl(Ap, X, mesh, axis, ring, layout, S, R)
            sp.fence(out)
        _obs_metrics.DEFAULT.counter("dist_wire_bytes_total",
                                     mode=Ap.mode).inc(wire)
        _obs_metrics.DEFAULT.counter("dist_shard_mxm_total",
                                     mode=Ap.mode).inc()
        return out
    return _shard_mxm_impl(Ap, X, mesh, axis, ring, layout, S, R)


def _shard_mxm_impl(Ap, X, mesh, axis, ring, layout, S, R):
    n_pad = S * R
    edge = isinstance(ring, EdgeSemiring)
    one_d = X.ndim == 1
    if one_d:
        X = X[:, None]
    if Ap.perm is not None:
        X = X[Ap.perm]
    # pad to a multiple of S; gather-mode X is n_cols long (rectangular
    # reals), halo-mode X is n (square) — both pad up to >= the index
    # range the column ids touch
    L = n_pad if Ap.mode == "halo" else max(-(-X.shape[0] // S) * S, n_pad)
    if X.shape[0] != L:
        X = jnp.pad(X, ((0, L - X.shape[0]), (0, 0)))
    vec_spec = P(axis, None)
    plan_spec = P(axis, None)
    mat_spec = P(axis, None, None)

    if layout == "sellcs":
        if Ap.sell is None:
            raise ValueError(
                "this RowPartitionedMatrix was built without the per-shard "
                "SELL-C-σ layout: pass sellcs=True to make_row_partition")
        out = _shard_sellcs(Ap, X, mesh, axis, ring, edge,
                            vec_spec, plan_spec)
    elif layout == "ell":
        out = _shard_ell(Ap, X, mesh, axis, ring, edge,
                         vec_spec, plan_spec, mat_spec, L)
    else:
        raise ValueError(f"layout must be ell|sellcs, got {layout!r}")

    # drop the pad rows and undo the placement in one gather: inv_perm
    # (or the identity) indexes only real rows.  The operand is sharded
    # over ``axis``, so the gather names its (replicated) output
    # sharding — jax refuses to guess one for a sharded operand.
    rows = (Ap.inv_perm if Ap.inv_perm is not None
            else np.arange(Ap.n_rows))
    out = out.at[jnp.asarray(rows)].get(
        out_sharding=NamedSharding(mesh, P()))
    return out[:, 0] if one_d else out


def _shard_ell(Ap, X, mesh, axis, ring, edge, vec_spec, plan_spec,
               mat_spec, L):
    from repro.kernels.sellcs_spmm.ref import slot_sum

    halo = Ap.mode == "halo"

    def local(ell_cols, ell_vals, x_local, *plan):
        ell_cols = ell_cols[0]                            # (R, M) this shard
        ell_vals = ell_vals[0]
        if halo:
            x_src = _exchange(Ap, x_local, plan[0][0], axis)
        else:
            x_src = jax.lax.all_gather(x_local, axis, axis=0, tiled=True)
        # slot-by-slot (R, k) fold (sellcs_spmm.ref.slot_sum).  Pad
        # slots carry val=0 and every ring _dist_supports admits has the
        # reals base and annihilates zero contributions.
        if edge:
            # x_i is this shard's own rows — x_local directly (edge
            # rings are square-gated, so the row and column spaces and
            # their paddings coincide)
            term = lambda c, v: ring.edge_mul(v[:, None], x_src[c], x_local)
        else:
            term = lambda c, v: ring.mul(v[:, None], x_src[c])
        return slot_sum(ell_cols, ell_vals, term)

    args = [Ap.ell_cols, Ap.ell_vals, X]
    specs = [mat_spec, mat_spec, vec_spec]
    if halo:
        args.append(Ap.send_idx)
        specs.append(plan_spec)
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=vec_spec, check_vma=False)
    return fn(*args)


def _shard_sellcs(Ap, X, mesh, axis, ring, edge, vec_spec, plan_spec):
    from repro.kernels.sellcs_spmm.ref import (
        sellcs_shard_plap_apply_ref, sellcs_shard_spmm_ref)

    sell = Ap.sell
    halo = Ap.mode == "halo"
    n_runs = len(sell.run_cols)

    def local(x_local, inv, *rest):
        if halo:
            x_src = _exchange(Ap, x_local, rest[0][0], axis)
            rest = rest[1:]
        else:
            x_src = jax.lax.all_gather(x_local, axis, axis=0, tiled=True)
        cols = rest[:n_runs]
        vals = rest[n_runs:2 * n_runs]
        own = rest[2 * n_runs:]
        outs = []
        for c, v, o in zip(cols, vals, own):
            if edge:
                p, eps = ring.params
                outs.append(sellcs_shard_plap_apply_ref(
                    c[0], v[0], x_src, x_local[o[0]], p, eps))
            elif ring.name == "reals_+x":
                outs.append(sellcs_shard_spmm_ref(c[0], v[0], x_src))
            else:
                vb = v[0][..., None]
                outs.append(fast_paths(ring).padded(ring.mul(vb, x_src[c[0]])))
        return jnp.concatenate(outs, axis=0)[inv[0]]      # back to local order

    args = [X, sell.inv]
    specs = [vec_spec, plan_spec]
    if halo:
        args.append(Ap.send_idx)
        specs.append(plan_spec)
    args += list(sell.run_cols) + list(sell.run_vals) + list(sell.run_own)
    specs += ([P(axis, None, None)] * 2 * n_runs + [plan_spec] * n_runs)
    fn = jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                       out_specs=vec_spec, check_vma=False)
    return fn(*args)


# ------------------------------------------------------------- launch path

def is_distributed_initialized() -> bool:
    """Whether jax.distributed has been initialized in this process."""
    try:
        from jax._src import distributed as _dst
        return _dst.global_state.client is not None
    except Exception:
        return False


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> bool:
    """Guarded ``jax.distributed.initialize`` for multi-process meshes.

    Resolves the coordinator triple from the arguments or the standard
    environment (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID) and initializes once.  Single-process launches (no
    coordinator configured, or num_processes <= 1) and already-
    initialized processes are no-ops — returns True iff this call
    performed the initialization, so the same entry point serves the
    one-host dev loop and a real multi-host launch.
    """
    if is_distributed_initialized():
        return False
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and "JAX_PROCESS_ID" in os.environ:
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None or not num_processes or num_processes <= 1:
        return False
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def device_mesh(axis: str = "data", n_shards: Optional[int] = None):
    """1-D mesh over the (global) device set for the dist backends.

    Calls ``init_distributed`` first so a multi-process launch sees the
    full device set; single-process it is just ``make_mesh`` over the
    local devices (e.g. the forced host devices of the tests/bench).
    """
    init_distributed()
    n = n_shards if n_shards is not None else len(jax.devices())
    return jax.make_mesh((n,), (axis,))
