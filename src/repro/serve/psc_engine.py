"""Clustering-as-a-service: the batched, warm-started PSC serve engine
(DESIGN.md §8).

``serve/engine.py`` serves an LLM by compiling ONE static-shape decode
step and reusing it for every token of every request.  This module is
the clustering analogue for a stream of graph requests:

  * **shape-bucketed batching** — requests pad onto a power-of-two
    (n, nnz, k) bucket lattice (``serve.bucketing``) and the whole
    SCF/Newton p-continuation runs ``jax.vmap``-ed across a bucket, so
    each bucket compiles exactly one trace no matter how many requests
    it serves.  A Newton bucket of at most ``DENSE_MAX_N`` vertices
    runs its p-Laplacian on each lane's dense (n, n) adjacency (the
    "dense" backend: no gather or scatter in the solver's loops);
    larger buckets run the COO segment path.  The per-bucket jitted
    solve is memoized through the solver registry's trace scaffolding
    (``registry.memoized`` / ``mark_trace``), so retraces are observable
    the same way the Newton driver's are.
  * **warm-start cache** — an LRU on graph fingerprints
    (``serve.warm_cache``).  A hit skips the p=2 eigensolve and the
    continuation descent entirely: the cached embedding re-enters the
    registry at the END of the p schedule (``solvers.warm_start`` — the
    nonlinear lift of ``lobpcg.smallest_eigvecs``' X0 substrate).
  * **incremental re-clustering** — ``update()`` takes an
    :class:`~repro.serve.churn.EdgeDelta` against a previously served
    graph: weight-only deltas ride ``with_vals`` + a warm solve;
    pattern deltas patch the cached multilevel hierarchy and run a
    refine-only V-cycle (``serve.churn``).
  * **admission + metrics** — a request queue with per-bucket batch
    assembly under a max-wait deadline, per-request :class:`ServeStats`
    (queue time, solve time, cache tier, trace reuse) and engine-level
    throughput counters.

Graphs larger than the bucket lattice (``max_bucket_n``) take the
*solo* lane: the flat (or multilevel) pipeline per request — the same
warm-start and churn machinery applies, only unbatched.

Determinism contract: a bucketed solve discretizes with the flat
pipeline's exact stage-3 key (``psc.stage_keys`` / ``psc.discretize``)
and computes RCut on the caller's ORIGINAL graph, so a padded, batched
request returns the same labels as ``p_spectral_cluster`` on the bare
graph (pinned by tests/test_psc_serve.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import metrics, plap
from repro.core import psc as _psc
from repro.core.grassmann import rtr_minimize
from repro.core.psc import PSCConfig
from repro.core.solvers import registry
from repro.core.solvers.guard import SolverDivergence
from repro.grblas.api import Descriptor
from repro.grblas.backends import BackendUnavailableError
from repro.grblas.containers import (DENSE_MAX_N, GraphFingerprint,
                                     SparseMatrix)
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.serve.bucketing import (BucketBatch, BucketSpec, assemble_batch,
                                   bucket_for, pad_embeddings)
from repro.serve.churn import EdgeDelta, apply_edge_delta, \
    incremental_recluster
from repro.serve.warm_cache import CacheEntry, WarmCache

# Spectral shift applied to pad-vertex diagonals in the batched dense
# eigensolves: isolated pad rows contribute extra Laplacian null-space,
# and this pushes it far above any graph eigenvalue so the smallest-k
# Ritz selection only ever sees the real spectrum.
_PAD_SHIFT = 1.0e6

_COO = Descriptor(backend="coo")
_DENSE = Descriptor(backend="dense")

# Fault-injection seams (repro.testing.faultinject, DESIGN.md §9): when
# set, called right before a bucket batch solve / a churn re-solve.
# Raising from them exercises the quarantine-bisect and retry paths
# deterministically; production leaves them None.
_SOLVE_FAULT = None     # fn(pends: List[_Pending]) -> None
_CHURN_FAULT = None     # fn(pend: _Pending, attempt: int) -> None


# --------------------------------------------------------------- stats types

@dataclasses.dataclass
class ServeStats:
    """Per-request accounting, returned alongside every result."""

    req_id: int
    n: int
    nnz: int
    k: int
    lane: str                    # "bucket" | "solo"
    mode: str                    # "cold" | "warm" | "churn"
    cache_tier: Optional[str]    # None | "exact" | "pattern"
    bucket: Optional[tuple]      # BucketSpec key (bucket lane only)
    batch_size: int
    queue_s: float               # admission to launch (to failure, if failed)
    solve_s: float
    trace_new: bool              # this request's batch compiled a new trace
    p_final: float
    # resilience accounting (DESIGN.md §9) — defaulted for back-compat
    degrade: int = 0             # 0 none | 1 schedule-tail-only | 2 p=2-init
    retries: int = 0             # churn-path retry count before success
    failure_kind: Optional[str] = None   # taxonomy key (failed requests)
    error: Optional[str] = None          # human-readable failure detail
    finish_s: float = 0.0        # this request's own stage 3 (0 if failed)


@dataclasses.dataclass
class ServeResult:
    req_id: int
    labels: np.ndarray
    U: np.ndarray
    rcut: float
    ncut: float
    stats: ServeStats
    # failed requests carry the structured error here (labels/U None,
    # rcut/ncut NaN); healthy requests leave it None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclasses.dataclass
class _Pending:
    req_id: int
    W: SparseMatrix
    k: int
    fp: GraphFingerprint
    spec: Optional[BucketSpec]
    mode: str                       # "cold" | "warm"
    cache_tier: Optional[str]
    warm_U: Optional[np.ndarray]
    arrival: float
    churn: bool = False
    touched: Optional[np.ndarray] = None
    pattern_changed: bool = False
    hierarchy: object = None
    degrade: int = 0                # deadline degradation level (0/1/2)


# ------------------------------------------------------ batched solver build

def _dense_smallest(L: jnp.ndarray, mask: jnp.ndarray, k: int):
    """Smallest-k eigenvectors of a padded dense operator: pad diagonals
    get the ``_PAD_SHIFT`` so the isolated-vertex null-space sorts above
    every real eigenvalue; pad rows of the result are re-zeroed (eigh
    leaves only FP dust there) to restore the exact-zero invariant."""
    L = L + jnp.diag((1.0 - mask) * _PAD_SHIFT)
    _, evecs = jnp.linalg.eigh(L)
    return evecs[:, :k] * mask[:, None]


def _batched_init(W: SparseMatrix, mask: jnp.ndarray, k: int, cfg):
    """Stage 1 of the flat pipeline, batched: the dense-eigh path of
    ``lobpcg.smallest_eigvecs`` (buckets are capped at the same n where
    the flat solver itself goes dense, so the two paths mirror)."""
    dense = W.to_dense()
    deg = jnp.sum(dense, axis=1)
    L = jnp.diag(deg) - dense
    if cfg.normalized_init:
        dih = jax.lax.rsqrt(jnp.maximum(deg, 1e-12))
        L = dih[:, None] * L * dih[None, :]
    U = _dense_smallest(L, mask, k)
    return jnp.linalg.qr(U)[0]


def _bucket_layout(spec: BucketSpec, cfg) -> str:
    """The layout a bucket's Newton solve runs on: "dense" up to
    ``DENSE_MAX_N`` vertices (the n where stage 1 goes dense too), "coo"
    above it and for the scf step."""
    if cfg.solver == "newton" and spec.n <= DENSE_MAX_N:
        return "dense"
    return "coo"


def _make_level_step(cfg):
    """One continuation level of the batched solve: (W, mask, U, p) ->
    (U', fval), traceable end to end (vmap/scan-safe).

    newton: ``rtr_minimize`` — its lax.while_loop batches with
    per-element semantics, so each graph in the bucket keeps its own
    trust-region trajectory; the Hessian's U-only operands are built
    once per Newton step (``plap.hvp_at``), on the dense backend when W
    carries its dense layout (``_bucket_layout``), else on COO.
    scf: fixed-sweep IRLS with a per-element convergence freeze (a
    converged element stops updating, matching the host driver's early
    exit) and the dense eigensolve of the flat ≤1024-vertex path."""
    eps = cfg.eps
    if cfg.solver == "newton":
        def step(W, mask, U, p):
            desc = _COO if W.dense is None else _DENSE
            f = lambda V: plap.value(W, V, p, eps, desc=desc)
            g = lambda V: plap.euc_grad(W, V, p, eps, desc=desc)
            h_at = lambda V: plap.hvp_at(W, V, p, eps, cfg.hvp_mode,
                                         desc=desc)
            res = rtr_minimize(f, g, h_at, U, max_iters=cfg.newton_iters,
                               tcg_iters=cfg.tcg_iters,
                               grad_tol=cfg.grad_tol)
            return res.U, res.fval

        return step

    if cfg.solver == "scf":
        sweeps, tol = max(int(cfg.scf_sweeps), 1), cfg.scf_tol

        def step(W, mask, U, p):
            k = U.shape[-1]

            def sweep(carry, _):
                U, done = carry
                d = U[W.rows] - U[W.cols]
                g2 = jnp.sum(d * d, axis=-1)
                what = W.vals * (g2 + eps) ** ((p - 2.0) / 2.0)
                dense = jnp.zeros((W.n_rows, W.n_rows), U.dtype
                                  ).at[W.rows, W.cols].add(what)
                L = jnp.diag(jnp.sum(dense, axis=1)) - dense
                V = jnp.linalg.qr(_dense_smallest(L, mask, k))[0]
                drift = k - jnp.sum((V.T @ U) ** 2)
                U = jnp.where(done, U, V)
                return (U, done | (drift < tol)), None

            (U, _), _ = jax.lax.scan(sweep, (U, False), None, length=sweeps)
            return U, plap.value(W, U, p, eps, desc=_COO)

        return step

    raise ValueError(
        f"bucket lane supports solvers 'newton' and 'scf', not "
        f"{cfg.solver!r} (route larger drivers through the solo lane)")


def _solver_sig(cfg) -> tuple:
    return (cfg.solver, cfg.hvp_mode, cfg.eps, cfg.newton_iters,
            cfg.tcg_iters, cfg.grad_tol, cfg.scf_sweeps, cfg.scf_tol,
            cfg.normalized_init, cfg.p_target, cfg.p_factor,
            cfg.warm_p_steps)


def _bucket_solver(spec: BucketSpec, cfg):
    """The memoized jitted batched solve for one bucket spec.

    Cold: dense p=2 init + lax.scan over the full continuation schedule
    (p traced per scan step, static length).  Warm: scan over the last
    ``cfg.warm_p_steps`` schedule values from the supplied embeddings.
    On the "dense" layout (``_bucket_layout``) each lane's adjacency
    tile is built once, before both.
    Exactly one trace per (spec, solver signature) — ``mark_trace``
    lands the key in ``registry.SOLVER_TRACES`` so tests and the bench
    can assert trace reuse across a mixed request stream."""
    key = spec.key + _solver_sig(cfg)

    def build():
        if spec.mode == "cold":
            ps = jnp.asarray(registry.p_schedule(cfg), jnp.float32)
        else:
            tail = registry.p_schedule(cfg)[-max(int(cfg.warm_p_steps), 1):]
            ps = jnp.asarray(tail, jnp.float32)
        layout = _bucket_layout(spec, cfg)
        step = _make_level_step(cfg)
        n_b, nnz_b, k = spec.n, spec.nnz, spec.k

        def one(rows, cols, vals, mask, U0):
            W = SparseMatrix(n_rows=n_b, n_cols=n_b, nnz=nnz_b,
                             rows=rows, cols=cols, vals=vals)
            if layout == "dense":
                W = W.with_dense()
            if spec.mode == "cold":
                U = _batched_init(W, mask, k, cfg)
            else:
                U = jnp.linalg.qr(U0 * mask[:, None])[0]

            def body(U, p):
                U2, fv = step(W, mask, U, p)
                return U2, fv

            U, fvals = jax.lax.scan(body, U, ps)
            return U, fvals

        def solve(rows, cols, vals, mask, U0):
            registry.mark_trace(key)
            return jax.vmap(one)(rows, cols, vals, mask, U0)

        return jax.jit(solve)

    return registry.memoized(key, build), key


# ------------------------------------------------------------------- engine

class EngineStats:
    """Engine-level counters — live *views* over the engine's
    :class:`~repro.obs.metrics.MetricsRegistry` (DESIGN.md §10).

    Historically a dataclass of plain ints incremented beside the
    cache's own counters (two sets of books).  Every counter attribute
    now reads through to one metric family, and ``stats.field += 1``
    still works — the property setter forwards the delta to the
    underlying monotonic counter — so call sites and external readers
    are unchanged.  ``n_failed`` / ``failures`` both derive from the
    single labeled ``serve_failed_total`` family and can never
    disagree.  ``solve_s`` stays a plain float (a derived timing, not a
    monotonic count).
    """

    # attribute -> counter family backing it
    _VIEWS = {
        "n_requests": "serve_requests_total",
        "n_results": "serve_results_total",
        "n_batches": "serve_batches_total",
        "n_solo": "serve_solo_total",
        "n_churn": "serve_churn_total",
        "traces": "serve_traces_total",          # serve-lane compiles
        "n_degraded": "serve_degraded_total",    # served at degrade >= 1
        "n_retried": "serve_churn_retries_total",
        "n_quarantined": "serve_quarantined_total",
        "n_quarantine_splits": "serve_quarantine_splits_total",
    }

    def __init__(self, registry: "_obs_metrics.MetricsRegistry" = None):
        self.registry = registry if registry is not None \
            else _obs_metrics.MetricsRegistry()
        self.solve_s = 0.0

    def record_failure(self, kind: str) -> None:
        """The one write path for the failure taxonomy."""
        self.registry.counter("serve_failed_total", kind=kind).inc()

    @property
    def n_failed(self) -> int:
        """Requests that returned a structured error (any kind)."""
        return int(self.registry.total("serve_failed_total"))

    @property
    def failures(self) -> Dict[str, int]:
        """Failure-taxonomy histogram (DESIGN.md §9), reconstructed
        from the ``kind`` label of ``serve_failed_total``."""
        vals = self.registry.labeled_values("serve_failed_total", "kind")
        return {k: int(v) for k, v in vals.items()}

    def as_dict(self) -> dict:
        out = {name: getattr(self, name)
               for name in ("n_requests", "n_results", "n_batches",
                            "n_solo", "n_churn", "traces")}
        out["solve_s"] = self.solve_s
        for name in ("n_failed", "n_degraded", "n_retried",
                     "n_quarantined", "n_quarantine_splits"):
            out[name] = getattr(self, name)
        out["failures"] = self.failures
        return out

    def exposition(self) -> str:
        """Prometheus text exposition of the whole engine registry."""
        return self.registry.exposition()


def _stat_view(metric: str) -> property:
    def fget(self):
        return int(self.registry.value(metric))

    def fset(self, value):
        self.registry.counter(metric).inc(value - self.registry.value(metric))

    return property(fget, fset)


for _field, _metric in EngineStats._VIEWS.items():
    setattr(EngineStats, _field, _stat_view(_metric))
del _field, _metric


def _classify(err) -> str:
    """Failure-taxonomy key of an exception (DESIGN.md §9)."""
    if isinstance(err, BackendUnavailableError):
        return "backend_error"
    if isinstance(err, SolverDivergence):
        return "solver_divergence"
    from repro.graphs.validate import GraphValidationError

    if isinstance(err, GraphValidationError):
        return "invalid_input"
    if isinstance(err, BaseException):
        return "exception"
    return "nonfinite_result"


class ClusterServeEngine:
    """Batched, warm-started p-spectral clustering server.

    >>> eng = ClusterServeEngine(PSCConfig(k=4))
    >>> rid = eng.submit(W)
    >>> res = eng.flush()[rid]           # labels, rcut, ServeStats

    ``submit`` enqueues; batches launch when a bucket fills to
    ``max_batch`` or its oldest request has waited ``max_wait_s``
    (``poll`` drives the clock; ``flush`` drains everything).  Requests
    above ``max_bucket_n`` vertices run the solo lane — the flat
    pipeline, or the multilevel V-cycle when ``ml`` is given, with the
    same cache semantics.
    """

    def __init__(self, cfg: Optional[PSCConfig] = None, *,
                 cache_capacity: int = 64, max_batch: int = 8,
                 max_wait_s: float = 0.05, max_bucket_n: int = DENSE_MAX_N,
                 min_bucket_n: int = 64, min_bucket_nnz: int = 128,
                 ml=None, weight_quant: float = 1e-6,
                 deadline_s: Optional[float] = None,
                 tail_frac: float = 0.5, churn_retries: int = 2,
                 retry_backoff_s: float = 0.01,
                 validate_inputs: bool = False):
        self.cfg = cfg if cfg is not None else PSCConfig()
        if self.cfg.reorder != "none":
            raise ValueError("the serve engine owns vertex order; use "
                             "reorder='none' in the template config")
        # one registry for engine + cache: EngineStats and
        # WarmCache.stats() are views over it, never separate books
        self.metrics = _obs_metrics.MetricsRegistry()
        self.cache = WarmCache(cache_capacity, metrics=self.metrics)
        self.max_batch = int(max_batch)
        self.max_wait_s = float(max_wait_s)
        self.max_bucket_n = int(max_bucket_n)
        self.min_bucket_n = int(min_bucket_n)
        self.min_bucket_nnz = int(min_bucket_nnz)
        self.ml = ml
        self.weight_quant = float(weight_quant)
        # resilience knobs (DESIGN.md §9): a request older than
        # ``tail_frac * deadline_s`` degrades to a schedule-tail-only
        # solve (level 1); older than ``deadline_s`` to p=2-init labels
        # (level 2).  Churn re-solves retry ``churn_retries`` times with
        # exponential backoff before falling back to a cold solve.
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.tail_frac = float(tail_frac)
        self.churn_retries = int(churn_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.validate_inputs = bool(validate_inputs)
        self._sleep = time.sleep          # test seam (no real sleeps)
        self._buckets: Dict[tuple, List[_Pending]] = {}
        self._solo: List[_Pending] = []
        self._results: Dict[int, ServeResult] = {}
        self._next_id = 0
        self.stats = EngineStats(self.metrics)
        self._bucketable = self.cfg.solver in ("newton", "scf")

    def exposition(self) -> str:
        """Prometheus text exposition of the engine's registry (engine
        counters + warm-cache counters + queue/occupancy instruments)."""
        return self.metrics.exposition()

    def _queue_depth(self) -> int:
        return sum(len(q) for q in self._buckets.values()) + len(self._solo)

    def _note_queue(self) -> None:
        self.metrics.gauge("serve_queue_depth").set(self._queue_depth())

    # ------------------------------------------------------------ admission

    def submit(self, W: SparseMatrix, k: Optional[int] = None) -> int:
        """Enqueue a clustering request; returns its request id."""
        return self._admit(W, k=k)

    def update(self, base: SparseMatrix, delta: EdgeDelta,
               k: Optional[int] = None) -> int:
        """Enqueue an incremental re-cluster of ``base`` under ``delta``.

        With a cached solve of ``base`` this is the churn fast path
        (warm solve on the edited weights; hierarchy patch + refine-only
        V-cycle on the solo/multilevel lane).  Without one it degrades
        to a cold solve of the edited graph."""
        d = apply_edge_delta(base, delta)
        base_fp = base.fingerprint(self.weight_quant)
        entry = self.cache.peek(base_fp)
        return self._admit(d.W, k=k, churn=True, churn_entry=entry,
                           touched=d.touched,
                           pattern_changed=d.pattern_changed)

    def _admit(self, W: SparseMatrix, k: Optional[int], churn: bool = False,
               churn_entry: Optional[CacheEntry] = None,
               touched=None, pattern_changed: bool = False) -> int:
        k = int(k) if k is not None else self.cfg.k
        if k < 1 or k > max(W.n_rows, 1):
            raise ValueError(f"k={k} invalid for an n={W.n_rows} graph "
                             f"(need 1 <= k <= n)")
        rid = self._next_id
        self._next_id += 1
        self.stats.n_requests += 1
        if self.validate_inputs:
            from repro.graphs.validate import quick_check

            issue = quick_check(W)
            if issue is not None:
                # reject at admission: the request gets its structured
                # error immediately and never reaches a batch
                pend = _Pending(req_id=rid, W=W, k=k, fp=None, spec=None,
                                mode="cold", cache_tier=None, warm_U=None,
                                arrival=time.monotonic(), churn=churn)
                self._fail(pend, issue, kind="invalid_input",
                           lane="admission")
                return rid
        fp = W.fingerprint(self.weight_quant)

        if churn:
            tier, warm_U, hier = None, None, None
            if churn_entry is not None and len(churn_entry.labels) == W.n_rows:
                tier, warm_U = "exact", churn_entry.U
                hier = churn_entry.hierarchy
            mode = "warm" if warm_U is not None else "cold"
        else:
            entry, tier = self.cache.lookup(fp)
            warm_U = entry.U if entry is not None else None
            hier = entry.hierarchy if entry is not None else None
            if warm_U is not None and len(warm_U) != W.n_rows:
                warm_U, tier, hier = None, None, None   # size collision
            mode = "warm" if warm_U is not None else "cold"

        pend = _Pending(req_id=rid, W=W, k=k, fp=fp, spec=None, mode=mode,
                        cache_tier=tier, warm_U=warm_U,
                        arrival=time.monotonic(), churn=churn,
                        touched=touched, pattern_changed=pattern_changed,
                        hierarchy=hier)
        # k == 1 / k == n requests ride the solo lane: the pipeline
        # answers them in closed form there, while the batched bucket
        # solve assumes a proper 1 < k < n eigenproblem
        if self._bucketable and W.n_rows <= self.max_bucket_n \
                and 1 < k < W.n_rows \
                and not (churn and self.ml is not None):
            spec = bucket_for(W, k, mode, self.min_bucket_n,
                              self.min_bucket_nnz)
            pend.spec = spec
            self._buckets.setdefault(spec.key, []).append(pend)
        else:
            self._solo.append(pend)
        self._note_queue()
        return rid

    # ------------------------------------------------------------- draining

    def poll(self, now: Optional[float] = None) -> Dict[int, ServeResult]:
        """Launch every due batch (bucket full, or oldest request past
        the max-wait deadline) and all solo requests; return results
        completed so far (cumulative).  A poll that launches anything is
        one ``serve.poll`` span; one that launches nothing records
        none."""
        now = time.monotonic() if now is None else now
        depth = self._queue_depth()
        self._apply_deadlines(now)
        due: List[List[_Pending]] = []
        for bkey in list(self._buckets):
            q = self._buckets[bkey]
            while q and (len(q) >= self.max_batch
                         or now - q[0].arrival >= self.max_wait_s):
                due.append(q[:self.max_batch])
                q = q[self.max_batch:]
            if q:
                self._buckets[bkey] = q
            else:
                del self._buckets[bkey]
        if due or self._solo:
            with _obs_trace.ACTIVE.span("serve.poll", cat="serve",
                                        launches=len(due) + len(self._solo),
                                        queue_depth=depth):
                for take in due:
                    self._run_bucket(take)
                while self._solo:
                    self._run_solo(self._solo.pop(0))
        self._note_queue()
        return dict(self._results)

    def flush(self) -> Dict[int, ServeResult]:
        """Drain every queued request regardless of deadlines."""
        self._apply_deadlines(time.monotonic())
        for bkey in list(self._buckets):
            q = self._buckets.pop(bkey)
            for i in range(0, len(q), self.max_batch):
                self._run_bucket(q[i:i + self.max_batch])
        while self._solo:
            self._run_solo(self._solo.pop(0))
        self._note_queue()
        return dict(self._results)

    def serve(self, graphs, k: Optional[int] = None) -> List[ServeResult]:
        """Convenience batch API: submit everything, flush, return
        results in submission order."""
        rids = [self.submit(W, k=k) for W in graphs]
        done = self.flush()
        return [done[r] for r in rids]

    def take(self, req_id: int) -> ServeResult:
        return self._results.pop(req_id)

    # ------------------------------------------------------------ deadlines

    def _degrade_level(self, elapsed: float) -> int:
        """0 = full solve, 1 = schedule-tail-only (p=2 eigensolve + one
        tail step), 2 = p=2-init labels (classical spectral, no
        continuation) — degrade instead of missing the deadline."""
        if self.deadline_s is None:
            return 0
        if elapsed >= self.deadline_s:
            return 2
        if elapsed >= self.tail_frac * self.deadline_s:
            return 1
        return 0

    def _apply_deadlines(self, now: float) -> None:
        """Move deadline-pressed cold bucket requests to the solo lane
        with their degrade level pinned (a degraded solve has a
        different schedule, so it can't share the bucket's trace)."""
        if self.deadline_s is None:
            return
        for bkey in list(self._buckets):
            keep: List[_Pending] = []
            for pend in self._buckets[bkey]:
                lvl = self._degrade_level(now - pend.arrival)
                if lvl > 0 and pend.mode == "cold" and not pend.churn:
                    pend.degrade = lvl
                    pend.spec = None
                    self._solo.append(pend)
                else:
                    keep.append(pend)
            if keep:
                self._buckets[bkey] = keep
            else:
                del self._buckets[bkey]

    # ------------------------------------------------------------ execution

    def _fail(self, pend: _Pending, err, *, kind: str, lane: str) -> None:
        """Record a structured per-request failure: the request resolves
        (poll/flush/take all see it) with ``error`` set and no labels —
        it never poisons its batch neighbors and never enters the
        cache."""
        msg = f"{type(err).__name__}: {err}" if isinstance(
            err, BaseException) else str(err)
        st = ServeStats(
            req_id=pend.req_id, n=pend.W.n_rows, nnz=pend.W.nnz, k=pend.k,
            lane=lane, mode="churn" if pend.churn else pend.mode,
            cache_tier=pend.cache_tier,
            bucket=pend.spec.key if pend.spec else None, batch_size=0,
            queue_s=time.monotonic() - pend.arrival, solve_s=0.0,
            trace_new=False, p_final=float("nan"), degrade=pend.degrade,
            failure_kind=kind, error=msg)
        self._results[pend.req_id] = ServeResult(
            req_id=pend.req_id, labels=None, U=None, rcut=float("nan"),
            ncut=float("nan"), stats=st, error=msg)
        self.stats.n_results += 1
        self.stats.record_failure(kind)
        _obs_trace.ACTIVE.instant("serve.fail", cat="serve",
                                  req_id=pend.req_id, kind=kind, lane=lane)

    def _solve_bucket(self, pends: List[_Pending], spec) -> tuple:
        """The batched solve itself (no per-request error handling —
        ``_run_bucket`` owns quarantine)."""
        t0 = time.monotonic()
        solver, key = _bucket_solver(spec, self.cfg)
        n_traces0 = sum(1 for t in registry.SOLVER_TRACES if t == key)
        if _SOLVE_FAULT is not None:
            _SOLVE_FAULT(pends)
        batch: BucketBatch = assemble_batch([p.W for p in pends], spec)
        if spec.mode == "warm":
            U0 = pad_embeddings([p.warm_U for p in pends], spec)
        else:
            U0 = np.zeros((len(pends), spec.n, spec.k), np.float32)
        # pad the batch axis to max_batch (replicating the last request's
        # lanes) so a partial batch reuses the full batch's trace — the
        # one-trace-per-bucket guarantee holds for deadline launches too
        fill = self.max_batch - len(pends)

        def _fill(a):
            return a if fill <= 0 else \
                np.concatenate([a, np.repeat(a[-1:], fill, axis=0)])

        layout = _bucket_layout(spec, self.cfg)
        self.metrics.counter("serve_bucket_launches_total",
                             layout=layout).inc()
        with _obs_trace.ACTIVE.span("serve.bucket_solve", cat="serve",
                                    bucket=str(spec.key), mode=spec.mode,
                                    batch=len(pends), n=spec.n,
                                    nnz=spec.nnz, k=spec.k, layout=layout,
                                    req_ids=[p.req_id for p in pends]) as sp:
            U, fvals = solver(jnp.asarray(_fill(batch.rows)),
                              jnp.asarray(_fill(batch.cols)),
                              jnp.asarray(_fill(batch.vals)),
                              jnp.asarray(_fill(batch.mask)),
                              jnp.asarray(_fill(U0)))
            sp.fence(U)
            trace_new = sum(1 for t in registry.SOLVER_TRACES if t == key) \
                > n_traces0
            sp.set(trace_new=trace_new)
        U = np.asarray(U)
        return U, trace_new, time.monotonic() - t0

    def _run_bucket(self, pends: List[_Pending]) -> None:
        spec = pends[0].spec
        self.metrics.histogram("serve_batch_occupancy",
                               buckets=(1, 2, 4, 8, 16, 32)
                               ).observe(len(pends))
        launched = time.monotonic()
        try:
            U, trace_new, solve_s = self._solve_bucket(pends, spec)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:            # noqa: BLE001 — quarantined
            if len(pends) == 1:
                # bisection bottomed out: THIS request is the poison
                self.stats.n_quarantined += 1
                self._fail(pends[0], exc, kind=_classify(exc),
                           lane="bucket")
                return
            # a thrown batch solve names no culprit: bisect — survivors
            # re-run, the poisoned half recurses down to one request
            self.stats.n_quarantine_splits += 1
            _obs_trace.ACTIVE.instant("serve.quarantine_split", cat="serve",
                                      batch=len(pends),
                                      bucket=str(spec.key))
            mid = len(pends) // 2
            self._run_bucket(pends[:mid])
            self._run_bucket(pends[mid:])
            return
        if trace_new:
            self.stats.traces += 1
        self.stats.n_batches += 1
        self.stats.solve_s += solve_s
        p_final = float(registry.p_schedule(self.cfg)[-1])
        for b, pend in enumerate(pends):
            Ub = U[b, :pend.W.n_rows]
            if not np.isfinite(Ub).all():
                # vmap lanes are numerically independent, so a NaN here
                # is THIS request's own divergence (bad weights, solver
                # blow-up) — quarantine it, neighbors are untouched
                self.stats.n_quarantined += 1
                self._fail(pend, "non-finite embedding from the batched "
                                 "solve (request-local divergence)",
                           kind="nonfinite_result", lane="bucket")
                continue
            self._finish(pend, Ub, lane="bucket", lane_index=b,
                         batch_size=len(pends), launched=launched,
                         solve_s=solve_s, trace_new=trace_new,
                         p_final=p_final, hierarchy=None)

    def _churn_solve(self, pend: _Pending, cfg) -> tuple:
        """The churn re-solve with retry-with-backoff: transient faults
        (a flaky backend, a mid-flight divergence) retry up to
        ``churn_retries`` times; exhaustion falls back to a cold solve
        of the edited graph (correct, just slower)."""
        last = None
        for attempt in range(self.churn_retries + 1):
            try:
                if _CHURN_FAULT is not None:
                    _CHURN_FAULT(pend, attempt)
                res, hierarchy, _ = incremental_recluster(
                    pend.W, pend.touched, pend.pattern_changed,
                    pend.warm_U, cfg, ml=self.ml,
                    hierarchy=pend.hierarchy)
                return res, hierarchy, attempt
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as exc:        # noqa: BLE001 — retried
                last = exc
                if attempt < self.churn_retries:
                    self.stats.n_retried += 1
                    _obs_trace.ACTIVE.instant(
                        "serve.retry", cat="serve", req_id=pend.req_id,
                        attempt=attempt, error=type(exc).__name__)
                    self._sleep(self.retry_backoff_s * (2.0 ** attempt))
        # retries exhausted: cold-solve the edited graph from scratch
        cold = dataclasses.replace(cfg, init_U=None,
                                   multilevel=self.ml)
        try:
            res = _psc.p_spectral_cluster(pend.W, cold)
        except Exception:
            raise last if last is not None else RuntimeError(
                "churn fallback failed")
        return res, None, self.churn_retries + 1

    def _run_solo(self, pend: _Pending) -> None:
        with _obs_trace.ACTIVE.span(
                "serve.solo_solve", cat="serve", req_id=pend.req_id,
                n=pend.W.n_rows, nnz=pend.W.nnz, k=pend.k,
                mode="churn" if pend.churn else pend.mode) as sp:
            self._run_solo_impl(pend, sp)

    def _run_solo_impl(self, pend: _Pending, sp) -> None:
        t0 = time.monotonic()
        self.stats.n_solo += 1
        cfg = dataclasses.replace(self.cfg, k=pend.k)
        hierarchy = None
        retries = 0
        if self.deadline_s is not None and not pend.churn \
                and pend.mode == "cold":
            pend.degrade = max(pend.degrade,
                               self._degrade_level(t0 - pend.arrival))
        sp.set(degrade=pend.degrade)
        try:
            if pend.churn and pend.warm_U is not None:
                res, hierarchy, retries = self._churn_solve(pend, cfg)
            elif pend.degrade == 2:
                # level 2: p=2-init labels — one eigensolve, no descent
                from repro.core import lobpcg

                _, U0 = lobpcg.smallest_eigvecs(
                    pend.W, pend.k, normalized=cfg.normalized_init,
                    seed=cfg.seed)
                self.stats.n_degraded += 1
                _obs_trace.ACTIVE.instant("serve.degrade", cat="serve",
                                          req_id=pend.req_id, level=2)
                solve_s = time.monotonic() - t0
                self.stats.solve_s += solve_s
                self._finish(pend, np.asarray(jnp.linalg.qr(U0)[0]),
                             lane="solo", lane_index=0, batch_size=1,
                             launched=t0, solve_s=solve_s,
                             trace_new=False, p_final=2.0, hierarchy=None)
                return
            else:
                if pend.degrade == 1:
                    # level 1: schedule tail only — p=2 eigensolve in,
                    # one warm step at p_target out
                    from repro.core import lobpcg

                    _, U0 = lobpcg.smallest_eigvecs(
                        pend.W, pend.k, normalized=cfg.normalized_init,
                        seed=cfg.seed)
                    cfg = dataclasses.replace(
                        cfg, init_U=np.asarray(jnp.linalg.qr(U0)[0]),
                        warm_p_steps=1, multilevel=None)
                    self.stats.n_degraded += 1
                    _obs_trace.ACTIVE.instant("serve.degrade", cat="serve",
                                              req_id=pend.req_id, level=1)
                elif pend.warm_U is not None:
                    cfg = dataclasses.replace(cfg, init_U=pend.warm_U,
                                              multilevel=None)
                elif self.ml is not None:
                    cfg = dataclasses.replace(cfg, multilevel=self.ml)
                res = _psc.p_spectral_cluster(pend.W, cfg)
                if self.ml is not None and pend.warm_U is None \
                        and pend.degrade == 0:
                    # keep the hierarchy for future churn ticks
                    from repro.multilevel import build_hierarchy
                    from repro.multilevel.vcycle import _layout_kwargs
                    hierarchy = build_hierarchy(
                        pend.W, coarse_size=self.ml.coarse_size,
                        max_levels=self.ml.max_levels,
                        min_reduction=self.ml.min_reduction,
                        rounds=self.ml.match_rounds,
                        layout_kwargs=_layout_kwargs(cfg),
                        sparsify=self.ml.sparsify,
                        max_agg=self.ml.match_max_agg)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as exc:            # noqa: BLE001 — isolated
            self._fail(pend, exc, kind=_classify(exc), lane="solo")
            return
        if not np.isfinite(np.asarray(res.U)).all():
            self._fail(pend, "non-finite embedding from the solo solve",
                       kind="nonfinite_result", lane="solo")
            return
        solve_s = time.monotonic() - t0
        self.stats.solve_s += solve_s
        sp.set(retries=retries)
        p_final = res.p_path[-1] if res.p_path else \
            float(registry.p_schedule(self.cfg)[-1])
        self._finish(pend, np.asarray(res.U), lane="solo", lane_index=0,
                     batch_size=1, launched=t0, solve_s=solve_s,
                     trace_new=False, p_final=p_final, hierarchy=hierarchy,
                     precomputed=res, retries=retries)

    def _finish(self, pend: _Pending, U: np.ndarray, *, lane: str,
                lane_index: int, batch_size: int, launched: float,
                solve_s: float, trace_new: bool, p_final: float, hierarchy,
                precomputed=None, retries: int = 0) -> None:
        """Stage 3 + metrics on the caller's original graph, cache
        store, stats.  ``launched`` is when the solve that served this
        request started; the ``serve.finish`` span and ``finish_s``
        cover this request's stage 3 alone (its end follows host
        reads of the labels and cuts, so it needs no fence)."""
        W, k = pend.W, pend.k
        t0 = time.monotonic()
        with _obs_trace.ACTIVE.span("serve.finish", cat="serve",
                                    req_id=pend.req_id, lane=lane,
                                    lane_index=lane_index):
            if precomputed is not None:
                labels = np.asarray(precomputed.labels)
                rcut, ncut = precomputed.rcut, precomputed.ncut
            else:
                _, k_final = _psc.stage_keys(self.cfg.seed)
                labels = np.asarray(_psc.discretize(
                    jnp.asarray(U), k, k_final,
                    restarts=self.cfg.kmeans_restarts,
                    iters=self.cfg.kmeans_iters))
                rcut = float(metrics.rcut(W, labels, k))
                ncut = float(metrics.ncut(W, labels, k))
            self.cache.store(CacheEntry(
                U=np.asarray(U), labels=labels, p_final=p_final, rcut=rcut,
                fingerprint=pend.fp, hierarchy=hierarchy))
        finish_s = time.monotonic() - t0
        st = ServeStats(
            req_id=pend.req_id, n=W.n_rows, nnz=W.nnz, k=k, lane=lane,
            mode="churn" if pend.churn else pend.mode,
            cache_tier=pend.cache_tier,
            bucket=pend.spec.key if pend.spec else None,
            batch_size=batch_size, queue_s=launched - pend.arrival,
            solve_s=solve_s, trace_new=trace_new, p_final=p_final,
            degrade=pend.degrade, retries=retries, finish_s=finish_s)
        self._results[pend.req_id] = ServeResult(
            req_id=pend.req_id, labels=labels, U=np.asarray(U), rcut=rcut,
            ncut=ncut, stats=st)
        self.stats.n_results += 1
        if pend.churn:
            self.stats.n_churn += 1
