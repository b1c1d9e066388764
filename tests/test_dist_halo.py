"""Halo-exchange plan construction + the dist_sellcs sharded layout.

Two layers:

* host-side plan tests run in the main process (make_row_partition is
  host numpy; no mesh needed) — plan invariants, the halo/gather
  fallback boundary, the edge-ring square gate, wire-byte accounting;
* a subprocess test under a forced multi-device host platform proves
  the plans compose under a real mesh: halo == gather == coo across
  rings and k, cluster-aligned placement beats shuffled placement in
  wire bytes on a 2-cluster SBM, and the per-shard SELL-C-σ layout
  matches everything else on a skewed-degree graph.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graphs import delaunay_graph, sbm_graph
from repro.grblas import (Descriptor, HALO_FALLBACK_FRAC, SparseMatrix,
                          available_backends, make_row_partition, mxm)
from repro.grblas.semiring import plap_edge_semiring, reals_ring

SRC = Path(__file__).resolve().parent.parent / "src"
N_DEV = os.environ.get("DIST_TEST_DEVICES", "8")


def _graph(r=8, seed=0):
    W, _ = delaunay_graph(r, seed=seed)
    return W


# ------------------------------------------------------- host-side plan

def test_halo_plan_covers_every_remote_column():
    W = _graph()
    S = 4
    Ap = make_row_partition(W, S)
    assert Ap.mode == "halo"
    R, H = Ap.rows_per_shard, Ap.halo_width
    cols = np.asarray(Ap.ell_cols)       # extended-local ids
    send = np.asarray(Ap.send_idx)
    x = np.random.default_rng(0).standard_normal(S * R)
    # simulate the exchange with numpy: shard d's extended vector is its
    # locals plus, at R + s*H + h, row send[s, d*H + h] of shard s
    for d in range(S):
        x_ext = np.concatenate(
            [x[d * R:(d + 1) * R]]
            + [x[s * R + send[s, d * H:(d + 1) * H]] for s in range(S)])
        assert cols[d].max() < R + S * H
        # the remap must deliver exactly the global column's value
        glob = np.asarray(
            make_row_partition(W, S, mode="gather").ell_cols)[d]
        np.testing.assert_array_equal(x_ext[cols[d]], x[glob])


def test_halo_fallback_boundary():
    W = _graph()
    S = 4
    R = -(-W.n_rows // S)
    Ap = make_row_partition(W, S)
    assert Ap.mode == "halo"
    assert Ap.halo_width <= HALO_FALLBACK_FRAC * R
    # scrambled placement destroys locality -> halo denser than the
    # gather it would replace -> the plan falls back at build time,
    # keeping the computed width so wire_bytes explains the decision
    rng = np.random.default_rng(1)
    asg = rng.permutation(W.n_rows)
    Apx = make_row_partition(W, S, assignment=asg)
    assert Apx.mode == "gather" and Apx.send_idx is None
    assert Apx.halo_width > HALO_FALLBACK_FRAC * R
    # forcing halo on the EXACT placement the auto rule rejected still
    # builds a valid (if wasteful) plan with the same width
    Apf = make_row_partition(W, S, assignment=asg, mode="halo")
    assert Apf.mode == "halo"
    assert Apf.halo_width == Apx.halo_width
    assert Apf.wire_bytes(1)["halo"] >= Ap.wire_bytes(1)["halo"]


def test_wire_bytes_accounting():
    W = _graph()
    S = 4
    Ap = make_row_partition(W, S)
    wb = Ap.wire_bytes(k=8)
    assert wb["halo"] == S * (S - 1) * Ap.halo_width * 8 * 4
    assert wb["gather"] == S * (S - 1) * Ap.rows_per_shard * 8 * 4
    assert wb["halo"] < wb["gather"]
    assert wb["halo_rows_true"] <= S * (S - 1) * Ap.halo_width


def test_edge_ring_square_gate_routes_rectangular_away_from_dist():
    """Regression (satellite): _dist_supports admitted edge rings on
    rectangular operators, and the shard body then read misaligned
    x_i rows.  The gate must exclude dist (and dist_sellcs) exactly
    like every other edge-ring backend excludes itself."""
    W = _graph()
    n = W.n_rows
    r, c, v = W.host_coo()
    Wrect = SparseMatrix.from_coo(r, c, v, (n, n + 32), build_ell=True)
    mesh = jax.make_mesh((1,), ("data",))
    d = Descriptor(mesh=mesh)
    ring = plap_edge_semiring(1.5, eps=1e-8)
    X = jnp.ones((n + 32, 2), jnp.float32)
    names = available_backends(Wrect, X, ring, desc=d)
    assert "dist" not in names and "dist_sellcs" not in names
    # naming the backend anyway fails loudly
    from repro.grblas import BackendUnavailableError
    with pytest.raises(BackendUnavailableError):
        mxm(Wrect, X, ring, desc=Descriptor(backend="dist", mesh=mesh))
    # square operators still route to dist first
    Xsq = jnp.ones((n, 2), jnp.float32)
    assert available_backends(W, Xsq, ring, desc=d)[0] == "dist"


def test_assignment_requires_square():
    W = _graph()
    r, c, v = W.host_coo()
    Wrect = SparseMatrix.from_coo(r, c, v, (W.n_rows, W.n_rows + 8),
                                  build_ell=True)
    with pytest.raises(ValueError, match="square"):
        make_row_partition(Wrect, 4, assignment=np.zeros(W.n_rows, int))
    with pytest.raises(ValueError, match="square|n_shards"):
        make_row_partition(Wrect, 4, mode="halo")


def test_dist_sellcs_requires_layout_on_prebuilt_partition():
    W = _graph()
    mesh = jax.make_mesh((1,), ("data",))
    Ap = make_row_partition(W, 1)               # no sellcs slicing
    X = jnp.ones((W.n_rows, 2), jnp.float32)
    d = Descriptor(backend="dist_sellcs", mesh=mesh)
    from repro.grblas import BackendUnavailableError
    with pytest.raises(BackendUnavailableError):
        mxm(Ap, X, desc=d)
    Aps = make_row_partition(W, 1, sellcs=True)
    got = np.asarray(mxm(Aps, X, desc=d))
    np.testing.assert_allclose(got, np.asarray(mxm(W, X)),
                               rtol=2e-5, atol=2e-5)


def test_sellcs_plan_is_spmd_uniform():
    """Every width run must have identical shapes on all shards — the
    shard_map body is one program."""
    W, _ = sbm_graph([60, 60, 60, 60], 0.3, 0.02, seed=0)
    Ap = make_row_partition(W, 4, sellcs=True, sell_c=8)
    sell = Ap.sell
    S = Ap.n_shards
    for cols, vals, own in zip(sell.run_cols, sell.run_vals, sell.run_own):
        assert cols.shape[0] == S and vals.shape == cols.shape
        assert own.shape == cols.shape[:2]
        assert cols.shape[1] % sell.sell_c == 0
    assert sell.inv.shape == (S, Ap.rows_per_shard)
    # widths strictly decrease across runs (descending degree sort)
    widths = [c.shape[2] for c in sell.run_cols]
    assert widths == sorted(widths, reverse=True)


# ------------------------------------------------- mesh composition test

SCRIPT = textwrap.dedent("""
    import os
    N = int(os.environ["DIST_TEST_DEVICES"])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N}"
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.graphs import sbm_graph
    from repro.grblas import (Descriptor, device_mesh, init_distributed,
                              make_row_partition, mxm)
    from repro.grblas.semiring import plap_edge_semiring

    # the launch path: single-process init is a guarded no-op, the mesh
    # spans the forced host devices
    assert init_distributed() is False
    mesh_all = device_mesh()
    assert int(mesh_all.shape["data"]) == N
    S = 4
    mesh = jax.make_mesh((S,), ("data",))        # 4-shard submesh
    d = Descriptor(backend="dist", mesh=mesh)
    ds = Descriptor(backend="dist_sellcs", mesh=mesh)
    rng = np.random.default_rng(0)
    ring = plap_edge_semiring(1.4, eps=1e-8)

    # 4-cluster SBM, one cluster per shard: the halo carries only cut
    # rows and beats the all-gather in wire bytes (Bernoulli blocks are
    # expanders — only cluster:shard-aligned placement has a small cut)
    W, truth = sbm_graph([128] * S, 0.06, 0.002, seed=0)
    X = jnp.asarray(rng.standard_normal((W.n_rows, 16)), jnp.float32)
    want = np.asarray(mxm(W, X))
    wante = np.asarray(mxm(W, X, ring))
    Ap = make_row_partition(W, S, assignment=truth)
    assert Ap.mode == "halo", Ap.mode
    wb = Ap.wire_bytes(k=16)
    assert wb["halo"] < wb["gather"], wb
    got = np.asarray(mxm(Ap, X, desc=d))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    gote = np.asarray(mxm(Ap, X, ring, desc=d))
    np.testing.assert_allclose(gote, wante, rtol=2e-4, atol=2e-5)

    # shuffled placement pays a bigger halo than the aligned one
    shuf = rng.permutation(W.n_rows)
    Apx = make_row_partition(W, S, assignment=shuf, mode="halo")
    assert Apx.halo_width >= Ap.halo_width
    np.testing.assert_allclose(np.asarray(mxm(Apx, X, desc=d)), want,
                               rtol=2e-5, atol=2e-5)

    # the literal satellite criterion: 2-cluster SBM, cluster-aligned
    # (one cluster per shard on a 2-shard submesh), halo < gather bytes
    W2, truth2 = sbm_graph([256, 256], 0.04, 0.001, seed=0)
    Ap2 = make_row_partition(W2, 2, assignment=truth2)
    assert Ap2.mode == "halo"
    wb2 = Ap2.wire_bytes(k=16)
    assert wb2["halo"] < wb2["gather"], wb2
    d2 = Descriptor(backend="dist", mesh=jax.make_mesh((2,), ("data",)))
    X2 = jnp.asarray(rng.standard_normal((W2.n_rows, 16)), jnp.float32)
    np.testing.assert_allclose(np.asarray(mxm(Ap2, X2, desc=d2)),
                               np.asarray(mxm(W2, X2)),
                               rtol=2e-5, atol=2e-5)

    # halo == forced gather == coo, and the per-shard SELL-C-σ layout
    # agrees for both ring kinds
    Apg = make_row_partition(W, S, assignment=truth, mode="gather")
    np.testing.assert_allclose(np.asarray(mxm(Apg, X, desc=d)), want,
                               rtol=2e-5, atol=2e-5)
    Aps = make_row_partition(W, S, assignment=truth, sellcs=True, sell_c=8)
    np.testing.assert_allclose(np.asarray(mxm(Aps, X, desc=ds)), want,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mxm(Aps, X, ring, desc=ds)), wante,
                               rtol=2e-4, atol=2e-5)

    # k sweep through the sellcs shard layout too
    for k in (1, 8, 32):
        Xk = jnp.asarray(rng.standard_normal(
            (W.n_rows,) if k == 1 else (W.n_rows, k)), jnp.float32)
        np.testing.assert_allclose(np.asarray(mxm(Aps, Xk, desc=ds)),
                                   np.asarray(mxm(W, Xk)),
                                   rtol=2e-5, atol=2e-5)
    print("DIST_HALO_OK")
""")


def test_dist_halo_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu",
                            "DIST_TEST_DEVICES": N_DEV},
                       capture_output=True, text=True, timeout=560)
    assert "DIST_HALO_OK" in r.stdout, r.stdout + "\n" + r.stderr
