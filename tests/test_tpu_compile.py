"""Ahead-of-time compiles of the clustering hot-loop ops for a TPU v5e.

The TPU compiler ships with jax and compiles for a chip that is
described, not attached, so these tests guard what the chip would
accept at real sizes without one: the p-Laplacian apply / HVP as the
TPU runs them on the ELL path (n = 2^20) and on the SELL-C-σ path
(n = 2^18), the BSR ``edge_pallas`` kernels at a real block count, and
the refusal that keeps the SELL-C-σ Pallas kernels off the chip
(``grblas.backends.sellcs_uses_pallas``).

Operands are shapes only (``jax.ShapeDtypeStruct`` on the described
device), so nothing is allocated.  The topology is described inside a
module fixture, never at import: only one process may load the TPU
library, and describing it is deferred until a test of this file runs.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.grblas import Descriptor, SparseMatrix, mxm
from repro.grblas.backends import select_backend
from repro.grblas.semiring import (plap_edge_semiring, plap_hvp_edge_semiring,
                                   reals_ring)

K = 4
P, EPS = 1.4, 1e-8
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # the persistent cache cannot read back entries compiled for a
    # described chip; keep these compiles out of it, and in 32-bit mode
    # as the chip runs them
    cache = jax.config.jax_enable_compilation_cache
    x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    with pytest.MonkeyPatch.context() as mp:
        # trace the programs the chip runs: the platform rules
        # (auto dispatch, sellcs_spmm.ref.slot_sum) ask default_backend
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)
    jax.config.update("jax_enable_x64", x64)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ell_matrix(sh, n, width, nnz):
    S = lambda shape, dt: _sds(sh, shape, dt)
    return SparseMatrix(n_rows=n, n_cols=n, nnz=nnz,
                        rows=S((nnz,), jnp.int32), cols=S((nnz,), jnp.int32),
                        vals=S((nnz,), jnp.float32),
                        ell_cols=S((n, width), jnp.int32),
                        ell_vals=S((n, width), jnp.float32))


def _sellcs_matrix(sh, n, runs, C=32):
    """SELL-C-σ layout with the given (rows, width) width runs: a hub
    run, a mid run, and the long low-degree background."""
    S = lambda shape, dt: _sds(sh, shape, dt)
    row0 = tuple(int(x) for x in np.cumsum([0] + [r for r, _ in runs])[:-1])
    n_pad = sum(r for r, _ in runs)
    nnz = sum(r * w for r, w in runs)
    return SparseMatrix(
        n_rows=n, n_cols=n, nnz=nnz, rows=S((nnz,), jnp.int32),
        cols=S((nnz,), jnp.int32), vals=S((nnz,), jnp.float32),
        sell_c=C, sell_sigma=n, sell_n_pad=n_pad, sell_row0=row0,
        sell_perm=S((n_pad,), jnp.int32), sell_inv=S((n,), jnp.int32),
        sell_cols=tuple(S((r, w), jnp.int32) for r, w in runs),
        sell_vals=tuple(S((r, w), jnp.float32) for r, w in runs),
        sell_scatter=tuple(S((r, w), jnp.int32) for r, w in runs))


def _ring_args(name, sh, n):
    X = _sds(sh, (n, K), jnp.float32)
    if name == "plap_hvp":
        return plap_hvp_edge_semiring(P, EPS), (X, X)
    if name == "plap_apply":
        return plap_edge_semiring(P, EPS), X
    return reals_ring, X


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM, used
    return compiled


@pytest.mark.parametrize("ring_name", ["plap_apply", "plap_hvp"])
def test_ell_path_ops_compile(one_chip, ring_name):
    """delaunay-like graph at n = 2^20 (6 nnz per row, ELL width 16)
    under backend="auto" — the flat-solve path of a low-skew graph."""
    n = 2 ** 20
    W = _ell_matrix(one_chip, n, 16, 6 * n)
    ring, X = _ring_args(ring_name, one_chip, n)
    assert select_backend(W, X, ring, Descriptor()).name == "ell"
    compiled = _compile(lambda W, X: mxm(W, X, ring), W, X)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("ring_name", ["reals", "plap_apply", "plap_hvp"])
def test_sellcs_path_ops_compile(one_chip, ring_name):
    """Skewed-degree graph at n = 2^18 on the sellcs backend: the chip
    runs its XLA path (no Mosaic kernel in the program)."""
    n = 2 ** 18
    W = _sellcs_matrix(one_chip, n, [(64, 512), (128, 48), (n - 192, 24)])
    ring, X = _ring_args(ring_name, one_chip, n)
    desc = Descriptor(backend="sellcs")
    compiled = _compile(lambda W, X: mxm(W, X, ring, desc=desc), W, X)
    assert "tpu_custom_call" not in compiled.as_text()


def test_sellcs_pallas_kernel_refused_by_tpu_compiler(one_chip):
    """The evidence behind sellcs_uses_pallas: Mosaic cannot lower the
    kernels' sublane gather.  When this starts compiling, the rule can
    hand the chip back to the kernels."""
    from repro.kernels.sellcs_spmm import sellcs_plap_apply_pallas

    n_pad = 4096
    cols = _sds(one_chip, (n_pad, 8), jnp.int32)
    vals = _sds(one_chip, (n_pad, 8), jnp.float32)
    Xp = _sds(one_chip, (n_pad, K), jnp.float32)
    with pytest.raises(Exception, match="Shape mismatch in input, indices"):
        jax.jit(lambda c, v, x: sellcs_plap_apply_pallas(
            c, v, x, 32, p=P, eps=EPS)).lower(cols, vals, Xp).compile()


@pytest.mark.parametrize("kind", ["apply", "hvp"])
def test_edge_pallas_kernels_compile(one_chip, kind):
    """The fused BSR p-Laplacian kernels at a real block count
    (2^18 rows in 128-row blocks, four tiles per block row)."""
    from repro.kernels.plap_edge.plap_edge import (plap_apply_pallas,
                                                   plap_hvp_pallas)

    n_rb, n_blocks, bs = 2048, 8192, 128
    blocks = _sds(one_chip, (n_blocks, bs, bs), jnp.float32)
    idx = _sds(one_chip, (n_blocks,), jnp.int32)
    X = _sds(one_chip, (n_rb * bs, K), jnp.float32)
    if kind == "apply":
        fn = lambda b, i, r, x: plap_apply_pallas(b, i, r, x, n_rb, bs, P, EPS)
        args = (blocks, idx, idx, X)
    else:
        fn = lambda b, i, r, u, e: plap_hvp_pallas(b, i, r, u, e, n_rb, bs,
                                                   P, EPS)
        args = (blocks, idx, idx, X, X)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def _innermost_loop_text(hlo: str) -> str:
    """The HLO text of the innermost ``while`` body (the one that holds
    no other while), with every computation it calls."""
    import re

    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)

    def closure(name, seen):
        for callee in re.findall(r"(?:calls|to_apply|body|condition)=%?"
                                 r"([\w.\-]+)", "\n".join(comps[name])):
            if callee in comps and callee not in seen:
                seen.add(callee)
                closure(callee, seen)
        return seen

    bodies = re.findall(r" while\(.*?body=%?([\w.\-]+)", hlo)
    texts = ["\n".join(line for c in closure(b, {b}) for line in comps[c])
             for b in bodies]
    inner = [t for t in texts if " while(" not in t]
    assert len(inner) == 1, len(inner)
    return inner[0]


def _vertex_op_lines(text: str, op: str, dims) -> list:
    """HLO lines of ``op`` whose shapes carry one of ``dims``."""
    import re

    return [line for line in text.splitlines() if f" {op}(" in line
            and any(int(d) in dims
                    for shape in re.findall(r"\[([\d,]+)\]", line)
                    for d in shape.split(","))]


def test_dense_bucket_solver_builds_what_once_per_newton_step(one_chip):
    """The serve bucket lane at the stream cell's bucket (n_b 128, nnz
    2048, k 4, batch 8) as the chip compiles it: no gather over the
    vertex or edge axis, no scatter in any loop, and no power over the
    (8, 4, 128, 128) pairwise tile in the tCG loop (W-hat is built once
    per Newton step, not re-derived inside each tCG step's product
    fusion)."""
    from repro.core import PSCConfig
    from repro.serve import psc_engine
    from repro.serve.bucketing import BucketSpec

    n, nnz, b = 128, 2048, 8
    cfg = PSCConfig(k=K, reorder="none", newton_iters=20, tcg_iters=12)
    solver, _ = psc_engine._bucket_solver(
        BucketSpec(n=n, nnz=nnz, k=K, mode="cold"), cfg)
    S = lambda shape, dt: _sds(one_chip, shape, dt)
    hlo = solver.lower(S((b, nnz), jnp.int32), S((b, nnz), jnp.int32),
                       S((b, nnz), jnp.float32), S((b, n), jnp.float32),
                       S((b, n, K), jnp.float32)).compile().as_text()
    tcg = _innermost_loop_text(hlo)
    assert _vertex_op_lines(hlo, "gather", (n, nnz)) == []
    assert hlo.count(" scatter(") == 1
    assert " scatter(" not in tcg
    tile_pows = [line for line in tcg.splitlines()
                 if "f32[8,4,128,128]" in line and " power(" in line]
    assert tile_pows == []
