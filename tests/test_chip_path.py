"""The pieces that put the clustering main path on a TPU: the compile
cache rule, the SELL-C-σ implementation rule, span timing that knows
when it runs under a jax transformation, and ``chip_smoke.py`` itself
(refusal off the chip, and its tiny-size rehearsal)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import PSCConfig
from repro.core.solvers import newton
from repro.graphs import sbm_graph
from repro.grblas import Descriptor, mxm
from repro.grblas.backends import sellcs_uses_pallas
from repro.grblas.semiring import plap_edge_semiring
from repro.launch import compile_cache
from repro.obs import trace as obs_trace

ROOT = Path(__file__).resolve().parent.parent


# ------------------------------------------------------------ compile cache

def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself: no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert Path(first) == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# --------------------------------------------------- sellcs implementation

def _skewed_graph():
    W, _ = sbm_graph([200, 8], p_in=0.03, p_out=0.5, seed=0)
    assert W.sell_cols is not None and W.ell_cols is None
    return W


@pytest.mark.parametrize("interpret", [False, True])
def test_sellcs_implementation_rule_on_tpu(monkeypatch, interpret):
    """On the TPU the sellcs backend runs its XLA path, so the Newton
    memo keeps p traced (one trace per schedule); only interpret mode
    takes the Pallas kernels, which bake p in."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from jax.experimental import pallas as pl

    calls = []
    real = pl.pallas_call

    def counting(*a, **kw):
        calls.append(kw.get("interpret"))
        return real(*a, **kw)

    monkeypatch.setattr(pl, "pallas_call", counting)
    W = _skewed_graph()
    X = jnp.asarray(np.random.default_rng(0).standard_normal((W.n_rows, 4)),
                    jnp.float32)
    ring = plap_edge_semiring(1.5, 1e-8)
    got = mxm(W, X, ring, desc=Descriptor(backend="sellcs",
                                          interpret=interpret))
    want = mxm(W, X, ring, desc=Descriptor(backend="coo"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert sellcs_uses_pallas(interpret) is interpret
    assert bool(calls) is interpret
    cfg = PSCConfig(k=4, backend="sellcs", interpret=interpret)
    assert newton._needs_static_p(cfg, W, X) is interpret


# ------------------------------------------------------------- under_trace

def test_under_trace_sees_transformations_without_tracer_operands():
    assert not obs_trace.under_trace()
    seen = []

    def probe(x):
        seen.append(obs_trace.under_trace())   # no tracer handed in
        return x

    jax.jit(probe)(1.0)
    jax.vmap(probe)(jnp.ones(3))
    assert seen == [True, True]


def test_mxm_under_jit_with_closed_over_operand_is_not_timed():
    """A jitted region that closes over a concrete multivector must
    record a dispatch instant, not a wall-clock span of the trace."""
    W = _skewed_graph()
    X = jnp.ones((W.n_rows, 2), jnp.float32)
    tracer = obs_trace.Tracer()
    with obs_trace.use(tracer):
        jax.jit(lambda: mxm(W, X))()
        mxm(W, X)
    spans = [s for s in tracer.spans if s.name == "grblas.mxm"]
    instants = [e for e in tracer.events if e["name"] == "grblas.dispatch"]
    assert len(spans) == 1 and len(instants) == 1


# -------------------------------------------------------------- chip_smoke

def _run_smoke(args, cwd=ROOT, devices=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_chip_smoke_refuses_a_machine_without_tpu():
    r = _run_smoke([])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_smoke([], cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("args,devices", [
    (["--rehearse"], 1),
    (["--rehearse", "--four-chips"], 4),
])
def test_chip_smoke_rehearsal(args, devices):
    r = _run_smoke(args, devices=devices)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(x) for x in r.stdout.splitlines()
             if x.startswith("{")]
    assert lines[-1]["rehearsal"] == "passed"
    assert lines[-1]["device"]["count"] == devices
    phases = [x for x in lines if x.get("phase") not in (None, "start")]
    assert phases and all(
        c["ok"] for x in phases for c in x["checks"].values())
    assert all(x["pallas_calls"] == 0 for x in phases)


# ------------------------------------------------------ slot-loop folds

@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("case", ["ell_reals", "ell_plap_apply",
                                  "ell_plap_hvp", "sellcs_reals",
                                  "sellcs_plap_apply", "sellcs_plap_hvp",
                                  "sellcs_multivals"])
def test_slot_loop_folds_match_coo(monkeypatch, case, platform):
    """The padded layouts fold slot by slot on the TPU (the form its
    compiler handles fast) and in one gather elsewhere; both forms must
    equal the coo path."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    from repro.graphs import grid_graph
    from repro.grblas.semiring import plap_hvp_edge_semiring, reals_ring

    W = grid_graph(80, 80, build_ell=True, build_sellcs=True)
    rng = np.random.default_rng(1)
    U, E = (jnp.asarray(rng.standard_normal((W.n_rows, 3)), jnp.float32)
            for _ in range(2))
    backend = case.split("_")[0]
    ring, X = reals_ring, U
    if case.endswith("_plap_apply"):
        ring = plap_edge_semiring(1.3, 1e-8)
    elif case.endswith("_plap_hvp"):
        ring, X = plap_hvp_edge_semiring(1.3, 1e-8), (U, E)
    elif case == "sellcs_multivals":
        W = W.with_vals(jnp.asarray(rng.random((W.nnz, 3)), jnp.float32))
    got = mxm(W, X, ring, desc=Descriptor(backend=backend))
    want = mxm(W, X, ring, desc=Descriptor(backend="coo"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
