"""Unit tests for the repro.dist subsystem: logical-axis rule
resolution (full / partial / replicated, divisibility fallback, axis
reuse) and int8 gradient compression."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (AxisRules, DEFAULT_RULES, DP_RULES,
                                 active_rules, constrain, logical_to_mesh,
                                 resolve_spec, rules_for, set_active_rules,
                                 use_rules)
from repro.dist.compression import (compressed_psum_tree, dequantize_int8,
                                    init_error_feedback, quantize_int8)
from jax.sharding import AbstractMesh


def single_pod():
    # shape-only stand-in for make_production_mesh(multi_pod=False):
    # resolve_spec reads mesh.shape, never device placement
    return AbstractMesh((16, 16), ("data", "model"))


def multi_pod():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


# ------------------------------------------------------------ resolve_spec

def test_fully_sharded_param():
    spec = resolve_spec((4096, 16384), ("embed", "mlp"), single_pod(),
                        DEFAULT_RULES)
    assert spec == P(None, "model")


def test_replicated_axes_trim():
    spec = resolve_spec((64, 64), ("latent", None), single_pod(),
                        DEFAULT_RULES)
    assert spec == P()


def test_divisibility_fallback_replicates():
    # 6 kv heads don't divide the 16-wide model axis -> replicate them;
    # batch=2 doesn't divide data=16 either -> whole spec degrades
    spec = resolve_spec((32, 6, 128, 64), ("batch", "kv", "seq", None),
                        single_pod(), DEFAULT_RULES)
    assert spec == P("data")
    spec = resolve_spec((2, 6, 128, 64), ("batch", "kv", "seq", None),
                        single_pod(), DEFAULT_RULES)
    assert spec == P()


def test_partial_candidate_list():
    # batch: ("pod", "data") — pod absent on a single pod, data applies
    spec = resolve_spec((32, 1024), ("batch", "seq"), single_pod(),
                        DEFAULT_RULES)
    assert spec == P("data")
    spec = resolve_spec((32, 1024), ("batch", "seq"), multi_pod(),
                        DEFAULT_RULES)
    assert spec == P(("pod", "data"))


def test_axis_consumed_once():
    # pure-DP batch takes data AND model; seq_sp then finds model used
    spec = resolve_spec((256, 512, 64), ("batch", "seq_sp", "embed"),
                        single_pod(),
                        DP_RULES.extend(seq_sp=("model",)))
    assert spec == P(("data", "model"))


def test_attn_batch_spreads_over_model():
    spec = resolve_spec((256, 8, 128, 64),
                        ("attn_batch", None, "seq", None),
                        single_pod(), DEFAULT_RULES)
    assert spec == P(("data", "model"))


def test_extend_overrides():
    rules = DEFAULT_RULES.extend(embed=("model",))
    assert resolve_spec((4096,), ("embed",), single_pod(), rules) \
        == P("model")
    # the base table is untouched
    assert resolve_spec((4096,), ("embed",), single_pod(), DEFAULT_RULES) \
        == P()


def test_logical_to_mesh_ignores_shape():
    out = logical_to_mesh(("batch", "mlp", None), single_pod(),
                          DEFAULT_RULES)
    assert out == ("data", "model", None)


# ------------------------------------------- factored optimizer moments

def test_factored_moment_specs_reresolve_not_slice():
    """Dropping a dim frees its mesh axis: the col moment of a
    ("heads", "mlp") param — both logical names candidate for "model",
    heads wins on the full param — must shard over "model" once heads
    is gone.  Hand-slicing the param's PartitionSpec (the old
    launch/dryrun.py::opt_state_shardings) replicated it."""
    from repro.dist.sharding import factored_moment_specs

    mesh = single_pod()
    full = resolve_spec((32, 16384), ("heads", "mlp"), mesh, DEFAULT_RULES)
    assert full == P("model")                  # mlp lost the greedy race
    row, col = factored_moment_specs((32, 16384), ("heads", "mlp"), mesh,
                                     DEFAULT_RULES)
    assert row == P("model")                   # (32,) heads keeps model
    assert col == P("model")                   # (16384,) mlp now gets it
    # hand-slicing operated on the trimmed param spec (trailing Nones
    # dropped, so entries don't even align with dims): parts[:-1] here
    # replicated the row moment the param itself shards
    assert P(*tuple(full)[:-1]) == P()


def test_factored_moment_specs_divisibility_rechecked():
    """Divisibility is checked against the MOMENT's extents: a (48, 6)
    ("mlp", "kv") param replicates kv (6 % 16 != 0); the row moment
    (48,) still shards over model because 48 divides 16."""
    from repro.dist.sharding import factored_moment_specs

    mesh = single_pod()
    row, col = factored_moment_specs((48, 6), ("mlp", "kv"), mesh,
                                     DEFAULT_RULES)
    assert row == P("model") and col == P()


def test_opt_state_shardings_use_factored_specs():
    """dryrun.opt_state_shardings derives adafactor moments through
    factored_moment_specs (ROADMAP AxisRules follow-up): every moment's
    spec equals a fresh resolve on its own (shape, logical)."""
    from repro.dist.sharding import factored_moment_specs
    from repro.launch import dryrun
    from repro.models import model as M
    from repro.models.layers import is_pab
    from repro.configs import get_config

    cfg = get_config("gemma-2b")
    mesh = jax.make_mesh((1,), ("model",))
    state = dryrun.opt_state_shardings("adafactor", cfg, mesh)
    ab_leaves = jax.tree.leaves(M.abstract_params(cfg), is_leaf=is_pab)
    mo_leaves = jax.tree.leaves(
        state.moments,
        is_leaf=lambda x: type(x).__name__ == "FactoredMoment")
    assert len(ab_leaves) == len(mo_leaves) > 0
    for a, m in zip(ab_leaves, mo_leaves):
        if len(a.shape) >= 2:
            row, col = factored_moment_specs(a.shape, a.logical, mesh)
            assert m.row.spec == row and m.col.spec == col
        else:
            assert m.spec == resolve_spec(a.shape, a.logical, mesh)


# --------------------------------------------------- active rules registry

def test_rules_for_thresholds():
    assert rules_for(2e9) is DP_RULES
    assert rules_for(400e9) is DEFAULT_RULES


def test_set_active_rules_roundtrip():
    prev = set_active_rules(DP_RULES)
    try:
        assert active_rules() is DP_RULES
    finally:
        set_active_rules(prev)
    assert active_rules() is prev


def test_use_rules_scopes():
    base = active_rules()
    with use_rules(DP_RULES):
        assert active_rules() is DP_RULES
    assert active_rules() is base


def test_constrain_none_mesh_identity():
    x = jnp.ones((4, 4))
    assert constrain(x, None, ("batch", None)) is x


def test_constrain_resolves_under_jit():
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.ones((4, 8))
    y = jax.jit(lambda v: constrain(v, mesh, ("batch", "embed")))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# ------------------------------------------------------------- compression

def test_quantize_roundtrip_bound():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((128, 32)), jnp.float32)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    assert int(jnp.max(jnp.abs(q))) <= 127
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) / 2 + 1e-7       # round-to-nearest bound


def test_quantize_zero_input():
    q, s = quantize_int8(jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(q), 0)
    assert np.isfinite(float(s))


def test_error_feedback_accumulates_residual():
    g = {"a": jnp.asarray([[0.3, -1.7, 0.002]], jnp.float32)}
    err = init_error_feedback(g)
    mesh = jax.make_mesh((1,), ("data",))
    out, err2 = compressed_psum_tree(g, err, mesh, "data")
    q, s = quantize_int8(g["a"])
    np.testing.assert_allclose(np.asarray(out["a"]),
                               np.asarray(dequantize_int8(q, s)))
    np.testing.assert_allclose(np.asarray(err2["a"]),
                               np.asarray(g["a"] - out["a"]), atol=1e-7)


def test_compressed_train_step_converges():
    """make_train_step(grad_compression='int8') threads the residual and
    still drives the loss down."""
    from repro.configs import get_reduced_config
    from repro.data import SyntheticTokens
    from repro.models import model as M
    from repro.train import (TrainConfig, init_compression_state,
                             make_optimizer, make_train_step)

    cfg = get_reduced_config("gemma-2b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    data = SyntheticTokens(cfg, batch=4, seq=32, seed=0)
    mesh = jax.make_mesh((1,), ("data",))
    tc = TrainConfig(optimizer="adamw", learning_rate=5e-3, warmup_steps=2,
                     total_steps=40, clip_norm=1.0, grad_compression="int8")
    opt = make_optimizer(tc)
    step = jax.jit(make_train_step(cfg, tc, mesh=mesh, opt=opt))
    opt_state = opt.init(params)
    err = init_compression_state(params)
    losses = []
    for i in range(20):
        params, opt_state, err, m = step(params, opt_state, err,
                                         data.batch_at(i % 4))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses[::5]
    assert np.isfinite(losses).all()
