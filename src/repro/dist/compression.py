"""Int8 gradient compression with error feedback for data-parallel
gradient reduction (1-bit-Adam / EF-SGD style).

On a pure-DP mesh the gradient all-reduce is the only inter-replica
traffic; shipping int8 instead of fp32 cuts it 4x.  Naive quantisation
biases the step, so the quantisation residual is carried forward and
added to the next step's gradient (*error feedback*): the running MEAN
of the compressed stream converges to the true gradient, which is the
contract tested in tests/test_train_substrate.py.

NOTE on what is modelled vs. realised: this module implements the
*numerics* of compressed reduction (quantise -> reduce -> residual
carry).  The psum here runs on the dequantised fp32 values, so under
GSPMD-jit the wire bytes are NOT yet reduced — realising the 4x needs
the explicit-SPMD train step that all-gathers (q, scale) pairs over
the axis (ROADMAP open item); the step-level contract and convergence
behaviour are identical, which is what callers depend on today.

API (leaf-wise over arbitrary pytrees):
  quantize_int8(x)            -> (int8 values, float32 scalar scale)
  dequantize_int8(q, scale)   -> float32 reconstruction
  init_error_feedback(tree)   -> zero residual tree
  compressed_psum_tree(grads, err, mesh, axis)
                              -> (reduced grads, new residual tree)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray):
    """Symmetric per-tensor int8 quantisation.

    Returns (q, scale) with q in [-127, 127] and x ~= q * scale; the
    worst-case elementwise error is scale/2 (round-to-nearest).
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, jnp.asarray(1e-30, jnp.float32)) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def init_error_feedback(tree):
    """Zero quantisation-residual state shaped like the gradient tree."""
    return jax.tree.map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), tree)


def _pmean_tree(tree, mesh, axis):
    """Mean of per-device leaf values along ``axis`` (identity if the
    axis has one device — e.g. CPU tests)."""
    shape = dict(mesh.shape)
    if axis not in shape:
        raise ValueError(f"compression axis {axis!r} not in mesh axes "
                         f"{tuple(shape)}")
    size = shape[axis]
    if size <= 1:
        return tree

    def body(t):
        return jax.tree.map(lambda v: jax.lax.psum(v, axis) / size, t)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    return fn(tree)


def compressed_psum_tree(grads, err, mesh, axis: str = "data"):
    """Error-feedback-compensated compressed gradient reduction.

    Per leaf: c = g + err is quantised to int8, the dequantised value
    is mean-reduced over the ``axis`` replicas, and the local residual
    c - deq(c) becomes the next step's err.  Returns (reduced, new_err);
    thread new_err through successive steps (see train/loop.py).
    """
    comp = jax.tree.map(
        lambda g, e: g.astype(jnp.float32) + e, grads, err)
    deq = jax.tree.map(
        lambda c: dequantize_int8(*quantize_int8(c)), comp)
    new_err = jax.tree.map(jnp.subtract, comp, deq)
    return _pmean_tree(deq, mesh, axis), new_err
