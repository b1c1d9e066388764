"""Pure-jnp oracles for the SELL-C-σ SpMM kernels.

Operate on one width run of the sliced layout (containers._build_sellcs):
cols/vals (rows_r, w) in the PERMUTED row space, multivectors already
σ-permuted to (n_pad, k).  These are also the vectorized execution path
of the "sellcs" backend on every platform (the TPU included) — per-run
gather + ring fold, the sliced analogue of the full-ELL gather path.

Every fold goes through ``slot_sum``.  On the TPU it loops over the w
slots of the padded rows, gathering one (rows, k) block per slot: the
TPU compiler takes minutes to compile the one-shot gather of a
(rows, w) index array once rows is between about 2^14 and 2^18, and the
one-shot (rows, w, k) block pads k to the 128-lane width; the slot loop
compiles in about a second and keeps only (rows, k) live.  Elsewhere it
gathers all slots at once, which runs 3-5x faster than the loop on the
CPU for the SELL-C-σ folds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import phi as PHI


def slot_sum(cols, vals, term):
    """sum over the w slots s of term(cols[:, s], vals[:, s]).

    ``term(c, v)`` maps one slot — c (rows,) column ids, v (rows,) or
    (rows, k) stored values — to its (rows[, k]) contribution.  Pad
    slots must contribute zero (val = 0), the ELL pad-soundness
    contract.  One static rule picks the form: a slot loop on the TPU,
    one gather of all slots elsewhere (see the module docstring)."""
    if jax.default_backend() != "tpu":
        return jnp.sum(jax.vmap(term, in_axes=1)(cols, vals), axis=0)
    rows, w = cols.shape

    def body(s, acc):
        c = jax.lax.dynamic_index_in_dim(cols, s, axis=1, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vals, s, axis=1, keepdims=False)
        return acc + term(c, v)

    out = jax.eval_shape(
        term, jax.ShapeDtypeStruct((rows,), cols.dtype),
        jax.ShapeDtypeStruct((rows,) + vals.shape[2:], vals.dtype))
    return jax.lax.fori_loop(0, w, body, jnp.zeros(out.shape, out.dtype))


def _col(v):
    """Lift a slot's (rows,) values against (rows, k) rows; (rows, k)
    multivalues pass through."""
    return v[:, None] if v.ndim == 1 else v


def sellcs_spmm_ref(cols, vals, Xp):
    """Reals-ring run: y = sum_w vals * Xp[cols].  vals may be (rows, w)
    or (rows, w, k) multivalues (with_vals' Alg-1 W-hat)."""
    return slot_sum(cols, vals, lambda c, v: _col(v) * Xp[c])


def sellcs_plap_apply_ref(cols, vals, Xp, row0: int, p: float, eps: float):
    """p-Laplacian apply run: y_i = sum_j w_ij phi_p(x_i - x_j)."""
    x_i = Xp[row0:row0 + cols.shape[0]]
    return slot_sum(cols, vals,
                    lambda c, v: v[:, None] * PHI.phi(x_i - Xp[c], p, eps))


def sellcs_plap_hvp_ref(cols, vals, Up, Ep, row0: int, p: float, eps: float):
    """Newton HVP run: y_i = sum_j w_ij phi'(u_i-u_j)(e_i-e_j)."""
    rows = cols.shape[0]
    u_i, e_i = Up[row0:row0 + rows], Ep[row0:row0 + rows]
    return slot_sum(cols, vals, lambda c, v: v[:, None] * PHI.phi_prime(
        u_i - Up[c], p, eps) * (e_i - Ep[c]))


# --- shard-local variants (the "dist_sellcs" backend, grblas.dist) ---
# Same per-run gather+fold, but the column ids index a shard's
# extended-local vector (locals then halo slots) and the own rows are an
# explicit gather (the σ-sort is per shard, so own rows aren't a
# contiguous row0 slice of the source vector).

def sellcs_shard_spmm_ref(cols, vals, x_src):
    """Reals-ring run of one shard: y = sum_w vals * x_src[cols]."""
    return slot_sum(cols, vals, lambda c, v: v[:, None] * x_src[c])


def sellcs_shard_plap_apply_ref(cols, vals, x_src, x_own, p: float,
                                eps: float):
    """p-Laplacian apply run of one shard; x_own: (rows, k) the packed
    rows' own entries (gathered from the shard-local vector)."""
    return slot_sum(cols, vals, lambda c, v: v[:, None] * PHI.phi(
        x_own - x_src[c], p, eps))
