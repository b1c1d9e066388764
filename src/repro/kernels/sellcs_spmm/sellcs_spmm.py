"""SELL-C-σ SpMM Pallas kernels — sliced-ELLPACK with per-slice padding.

One ``pallas_call`` per *width run* (consecutive slices of equal padded
width w — contiguous after the σ-window degree sort), grid = one step
per slice.  Per grid step the kernel sees:

    cols  (C, w) int32   slice column indices, PERMUTED row space
    vals  (C, w) dtype   slice stored values (pads are 0)
    Xp    (n_pad, k)     the σ-permuted multivector, whole, VMEM-resident
    own   (C, k)         the slice's own rows of Xp (edge-semiring kinds)

and writes the slice's (C, k) output block.  The neighbour gather is a
``jnp.take`` along the sublane axis of the VMEM-resident Xp (Mosaic
dynamic gather; exact in interpret mode).  C should be a multiple of the
f32 sublane (8) and ideally the 128 lane width on real TPUs so the
output block tiles cleanly.

Keeping Xp whole in VMEM bounds this kernel to n_pad * k * 4 bytes of
VMEM (~0.5 MB at n=32k, k=4); beyond that the production path is the
same kernel over row-partitioned shards (the "dist" backend composes),
or an HBM-resident Xp with per-slice DMA gathers.

The TPU compiler refuses these kernels at every size: Mosaic cannot
lower the ``jnp.take`` sublane gather ("Shape mismatch in input,
indices and output").  So they run only in interpret mode, as the
numerics pin of the jnp path the "sellcs" backend runs on the chip
(``grblas.backends.sellcs_uses_pallas``).

Three ring kinds, mirroring the ELL/edge capability split:

    sellcs_spmm_pallas       y_i = sum_j a_ij x_j            (reals ring)
    sellcs_plap_apply_pallas y_i = sum_j w_ij phi_p(x_i-x_j) (gradient op)
    sellcs_plap_hvp_pallas   y_i = sum_j w_ij phi'(u_i-u_j)(e_i-e_j)

Pad entries store col=self, val=0: each kind's multiply annihilates on
w=0, so the pad contributes the add-identity (the ELL pad-soundness
contract, DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import phi as PHI


def _gather(x, idx):
    """(C*w,) row gather from the VMEM-resident (n_pad, k) multivector."""
    C, w = idx.shape
    return jnp.take(x, idx.reshape(-1), axis=0).reshape(C, w, x.shape[-1])


def _reals_kernel(cols_ref, vals_ref, x_ref, y_ref):
    g = _gather(x_ref[...], cols_ref[...])             # (C, w, k)
    y_ref[...] = jnp.sum(vals_ref[...][..., None] * g, axis=1)


def _apply_kernel(p, eps, cols_ref, vals_ref, x_ref, xo_ref, y_ref):
    g = _gather(x_ref[...], cols_ref[...])             # x_j  (C, w, k)
    x_i = xo_ref[...][:, None, :]                      # own rows
    contrib = vals_ref[...][..., None] * PHI.phi(x_i - g, p, eps)
    y_ref[...] = jnp.sum(contrib, axis=1)


def _hvp_kernel(p, eps, cols_ref, vals_ref, u_ref, uo_ref, e_ref, eo_ref,
                y_ref):
    idx = cols_ref[...]
    du = uo_ref[...][:, None, :] - _gather(u_ref[...], idx)
    de = eo_ref[...][:, None, :] - _gather(e_ref[...], idx)
    contrib = vals_ref[...][..., None] * PHI.phi_prime(du, p, eps) * de
    y_ref[...] = jnp.sum(contrib, axis=1)


def _run_specs(C, w, n_pad, k, slice0):
    slc = pl.BlockSpec((C, w), lambda s: (s, 0))       # cols / vals
    full = pl.BlockSpec((n_pad, k), lambda s: (0, 0))  # whole Xp resident
    own = pl.BlockSpec((C, k), lambda s: (s + slice0, 0))
    out = pl.BlockSpec((C, k), lambda s: (s, 0))
    return slc, full, own, out


def _call(kernel, n_slices, in_specs, out_spec, rows_r, k, dtype, interpret,
          args):
    return pl.pallas_call(
        kernel,
        grid=(n_slices,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((rows_r, k), dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*args)


@functools.partial(jax.jit, static_argnames=("slice_c", "slice0", "interpret"))
def sellcs_spmm_pallas(cols, vals, Xp, slice_c: int, slice0: int = 0,
                       interpret: bool = False) -> jnp.ndarray:
    """Reals-ring SpMM over one width run.  cols/vals: (rows_r, w);
    Xp: (n_pad, k) permuted multivector.  Returns (rows_r, k)."""
    rows_r, w = cols.shape
    n_pad, k = Xp.shape
    n_slices = rows_r // slice_c
    slc, full, _, out = _run_specs(slice_c, w, n_pad, k, slice0)
    return _call(_reals_kernel, n_slices, [slc, slc, full], out,
                 rows_r, k, Xp.dtype, interpret, (cols, vals, Xp))


@functools.partial(jax.jit, static_argnames=("slice_c", "slice0", "p", "eps",
                                             "interpret"))
def sellcs_plap_apply_pallas(cols, vals, Xp, slice_c: int, slice0: int = 0,
                             p: float = 1.5, eps: float = 1e-9,
                             interpret: bool = False) -> jnp.ndarray:
    """p-Laplacian apply over one width run (edge kind "plap_apply")."""
    rows_r, w = cols.shape
    n_pad, k = Xp.shape
    n_slices = rows_r // slice_c
    slc, full, own, out = _run_specs(slice_c, w, n_pad, k, slice0)
    return _call(functools.partial(_apply_kernel, p, eps), n_slices,
                 [slc, slc, full, own], out, rows_r, k, Xp.dtype, interpret,
                 (cols, vals, Xp, Xp))


@functools.partial(jax.jit, static_argnames=("slice_c", "slice0", "p", "eps",
                                             "interpret"))
def sellcs_plap_hvp_pallas(cols, vals, Up, Ep, slice_c: int, slice0: int = 0,
                           p: float = 1.5, eps: float = 1e-9,
                           interpret: bool = False) -> jnp.ndarray:
    """Newton HVP (pair-edge kind "plap_hvp") over one width run."""
    rows_r, w = cols.shape
    n_pad, k = Up.shape
    n_slices = rows_r // slice_c
    slc, full, own, out = _run_specs(slice_c, w, n_pad, k, slice0)
    return _call(functools.partial(_hvp_kernel, p, eps), n_slices,
                 [slc, slc, full, own, full, own], out, rows_r, k, Up.dtype,
                 interpret, (cols, vals, Up, Up, Ep, Ep))
