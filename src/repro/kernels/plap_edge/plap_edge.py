"""Fused p-Laplacian edge-semiring SpMM — the paper's semiring-
parameterized grb::vxm as a TPU Pallas kernel.

Two variants over the same BSR tile layout as bsr_spmm:

  plap_apply_pallas : y_i += sum_j w_ij phi_p(x_i - x_j)       (gradient op)
  plap_hvp_pallas   : y_i += sum_j w_ij phi'(u_i-u_j)(e_i-e_j)  (Newton HVP)

The nonlinearity runs on the VPU over a (bs, bs, k_tile) broadcast in
VMEM; nothing (W-hat, differences) is materialized in HBM — this is the
matrix-free adaptation of Algorithm 1 (DESIGN.md §2, item 4).

VMEM at bs=128, k_tile=4: tile 64 KB + 3 vectors 6 KB + broadcast
(bs,bs,k) 256 KB ~= 0.33 MB.  Arithmetic intensity ~ bs*k flops/byte of
tile traffic — compute-dense enough to hide the HBM stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import phi as PHI


def _apply_kernel(p, eps, indices_ref, row_ids_ref, blocks_ref,
                  xc_ref, xr_ref, y_ref):
    b = pl.program_id(0)
    row = row_ids_ref[b]
    prev_row = row_ids_ref[jnp.maximum(b - 1, 0)]

    @pl.when(jnp.logical_or(b == 0, row != prev_row))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    w = blocks_ref[0]                                  # (bs, bs)
    x_j = xc_ref[...]                                  # (bs, k)  neighbours
    x_i = xr_ref[...]                                  # (bs, k)  own rows
    diff = x_i[:, None, :] - x_j[None, :, :]           # (bs, bs, k)
    contrib = w[:, :, None] * PHI.phi(diff, p, eps)
    y_ref[...] += jnp.sum(contrib, axis=1)


def _hvp_kernel(p, eps, indices_ref, row_ids_ref, blocks_ref,
                uc_ref, ur_ref, ec_ref, er_ref, y_ref):
    b = pl.program_id(0)
    row = row_ids_ref[b]
    prev_row = row_ids_ref[jnp.maximum(b - 1, 0)]

    @pl.when(jnp.logical_or(b == 0, row != prev_row))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    w = blocks_ref[0]
    du = ur_ref[...][:, None, :] - uc_ref[...][None, :, :]
    de = er_ref[...][:, None, :] - ec_ref[...][None, :, :]
    contrib = w[:, :, None] * PHI.phi_prime(du, p, eps) * de
    y_ref[...] += jnp.sum(contrib, axis=1)


def _common_specs(bs, k):
    col_spec = pl.BlockSpec((bs, k), lambda b, idx, rid: (idx[b], 0))
    row_spec = pl.BlockSpec((bs, k), lambda b, idx, rid: (rid[b], 0))
    blk_spec = pl.BlockSpec((1, bs, bs), lambda b, idx, rid: (b, 0, 0))
    out_spec = pl.BlockSpec((bs, k), lambda b, idx, rid: (rid[b], 0))
    return blk_spec, col_spec, row_spec, out_spec


@functools.partial(jax.jit, static_argnames=("n_row_blocks", "block_size",
                                              "p", "eps", "interpret"))
def plap_apply_pallas(blocks, indices, row_ids, X, n_row_blocks,
                      block_size=128, p=1.5, eps=1e-9, interpret=False):
    n_blocks, bs, _ = blocks.shape
    k = X.shape[1]
    blk, colv, rowv, out = _common_specs(bs, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_blocks,),
        in_specs=[blk, colv, rowv], out_specs=out)
    return pl.pallas_call(
        functools.partial(_apply_kernel, p, eps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bs, k), X.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(indices, row_ids, blocks, X, X)


@functools.partial(jax.jit, static_argnames=("n_row_blocks", "block_size",
                                              "p", "eps", "interpret"))
def plap_hvp_pallas(blocks, indices, row_ids, U, Eta, n_row_blocks,
                    block_size=128, p=1.5, eps=1e-9, interpret=False):
    n_blocks, bs, _ = blocks.shape
    k = U.shape[1]
    blk, colv, rowv, out = _common_specs(bs, k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_blocks,),
        in_specs=[blk, colv, rowv, colv, rowv], out_specs=out)
    return pl.pallas_call(
        functools.partial(_hvp_kernel, p, eps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bs, k), U.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(indices, row_ids, blocks, U, U, Eta, Eta)
