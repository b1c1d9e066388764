"""Block-sparse (BSR) SpMM Pallas kernel — the MXU-native SpMV of the
paper's grb::vxm (DESIGN.md §2: CRS gather -> 128x128 dense tiles).

Layout: the matrix is a list of dense (bs, bs) tiles, sorted by
row-block id; ``indices[b]`` is the column-block, ``row_ids[b]`` the
row-block of stored tile b.  The multivector X is (n_cols_pad, k).

Grid = (n_blocks,): one program per stored tile.  Tiles of one row-block
are consecutive, so the output tile (selected by row_ids via scalar
prefetch) stays resident in VMEM across those grid steps — the classic
Pallas reduction-revisiting pattern.  First visit zero-inits.

VMEM per step: bs*bs*4 (tile) + 2*bs*k*4 (X in, Y out) ~= 66 KB at
bs=128, k=16 — far under the ~16 MB v5e VMEM budget; the MXU sees a
(128,128)x(128,k) matmul per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(indices_ref, row_ids_ref, blocks_ref, x_ref, y_ref):
    b = pl.program_id(0)
    row = row_ids_ref[b]
    prev_row = row_ids_ref[jnp.maximum(b - 1, 0)]
    is_first = jnp.logical_or(b == 0, row != prev_row)

    @pl.when(is_first)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    blk = blocks_ref[0]                      # (bs, bs)
    x = x_ref[...]                           # (bs, k)
    y_ref[...] += jnp.dot(blk, x, preferred_element_type=y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_row_blocks", "block_size",
                                              "interpret"))
def bsr_spmm_pallas(blocks: jnp.ndarray, indices: jnp.ndarray,
                    row_ids: jnp.ndarray, X: jnp.ndarray,
                    n_row_blocks: int, block_size: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Y = A @ X for BSR A. X: (n_col_blocks*bs, k) -> Y: (n_row_blocks*bs, k).

    Requires tiles sorted by row_ids (SparseMatrix._build_bsr guarantees).
    """
    n_blocks, bs, _ = blocks.shape
    k = X.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda b, idx, rid: (b, 0, 0)),
            pl.BlockSpec((bs, k), lambda b, idx, rid: (idx[b], 0)),
        ],
        out_specs=pl.BlockSpec((bs, k), lambda b, idx, rid: (rid[b], 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * bs, k), X.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # revisits output: sequential
    )(indices, row_ids, blocks, X)
