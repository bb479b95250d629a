"""Block-sparse (BSR) message-passing SpMM as a Pallas TPU kernel.

The GNN aggregation ``out[v] = sum_{(u,v)} w_uv * x[u]`` is a sparse-matrix
x dense-feature product.  GPU kernels (GE-SpMM) use warp-level row gathers;
the TPU-native adaptation (DESIGN.md §2) converts the adjacency to BSR tiles
of (BLK x BLK) so every nonzero block becomes one MXU matmul:

    out[row_block] += A_tile[nz] @ x[col_block(nz)]

Scalar-prefetch (PrefetchScalarGridSpec) drives the *data-dependent*
BlockSpec index maps: grid = (row_blocks, max_nnz_per_row); step (i, k)
loads A tile ``a_idx[i, k]`` and x block ``x_idx[i, k]`` — rows with fewer
blocks point at a zero tile, so no dynamic control flow is needed in the
kernel body.  VMEM footprint per step: BLK*BLK (A) + BLK*D_TILE (x) +
BLK*D_TILE (out accumulator), all MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel(a_idx_ref, x_idx_ref, a_ref, x_ref, o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                          preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_row_blocks", "max_k", "blk", "d_tile",
                                    "interpret"))
def bsr_spmm(a_idx: jax.Array, x_idx: jax.Array, a_blocks: jax.Array,
             x: jax.Array, *, n_row_blocks: int, max_k: int, blk: int,
             d_tile: int | None = None, interpret: bool) -> jax.Array:
    """a_blocks [nnzb+1, blk, blk] (last tile all-zero pad);
    a_idx/x_idx [n_row_blocks, max_k]; x [n_col_blocks*blk, d]."""
    d = x.shape[1]
    d_tile = d_tile or min(d, 512)
    assert d % d_tile == 0
    grid = (n_row_blocks, max_k, d // d_tile)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # a_idx, x_idx
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk, blk),
                         lambda i, k, j, a_idx, x_idx: (a_idx[i, k], 0, 0)),
            pl.BlockSpec((blk, d_tile),
                         lambda i, k, j, a_idx, x_idx: (x_idx[i, k], j)),
        ],
        out_specs=pl.BlockSpec((blk, d_tile),
                               lambda i, k, j, a_idx, x_idx: (i, j)),
    )

    def kernel(a_idx_ref, x_idx_ref, a_ref, x_ref, o_ref):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.dot(a_ref[0], x_ref[...],
                              preferred_element_type=o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_row_blocks * blk, d), x.dtype),
        interpret=interpret,
    )(a_idx, x_idx, a_blocks, x)
