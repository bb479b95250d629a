"""Sum-mode EmbeddingBag as a Pallas TPU kernel (DLRM hot path).

The bag lookup is a *data-dependent gather*: TPU BlockSpecs cannot gather
arbitrary rows inside one block, but scalar-prefetched indices CAN drive the
block index map — so the grid iterates (bag, d_tile, hot) and each step DMAs
exactly the embedding row the bag needs, accumulating in the output block
(the hot axis is innermost, so each output block is revisited only on
consecutive steps, and the sequential TPU grid makes the accumulation
race-free).  HBM traffic is exactly hot x d per bag — the roofline minimum —
while the naive XLA lowering of take+sum materializes [B, hot, d].

Table and output are viewed as ``[rows, 1, d]`` with ``(None, 1, d_tile)``
blocks: a ``(1, d_tile)`` block of a 2-D array breaks Mosaic's rule that the
last two block dims divide by (8, 128), while a squeezed leading dim over a
unit second-minor dim satisfies it.  The index rectangle is prefetched
into SMEM whole, so a large one is gathered in row chunks that fit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, table_ref, out_ref):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += table_ref[...]


def _bag(idx: jax.Array, table3: jax.Array, d_tile: int,
         interpret: bool) -> jax.Array:
    """One pallas_call: idx [B, hot] over table3 [V, 1, d] -> [B, 1, d]."""
    B, hot = idx.shape
    d = table3.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, d // d_tile, hot),
        in_specs=[pl.BlockSpec((None, 1, d_tile),
                               lambda b, j, h, idx: (idx[b, h], 0, j))],
        out_specs=pl.BlockSpec((None, 1, d_tile),
                               lambda b, j, h, idx: (b, 0, j)),
    )
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, d), table3.dtype),
        interpret=interpret,
        name="embedding_bag",
    )(idx, table3)


# the scalar-prefetched index block lives in SMEM (1 MiB on a v5e core);
# larger index rectangles are gathered in row chunks of at most this many
# entries, one kernel call per chunk
_SMEM_INDEX_ENTRIES = 32 * 1024


@functools.partial(jax.jit, static_argnames=("d_tile", "interpret"))
def embedding_bag_pallas(idx: jax.Array, table: jax.Array, *,
                         d_tile: int | None = None,
                         interpret: bool) -> jax.Array:
    """idx [B, hot] int32; table [V, d] -> [B, d]."""
    B, hot = idx.shape
    V, d = table.shape
    d_tile = d_tile or d
    assert d % d_tile == 0
    table3 = table.reshape(V, 1, d)
    rows = max(1, min(B, _SMEM_INDEX_ENTRIES // hot))
    if rows == B:
        return _bag(idx, table3, d_tile, interpret).reshape(B, d)
    n_chunks = -(-B // rows)
    # pad rows gather row 0 and are sliced off
    chunks = jnp.pad(idx, ((0, n_chunks * rows - B), (0, 0))) \
        .reshape(n_chunks, rows, hot)
    out = jax.lax.map(lambda ix: _bag(ix, table3, d_tile, interpret), chunks)
    return out.reshape(n_chunks * rows, d)[:B]
