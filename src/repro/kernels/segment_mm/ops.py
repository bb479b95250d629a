"""jit'd wrapper: COO edge list -> BSR -> Pallas SpMM."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from .kernel import bsr_spmm


def coo_to_bsr(src: np.ndarray, dst: np.ndarray, w: np.ndarray, n: int,
               blk: int = 128):
    """Host-side conversion of a (dst-major) edge list into BSR tiles.

    Returns (a_idx [nbr, max_k], x_idx [nbr, max_k], a_blocks [nnzb+1, blk, blk],
    n_row_blocks, n_pad).  Tile (bi, bj) holds w at [dst % blk, src % blk].
    """
    n_pad = ((n + blk - 1) // blk) * blk
    nbr = n_pad // blk
    bi = dst // blk
    bj = src // blk
    key = bi * nbr + bj
    uniq, inv = np.unique(key, return_inverse=True)
    nnzb = uniq.shape[0]
    a_blocks = np.zeros((nnzb + 1, blk, blk), dtype=np.float32)
    a_blocks[inv, dst % blk, src % blk] += w  # duplicate edges accumulate
    ub_i, ub_j = uniq // nbr, uniq % nbr
    max_k = max(int(np.bincount(ub_i, minlength=nbr).max()), 1)
    a_idx = np.full((nbr, max_k), nnzb, dtype=np.int32)  # pad -> zero tile
    x_idx = np.zeros((nbr, max_k), dtype=np.int32)
    slot = np.zeros(nbr, dtype=np.int64)
    for t in range(nnzb):
        i = ub_i[t]
        a_idx[i, slot[i]] = t
        x_idx[i, slot[i]] = ub_j[t]
        slot[i] += 1
    return a_idx, x_idx, a_blocks, nbr, n_pad


def segment_mm(src, dst, w, x, n: int, blk: int = 128, *,
               interpret: bool) -> jax.Array:
    """Drop-in for ref.segment_mm_ref using the Pallas BSR kernel."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    w = np.asarray(w, dtype=np.float32)
    a_idx, x_idx, a_blocks, nbr, n_pad = coo_to_bsr(src, dst, w, n, blk)
    d = x.shape[1]
    x_pad = jnp.pad(jnp.asarray(x), ((0, n_pad - n), (0, 0)))
    d_tile = d if d % 128 else min(d, 512)
    out = bsr_spmm(jnp.asarray(a_idx), jnp.asarray(x_idx),
                   jnp.asarray(a_blocks, dtype=x_pad.dtype), x_pad,
                   n_row_blocks=nbr, max_k=a_idx.shape[1], blk=blk,
                   d_tile=d_tile, interpret=interpret)
    return out[:n]
