"""Fused RIPPLE apply phase as a Pallas TPU kernel.

Per hop, every affected vertex applies its mailbox and recomputes the
UPDATE: ``S' = S + M;  h = act(norm(S', k) @ W + b)``.  Unfused this is 3
HBM round-trips over the [R, d] rows; fused it is one read of (S, M, k),
one MXU matmul over W tiles, one write of (S', h).

Grid: (row_tiles, out_tiles, k_tiles); the S'+normalize epilogue fires on
the first k step, accumulation in an fp32 VMEM scratch, bias+activation on
the last k step.  Tiles are MXU-aligned (multiples of 128 where dims allow).
The degree vector and the bias travel as 2-D ``[R, 1]`` / ``[1, Dout]``
blocks: Mosaic tiles a 1-D block in 128-lane units, which a ``(row_tile,)``
block of the degree vector does not match once R exceeds one tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(S_ref, M_ref, k_ref, W_ref, b_ref, Snew_ref, h_ref, acc_ref,
            *, mean: bool, relu: bool, n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    S_new = S_ref[...] + M_ref[...]
    Snew_ref[...] = S_new  # write-back (same value for every j tile)
    x = S_new
    if mean:
        x = x / jnp.maximum(k_ref[...], 1.0)
    acc_ref[...] += jnp.dot(x.astype(jnp.float32), W_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(kk == n_k - 1)
    def _fin():
        h = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            h = jnp.maximum(h, 0.0)
        h_ref[...] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mean", "relu", "row_tile",
                                             "k_tile", "out_tile", "interpret"))
def delta_apply_pallas(S, mailbox, k, W, b, *, mean: bool, relu: bool,
                       row_tile: int = 128, k_tile: int = 128,
                       out_tile: int = 128, interpret: bool):
    R, Din = S.shape
    Dout = W.shape[1]
    row_tile = min(row_tile, R)
    k_tile = min(k_tile, Din)
    out_tile = min(out_tile, Dout)
    assert R % row_tile == 0 and Din % k_tile == 0 and Dout % out_tile == 0
    n_k = Din // k_tile
    grid = (R // row_tile, Dout // out_tile, n_k)

    kern = functools.partial(_kernel, mean=mean, relu=relu, n_k=n_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, k_tile), lambda i, j, kk: (i, kk)),   # S
            pl.BlockSpec((row_tile, k_tile), lambda i, j, kk: (i, kk)),   # M
            pl.BlockSpec((row_tile, 1), lambda i, j, kk: (i, 0)),         # k
            pl.BlockSpec((k_tile, out_tile), lambda i, j, kk: (kk, j)),   # W
            pl.BlockSpec((1, out_tile), lambda i, j, kk: (0, j)),         # b
        ],
        out_specs=[
            pl.BlockSpec((row_tile, k_tile), lambda i, j, kk: (i, kk)),   # S'
            pl.BlockSpec((row_tile, out_tile), lambda i, j, kk: (i, j)),  # h
        ],
        out_shape=[jax.ShapeDtypeStruct((R, Din), S.dtype),
                   jax.ShapeDtypeStruct((R, Dout), S.dtype)],
        scratch_shapes=[pltpu.VMEM((row_tile, out_tile), jnp.float32)],
        interpret=interpret,
        name="delta_apply",
    )(S, mailbox, k.reshape(R, 1), W, b.reshape(1, Dout))
