"""Fused GIN MLP apply phase as a Pallas TPU kernel.

The two-matmul sibling of delta_apply, covering the last jnp-only hop
apply: per hop, every affected vertex folds its delta mailbox into the
tracked aggregate and runs GIN's UPDATE::

    S' = S + M;  z = (1 + eps) * h_prev + norm(S', k)
    h  = act(relu(z @ W1 + b1) @ W2 + b2)

Unfused this is 4 HBM round-trips over the [R, d] rows (fold, z, two
matmuls); fused it is one read of (S, M, h_prev, k), two chained MXU
matmuls with the hidden activation kept in registers/VMEM, one write of
(S', h).

Grid: (row_tiles, out_tiles).  The MLP's inner dims (d_in and d_hidden)
are loaded whole per step — GIN hidden widths in this repo are O(128), so
W1 and the W2 column tile sit comfortably in VMEM and no k-loop carry for
the *hidden* activation is needed (an h1 scratch would otherwise have to
persist across two grid axes).  ``eps`` is a traced scalar and travels in
SMEM; the degree vector and biases travel as 2-D ``[R, 1]`` / ``[1, D]``
blocks, the layout Mosaic accepts for them at every row count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(eps_ref, S_ref, M_ref, Hp_ref, k_ref, W1_ref, b1_ref, W2_ref,
            b2_ref, Snew_ref, h_ref, *, mean: bool, relu: bool):
    S_new = S_ref[...] + M_ref[...]
    Snew_ref[...] = S_new  # write-back (same value for every j tile)
    x = S_new
    if mean:
        x = x / jnp.maximum(k_ref[...], 1.0)
    z = (1.0 + eps_ref[0, 0]) * Hp_ref[...] + x
    h1 = jnp.maximum(
        jnp.dot(z.astype(jnp.float32), W1_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
        + b1_ref[...].astype(jnp.float32), 0.0)
    h = jnp.dot(h1, W2_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST) \
        + b2_ref[...].astype(jnp.float32)
    if relu:
        h = jnp.maximum(h, 0.0)
    h_ref[...] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mean", "relu", "row_tile",
                                             "out_tile", "interpret"))
def mlp_apply_pallas(eps, S, mailbox, h_prev, k, W1, b1, W2, b2, *,
                     mean: bool, relu: bool, row_tile: int = 128,
                     out_tile: int = 128, interpret: bool):
    R, Din = S.shape
    Dh = W1.shape[1]
    Dout = W2.shape[1]
    row_tile = min(row_tile, R)
    out_tile = min(out_tile, Dout)
    assert R % row_tile == 0 and Dout % out_tile == 0
    grid = (R // row_tile, Dout // out_tile)

    kern = functools.partial(_kernel, mean=mean, relu=relu)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                 # eps (1,1)
            pl.BlockSpec((row_tile, Din), lambda i, j: (i, 0)),    # S
            pl.BlockSpec((row_tile, Din), lambda i, j: (i, 0)),    # M
            pl.BlockSpec((row_tile, Din), lambda i, j: (i, 0)),    # h_prev
            pl.BlockSpec((row_tile, 1), lambda i, j: (i, 0)),      # k
            pl.BlockSpec((Din, Dh), lambda i, j: (0, 0)),          # W1
            pl.BlockSpec((1, Dh), lambda i, j: (0, 0)),            # b1
            pl.BlockSpec((Dh, out_tile), lambda i, j: (0, j)),     # W2
            pl.BlockSpec((1, out_tile), lambda i, j: (0, j)),      # b2
        ],
        out_specs=[
            pl.BlockSpec((row_tile, Din), lambda i, j: (i, 0)),    # S'
            pl.BlockSpec((row_tile, out_tile), lambda i, j: (i, j)),  # h
        ],
        out_shape=[jax.ShapeDtypeStruct((R, Din), S.dtype),
                   jax.ShapeDtypeStruct((R, Dout), S.dtype)],
        interpret=interpret,
        name="mlp_apply",
    )(eps, S, mailbox, h_prev, k.reshape(R, 1), W1, b1.reshape(1, Dh), W2,
      b2.reshape(1, Dout))
