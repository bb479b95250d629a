"""Fused monotonic (max/min) RIPPLE apply phase as a Pallas TPU kernel.

The segment-max sibling of delta_apply: per hop, every affected vertex
folds its candidate-extremum mailbox into the tracked aggregate and
recomputes the UPDATE::

    S' = extremum(S, M);   h = act(finite(S') @ W + b)

where ``M`` holds the per-row candidate extremum (the aggregator identity,
+/-inf, in rows with no candidates — GROW events that don't beat ``S``
vanish inside the elementwise min/max) and ``finite`` maps identity rows to
0, matching the engines' empty-neighborhood convention.  Unfused this is 3
HBM round-trips over the [R, d] rows; fused it is one read of (S, M), one
MXU matmul over W tiles, one write of (S', h).

Grid: (row_tiles, out_tiles, k_tiles); the extremum+mask epilogue fires on
every k step (cheap, VPU), accumulation in an fp32 VMEM scratch, bias +
activation on the last k step.  Tiles are MXU-aligned (multiples of 128
where dims allow); the bias travels as a 2-D ``[1, Dout]`` block, since
Mosaic refuses a 1-D ``(out_tile,)`` block once Dout spans several tiles.
Contributor-ref maintenance stays outside the kernel: it is gather/compare
bound, not matmul bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(S_ref, M_ref, W_ref, b_ref, Snew_ref, h_ref, acc_ref,
            *, maximize: bool, relu: bool, n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    combine = jnp.maximum if maximize else jnp.minimum
    S_new = combine(S_ref[...], M_ref[...])
    Snew_ref[...] = S_new  # write-back (same value for every j tile)
    x = jnp.where(jnp.isfinite(S_new), S_new, 0.0)
    acc_ref[...] += jnp.dot(x.astype(jnp.float32), W_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(kk == n_k - 1)
    def _fin():
        h = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            h = jnp.maximum(h, 0.0)
        h_ref[...] = h.astype(h_ref.dtype)


def _kernel_masked(S_ref, M_ref, RG_ref, Mk_ref, W_ref, b_ref, Snew_ref,
                   h_ref, acc_ref, *, maximize: bool, relu: bool, n_k: int):
    """Per-dim masked variant: shrunk (row, dim) cells swap in their
    re-aggregated value before the candidate fold, all in one HBM pass::

        base = mask ? reagg : S;  S' = extremum(base, M)
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    combine = jnp.maximum if maximize else jnp.minimum
    base = jnp.where(Mk_ref[...] != 0, RG_ref[...], S_ref[...])
    S_new = combine(base, M_ref[...])
    Snew_ref[...] = S_new  # write-back (same value for every j tile)
    x = jnp.where(jnp.isfinite(S_new), S_new, 0.0)
    acc_ref[...] += jnp.dot(x.astype(jnp.float32), W_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(kk == n_k - 1)
    def _fin():
        h = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if relu:
            h = jnp.maximum(h, 0.0)
        h_ref[...] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("maximize", "relu", "row_tile",
                                             "k_tile", "out_tile", "interpret"))
def extremum_apply_pallas(S, mailbox, W, b, reagg=None, mask=None, *,
                          maximize: bool, relu: bool,
                          row_tile: int = 128, k_tile: int = 128,
                          out_tile: int = 128, interpret: bool):
    R, Din = S.shape
    Dout = W.shape[1]
    row_tile = min(row_tile, R)
    k_tile = min(k_tile, Din)
    out_tile = min(out_tile, Dout)
    assert R % row_tile == 0 and Din % k_tile == 0 and Dout % out_tile == 0
    masked = reagg is not None
    assert masked == (mask is not None), "reagg and mask travel together"
    n_k = Din // k_tile
    grid = (R // row_tile, Dout // out_tile, n_k)

    row_k = pl.BlockSpec((row_tile, k_tile), lambda i, j, kk: (i, kk))
    in_specs = [row_k, row_k]                                         # S, M
    args = [S, mailbox]
    if masked:
        in_specs += [row_k, row_k]                                    # RG, MK
        args += [reagg, mask]
    in_specs += [
        pl.BlockSpec((k_tile, out_tile), lambda i, j, kk: (kk, j)),   # W
        pl.BlockSpec((1, out_tile), lambda i, j, kk: (0, j)),         # b
    ]
    args += [W, b.reshape(1, Dout)]

    kern = functools.partial(_kernel_masked if masked else _kernel,
                             maximize=maximize, relu=relu, n_k=n_k)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((row_tile, k_tile), lambda i, j, kk: (i, kk)),   # S'
            pl.BlockSpec((row_tile, out_tile), lambda i, j, kk: (i, j)),  # h
        ],
        out_shape=[jax.ShapeDtypeStruct((R, Din), S.dtype),
                   jax.ShapeDtypeStruct((R, Dout), S.dtype)],
        scratch_shapes=[pltpu.VMEM((row_tile, out_tile), jnp.float32)],
        interpret=interpret,
        name="extremum_apply_masked" if masked else "extremum_apply",
    )(*args)
