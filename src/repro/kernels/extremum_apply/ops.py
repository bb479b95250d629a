"""jit wrapper for the fused monotonic apply, padding to tile multiples.

Row/feature padding uses the aggregator identity (+/-inf) in the mailbox
and 0 in ``S`` so padded lanes stay inert through the extremum and the
finite-mask (padded W rows/b entries are zero anyway); the pad is sliced
off before returning.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import extremum_apply_pallas


def _pad_to(x, mult, axis, fill=0.0):
    r = x.shape[axis] % mult
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad, constant_values=fill)


def extremum_apply(S, mailbox, W, b, *, reagg=None, mask=None,
                   maximize: bool = True, relu: bool = True,
                   interpret: bool):
    """Fused S' = extremum(S, M); h = act(finite(S')@W + b).  128-tiles.

    With ``reagg``/``mask`` (the per-dim SHRINK variant) the base rows are
    ``mask ? reagg : S`` — re-aggregated (row, dim) cells replace the
    stored extremum before the candidate fold, fused into the same pass.
    Masked padding cells stay 0 so padded lanes remain inert.
    """
    R0, Din0 = S.shape
    Dout0 = W.shape[1]
    ident = -jnp.inf if maximize else jnp.inf
    rt = min(128, max(8, R0))
    kt = min(128, Din0)
    ot = min(128, Dout0)
    S = _pad_to(_pad_to(S, rt, 0), kt, 1)
    mailbox = _pad_to(_pad_to(mailbox, rt, 0, fill=ident), kt, 1, fill=ident)
    W = _pad_to(_pad_to(W, kt, 0), ot, 1)
    b = _pad_to(b, ot, 0)
    if reagg is not None:
        reagg = _pad_to(_pad_to(reagg, rt, 0), kt, 1)
        mask = _pad_to(_pad_to(mask, rt, 0), kt, 1)
    S_new, h = extremum_apply_pallas(S, mailbox, W, b, reagg, mask,
                                     maximize=maximize,
                                     relu=relu, row_tile=rt, k_tile=kt,
                                     out_tile=ot, interpret=interpret)
    return S_new[:R0, :Din0], h[:R0, :Dout0]
