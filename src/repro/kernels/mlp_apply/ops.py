"""jit wrapper for the fused GIN apply, padding to tile multiples.

Padding is inert by construction: padded S/M/h_prev rows and cols are 0,
padded W1 rows / W2 rows are 0, padded b1/b2 entries are 0 — so the padded
hidden lanes hold relu(0) = 0 and contribute nothing; the pad is sliced
off before returning.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import mlp_apply_pallas


def _pad_to(x, mult, axis):
    r = x.shape[axis] % mult
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad)


def mlp_apply(S, mailbox, h_prev, k, eps, W1, b1, W2, b2, *,
              mean: bool = False, relu: bool = True, interpret: bool):
    """Fused S' = S + M; h = act(relu(((1+eps)h + norm(S'))@W1+b1)@W2+b2)."""
    R0, Din0 = S.shape
    Dh0 = W1.shape[1]
    Dout0 = W2.shape[1]
    rt = min(128, max(8, R0))
    kt = min(128, Din0)
    ht = min(128, Dh0)
    ot = min(128, Dout0)
    S = _pad_to(_pad_to(S, rt, 0), kt, 1)
    mailbox = _pad_to(_pad_to(mailbox, rt, 0), kt, 1)
    h_prev = _pad_to(_pad_to(h_prev, rt, 0), kt, 1)
    k = _pad_to(k, rt, 0)
    W1 = _pad_to(_pad_to(W1, kt, 0), ht, 1)
    b1 = _pad_to(b1, ht, 0)
    W2 = _pad_to(_pad_to(W2, ht, 0), ot, 1)
    b2 = _pad_to(b2, ot, 0)
    eps = jnp.asarray(eps, dtype=jnp.float32).reshape(1, 1)
    S_new, h = mlp_apply_pallas(eps, S, mailbox, h_prev, k, W1, b1, W2, b2,
                                mean=mean, relu=relu, row_tile=rt,
                                out_tile=ot, interpret=interpret)
    return S_new[:R0, :Din0], h[:R0, :Dout0]
