"""jit wrapper for the fused apply with shape padding to tile multiples."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import delta_apply_pallas


def _pad_to(x, mult, axis):
    r = x.shape[axis] % mult
    if r == 0:
        return x, 0
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad), mult - r


def delta_apply(S, mailbox, k, W, b, *, mean: bool = False, relu: bool = True,
                interpret: bool):
    """Fused S' = S + M; h = act(norm(S')@W + b).  Pads to 128-tiles."""
    R0, Din0 = S.shape
    Dout0 = W.shape[1]
    rt = min(128, max(8, R0))
    S, _ = _pad_to(S, rt, 0)
    mailbox, _ = _pad_to(mailbox, rt, 0)
    k, _ = _pad_to(k, rt, 0)
    kt = min(128, Din0)
    S, _ = _pad_to(S, kt, 1)
    mailbox, _ = _pad_to(mailbox, kt, 1)
    W, _ = _pad_to(_pad_to(W, kt, 0)[0], min(128, Dout0), 1)
    b, _ = _pad_to(b, min(128, Dout0), 0)
    S_new, h = delta_apply_pallas(S, mailbox, k, W, b, mean=mean, relu=relu,
                                  row_tile=rt, k_tile=kt,
                                  out_tile=min(128, Dout0),
                                  interpret=interpret)
    return S_new[:R0, :Din0], h[:R0, :Dout0]
