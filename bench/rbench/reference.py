"""Plain float32 reference of the configurations' GNN layers.

Written from the layer equations, importing nothing of the program:

    GraphConv + sum    S^l[v] = sum_{u->v} h^{l-1}[u]
                       h^l    = S^l W + b             (relu below the top)
    GraphSAGE + max    S^l[v] = max_{u->v} h^{l-1}[u]  (elementwise, -inf
                                                        in a row with no
                                                        in-edge)
                       x^l    = S^l with -inf read as 0
                       h^l    = h^{l-1} W_self + x^l W_nbr + b

It runs on the default device, one layer at a time, reducing the edges in
fixed-size blocks so that no ``[E, d]`` message array is ever held whole.

``precision="highest"`` computes the products in float32 (what the
configurations state). ``precision="bf16x3"`` is the control: each product
in three bfloat16 passes, hi*hi + hi*lo + lo*hi with float32 accumulation,
the TPU's ``Precision.HIGH``, spelled out so that it computes the same on
any backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

EDGE_BLOCK = 1 << 18


def _mm(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "bf16x3":
        raise ValueError(precision)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def _split(a):
    """``a`` as a bfloat16 head (rounded to nearest even) and tail. The
    head is rounded with integer operations, not by a round trip through
    bfloat16, which a compiler allowed excess precision may fold away (XLA
    on a TPU does, and the three passes then collapse into one)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & np.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


@partial(jax.jit, static_argnames=("agg",), donate_argnames=("acc",))
def _reduce_block(acc, h, src, dst, *, agg: str):
    # padded edges read row 0 and land in the extra last row, dropped later
    msgs = h[src]
    if agg == "sum":
        return acc + jax.ops.segment_sum(msgs, dst, num_segments=acc.shape[0])
    return jnp.maximum(acc, jax.ops.segment_max(msgs, dst,
                                                num_segments=acc.shape[0]))


def aggregate(h, src: np.ndarray, dst: np.ndarray, agg: str):
    """S = segment-reduce of h[src] over dst, edge block by edge block."""
    n, d = h.shape
    fill = 0.0 if agg == "sum" else -jnp.inf
    acc = jnp.full((n + 1, d), fill, jnp.float32)
    for i in range(0, max(src.size, 1), EDGE_BLOCK):
        s = np.zeros(EDGE_BLOCK, np.int32)
        t = np.full(EDGE_BLOCK, n, np.int32)
        blk = slice(i, i + EDGE_BLOCK)
        s[:src[blk].size] = src[blk]
        t[:dst[blk].size] = dst[blk]
        acc = _reduce_block(acc, h, jnp.asarray(s), jnp.asarray(t), agg=agg)
    return acc[:n]


@partial(jax.jit, static_argnames=("family", "last", "precision"))
def _update(p, h_prev, s, *, family: str, last: bool, precision: str):
    x = jnp.where(jnp.isfinite(s), s, 0.0)
    if family == "gc":
        out = _mm(x, p["w"], precision) + p["b"]
    else:
        out = _mm(h_prev, p["w_self"], precision) \
            + _mm(x, p["w_nbr"], precision) + p["b"]
    return out if last else jnp.maximum(out, 0.0)


def forward(family: str, agg: str, params: list[dict], x: np.ndarray,
            src: np.ndarray, dst: np.ndarray, *, precision: str = "highest"):
    """Per-layer (H, S) as host arrays: H[0] = x, H[l] and S[l] for l >= 1
    (S[0] is an empty placeholder)."""
    h = jnp.asarray(x, jnp.float32)
    H, S = [np.asarray(x, np.float32)], [np.zeros((0,), np.float32)]
    L = len(params)
    for l, p in enumerate(params):
        s = aggregate(h, src, dst, agg)
        p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
        h = _update(p, h, s, family=family, last=l == L - 1,
                    precision=precision)
        S.append(np.asarray(s))
        H.append(np.asarray(h))
    return H, S


def gap(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest entry gap |got - ref|, over the largest finite |ref|.

    Equal infinities (a max aggregate's empty row) have no gap; an
    infinity on one side only is an infinite gap."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        return float("inf")
    fin = np.isfinite(ref)
    if not np.array_equal(fin, np.isfinite(got)) or \
            not np.array_equal(got[~fin], ref[~fin]):
        return float("inf")
    if not fin.any():
        return 0.0
    scale = max(float(np.abs(ref[fin]).max()), 1e-30)
    return float(np.abs(got[fin] - ref[fin]).max()) / scale
