"""Pallas TPU kernels for the compute hot-spots.

    segment_mm     — block-sparse (BSR) message-passing SpMM on the MXU
    delta_apply    — fused RIPPLE mailbox-apply + UPDATE matmul + activation
    extremum_apply — fused monotonic fold (+ per-dim shrink mask) + UPDATE
    mlp_apply      — fused GIN apply: fold + z-term + two chained matmuls
    embedding_bag  — multi-hot gather-reduce with scalar-prefetched indices
    flash_attention— causal online-softmax attention with GQA

Each ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper) and
ref.py (pure-jnp oracle).  Every wrapper takes ``interpret`` as a required
keyword; callers on the serving path get it from :func:`interpret_mode`.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Whether the Pallas kernels must run in interpret mode: on every
    backend but a TPU, where they compile to Mosaic kernels instead."""
    return jax.default_backend() != "tpu"
