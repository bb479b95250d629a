"""jit wrapper for the flash attention kernel."""
from __future__ import annotations

import jax

from .kernel import flash_attention_pallas


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool) -> jax.Array:
    return flash_attention_pallas(q, k, v, bq=bq, bkv=bkv,
                                  interpret=interpret)
