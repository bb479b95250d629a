"""jit wrapper around the Pallas EmbeddingBag."""
from __future__ import annotations

import jax

from .kernel import embedding_bag_pallas


def embedding_bag_kernel(table: jax.Array, idx: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """Drop-in for models.recsys.dlrm.embedding_bag."""
    return embedding_bag_pallas(idx, table, interpret=interpret)
