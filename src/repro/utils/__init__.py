"""Shared small utilities: padding, bucketing, the compile-cache home."""
from __future__ import annotations

import math
import os

import numpy as np

# src/repro/utils/__init__.py -> the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def next_bucket(n: int, *, minimum: int = 16) -> int:
    """Round ``n`` up to the next power of two (>= minimum).

    Bucketing dynamic sizes to powers of two bounds the number of distinct
    jit compilations to O(log n) while wasting at most 2x padding.
    """
    if n <= minimum:
        return minimum
    return 1 << math.ceil(math.log2(n))


def pad_to(arr: np.ndarray, size: int, fill=0) -> np.ndarray:
    """Pad axis 0 of ``arr`` with ``fill`` up to ``size`` entries."""
    if arr.shape[0] == size:
        return arr
    if arr.shape[0] > size:
        raise ValueError(f"cannot pad {arr.shape[0]} down to {size}")
    pad_width = [(0, size - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width, constant_values=fill)


def use_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home; returns it.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, so that a later run of the
    same checkout finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}EiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(n) < 1000.0:
            return f"{n:.3g}{unit}"
        n /= 1000.0
    return f"{n:.3g}E"
