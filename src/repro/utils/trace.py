"""Host spans of the served path, in the JAX profiler's own trace.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: it is recorded when a
profiler session is active (``jax.profiler.start_trace`` or
``start_server``), on the same clock as the device's operations, and is a
no-op context manager otherwise. Every span opened on a thread while
``micro_batch(seq)`` is open there carries ``batch=seq``, so the spans of
one micro-batch share an identifier without each layer being handed it.
"""
from __future__ import annotations

import contextlib
import contextvars

from jax.profiler import TraceAnnotation

MICRO_BATCH = "ripple.serve.micro_batch"

_batch: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "ripple_micro_batch", default=None)


def span(name: str) -> TraceAnnotation:
    """A span named ``name``, tagged with the open micro-batch, if any."""
    seq = _batch.get()
    return TraceAnnotation(name) if seq is None \
        else TraceAnnotation(name, batch=seq)


@contextlib.contextmanager
def micro_batch(seq: int):
    """The root span of one micro-batch; spans opened inside it on this
    thread carry ``batch=seq``."""
    token = _batch.set(seq)
    try:
        with TraceAnnotation(MICRO_BATCH, batch=seq):
            yield
    finally:
        _batch.reset(token)
