"""``engine.compiles.sat``.

Backend compiles (or compile-cache loads) inside the window, counted
from JAX's ``/jax/core/compile/backend_compile_duration`` events.
"""

LAYER = "jit and cap ladder (core/device_engine.py)"
MOVES = "updates_per_s"


def read(w):
    return float(w.compiles)
