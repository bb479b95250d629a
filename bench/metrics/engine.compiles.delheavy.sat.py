"""``engine.compiles.delheavy.sat``.

Backend compiles (or compile-cache loads) inside the window, counted
from JAX's ``/jax/core/compile/backend_compile_duration`` events: in the
deletion-heavy cell, a rung of the monotonic propagate's four-channel cap
ladder (rows, edges, pulled elements, (row, dim) pairs) first needed there.
"""

LAYER = "jit and cap ladder (core/device_engine.py)"
MOVES = "updates_per_s"


def read(w):
    return float(w.compiles)
