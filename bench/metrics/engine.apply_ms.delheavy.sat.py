"""``engine.apply_ms.delheavy.sat``.

Mean milliseconds per micro-batch in ``apply_one``: host routing and
packing, dispatch and the device step (``GraphServer.batch_latencies``),
in the deletion-heavy cell, where the monotonic SHRINK hop is most of it.
"""
import numpy as np

LAYER = "session + device engine host path (api/session.py, core/device_engine.py)"
MOVES = "updates_per_s"


def read(w):
    return 1e3 * float(np.mean(w.batch_latencies)) if w.batch_latencies \
        else None
