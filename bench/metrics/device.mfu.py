"""``device.mfu``.

The whole propagate step's share of the chip's peak, in percent: the
operations the window's micro-batches require (``rbench/flops.py``) over
traced window seconds x chips x peak FLOP/s (``rbench/peaks.py``).
"""

LAYER = "device"
MOVES = "updates_per_s"


def read(w):
    if not w.trace or not w.flops or not w.peak_flops:
        return None
    return 100.0 * w.flops / (w.trace["window_s"] * w.chips * w.peak_flops)
