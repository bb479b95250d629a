"""``device.mfu.delheavy.sat``.

The whole propagate step's share of the chip's peak, in percent, in the
deletion-heavy cell: the operations the window's micro-batches require
(``rbench/flops.py``, the ``sage`` family's two products a row) over
traced window seconds x chips x peak FLOP/s (``rbench/peaks.py``). A
SHRINK re-aggregation's pulls are gathers, which the count does not credit.
"""

LAYER = "device"
MOVES = "updates_per_s"


def read(w):
    if not w.trace or not w.flops or not w.peak_flops:
        return None
    return 100.0 * w.flops / (w.trace["window_s"] * w.chips * w.peak_flops)
