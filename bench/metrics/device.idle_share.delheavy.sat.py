"""``device.idle_share.delheavy.sat``.

Percent of the traced window in which no operation ran on the device: 1
- busy / window, busy the union of device op intervals.
"""

LAYER = "device"
MOVES = "updates_per_s"


def read(w):
    if not w.trace or not w.trace["devices"] or w.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
