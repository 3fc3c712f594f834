"""``device_idle_pct`` — layer: device.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

1 - (union of the op intervals on the device's op line) / window, via
``jax.profiler.ProfileData``; on several chips the mean over devices.
"""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def read(r):
    if r["window_ns"] is None:
        return None
    idle = xplane.idle_share(r["trace"], r["window_ns"])
    return None if idle is None else 100.0 * idle
