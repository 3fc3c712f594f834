"""``collective_ms`` — layer: parallelism parallel/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Per step, the time a collective op was in flight on a device (async
pairs from ``-start`` to ``-done``): median over steps, mean over devices.
Nothing on one chip.
"""

LAYER = "parallelism parallel/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def read(r):
    if not r["step_pattern"]:
        return None
    stats = xplane.collective_stats(r["trace"], r["step_pattern"],
                                    r["window_ns"])
    return stats["collective_ms"] if stats else None
