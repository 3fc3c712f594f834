"""``collective_exposed_pct`` — layer: parallelism parallel/.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

Share of the window's collective time during which no other op ran on
that device.  Nothing on one chip.
"""

LAYER = "parallelism parallel/"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def read(r):
    if not r["step_pattern"]:
        return None
    stats = xplane.collective_stats(r["trace"], r["step_pattern"],
                                    r["window_ns"])
    return stats["exposed_pct"] if stats else None
