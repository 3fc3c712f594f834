"""``launch_gap_ms`` — layer: trainer train/trainer.py.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Median gap on the device between the end of one train-step program and
the start of the next (epoch turn-rounds are in the tail, not the
median).
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def read(r):
    if not r["step_pattern"]:
        return None
    gaps = xplane.launch_gaps_ms(r["trace"], r["step_pattern"],
                                 r["window_ns"])
    return xplane.median(gaps)
