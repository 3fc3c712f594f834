"""``step_device_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Median device duration of the train-step program (module line): the
median over devices of each device's median.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def read(r):
    if not r["step_pattern"]:
        return None
    return xplane.step_device_ms(r["trace"], r["step_pattern"],
                                 r["window_ns"])
