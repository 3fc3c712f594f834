"""``moe_experts_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``moe.experts``, forward + backward summed (the
backward's recomputed forward included): the grouped products over the
held experts of every expert layer (ops/grouped.py: gather, two products,
scatter-add, a tile at a time). From ``obs.profile.phases`` on the run's
own capture, handed on by the plane; ``None`` on a reading without the
phase.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.lm_readings import phase_ms


def read(r):
    return phase_ms(r, "moe.experts")
