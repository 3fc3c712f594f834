"""``conv_experts_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``moe.experts``, forward + backward summed (the
backward's recomputed forward included): the grouped
products over the held gated experts of the four sparse blocks
(ops/grouped.py ``gated_expert_mlp``: gather, three products,
scatter-add, a tile at a time).  From
``obs.profile.phases`` on the run's own capture, handed on by the plane;
``None`` on a reading without the phase or of another configuration's
kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.conv_lm_readings import conv_phase_ms


def read(r):
    return conv_phase_ms(r, "moe.experts")
