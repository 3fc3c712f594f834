"""``route256_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``moe.route``, forward + backward summed (the
backward's recomputed forward included): the gate product at full float32
precision over 256 outputs, the sigmoid, ``lax.top_k`` of 8, the weights
and the tile plan's sort (ops/grouped.py ``plan_tiles``) of every sparse
block.  From
``obs.profile.phases`` on the run's own capture, handed on by the plane;
``None`` on a reading without the phase or of another configuration's
kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.mixed_lm_readings import mixed_phase_ms


def read(r):
    return mixed_phase_ms(r, "moe.route")
