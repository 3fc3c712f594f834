"""``lm_head_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``lm.head``, forward + backward summed (the
backward's recomputed forward included): the untied head's product, log-
softmax and the token losses. From ``obs.profile.phases`` on the run's own
capture, handed on by the plane; ``None`` on a reading without the phase.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.lm_readings import phase_ms


def read(r):
    return phase_ms(r, "lm.head")
