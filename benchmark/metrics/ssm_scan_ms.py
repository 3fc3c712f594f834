"""``ssm_scan_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``ssm.scan``, forward + backward summed (the
backward's recomputed forward included): the chunked state-space scan of
every Mamba-2 layer (softplus of dt, the chunk products, the chunk
recurrence, the D skip). From ``obs.profile.phases`` on the run's own
capture, handed on by the plane; ``None`` on a reading without the phase.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.lm_readings import phase_ms


def read(r):
    return phase_ms(r, "ssm.scan")
