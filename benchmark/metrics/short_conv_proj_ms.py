"""``short_conv_proj_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``conv.proj``, forward + backward summed (the
backward's recomputed forward included): the four
``conv`` operators' two products, ``W_in`` (hidden -> 3 x hidden: ``B``,
``C``, ``u``) and ``W_out``.  From
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
    return conv_phase_ms(r, "conv.proj")
