"""``short_conv_mix_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``conv.mix``, forward + backward summed (the
backward's recomputed forward included): the four
``conv`` operators between their products: ``B * u``, the three taps of
the depth-wise causal convolution as shifted products, ``C *`` the
result; element-wise work the bytes bound.  From
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
    return conv_phase_ms(r, "conv.mix")
