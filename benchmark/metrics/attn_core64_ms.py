"""``attn_core64_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.core``, forward + backward summed (the
backward's recomputed forward included): the one
``full_attention`` block's causal flash core at 32 query heads over 8
KV heads of 64, which the kernels pad to 128 lanes (every q, k, v, o and
every product of the three kernels is half zeros), with the repeat of
the KV heads.  From
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
    return conv_phase_ms(r, "attn.core")
