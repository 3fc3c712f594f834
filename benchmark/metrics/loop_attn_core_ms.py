"""``loop_attn_core_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.core``, forward + backward summed: the
causal flash core of each of the 24 block applications (6 weight-shared
blocks, 4 passes) at 16 query and 16 key/value heads of 128.  From
``obs.profile.phases`` on the run's own capture, handed on by the plane;
``None`` on a reading without the phase or of another configuration's
kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.looped_lm_readings import looped_phase_ms


def read(r):
    return looped_phase_ms(r, "attn.core")
