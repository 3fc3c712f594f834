"""``attn_window64_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.window``, forward + backward summed (the
backward's recomputed forward included): the cores of the
``sliding_attention`` layers at their own head count (64 query heads over
8 KV heads, 512 keys a query: the banded flash kernels, a query block of
512 rows walking 2 key blocks).  From
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
    return mixed_phase_ms(r, "attn.window")
