"""``attn_full_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.core``, forward + backward summed (the
backward's recomputed forward included): the full-attention layers' causal
core in a cell whose model also has window layers (the flash kernels over
the whole square, masked above the diagonal).  From ``obs.profile.phases``
on the run's own capture, handed on by the plane; ``None`` on a reading
without the phase or of another configuration's kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.swa_lm_readings import swa_phase_ms


def read(r):
    return swa_phase_ms(r, "attn.core")
