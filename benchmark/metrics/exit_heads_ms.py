"""``exit_heads_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``lm.head``, forward + backward summed (the
backward's recomputed forward included): the four passes of the untied
49,152-wide head over each pass's normed state, their log-softmax and
each position's loss.  From ``obs.profile.phases`` on the run's own
capture, handed on by the plane; ``None`` on a reading without the phase
or of another configuration's kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.looped_lm_readings import looped_phase_ms


def read(r):
    return looped_phase_ms(r, "lm.head")
