"""``loop_exit_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``loop.exit``, forward + backward summed: the exit
gates of passes 1 .. 3, the exit distribution, the mixture of the four
passes' losses and its entropy.  From ``obs.profile.phases`` on the run's
own capture, handed on by the plane; ``None`` on a reading without the
phase (the parent of the PR that added the scope, a capture off the TPU)
or of another configuration's kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.looped_lm_readings import looped_phase_ms


def read(r):
    return looped_phase_ms(r, "loop.exit")
