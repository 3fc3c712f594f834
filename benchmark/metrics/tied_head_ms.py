"""``tied_head_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``lm.head``, forward + backward summed (the
backward's recomputed forward included): the tied
head's product ``h Emb^T`` over the held slice of the vocabulary, the
log-softmax and the loss; the table's gradient from this product is
here, the lookup's scatter-add under ``embed.gather``.  From
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
    return conv_phase_ms(r, "lm.head")
