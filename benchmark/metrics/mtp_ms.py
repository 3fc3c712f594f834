"""``mtp_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``mtp.merge``, ``mtp.block`` and ``mtp.head``,
forward + backward summed (the backward's recomputed forward included):
the multi-token prediction module whole: two norms, the next token's
embedding and ``W_m``; its latent attention and sparse feed-forward (their
inner ``attn.*`` and ``moe.*`` scopes fall under ``mtp.block``: the first
scope on an op's path names its phase); its final norm and the second
pass of the shared head.  From
``obs.profile.phases`` on the run's own capture, handed on by the plane;
``None`` on a reading without the phase or of another configuration's
kind.
"""

LAYER = "models models/ ops/"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark.mla_lm_readings import mla_phase_ms


def read(r):
    return mla_phase_ms(r, "mtp.merge", "mtp.block", "mtp.head")
