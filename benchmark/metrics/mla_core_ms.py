"""``mla_core_ms`` — layer: models models/ ops/.  Unit ``ms``, source
``device_trace``; should move ``train_rows_per_s``.

Device ms a step inside ``attn.core``, forward + backward summed (the
backward's recomputed forward included): the main blocks' causal flash
cores at 20 heads of 256 (queries and keys ``[nope 192 ; rope 64]``,
values 256), with the concatenation that builds q and k and the
broadcast of the one rotary key over the heads.  The multi-token
prediction module's own is under ``mtp.block`` (``mtp_ms``).  From
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
    return mla_phase_ms(r, "attn.core")
