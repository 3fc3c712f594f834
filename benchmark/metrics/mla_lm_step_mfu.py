"""``mla_lm_step_mfu`` — layer: models models/ ops/.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The share of the chip's peak FLOP/s the whole step reaches: the model's
FLOPs of one step (``benchmark/shapes_mla_lm.py`` ``train_step_flops``:
forward + backward of every product at the configuration's shapes and the
step's tokens, the latent products, the cores over ``i + 1`` keys a query
at 20 heads of 256, uniform routing, the multi-token prediction module's
block, ``W_m`` and second head pass; recomputation not counted) / peak
FLOP/s / ``step_device_ms``.
"""

LAYER = "models models/ ops/"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_mla_lm, xplane
from benchmark.mla_lm_readings import mla_shapes


def read(r):
    shapes = mla_shapes(r)
    if shapes is None or r["peaks"] is None or not r["step_pattern"]:
        return None
    ms = xplane.step_device_ms(r["trace"], r["step_pattern"],
                               r["window_ns"])
    if not ms:
        return None
    flops = shapes_mla_lm.train_step_flops(*shapes)
    return 100.0 * flops / r["peaks"]["flops_per_s"] / (ms / 1e3)
