"""``mixed_lm_step_mfu`` — layer: models models/ ops/.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The share of the chip's peak FLOP/s the whole step reaches: the model's
FLOPs of one step (``benchmark/shapes_mixed_lm.py`` ``train_step_flops``:
forward + backward of every product at the configuration's shapes and the
step's tokens, each attention layer at its own head count, a window
layer's queries counting ``min(i + 1, window)`` keys, uniform routing,
recomputation not counted) / peak FLOP/s / ``step_device_ms``.
"""

LAYER = "models models/ ops/"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_mixed_lm, xplane
from benchmark.mixed_lm_readings import mixed_shapes


def read(r):
    shapes = mixed_shapes(r)
    if shapes is None or r["peaks"] is None or not r["step_pattern"]:
        return None
    ms = xplane.step_device_ms(r["trace"], r["step_pattern"],
                               r["window_ns"])
    if not ms:
        return None
    cfg, tokens, seq = shapes
    flops = shapes_mixed_lm.train_step_flops(cfg, tokens, seq)
    return 100.0 * flops / r["peaks"]["flops_per_s"] / (ms / 1e3)
