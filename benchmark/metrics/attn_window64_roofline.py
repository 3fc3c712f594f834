"""``attn_window64_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the sliding-window layers' cores
of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_mixed_lm.py`` ``attention_flops`` (every query's
``min(i + 1, sliding_window)`` keys, scores and values, forward +
backward, at the sliding layers' own head count) and ``attention_bytes`` a
layer, times the sliding layers — over ``attn_window64_ms``.  At 64 heads
inside a 512 window the bytes bind (2.21 ms a layer on a v5e against 2.03
ms for the products).
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_mixed_lm
from benchmark.mixed_lm_readings import mixed_shapes
from benchmark.swa_lm_readings import roofline_pct


def read(r):
    shapes = mixed_shapes(r)
    if shapes is None:
        return None
    cfg, tokens, seq = shapes
    kind = shapes_mixed_lm.SLIDING
    return roofline_pct(
        r, "attn.window", shapes_mixed_lm.blocks_of(cfg, kind),
        shapes_mixed_lm.attention_flops(cfg, tokens, seq, kind),
        shapes_mixed_lm.attention_bytes(cfg, tokens, kind))
