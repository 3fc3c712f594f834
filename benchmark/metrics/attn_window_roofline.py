"""``attn_window_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the sliding-window layers' cores
of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_swa_lm.py`` ``attention_flops`` (every query's
``min(i + 1, sliding_window)`` keys, scores and values, forward +
backward) and ``attention_bytes`` a layer, times the window layers — over
``attn_window_ms``.  The products bind.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_swa_lm
from benchmark.swa_lm_readings import roofline_pct, swa_shapes


def read(r):
    shapes = swa_shapes(r)
    if shapes is None:
        return None
    cfg, tokens, seq = shapes
    kind = shapes_swa_lm.SLIDING
    return roofline_pct(
        r, "attn.window", shapes_swa_lm.layers_of(cfg, kind),
        shapes_swa_lm.attention_flops(cfg, tokens, seq, kind),
        shapes_swa_lm.attention_bytes(cfg, tokens))
