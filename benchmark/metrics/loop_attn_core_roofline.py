"""``loop_attn_core_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the attention cores of a step —
max(FLOPs / peak, bytes / peak) of ``benchmark/shapes_looped_lm.py``
``core_flops`` (every query's ``i + 1`` keys, scores and values over 16
heads of 128, forward + backward) and ``core_bytes`` a block application,
times the 24 applications — over ``loop_attn_core_ms``.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_looped_lm
from benchmark.looped_lm_readings import looped_shapes
from benchmark.swa_lm_readings import roofline_pct


def read(r):
    shapes = looped_shapes(r)
    if shapes is None:
        return None
    cfg, tokens, seq = shapes
    return roofline_pct(
        r, "attn.core", shapes_looped_lm.applications(cfg),
        shapes_looped_lm.core_flops(cfg, tokens, seq),
        shapes_looped_lm.core_bytes(cfg, tokens))
