"""``mla_core_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the main blocks' attention cores
of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_mla_lm.py`` ``core_flops`` (every query's ``i + 1``
keys, scores over 256 and values over 256, forward + backward, 20 heads)
and ``core_bytes`` a layer, times the main blocks — over ``mla_core_ms``.
At 8,192 tokens the products bind (10.5 ms a layer on a v5e against 2.5
ms for the bytes).
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_mla_lm
from benchmark.mla_lm_readings import mla_shapes, roofline_pct


def read(r):
    shapes = mla_shapes(r)
    if shapes is None:
        return None
    cfg, tokens, seq = shapes
    return roofline_pct(
        r, "attn.core", shapes_mla_lm.attention_layers(cfg, False),
        shapes_mla_lm.core_flops(cfg, tokens, seq),
        shapes_mla_lm.core_bytes(cfg, tokens))
