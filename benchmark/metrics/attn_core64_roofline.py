"""``attn_core64_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the attention core of a step —
max(FLOPs / peak, bytes / peak) of ``benchmark/shapes_conv_lm.py``
``core_flops`` (every query's ``i + 1`` keys, scores and values over the
head's own 64 dimensions, 32 heads, forward + backward) and
``core_bytes`` a layer, times the ``full_attention`` layers — over
``attn_core64_ms``.  The kernels pad a head of 64 to 128 lanes: that half
of their products is not counted, so it shows as lost share.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_conv_lm
from benchmark.conv_lm_readings import conv_shapes
from benchmark.swa_lm_readings import roofline_pct


def read(r):
    shapes = conv_shapes(r)
    if shapes is None:
        return None
    cfg, tokens, seq = shapes
    return roofline_pct(
        r, "attn.core", shapes_conv_lm.attention_layers(cfg),
        shapes_conv_lm.core_flops(cfg, tokens, seq),
        shapes_conv_lm.core_bytes(cfg, tokens))
