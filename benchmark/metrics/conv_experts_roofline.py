"""``conv_experts_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the sparse blocks' held gated
experts of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_conv_lm.py`` ``experts_flops`` / ``experts_bytes`` a
layer (three matrices, for the pairs the step's ``moe_held_pairs`` counter
says landed on the held experts of a sparse layer, not the uniform share;
the held weights read twice and their gradient written once), times the
sparse blocks — over ``conv_experts_ms``.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_conv_lm
from benchmark.conv_lm_readings import conv_shapes, held_pairs_a_layer
from benchmark.swa_lm_readings import roofline_pct


def read(r):
    pairs, shapes = held_pairs_a_layer(r), conv_shapes(r)
    if pairs is None or shapes is None:
        return None
    cfg = shapes[0]
    return roofline_pct(
        r, "moe.experts", shapes_conv_lm.sparse_layers(cfg),
        shapes_conv_lm.experts_flops(cfg, pairs),
        shapes_conv_lm.experts_bytes(cfg, pairs))
