"""``mla_experts_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the main sparse blocks' held
gated experts of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_mla_lm.py`` ``experts_flops`` / ``experts_bytes`` a
layer (three matrices, for the pairs the step's ``moe_held_pairs`` counter
says landed on the held experts of a sparse layer, the module's among
them, not the uniform share; the held weights read twice and their
gradient written once), times the main sparse blocks — over
``mla_experts_ms``.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_mla_lm
from benchmark.mla_lm_readings import (
    held_pairs_a_layer,
    mla_shapes,
    roofline_pct,
)


def read(r):
    pairs, shapes = held_pairs_a_layer(r), mla_shapes(r)
    if pairs is None or shapes is None:
        return None
    cfg = shapes[0]
    return roofline_pct(
        r, "moe.experts", shapes_mla_lm.sparse_layers(cfg, False),
        shapes_mla_lm.experts_flops(cfg, pairs),
        shapes_mla_lm.experts_bytes(cfg, pairs))
