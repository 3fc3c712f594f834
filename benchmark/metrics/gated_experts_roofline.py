"""``gated_experts_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the held gated experts' products
of a step — max(FLOPs / peak, bytes / peak) of
``benchmark/shapes_swa_lm.py`` ``gated_experts_flops`` /
``gated_experts_bytes`` a layer (three matrices, for the pairs the step's
``moe_held_pairs`` counter says landed on the held experts, not the
uniform share: a router that trains beside held experts only drifts to
them; the held weights read twice and their gradient written once), times
the expert layers — over ``gated_experts_ms``.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_swa_lm
from benchmark.swa_lm_readings import (
    held_pairs_a_layer,
    roofline_pct,
    swa_shapes,
)


def read(r):
    pairs, shapes = held_pairs_a_layer(r), swa_shapes(r)
    if pairs is None or shapes is None:
        return None
    cfg = shapes[0]
    return roofline_pct(
        r, "moe.experts", len(cfg["layer_types"]),
        shapes_swa_lm.gated_experts_flops(cfg, pairs),
        shapes_swa_lm.gated_experts_bytes(cfg, pairs))
