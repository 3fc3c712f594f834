"""``moe_experts_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the held experts' products of a
step — max(FLOPs / peak, bytes / peak) of ``benchmark/shapes_lm.py``
``moe_experts_flops`` / ``moe_experts_bytes`` a layer (for the pairs the
step's ``moe_held_pairs`` counter says landed on the held experts, not the
uniform share: a router that trains beside held experts only drifts to
them; the held weights read twice and their gradient written once), times
the expert layers — over ``moe_experts_ms``.
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_lm
from benchmark.lm_readings import held_pairs_a_layer, roofline_pct


def read(r):
    pairs = held_pairs_a_layer(r)
    if pairs is None:
        return None
    return roofline_pct(r, "moe.experts", "E", shapes_lm.moe_experts_flops,
                        shapes_lm.moe_experts_bytes, load=pairs)
