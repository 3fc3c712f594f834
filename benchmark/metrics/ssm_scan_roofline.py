"""``ssm_scan_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the chunked scans of a step —
max(FLOPs / peak, bytes / peak) of ``benchmark/shapes_lm.py``
``ssm_scan_flops`` / ``ssm_scan_bytes`` a layer, forward + backward, times
the Mamba-2 layers — over ``ssm_scan_ms`` (which holds the recomputed
forward too: recomputation is not useful work).
"""

LAYER = "kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import shapes_lm
from benchmark.lm_readings import roofline_pct


def read(r):
    return roofline_pct(r, "ssm.scan", "M", shapes_lm.ssm_scan_flops,
                        shapes_lm.ssm_scan_bytes)
