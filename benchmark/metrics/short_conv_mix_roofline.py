"""``short_conv_mix_roofline`` — layer: kernels ops/ssm_scan.py ops/grouped.py Pallas flash attention.  Unit ``%``, source
``device_trace``; should move ``train_rows_per_s``.

The least time the chip could take for the ``conv`` operators' mixing of
a step — the bytes of ``benchmark/shapes_conv_lm.py`` ``mix_bytes`` a
layer (``B``, ``C``, ``u`` and ``dOut`` read and ``y``, ``dB``, ``dC``
and ``du`` written, forward + backward: 11 tensors of tokens x hidden
float32; the products are a few multiplies an element and never bind)
/ peak bytes/s, times the ``conv`` layers — over ``short_conv_mix_ms``.
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
    cfg, tokens, _ = shapes
    return roofline_pct(r, "conv.mix", shapes_conv_lm.conv_layers(cfg), 0.0,
                        shapes_conv_lm.mix_bytes(cfg, tokens))
