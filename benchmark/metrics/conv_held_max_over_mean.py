"""``conv_held_max_over_mean`` — layer: models models/ ops/.  Unit ``x``, source
``program_counter``; should move ``train_rows_per_s``.

How uneven the routed load on the held experts is: the largest number of
(token, choice) pairs on one held expert of one layer over the mean a held
expert gets, per step, averaged over the last epoch's steps.  From the
counters the step returns beside its loss (``moe_held_pairs``: pairs on
held experts summed over the sparse layers; ``moe_held_max``: the largest
held expert's pairs).  1 is even; the configuration's ``expert_tile``
holds an expert's pairs in one tile up to 1.5.
"""

LAYER = "models models/ ops/"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"

from benchmark import shapes_conv_lm
from benchmark.conv_lm_readings import conv_shapes, counters


def read(r):
    found = counters(r)
    if found is None:
        return None
    cfg = conv_shapes(r)[0]
    slots = int(cfg["experts_held"][1]) * shapes_conv_lm.sparse_layers(cfg)
    ratios = [m / (p / slots) for p, m in zip(found["moe_held_pairs"],
                                              found["moe_held_max"]) if p]
    return sum(ratios) / len(ratios) if ratios else None
