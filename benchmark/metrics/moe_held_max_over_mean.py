"""``moe_held_max_over_mean`` — layer: models models/ ops/.  Unit ``x``, source
``program_counter``; should move ``train_rows_per_s``.

How uneven the routed load on the held experts is: the largest number of
(token, choice) pairs on one held expert of one layer over the mean a held
expert gets, per step, averaged over the last epoch's steps.  From the
counters the step returns beside its loss (pairs on held experts summed
over the expert layers; the largest held expert's pairs).  1 is even.
"""

LAYER = "models models/ ops/"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"

from benchmark import shapes_lm
from benchmark.lm_readings import lm_shapes


def read(r):
    counters, shapes = r["spans"].get("@counters"), lm_shapes(r)
    if not counters or shapes is None:
        return None
    cfg = shapes[0]
    slots = int(cfg["experts_held"][1]) * shapes_lm.layers_of(cfg, "E")
    ratios = [m / (p / slots) for p, m in zip(counters["moe_held_pairs"],
                                              counters["moe_held_max"]) if p]
    return sum(ratios) / len(ratios) if ratios else None
