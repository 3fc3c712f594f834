"""``infeed_wait_pct`` — layer: ingest data/.  Unit ``%``, source
``program_span``; should move ``train_rows_per_s``.

Sum of the trainer's ``step.infeed.wait`` spans (the consumer stalled on
the put thread's queue) over the window's length.  Little where the step
dominates; most of the window where ingest does.
"""

LAYER = "ingest data/"
UNIT = "%"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    span = r["spans"].get("step.infeed.wait")
    if not span or not r["window_s"]:
        return None
    return 100.0 * span["total_s"] / r["window_s"]
