"""``dispatch_ms`` — layer: trainer train/trainer.py.  Unit ``ms``, source
``program_span``; should move ``train_rows_per_s``.

Mean ``step.dispatch`` per step: the host enqueueing the jitted step.
Where the device is the bottleneck this is the step time (the dispatch
blocks on the donated state); where the host is, it is launch overhead.
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    span = r["spans"].get("step.dispatch")
    return 1e3 * span["mean_s"] if span and span["count"] else None
