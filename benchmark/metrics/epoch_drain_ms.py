"""``epoch_drain_ms`` — layer: trainer train/trainer.py.  Unit ``ms``, source
``program_span``; should move ``train_rows_per_s``.

Mean ``epoch.drain`` an epoch of the window: from the exit of the loop
over the feed through the feed's close (the join of the put thread) and
``step.block`` (the value fetch, which waits for the device's last step)
to the epoch's mean.  ``None`` for a program that opens no such span.
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    span = r["spans"].get("epoch.drain")
    return 1e3 * span["mean_s"] if span and span["count"] else None
