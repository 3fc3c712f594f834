"""``epoch_fill_ms`` — layer: trainer train/trainer.py.  Unit ``ms``, source
``program_span``; should move ``train_rows_per_s``.

Mean ``epoch.fill`` an epoch of the window: from the moment the epoch
loop starts building its feed (the put thread's start, the stream's first
batch, its ``device_put``) to the first unit in the consumer's hands.
``None`` for a program that opens no such span.
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    span = r["spans"].get("epoch.fill")
    return 1e3 * span["mean_s"] if span and span["count"] else None
