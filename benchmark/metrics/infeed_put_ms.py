"""``infeed_put_ms`` — layer: ingest data/.  Unit ``ms``, source
``program_span``; should move ``train_rows_per_s``.

Mean ``step.infeed.put`` per batch: host-side pad + ``device_put`` on the
put thread (overlaps dispatch).
"""

LAYER = "ingest data/"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    span = r["spans"].get("step.infeed.put")
    return 1e3 * span["mean_s"] if span and span["count"] else None
