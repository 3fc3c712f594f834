"""``epoch_turn_ms`` — layer: trainer train/trainer.py.  Unit ``ms``, source
``program_span``; should move ``train_rows_per_s``.

The window's ``epoch.turn`` time (the epoch loop outside ``train_epoch``:
journal, autotuner, the stream's rebuild, callbacks, checkpoint; several
spans an epoch) over its epochs, which ``epoch.fill`` counts: one an
epoch.  ``None`` for a program that opens no ``epoch.fill``.
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_rows_per_s"


def read(r):
    turn, fill = (r["spans"].get(n) for n in ("epoch.turn", "epoch.fill"))
    if not turn or not fill or not fill["count"]:
        return None
    return 1e3 * turn["total_s"] / fill["count"]
