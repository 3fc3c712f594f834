"""``peak_hbm_bytes`` — layer: device.  Unit ``bytes``, source
``program_counter``; should move ``train_rows_per_s``.

The most HBM held on a device at the harness's readings (after the
reference, the first steps, the warm-up and the window):
``bytes_in_use + bytes_reserved`` of ``memory_stats()``, the largest over
devices.  The reservation is the loaded step's temporaries, which
``peak_bytes_in_use`` leaves out.  A capacity reading (how full the chip
is), set beside the 16 GB of the peaks table.
"""

LAYER = "device"
UNIT = "bytes"
SOURCE = "program_counter"
MOVES = "train_rows_per_s"


def read(r):
    return r["device"].get("memory_peak_bytes")
