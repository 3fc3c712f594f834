"""The table of peaks, keyed by ``device_kind``.  A device that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
import os


def lookup(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(
            f"benchmark: no peaks for device kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)})")
    return table[device_kind]
