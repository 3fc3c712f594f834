"""Seeded token rows for the language-model cells: gzip PSV shards
``target | id ... id | weight`` with the ids drawn uniformly below the
configuration's ``data.id_below`` (the slice of the vocabulary the chip
holds), ``data.tokens_per_row`` of them a row.  A row is one fixed-length,
unpacked sequence; the target column is 0 (the family's loss reads the
ids) and the weight 1.  The ids ride the float feature block as the
parser delivers it (float32 holds every integer below 2^24 exactly).

Never imports JAX.  One slot per cell, stamped like ``gen.py``'s.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil

import numpy as np


def write_shard(path: str, seed: int, shard: int, rows: int,
                data: dict) -> None:
    rng = np.random.default_rng([seed, shard])
    ids = rng.integers(0, int(data["id_below"]),
                       size=(rows, int(data["tokens_per_row"])))
    body = "".join("0|" + "|".join(map(str, row)) + "|1.0\n"
                   for row in ids.tolist())
    tmp = path + ".tmp"
    with gzip.open(tmp, "wb", compresslevel=1) as f:
        f.write(body.encode())
    os.replace(tmp, path)


def ensure_shards(work_dir: str, seed: int, rows: int, shards: int,
                  data: dict) -> tuple[list[str], bool]:
    """The cell's shards under ``work_dir`` (anything else there, shard
    cache included, is wiped when the stamp differs).  (paths, reused)."""
    stamp = {"seed": int(seed), "rows": int(rows), "shards": int(shards),
             "data": data}
    stamp_path = os.path.join(work_dir, "stamp.json")
    shard_dir = os.path.join(work_dir, "shards")
    paths = [os.path.join(shard_dir, f"part-{i:05d}.gz")
             for i in range(shards)]
    try:
        with open(stamp_path) as f:
            if json.load(f) == stamp and all(map(os.path.exists, paths)):
                return paths, True
    except (OSError, ValueError):
        pass
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide into {shards} shards")
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(shard_dir)
    for i, path in enumerate(paths):
        write_shard(path, seed, i, rows // shards, data)
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    return paths, False
