"""Seeded data for the benchmark's cells: rows and gzip PSV shards.

The generator is ``chip_smoke.py``'s ``synth_rows`` / ``write_shard``
(heavy-head, long-tail category codes carried as ``code * 1e-6``; a label
that is a noisy logistic of the numerics and the codes' parities), made
general: the numbers of numeric and categorical columns, the per-column
cardinalities and the skew come from the configuration's ``data`` block.
This module never imports JAX: shard writers are forked before the
process touches the chip.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil

import numpy as np


def cardinalities(data: dict) -> np.ndarray:
    """Per categorical column, log-spaced from ``cardinality_min`` to
    ``cardinality_max`` (float32 carries ``code * 1e-6`` exactly only
    below ~1.6e7, so the maximum stays under that)."""
    n = int(data["categorical"])
    if n == 0:
        return np.zeros(0, np.int64)
    return np.round(np.logspace(np.log10(data["cardinality_min"]),
                                np.log10(data["cardinality_max"]),
                                n)).astype(np.int64)


def zipf_codes(rng, rows: int, card: np.ndarray, s: float) -> np.ndarray:
    """(rows, len(card)) integer codes in [0, card): a bounded power law
    with exponent ``s`` per column, by the inverse of the continuous
    distribution function (rank 1 the most frequent)."""
    u = rng.random((rows, len(card)))
    n = card.astype(np.float64)[None, :]
    if abs(s - 1.0) < 1e-9:
        rank = np.exp(u * np.log(n))
    else:
        rank = ((n ** (1.0 - s) - 1.0) * u + 1.0) ** (1.0 / (1.0 - s))
    return np.minimum(np.floor(rank) - 1, n - 1).astype(np.int64)


def synth_rows(rng, rows: int, data: dict):
    """(features (rows, numeric + categorical) float32, labels (rows,))."""
    n_num, n_cat = int(data["numeric"]), int(data["categorical"])
    numeric = rng.normal(size=(rows, n_num))
    w = np.linspace(-1.0, 1.0, n_num)
    logit = numeric @ w * 0.7
    parts = [numeric]
    if n_cat:
        codes = zipf_codes(rng, rows, cardinalities(data),
                           float(data["zipf_s"]))
        logit = logit + ((codes % 5) - 2).sum(axis=1) * (2.0 / n_cat)
        parts.append(codes * float(data["code_scale"]))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int64)
    return np.concatenate(parts, axis=1).astype(np.float32), y


def write_shard(path: str, seed: int, shard: int, rows: int,
                data: dict) -> None:
    """One gzip PSV shard ``target|f...|weight``, as users' shards are."""
    x, y = synth_rows(np.random.default_rng([seed, shard]), rows, data)
    fmt = ("%d|" + "%.5f|" * int(data["numeric"])
           + "%.6f|" * int(data["categorical"]) + "1.0\n")
    body = "".join(
        fmt % (label, *feats) for label, feats in zip(y.tolist(), x.tolist()))
    tmp = path + ".tmp"
    with gzip.open(tmp, "wb", compresslevel=1) as f:
        f.write(body.encode())
    os.replace(tmp, path)


def _write_shard_job(job) -> None:
    write_shard(*job)


def ensure_shards(work_dir: str, seed: int, rows: int, shards: int,
                  data: dict, workers: int) -> tuple[list[str], bool]:
    """The cell's shards under ``work_dir`` (one slot: a stamp names the
    seed and sizes it holds; anything else there is wiped and written
    anew, shard cache included).  Returns (paths, reused).  Writers are
    forked processes that end before this returns; call it before the
    process imports JAX."""
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
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(shard_dir)
    if rows % shards:
        raise ValueError(f"{rows} rows do not divide into {shards} shards")
    jobs = [(p, seed, i, rows // shards, data) for i, p in enumerate(paths)]
    if workers > 1 and shards > 1:
        import multiprocessing

        pool = multiprocessing.get_context("fork").Pool(min(workers, shards))
        try:
            pool.map(_write_shard_job, jobs, chunksize=1)
        finally:  # the writers have ended before this returns
            pool.close()
            pool.join()
    else:
        for job in jobs:
            _write_shard_job(job)
    with open(stamp_path, "w") as f:
        json.dump(stamp, f)
    return paths, False


def first_batches(paths: list[str], batch: int, steps: int,
                  num_features: int):
    """The first ``steps`` batches of the stream read back from the shard
    files in plain Python (not through the program's parser), for the
    reference: file order, no shuffle, every row in the training split."""
    need, rows = steps * batch, []
    for path in paths:
        with gzip.open(path, "rt") as f:
            for line in f:
                rows.append(line.split("|"))
                if len(rows) == need:
                    break
        if len(rows) == need:
            break
    if len(rows) < need:
        raise ValueError(f"the shards hold {len(rows)} rows, need {need}")
    a = np.asarray(rows, dtype=np.float64)
    x = a[:, 1:1 + num_features].astype(np.float32)
    y = a[:, :1].astype(np.float32)
    w = a[:, 1 + num_features:2 + num_features].astype(np.float32)
    return [{"x": x[i * batch:(i + 1) * batch],
             "y": y[i * batch:(i + 1) * batch],
             "w": w[i * batch:(i + 1) * batch]} for i in range(steps)]
