"""Per-stage ingest profiling on the bench host (SURVEY.md §7.2 item 1).

Measures, in isolation, every stage of the streaming path so BENCH_r03 can
carry the per-stage breakdown VERDICT round 2 asked for:

  1. raw disk/page-cache read of compressed bytes
  2. gzip inflate (Python GzipFile 4MB reads, and raw zlib.decompressobj)
  3. native block parse of decompressed bytes (stpu_parse_buffer)
  4. numpy finalize/copy overhead
  5. ShardStream drain (full host pipeline, no jax)
  6. device_put transfer throughput (when a device is present)
  7. full stream -> prefetch -> jitted step (end-to-end rows/s)

Run: python scripts/profile_ingest.py [--rows N] [--no-device]
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import tempfile
import time
import zlib

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NUM_FEATURES = 30


def make_shards(root: str, total_rows: int, n_shards: int) -> tuple[list[str], int]:
    rng = np.random.default_rng(0)
    block_rows = 20_000
    x = rng.normal(size=(block_rows, NUM_FEATURES)).astype(np.float32)
    y = (rng.random(block_rows) < 0.3).astype(np.int32)
    lines = []
    for i in range(block_rows):
        cols = [str(int(y[i]))] + [f"{v:.5f}" for v in x[i]] + ["1.0"]
        lines.append("|".join(cols))
    block = ("\n".join(lines) + "\n").encode()
    rows_per_shard = total_rows // n_shards
    reps = max(1, rows_per_shard // block_rows)
    paths = []
    for s in range(n_shards):
        path = os.path.join(root, f"part-{s:05d}.gz")
        with gzip.open(path, "wb", compresslevel=1) as f:
            for _ in range(reps):
                f.write(block)
        paths.append(path)
    return paths, reps * block_rows * n_shards


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--no-device", action="store_true")
    args = ap.parse_args()

    from shifu_tensorflow_tpu.data import native
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.reader import RecordSchema, wanted_columns

    schema = RecordSchema(
        feature_columns=tuple(range(1, NUM_FEATURES + 1)),
        target_column=0,
        weight_column=NUM_FEATURES + 1,
    )
    out: dict = {"cpus": os.cpu_count()}

    with tempfile.TemporaryDirectory(prefix="stpu-prof-") as root:
        t0 = time.perf_counter()
        paths, nrows = make_shards(root, args.rows, 4)
        out["gen_s"] = round(time.perf_counter() - t0, 2)
        out["rows"] = nrows
        comp_bytes = sum(os.path.getsize(p) for p in paths)
        out["compressed_mb"] = round(comp_bytes / 1e6, 1)

        # 1. raw read of compressed bytes (page cache warm after gen)
        t0 = time.perf_counter()
        raw = []
        for p in paths:
            with open(p, "rb") as f:
                raw.append(f.read())
        dt = time.perf_counter() - t0
        out["read_compressed_mb_s"] = round(comp_bytes / dt / 1e6, 1)

        # 2a. inflate via zlib.decompressobj (gzip wrapper)
        t0 = time.perf_counter()
        decomp_bytes = 0
        bufs = []
        for r in raw:
            d = zlib.decompressobj(wbits=31)
            b = d.decompress(r)
            decomp_bytes += len(b)
            bufs.append(b)
        dt_inflate = time.perf_counter() - t0
        out["decompressed_mb"] = round(decomp_bytes / 1e6, 1)
        out["zlib_inflate_mb_s"] = round(decomp_bytes / dt_inflate / 1e6, 1)
        out["zlib_inflate_rows_s"] = round(nrows / dt_inflate, 0)

        # 2b. inflate via GzipFile in 4MB reads (the ShardStream path)
        t0 = time.perf_counter()
        for p in paths:
            with gzip.open(p, "rb") as f:
                while f.read(4 << 20):
                    pass
        dt = time.perf_counter() - t0
        out["gzipfile_inflate_mb_s"] = round(decomp_bytes / dt / 1e6, 1)

        # 3. native parse of decompressed buffers (no hashes; 1 thread)
        wanted = wanted_columns(schema)
        if native.available():
            t0 = time.perf_counter()
            total = 0
            for b in bufs:
                arr, _ = native.parse_buffer(
                    b, wanted, "|", want_hashes=False, n_threads=1
                )
                total += arr.shape[0]
            dt_parse = time.perf_counter() - t0
            out["native_parse_rows_s"] = round(total / dt_parse, 0)
            out["native_parse_mb_s"] = round(decomp_bytes / dt_parse / 1e6, 1)
            # with hashes
            t0 = time.perf_counter()
            for b in bufs:
                native.parse_buffer(b, wanted, "|", want_hashes=True, n_threads=1)
            out["native_parse_hash_rows_s"] = round(
                total / (time.perf_counter() - t0), 0
            )

        # 4. numpy finalize overhead (copies per parsed block)
        from shifu_tensorflow_tpu.data.reader import _finalize

        arr, _ = native.parse_buffer(bufs[0], wanted, "|", want_hashes=False)
        t0 = time.perf_counter()
        reps = 5
        for _ in range(reps):
            _finalize(arr, schema)
        out["finalize_rows_s"] = round(reps * arr.shape[0] / (time.perf_counter() - t0), 0)

        del raw, bufs

        # 5. ShardStream drain, no jax (host pipeline ceiling)
        for nr in (1, 2):
            stream = ShardStream(
                paths, schema, 16384, valid_rate=0.0, emit="train",
                n_readers=nr, drop_remainder=True,
            )
            t0 = time.perf_counter()
            rows = 0
            for b in stream:
                rows += b["x"].shape[0]
            dt = time.perf_counter() - t0
            out[f"shardstream_r{nr}_rows_s"] = round(rows / dt, 0)

        if not args.no_device:
            import jax

            dev = jax.devices()[0]
            out["platform"] = dev.platform
            # 6. device_put throughput, 16K-row batch
            batch = {
                "x": np.random.default_rng(0).normal(size=(16384, NUM_FEATURES)).astype(np.float32),
                "y": np.zeros((16384, 1), np.float32),
                "w": np.ones((16384, 1), np.float32),
            }
            from shifu_tensorflow_tpu.utils.profiling import true_sync

            nbytes = sum(v.nbytes for v in batch.values())
            true_sync(jax.device_put(batch, dev))
            t0 = time.perf_counter()
            reps = 50
            # overlapped puts; one element of every leaf of every put is
            # chained into an on-device accumulator so a SINGLE final
            # fetch proves all transfers completed inside the window
            # (utils/profiling.true_sync)
            acc = None
            for _ in range(reps):
                for leaf in jax.tree_util.tree_leaves(
                        jax.device_put(batch, dev)):
                    probe = (leaf.reshape(-1)[0] if leaf.ndim else leaf)
                    probe = probe.astype("float32")
                    acc = probe if acc is None else acc + probe
            true_sync(acc)
            dt = time.perf_counter() - t0
            out["device_put_mb_s"] = round(reps * nbytes / dt / 1e6, 1)
            out["device_put_rows_s"] = round(reps * 16384 / dt, 0)
            out["device_put_ms_per_batch"] = round(dt / reps * 1e3, 2)

    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
