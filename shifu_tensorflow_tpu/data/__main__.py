"""Dataset utility CLI: materialize the binary shard cache ahead of
training (and inspect/prune it).

The reference had no equivalent — every run re-parsed gzip PSV from
scratch (ssgd_monitor.py:348-454).  Pre-building the cache moves the
one-time parse cost out of the training job entirely, so even the first
epoch streams memory-mapped tensors:

    python -m shifu_tensorflow_tpu.data build \\
        --training-data-path hdfs://nn:9870/data/train \\
        --cache-dir /fast/cache --feature-columns 1,2,3 --target-column 0

    python -m shifu_tensorflow_tpu.data status --cache-dir /fast/cache
    python -m shifu_tensorflow_tpu.data prune  --cache-dir /fast/cache \\
        --max-bytes 50g
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="python -m shifu_tensorflow_tpu.data")
    sub = p.add_subparsers(dest="cmd", required=True)

    b = sub.add_parser("build", help="parse shards into the binary cache")
    b.add_argument("--training-data-path", required=True)
    b.add_argument("--cache-dir", required=True)
    b.add_argument("--feature-columns", default=None,
                   help="comma-separated column indices (or --column-config)")
    b.add_argument("--column-config", default=None,
                   help="ColumnConfig.json: column selection + ZSCALE stats")
    b.add_argument("--zscale", action="store_true",
                   help="apply ZSCALE from --column-config — MUST match the "
                        "training run's --zscale or the cache keys differ "
                        "and every lookup misses")
    b.add_argument("--target-column", type=int, default=None)
    b.add_argument("--weight-column", type=int, default=None)
    b.add_argument("--delimiter", default="|")
    b.add_argument("--salt", type=int, default=0,
                   help="MUST equal the training run's --seed (the salt is "
                        "part of the cache key and the train/valid routing)")
    b.add_argument("--feature-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="MUST match the training run's dtype gate "
                        "(bfloat16 runs on hash-free models)")
    b.add_argument("--readers", type=int, default=1,
                   help="parallel file builders (threads); cache writes "
                        "per file are independent")

    s = sub.add_parser("status", help="cache size and entry count")
    s.add_argument("--cache-dir", required=True)

    r = sub.add_parser("prune", help="evict oldest entries to a byte budget")
    r.add_argument("--cache-dir", required=True)
    r.add_argument("--max-bytes", required=True,
                   help="budget: bytes or memory string (50g, 512m)")
    return p


def _build_schema(args):
    """Mirror the training CLI's schema resolution (train/__main__.py
    resolve_schema) so the cache keys line up: same columns, same ZSCALE
    stats, same delimiter — or every training lookup would silently miss."""
    from shifu_tensorflow_tpu.config.model_config import ColumnConfig
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    cc = ColumnConfig.load(args.column_config) if args.column_config else None
    if args.feature_columns:
        features = tuple(int(c) for c in args.feature_columns.split(","))
    elif cc is not None:
        features = tuple(cc.selected_column_nums)
    else:
        raise SystemExit(
            "need --feature-columns or --column-config to define the schema"
        )
    target = (args.target_column if args.target_column is not None
              else (cc.target_column_num if cc else 0))
    weight = (args.weight_column if args.weight_column is not None
              else (cc.weight_column_num if cc else -1))
    schema = RecordSchema(
        feature_columns=features, target_column=target,
        weight_column=weight, delimiter=args.delimiter,
    )
    if args.zscale:
        if cc is None:
            raise SystemExit("--zscale needs --column-config for the stats")
        means, stds = cc.zscale_stats(features)
        schema = schema.with_zscale(means, stds)
    return schema


def _build(args) -> int:
    from concurrent.futures import ThreadPoolExecutor

    from shifu_tensorflow_tpu.data import cache as shard_cache
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.splitter import list_data_files

    schema = _build_schema(args)
    paths = list_data_files(args.training_data_path)
    if not paths:
        print(f"no files under {args.training_data_path}", file=sys.stderr)
        return 2

    def build_one(path: str) -> int | None:
        # cache writes always include routing hashes, so any later
        # train/valid split serves from these entries; drain the stream
        # (drop_remainder avoids fabricating padded batches) and report
        # the COMMITTED row count from the entry itself
        stream = ShardStream(
            [path], schema, 1 << 16, valid_rate=0.0, emit="train",
            salt=args.salt, cache_dir=args.cache_dir,
            feature_dtype=args.feature_dtype, drop_remainder=True,
        )
        for _ in stream:
            pass
        reader = shard_cache.lookup(
            args.cache_dir, path, schema, args.salt, args.feature_dtype
        )
        return None if reader is None else reader.n_rows

    t0 = time.perf_counter()
    rows = 0
    cached_files = 0
    with ThreadPoolExecutor(max_workers=max(1, args.readers)) as pool:
        for i, (path, n) in enumerate(zip(paths, pool.map(build_one, paths))):
            if n is None:
                print(f"warning: {path} did not cache (source not "
                      f"fingerprintable?)", file=sys.stderr)
                continue
            cached_files += 1
            rows += n
            print(f"[{i + 1}/{len(paths)}] {path}: {n} rows", flush=True)
    dt = time.perf_counter() - t0
    print(json.dumps({
        "files": len(paths), "cached_files": cached_files, "rows": rows,
        "rows_per_sec": round(rows / dt, 1),
        "elapsed_s": round(dt, 1),
        "cache_dir": args.cache_dir,
        "feature_dtype": args.feature_dtype,
    }), flush=True)
    # automation gates on this: a pre-warm that cached nothing (or only
    # part of the dataset) must not read as success
    return 0 if cached_files == len(paths) else 1


def _status(args) -> int:
    import os

    from shifu_tensorflow_tpu.data import cache as shard_cache

    try:
        names = os.listdir(args.cache_dir)
    except OSError as e:
        print(f"cannot read {args.cache_dir}: {e}", file=sys.stderr)
        return 2
    print(json.dumps({
        "entries": sum(1 for n in names if n.endswith(".meta.json")),
        "bytes": shard_cache.cache_size_bytes(args.cache_dir),
        "tmp_files": sum(1 for n in names if ".tmp." in n),
    }))
    return 0


def _prune(args) -> int:
    from shifu_tensorflow_tpu.config.conf import parse_memory_string
    from shifu_tensorflow_tpu.data import cache as shard_cache

    removed = shard_cache.prune_cache(
        args.cache_dir, parse_memory_string(args.max_bytes)
    )
    print(json.dumps({"removed": removed}))
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "build":
        # the only subcommand that can touch jax (dataset internals);
        # status/prune stay jax-free and fast
        from shifu_tensorflow_tpu.obs.compile import apply_persistent_cache

        apply_persistent_cache()
    return {"build": _build, "status": _status, "prune": _prune}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
