"""Binary columnar shard cache: parse PSV once, stream epochs at memcpy speed.

The reference re-reads and re-parses every gzip PSV shard from scratch each
run, and holds it all in Python lists (ssgd_monitor.py:348-454).  Multi-epoch
training — the normal case; the reference's own default config trains many
epochs over the same shards — re-pays the decompress+parse tax every epoch.

On the single-core TPU bench host that tax is the entire ingest budget:
measured ~206 MB/s gzip inflate + ~350 MB/s parse caps text ingest at
~0.5M rows/s, while page-cache reads run at ~1.8 GB/s.  So the first
pass over a shard writes its *finalized* tensors (ZSCALE applied,
weights clamped — reader._finalize output) plus the per-row train/valid
routing hashes to flat binary slabs; every later epoch memory-maps the
slabs and serves batches as zero-copy views.  The file format is
deliberately dumb — one raw little-endian array per file — so a reader
is ``np.memmap`` and nothing else.

Layout, per source shard, under ``cache_dir``::

    <key>.meta.json   {"version", "n_rows", "n_features", "has_hashes",
                       "feature_dtype", ...}
    <key>.x.f32       features  (n_rows x n_features) float32, row-major
                      (or <key>.x.bf16 — bfloat16 features halve slab reads
                      and host->device bytes for bf16 training runs)
    <key>.y.f32       targets   (n_rows,) float32
    <key>.w.f32       weights   (n_rows,) float32
    <key>.h.u32       crc32 routing hashes (n_rows,) uint32   [optional]

``key`` fingerprints the source file (path, size, mtime) AND the parse
configuration (wanted columns, delimiter, ZSCALE stats, salt, format
version): any change to either produces a different key, so stale entries
are simply never looked up.  Writes go to PID-suffixed temp files renamed
into place, meta last — a cache entry either exists completely or not at
all, and concurrent builders race benignly.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import threading
from dataclasses import dataclass
from typing import Iterator

import numpy as np

from shifu_tensorflow_tpu.data.reader import ParsedBlock, RecordSchema, wanted_columns
from shifu_tensorflow_tpu.utils import fs

CACHE_VERSION = 2
#: every slab name that can belong to an entry (both feature variants)
_SLABS = ("x.f32", "x.bf16", "y.f32", "w.f32", "h.u32")


def _feature_slab(feature_dtype: str) -> str:
    return "x.bf16" if feature_dtype == "bfloat16" else "x.f32"


def feature_np_dtype(name: str):
    """Feature-slab dtype: float32 (default) or bfloat16 — the MXU-native
    dtype, halving slab reads and host->device transfer for bf16 runs.
    Targets/weights stay float32 (loss normalization precision)."""
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    if name in ("float32", "", None):
        return np.dtype(np.float32)
    raise ValueError(f"unsupported cache feature dtype {name!r}")


_feature_dtype = feature_np_dtype  # intra-module alias
# distinguishes concurrent writers for the same key within one process
# (e.g. a train and a valid ShardStream iterating at once) — PID alone
# would have them truncate each other's temp slabs
_WRITER_SEQ = itertools.count()


def cache_key(src_path: str, schema: RecordSchema, salt: int,
              feature_dtype: str = "float32") -> str | None:
    """Fingerprint of (source file identity, parse config).  None when the
    source can't be fingerprinted — size alone is NOT enough (a shard
    replaced with same-size different content would silently serve stale
    rows forever), so a modification time is required too; remote backends
    supply it via FileSystem.mtime_ns."""
    try:
        size = fs.size(src_path)
        mtime_ns = fs.mtime_ns(src_path)
    except Exception:
        return None
    if mtime_ns is None:
        return None
    ident: dict = {"path": os.path.abspath(src_path) if "://" not in src_path
                   else src_path, "size": size, "mtime_ns": mtime_ns}
    cfg = {
        "version": CACHE_VERSION,
        "feature_dtype": feature_dtype or "float32",
        "wanted": list(wanted_columns(schema)),
        "delimiter": schema.delimiter,
        "means": list(schema.means),
        "stds": list(schema.stds),
        "weight_column": schema.weight_column,
        "salt": salt,
    }
    blob = json.dumps({"src": ident, "cfg": cfg}, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()


@dataclass
class ShardCacheReader:
    """Memory-mapped view of one cached shard."""

    n_rows: int
    n_features: int
    has_hashes: bool
    features: np.ndarray  # memmap (n_rows, n_features) float32
    targets: np.ndarray  # memmap (n_rows, 1) float32
    weights: np.ndarray  # memmap (n_rows, 1) float32
    hashes: np.ndarray | None  # memmap (n_rows,) uint32

    def blocks(
        self, rows_per_block: int = 1 << 18
    ) -> Iterator[tuple[ParsedBlock, np.ndarray | None]]:
        """Yield (finalized block, hashes) as zero-copy memmap views."""
        for i in range(0, self.n_rows, rows_per_block):
            j = min(i + rows_per_block, self.n_rows)
            yield (
                ParsedBlock(self.features[i:j], self.targets[i:j], self.weights[i:j]),
                self.hashes[i:j] if self.hashes is not None else None,
            )


def lookup(cache_dir: str, src_path: str, schema: RecordSchema,
           salt: int, feature_dtype: str = "float32"
           ) -> ShardCacheReader | None:
    """Open the cache entry for ``src_path``, or None on miss/corruption."""
    key = cache_key(src_path, schema, salt, feature_dtype)
    if key is None:
        return None
    meta_path = os.path.join(cache_dir, f"{key}.meta.json")
    try:
        with open(meta_path, "r", encoding="utf-8") as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if meta.get("version") != CACHE_VERSION:
        return None
    n = int(meta["n_rows"])
    nf = int(meta["n_features"])
    has_hashes = bool(meta.get("has_hashes"))
    if nf != schema.num_features:
        return None
    try:
        def mm(slab: str, dtype, shape):
            p = os.path.join(cache_dir, f"{key}.{slab}")
            expect = int(np.prod(shape)) * np.dtype(dtype).itemsize
            if os.path.getsize(p) != expect:
                raise OSError(f"cache slab size mismatch: {p}")
            if expect == 0:  # np.memmap rejects zero-length maps
                return np.empty(shape, dtype)
            return np.memmap(p, dtype=dtype, mode="r", shape=shape)

        if meta.get("feature_dtype", "float32") != (
                feature_dtype or "float32"):
            return None  # key collision should make this unreachable
        return ShardCacheReader(
            n_rows=n,
            n_features=nf,
            has_hashes=has_hashes,
            features=mm(_feature_slab(feature_dtype),
                        _feature_dtype(feature_dtype), (n, nf)),
            targets=mm("y.f32", np.float32, (n, 1)),
            weights=mm("w.f32", np.float32, (n, 1)),
            hashes=mm("h.u32", np.uint32, (n,)) if has_hashes else None,
        )
    except OSError:
        return None


def cache_size_bytes(cache_dir: str) -> int:
    """Total bytes of committed cache entries (temp files excluded)."""
    total = 0
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    for name in names:
        if ".tmp." in name:
            continue
        try:
            total += os.path.getsize(os.path.join(cache_dir, name))
        except OSError:
            continue
    return total


#: temp files / orphan slabs younger than this are assumed to belong to an
#: in-flight writer and are left alone by prune_cache
_ORPHAN_MIN_AGE_S = 3600.0


def prune_cache(cache_dir: str, max_bytes: int) -> int:
    """Evict whole entries, oldest meta-mtime first, until the cache fits
    ``max_bytes``; also sweep stale debris — ``.tmp.`` files from writers
    that died without abort() (SIGKILL mid-write) and slabs orphaned
    between commit()'s slab renames and the meta publish — once older than
    an hour.  Returns committed entries removed.  Safe against concurrent
    readers on POSIX: an open memmap keeps its data reachable after
    unlink; the entry simply stops being discoverable."""
    import time

    try:
        names = os.listdir(cache_dir)
    except OSError:
        return 0
    metas = [n for n in names if n.endswith(".meta.json")]
    keys = {m[: -len(".meta.json")] for m in metas}
    now = time.time()
    for name in names:
        if name.endswith(".meta.json"):
            continue
        stale = ".tmp." in name or name.split(".", 1)[0] not in keys
        if not stale:
            continue
        p = os.path.join(cache_dir, name)
        try:
            if now - os.path.getmtime(p) >= _ORPHAN_MIN_AGE_S:
                os.unlink(p)
        except OSError:
            continue
    entries = []
    for meta in metas:
        key = meta[: -len(".meta.json")]
        paths = [os.path.join(cache_dir, meta)] + [
            os.path.join(cache_dir, f"{key}.{s}")
            for s in _SLABS
            if os.path.exists(os.path.join(cache_dir, f"{key}.{s}"))
        ]
        try:
            with open(paths[0], "r", encoding="utf-8") as f:
                version = json.load(f).get("version")
        except (OSError, json.JSONDecodeError):
            version = None
        if version is None or (
            isinstance(version, int) and version < CACHE_VERSION
        ):
            # superseded/corrupt entry: unreadable by this binary's lookup,
            # so it would sit on disk forever — drop it regardless of the
            # budget.  NEWER versions are left alone: during a rolling
            # upgrade two binaries may share a cache_dir, and mutual
            # eviction would defeat the cache for both.
            for p in paths:
                try:
                    os.unlink(p)
                except OSError:
                    pass
            continue
        try:
            mtime = os.path.getmtime(paths[0])
            size = sum(os.path.getsize(p) for p in paths)
        except OSError:
            continue
        entries.append((mtime, size, paths))
    total = sum(e[1] for e in entries)
    removed = 0
    for mtime, size, paths in sorted(entries):
        if total <= max_bytes:
            break
        # meta first: the entry disappears atomically from lookup's view
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass
        total -= size
        removed += 1
    return removed


class ShardCacheWriter:
    """Streaming writer for one shard's cache entry.

    ``append`` takes finalized blocks in stream order; ``commit`` makes the
    entry visible atomically (slabs renamed first, meta last).  Anything
    short of commit leaves no visible entry.
    """

    def __init__(self, cache_dir: str, src_path: str, schema: RecordSchema,
                 salt: int, feature_dtype: str = "float32"):
        self.key = cache_key(src_path, schema, salt, feature_dtype)
        self.ok = self.key is not None
        if not self.ok:
            return
        os.makedirs(cache_dir, exist_ok=True)
        self.cache_dir = cache_dir
        self.src_path = src_path
        self.n_features = schema.num_features
        self.feature_dtype = feature_dtype or "float32"
        self._x_dtype = _feature_dtype(feature_dtype)
        self._slabs = (_feature_slab(feature_dtype), "y.f32", "w.f32",
                       "h.u32")
        self.n_rows = 0
        self.has_hashes: bool | None = None
        self._suffix = (
            f".tmp.{os.getpid()}.{threading.get_ident()}.{next(_WRITER_SEQ)}"
        )
        self._tmp = {s: os.path.join(cache_dir, f"{self.key}.{s}{self._suffix}")
                     for s in self._slabs}
        self._files = {s: open(p, "wb") for s, p in self._tmp.items()}

    def append(self, block: ParsedBlock, hashes: np.ndarray | None) -> None:
        if not self.ok:
            return
        if self.has_hashes is None:
            self.has_hashes = hashes is not None
        elif self.has_hashes != (hashes is not None):
            # mixed availability would desync the hash slab; drop the entry
            self.abort()
            return
        np.ascontiguousarray(block.features, self._x_dtype).tofile(
            self._files[self._slabs[0]])
        np.ascontiguousarray(block.targets, np.float32).tofile(
            self._files["y.f32"])
        np.ascontiguousarray(block.weights, np.float32).tofile(
            self._files["w.f32"])
        if hashes is not None:
            np.ascontiguousarray(hashes, np.uint32).tofile(self._files["h.u32"])
        self.n_rows += len(block)

    def commit(self) -> bool:
        if not self.ok:
            return False
        for f in self._files.values():
            f.close()
        for s in self._slabs:
            if s == "h.u32" and not self.has_hashes:
                os.unlink(self._tmp[s])
                continue
            os.replace(self._tmp[s], os.path.join(self.cache_dir,
                                                  f"{self.key}.{s}"))
        meta = {
            "version": CACHE_VERSION,
            "n_rows": self.n_rows,
            "n_features": self.n_features,
            "has_hashes": bool(self.has_hashes),
            "feature_dtype": self.feature_dtype,
            "src": self.src_path,
        }
        meta_tmp = os.path.join(self.cache_dir,
                                f"{self.key}.meta.json{self._suffix}")
        with open(meta_tmp, "w", encoding="utf-8") as f:
            json.dump(meta, f)
        os.replace(meta_tmp,
                   os.path.join(self.cache_dir, f"{self.key}.meta.json"))
        self.ok = False  # single-shot
        return True

    def abort(self) -> None:
        if not getattr(self, "_files", None):
            return
        for f in self._files.values():
            try:
                f.close()
            except OSError:
                pass
        for p in self._tmp.values():
            try:
                os.unlink(p)
            except OSError:
                pass
        self.ok = False
