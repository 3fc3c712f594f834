"""Staged pull-based ingest pipeline — the tf.data-class rebuild of the
streaming input path (ROADMAP item 2; design per the tf.data paper,
PAPERS.md: "tf.data: A Machine Learning Data Processing Framework").

The previous ``ShardStream`` was one producer thread per file bucket doing
read → inflate → parse → finalize → batch serially, so the ``n_readers``
knob bought nothing (cold reads went no faster at 2 or 4 readers) and
reader count changed the batch order (parallel ingest was opt-in and
irreproducible).  This module splits the work into composable stages with
bounded queues, each timed under its own obs span:

    shard plan ──► reader threads ──► decode pool ──► sequencer ──► shuffle
    (static          (IO + fused        (parse /        (ordered      buffer
     round-robin)     native stream)     finalize /      merge,        (seeded)
                                         cast)           pull-based)

- **readers** (``ingest.read``): N threads; shard *i* belongs to reader
  ``i % N`` — a static, deterministic assignment.  Each reader walks its
  shards in ascending order, producing chunk payloads (cache blocks /
  native-parsed arrays / raw byte chunks) into its own bounded queue.
  Per-shard transient faults retry under the PR-1 envelope
  (utils/retry.call, site ``ingest.read``) with chunk-offset resume:
  chunking is deterministic, so a re-opened shard skips the chunks it
  already submitted and continues where the fault hit — same contract the
  fs layer's ResumableReader gives remote byte streams, one level up.
  Chaos seam: ``faults.check("ingest.read.s<shard>")`` before every chunk.
- **decode pool** (``ingest.decode``): a shared thread pool running
  parse + finalize(ZSCALE/weight-clamp) + transport-dtype cast.  Readers
  submit each chunk and enqueue the *future*, so decode parallelism never
  reorders anything.  Cache-hit blocks (already finalized, memmap'd)
  bypass the pool entirely — the warm path stays zero-copy.
- **sequencer** (``ingest.wait``): the pull stage.  Runs in the consumer's
  thread, draining reader queues in global shard order and resolving
  futures in submission order — so the emitted block stream is a pure
  function of (path list, schema, salt), independent of reader count,
  decode width, queue depths, and thread interleaving.  ``ingest.wait``
  is the consumer-visible stall: the time the training loop actually
  waited on ingest.
- **shuffle** (``ingest.shuffle``): optional seeded window shuffle
  (``shuffle_rows`` > 0): consecutive windows of that many rows are
  permuted with a ``numpy`` Generator seeded from (seed, epoch-salt) —
  deterministic for a fixed seed regardless of parallelism.

Batching (fixed shapes, zero-weight padding) happens after the shuffle in
the same pull path, so batch composition at shard boundaries is identical
across reader counts — the property the seeded-shuffle reproducibility
tests pin (tests/test_ingest.py).

Lifecycle: the pipeline owns threads, so abandoning an epoch mid-stream
must release them.  ``close()`` (idempotent, also wired into the
generator's ``finally`` and ``ShardStream.close()``) stops producers,
drains queues so no thread is wedged on a full queue, joins readers,
shuts the decode pool down, and aborts any uncommitted cache writers.
Every trainer epoch path closes its stream in a ``finally``
(train/trainer.py), so health-guard rollbacks and mid-epoch exceptions
cannot leak producer threads.

Autotuning: ``StageStats`` accumulates per-stage busy/wait seconds; the
``data/autotune.py`` policy reads them (plus the installed tracer's step
spans) to size readers / decode workers / prefetch between epochs.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Iterator, Sequence

import numpy as np

from shifu_tensorflow_tpu.data.reader import (
    ParsedBlock,
    RecordSchema,
    route_is_valid,
    wanted_columns,
)
from shifu_tensorflow_tpu.utils import faults
from shifu_tensorflow_tpu.utils import fs
from shifu_tensorflow_tpu.utils import logs
from shifu_tensorflow_tpu.utils import retry as retry_util

log = logs.get("ingest")

_perf = time.perf_counter

#: queue markers (tuples keyed by these sentinels)
_SHARD_START = object()
_SHARD_END = object()


class StreamClosed(RuntimeError):
    """Raised by the sequencer when the pipeline is closed underneath it
    (another thread called close() while this one was pulling)."""


@dataclass
class StageStats:
    """Per-epoch stage accounting the autotuner consumes.  All the ``*_s``
    fields are SUMS across threads (2 readers busy for 1s each = 2.0), so
    busy fractions divide by the stage width × wall."""

    readers: int = 0
    decode_workers: int = 0
    read_s: float = 0.0  # reader-thread time producing chunks
    decode_s: float = 0.0  # decode-pool time parsing/finalizing
    wait_s: float = 0.0  # consumer-visible stall pulling the next block
    shuffle_s: float = 0.0
    rows: int = 0
    chunks: int = 0
    cache_chunks: int = 0  # chunks served from the binary shard cache
    retries: int = 0  # shard read attempts that were retried
    wall_s: float = 0.0  # first-pull → close wall clock
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False)

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + seconds)

    def bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def busy_fractions(self) -> dict[str, float]:
        """Stage busy / starvation ratios, each in [0, ~1]."""
        wall = self.wall_s or 1e-9
        return {
            "read_busy": self.read_s / (max(1, self.readers) * wall),
            "decode_busy": self.decode_s / (max(1, self.decode_workers)
                                            * wall),
            "wait_frac": min(1.0, self.wait_s / wall),
        }

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            return {
                "readers": self.readers,
                "decode_workers": self.decode_workers,
                "read_s": round(self.read_s, 6),
                "decode_s": round(self.decode_s, 6),
                "wait_s": round(self.wait_s, 6),
                "shuffle_s": round(self.shuffle_s, 6),
                "rows": self.rows,
                "chunks": self.chunks,
                "cache_chunks": self.cache_chunks,
                "retries": self.retries,
                "wall_s": round(self.wall_s, 6),
            }


class _Ready:
    """A pre-resolved 'future' for payloads that need no decode work
    (cache-hit blocks) — keeps the warm memmap path off the pool."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def result(self):
        return self._value


class _Err:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class ShardPipeline:
    """Parallel shard readers + decode pool + ordered pull sequencer.

    ``blocks()`` yields ``(full_block, hashes)`` tuples in deterministic
    (shard, chunk) order; routing/shuffle/batching stay with the caller
    (data/dataset.ShardStream).  The caller owns the lifecycle: iterate
    ``blocks()`` to completion or call ``close()``.
    """

    def __init__(
        self,
        paths: Sequence[str],
        schema: RecordSchema,
        *,
        salt: int = 0,
        n_readers: int = 1,
        decode_workers: int = 1,
        queue_depth: int = 4,
        block_bytes: int = 4 << 20,
        block_rows: int = 1 << 16,
        cache_dir: str | None = None,
        feature_dtype: str = "float32",
        need_hashes: bool = False,
        retry_policy: "retry_util.RetryPolicy | None" = None,
        stats: StageStats | None = None,
        close_timeout_s: float = 10.0,
        tracer=None,
        fault_site_prefix: str = "ingest",
        shard_offset: int = 0,
    ):
        self.paths = list(paths)
        self.schema = schema
        self.salt = salt
        self.n_readers = max(1, min(int(n_readers), max(1, len(self.paths))))
        self.decode_workers = max(1, int(decode_workers))
        self.queue_depth = max(1, int(queue_depth))
        self.block_bytes = block_bytes
        self.block_rows = max(1, int(block_rows))
        self.cache_dir = cache_dir
        self.feature_dtype = feature_dtype or "float32"
        self.need_hashes = need_hashes
        self.retry_policy = retry_policy
        self.stats = stats if stats is not None else StageStats()
        self.stats.readers = self.n_readers
        self.stats.decode_workers = self.decode_workers

        self.close_timeout_s = close_timeout_s
        # chaos-seam identity: the bulk scorer runs one pipeline PER
        # LEASED SHARD and keys faults to the job-global shard id
        # ("score.read.s<shard>"), not this pipeline's local index —
        # prefix + offset let it do that without a parallel seam scheme
        self.fault_site_prefix = fault_site_prefix
        self.shard_offset = int(shard_offset)
        # EXPLICIT span sink only (no fallback to the process-global
        # install): the validation stream runs untraced on purpose —
        # its ingest work must not inflate the train epoch's journaled
        # span budget (same discipline as _PipelinedPrefetch's
        # step.infeed.* seams, data/dataset.py)
        self.tracer = tracer
        self._stop = threading.Event()
        self._close_lock = threading.Lock()
        self._queues: list[queue.Queue] = [
            queue.Queue(maxsize=self.queue_depth)
            for _ in range(self.n_readers)
        ]
        self._pool: ThreadPoolExecutor | None = None
        self._threads: list[threading.Thread] = []
        self._writers: list = []  # live (uncommitted) cache writers
        self._writers_lock = threading.Lock()
        self._started = False
        self._closed = False
        self._t_start = 0.0

    # ---- lifecycle --------------------------------------------------------

    def start(self) -> "ShardPipeline":
        if self._started:
            return self
        self._started = True
        self._t_start = _perf()
        self._pool = ThreadPoolExecutor(
            max_workers=self.decode_workers,
            thread_name_prefix="stpu-ingest-decode",
        )
        self._threads = [
            threading.Thread(
                target=self._reader_main, args=(r,),
                name=f"stpu-ingest-read-{r}", daemon=True,
            )
            for r in range(self.n_readers)
        ]
        for t in self._threads:
            t.start()
        return self

    def close(self) -> None:
        """Stop producers, join threads, release the pool, abort
        uncommitted cache writers.  Idempotent; safe from any thread
        (the generator's ``finally`` and an abandoning consumer may race
        here — the lock serializes them, and the loser sees ``_closed``).

        The join is BOUNDED (``close_timeout_s``): a reader wedged in an
        uninterruptible fs read (dead remote socket with no timeout) can
        never observe the stop event, and an unbounded join would turn a
        health-guard rollback into an indefinite hang — worse than the
        thread leak it prevents.  Past the deadline the daemon thread is
        abandoned with a warning; it exits on its own the moment the
        blocked read returns (every loop edge checks ``_stop``)."""
        with self._close_lock:
            if self._closed:
                return
            self._closed = True
            self._stop.set()
            deadline = _perf() + self.close_timeout_s
            # drain so a producer blocked on a full queue can observe stop
            for t in self._threads:
                while t.is_alive():
                    for q in self._queues:
                        try:
                            q.get_nowait()
                        except queue.Empty:
                            pass
                    t.join(timeout=0.05)
                    if t.is_alive() and _perf() > deadline:
                        log.warning(
                            "ingest reader %s did not exit within %.1fs of "
                            "close() (blocked in an uninterruptible read?) "
                            "— abandoning the daemon thread", t.name,
                            self.close_timeout_s)
                        break
            self._finish_close()

    def _finish_close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None
        with self._writers_lock:
            writers, self._writers = self._writers[:], []
        for w in writers:
            try:
                w.abort()
            except Exception:
                pass
        if self._t_start:
            self.stats.wall_s = _perf() - self._t_start

    # ---- reader stage -----------------------------------------------------

    def _put(self, q: "queue.Queue", item) -> bool:
        """Bounded put that gives up when the pipeline is closing — a plain
        q.put could wedge a thread forever on an abandoned iterator."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _reader_main(self, r: int) -> None:
        q = self._queues[r]
        try:
            for shard_idx in range(r, len(self.paths), self.n_readers):
                self._produce_shard(shard_idx, self.paths[shard_idx], q)
                if self._stop.is_set():
                    return
        except BaseException as e:  # surface to the consumer, never die mute
            self._put(q, _Err(e))

    def _produce_shard(self, shard_idx: int, path: str,
                       q: "queue.Queue") -> None:
        """Emit one shard: START(writer) marker, chunk futures, END marker.
        Transient read faults retry with chunk-offset resume."""
        from shifu_tensorflow_tpu.data import cache as shard_cache

        cache_reader = None
        writer = None
        if self.cache_dir is not None:
            cache_reader = shard_cache.lookup(
                self.cache_dir, path, self.schema, self.salt,
                self.feature_dtype,
            )
            if cache_reader is not None and (
                    self.need_hashes and not cache_reader.has_hashes):
                cache_reader = None  # entry unusable for routed streams
            if cache_reader is None:
                writer = shard_cache.ShardCacheWriter(
                    self.cache_dir, path, self.schema, self.salt,
                    self.feature_dtype,
                )
                with self._writers_lock:
                    self._writers.append(writer)
        want_hashes = self.need_hashes or writer is not None

        if not self._put(q, (_SHARD_START, shard_idx, writer)):
            return
        submitted = 0

        def attempt() -> None:
            nonlocal submitted
            emitted = 0
            site = (f"{self.fault_site_prefix}.read."
                    f"s{self.shard_offset + shard_idx}")
            t0 = _perf()
            for payload in self._shard_chunks(path, cache_reader,
                                              want_hashes):
                if self._stop.is_set():
                    return
                if emitted < submitted:
                    # resume: chunking is deterministic, so everything
                    # submitted before the fault is simply skipped
                    emitted += 1
                    continue
                faults.check(site)
                dt = _perf() - t0
                self.stats.add("read_s", dt)
                if self.tracer is not None:
                    self.tracer.add("ingest.read", dt)
                fut = self._submit_decode(payload)
                if not self._put(q, (shard_idx, fut)):
                    return
                emitted += 1
                submitted = emitted
                self.stats.bump("chunks")
                if payload[0] == "block":  # memmap'd cache hit
                    self.stats.bump("cache_chunks")
                t0 = _perf()

        def on_retry_classify(exc: BaseException) -> bool:
            ok = retry_util.retryable(exc)
            if ok:
                self.stats.bump("retries")
            return ok

        retry_util.call(attempt, policy=self.retry_policy,
                        site=f"{self.fault_site_prefix}.read",
                        classify=on_retry_classify)
        self._put(q, (_SHARD_END, shard_idx, None))

    def _shard_chunks(self, path, cache_reader, want_hashes):
        """Deterministic chunk payloads for one shard, tagged by decode
        mode.  Chunk boundaries are a pure function of the source + fixed
        block sizes, so a retried shard re-produces the identical
        sequence (the resume skip depends on it)."""
        from shifu_tensorflow_tpu.data import native

        if cache_reader is not None:
            for block, hashes in cache_reader.blocks():
                yield ("block", block, hashes)
            return

        if "://" not in path or path.startswith("file://"):
            gen = native.stream_blocks(
                fs.strip_local(path), wanted_columns(self.schema),
                self.schema.delimiter, salt=self.salt,
                want_hashes=want_hashes, block_rows=self.block_rows,
            )
            if gen is not None:
                for arr, hashes in gen:
                    yield ("raw", arr, hashes)
                return

        yield from self._byte_chunks(path, want_hashes)

    def _byte_chunks(self, path: str, want_hashes: bool):
        """fs-layer fallback: decompressed byte chunks cut at line
        boundaries; the parse itself happens in the decode pool."""
        tail = b""
        with fs.open_maybe_gzip(path) as f:
            while True:
                chunk = f.read(self.block_bytes)
                if not chunk:
                    break
                data = tail + chunk
                cut = data.rfind(b"\n")
                if cut < 0:
                    tail = data
                    continue
                tail = data[cut + 1:]
                yield ("bytes", data[: cut + 1], want_hashes)
        if tail:
            yield ("bytes", tail, want_hashes)

    # ---- decode stage -----------------------------------------------------

    def _submit_decode(self, payload):
        kind = payload[0]
        if kind == "block":  # cache hit: already finalized, zero-copy
            return _Ready((payload[1], payload[2]))
        return self._pool.submit(self._decode, payload)

    def _decode(self, payload):
        """Pool worker: parse/finalize/cast one chunk → (block, hashes).
        The heavy pieces (native parse, numpy copies, zlib) release the
        GIL, so pool width scales real work on multi-core hosts; the
        pure-Python parse fallback is GIL-bound and only overlaps IO."""
        from shifu_tensorflow_tpu.data.reader import _finalize

        t0 = _perf()
        try:
            kind = payload[0]
            if kind == "raw":
                _, arr, hashes = payload
            else:  # "bytes"
                _, buf, want_hashes = payload
                arr, hashes = self._parse_bytes(buf, want_hashes)
            block = self._cast(_finalize(arr, self.schema))
            return block, hashes
        finally:
            dt = _perf() - t0
            self.stats.add("decode_s", dt)
            if self.tracer is not None:
                self.tracer.add("ingest.decode", dt)

    def _parse_bytes(self, buf: bytes, want_hashes: bool):
        from shifu_tensorflow_tpu.data import native
        from shifu_tensorflow_tpu.data.reader import parse_lines_full

        parsed = native.parse_buffer(
            buf, wanted_columns(self.schema), self.schema.delimiter,
            salt=self.salt, want_hashes=want_hashes,
        )
        if parsed is None:
            parsed = parse_lines_full(buf, self.schema, self.salt,
                                      want_hashes)
        return parsed

    def _cast(self, block: ParsedBlock) -> ParsedBlock:
        if self.feature_dtype == "float32":
            return block
        from shifu_tensorflow_tpu.data.cache import feature_np_dtype

        return ParsedBlock(
            block.features.astype(feature_np_dtype(self.feature_dtype)),
            block.targets, block.weights,
        )

    # ---- sequencer (pull stage) -------------------------------------------

    def _get(self, q: "queue.Queue"):
        t0 = _perf()
        try:
            while True:
                if self._stop.is_set():
                    raise StreamClosed("ingest pipeline closed mid-pull")
                try:
                    return q.get(timeout=0.1)
                except queue.Empty:
                    continue
        finally:
            dt = _perf() - t0
            self.stats.add("wait_s", dt)
            if self.tracer is not None:
                self.tracer.add("ingest.wait", dt)

    def blocks(self) -> Iterator[tuple[ParsedBlock, "np.ndarray | None"]]:
        """Yield (full finalized block, routing hashes) in deterministic
        shard→chunk order.  Cache writers are fed and committed here —
        the sequencer is the only stage that sees decoded blocks in
        order."""
        self.start()
        try:
            for shard_idx in range(len(self.paths)):
                q = self._queues[shard_idx % self.n_readers]
                writer = None
                started = False
                while True:
                    item = self._get(q)
                    if isinstance(item, _Err):
                        raise item.exc
                    tag = item[0]
                    if tag is _SHARD_START:
                        assert item[1] == shard_idx, "sequencer desync"
                        writer = item[2]
                        started = True
                        continue
                    if tag is _SHARD_END:
                        assert item[1] == shard_idx, "sequencer desync"
                        if writer is not None:
                            writer.commit()
                            with self._writers_lock:
                                if writer in self._writers:
                                    self._writers.remove(writer)
                        break
                    assert started and item[0] == shard_idx
                    block, hashes = self._resolve(item[1])
                    if writer is not None:
                        writer.append(block, hashes)
                    self.stats.bump("rows", len(block))
                    yield block, hashes
        finally:
            self.close()

    def _resolve(self, fut):
        if isinstance(fut, _Ready):
            return fut.result()
        t0 = _perf()
        try:
            return fut.result()
        finally:
            dt = _perf() - t0
            self.stats.add("wait_s", dt)
            if self.tracer is not None:
                self.tracer.add("ingest.wait", dt)


# ---- shuffle stage ---------------------------------------------------------

def shuffled_blocks(
    blocks: Iterator[ParsedBlock],
    shuffle_rows: int,
    seed: int,
    stats: StageStats | None = None,
    tracer=None,
) -> Iterator[ParsedBlock]:
    """Seeded window shuffle: buffer ``shuffle_rows`` rows, permute the
    window, emit it as one block.  Output is a pure function of (input
    order, shuffle_rows, seed) — and input order is deterministic
    (sequencer contract) — so a fixed seed reproduces the epoch order
    bit-identically at any reader/decode width."""
    if shuffle_rows <= 0:
        yield from blocks
        return
    rng = np.random.default_rng(seed)
    buf: list[ParsedBlock] = []
    buffered = 0

    def _flush() -> ParsedBlock:
        nonlocal buf, buffered
        t0 = _perf()
        merged = buf[0] if len(buf) == 1 else ParsedBlock.concat(buf)
        perm = rng.permutation(len(merged))
        out = ParsedBlock(
            merged.features[perm], merged.targets[perm],
            merged.weights[perm],
        )
        buf, buffered = [], 0
        if stats is not None:
            stats.add("shuffle_s", _perf() - t0)
        if tracer is not None:
            tracer.add("ingest.shuffle", _perf() - t0)
        return out

    for b in blocks:
        if len(b) == 0:
            continue
        buf.append(b)
        buffered += len(b)
        if buffered >= shuffle_rows:
            yield _flush()
    if buf:
        yield _flush()


# ---- routing + batch formation (pull path) ---------------------------------

def route_blocks(
    blocks: Iterator[tuple[ParsedBlock, "np.ndarray | None"]],
    *,
    emit: str,
    valid_rate: float,
) -> Iterator[ParsedBlock]:
    """Select the train/valid side of each block by the deterministic
    per-row content hash (reader.route_is_valid)."""
    for block, hashes in blocks:
        if valid_rate <= 0.0:
            if emit == "train" and len(block):
                yield block
            continue
        if hashes is None:
            raise ValueError("valid_rate > 0 requires routing hashes")
        is_valid = route_is_valid(hashes, valid_rate)
        keep = is_valid if emit == "valid" else ~is_valid
        if keep.all():
            if len(block):
                yield block
            continue
        kept = ParsedBlock(
            block.features[keep], block.targets[keep], block.weights[keep]
        )
        if len(kept):
            yield kept


def blocks_to_batches(
    blocks: Iterator[ParsedBlock],
    batch_size: int,
    num_features: int,
    *,
    drop_remainder: bool = False,
    stats_tap=None,
) -> Iterator[dict]:
    """Fixed-size batch formation with a single global carry.  Full
    batches inside a block are pure slices (views — zero copy on the
    memmap'd cache path); only carry top-ups at block boundaries copy.
    Because the pipeline is order-preserving, there is exactly ONE tail
    (at most batch_size-1 rows) regardless of reader count.

    ``stats_tap`` is the data-observability feed (an object with
    ``add_block(features)`` — obs/datastats.TrainDataSketch): each
    PRE-batching block's feature matrix is offered before slicing, so
    the sketch never sees the zero-weight padding rows the tail batch
    gains below.  Explicit-sink discipline, like the pipeline tracer:
    the caller decides which streams feed it (train-emit only — a
    validation stream polluting the exported baseline would hide
    exactly the train/serve skew the sketch exists to catch)."""
    from shifu_tensorflow_tpu.data.dataset import make_batch, pad_to_batch

    B = batch_size
    carry: ParsedBlock | None = None
    for block in blocks:
        if stats_tap is not None and len(block):
            stats_tap.add_block(block.features)
        i = 0
        if carry is not None and len(carry):
            take = min(B - len(carry), len(block))
            if take:
                carry = ParsedBlock.concat([
                    carry,
                    ParsedBlock(block.features[:take], block.targets[:take],
                                block.weights[:take]),
                ])
                i = take
            if len(carry) < B:
                continue
            yield make_batch(carry.features, carry.targets, carry.weights)
            carry = None
        n_full = i + ((len(block) - i) // B) * B
        for j in range(i, n_full, B):
            sl = slice(j, j + B)
            yield make_batch(block.features[sl], block.targets[sl],
                             block.weights[sl])
        if n_full < len(block):
            carry = ParsedBlock(
                block.features[n_full:], block.targets[n_full:],
                block.weights[n_full:],
            )
        else:
            carry = None
    if carry is not None and len(carry) and not drop_remainder:
        padded = pad_to_batch(carry, B)
        for i in range(0, len(padded), B):
            sl = slice(i, i + B)
            yield make_batch(padded.features[sl], padded.targets[sl],
                             padded.weights[sl])


# ---- knob resolution -------------------------------------------------------

@dataclass(frozen=True)
class IngestKnobs:
    """Resolved stage widths for one stream build."""

    readers: int = 1
    decode_workers: int = 1
    prefetch: int = 2  # device-put pipeline depth (batches in flight)


def default_knobs(cpu_count: int | None = None) -> IngestKnobs:
    """Conservative starting point the autotuner grows from: one reader
    per core up to 2, one decode worker, prefetch 2."""
    cpus = cpu_count if cpu_count is not None else (os.cpu_count() or 1)
    return IngestKnobs(readers=min(2, max(1, cpus)), decode_workers=1,
                       prefetch=2)
