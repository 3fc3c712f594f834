"""Batching and host→device infeed.

The reference loads every shard fully into Python lists and slices them with
``np.array_split`` per epoch (ssgd_monitor.py:348-454) — nonviable at the
1B-row target (SURVEY.md §7.2 item 1).  Here the input path is built for
TPU from the start:

- **fixed batch shapes**: every batch is exactly ``batch_size`` rows; the
  final partial batch is zero-padded with ``weight=0`` rows so the padded
  rows contribute nothing to the weighted loss and XLA sees one static
  shape (no recompilation, MXU-friendly);
- **streaming**: ``ShardStream`` fronts the staged pull pipeline
  (data/pipeline.py): parallel shard readers + decode pool + ordered
  sequencer + seeded shuffle buffer, overlapping host IO/decompression/
  parse with device step time while keeping the batch order a pure
  function of (paths, schema, salt) — reproducible at any parallelism;
- **prefetch to device**: ``prefetch_to_device`` keeps ``depth`` batches
  resident ahead of the consumer via ``jax.device_put``; ``pipelined=True``
  moves production+placement onto a put thread so batch k+1's transfer
  overlaps batch k's dispatch (``step.infeed.wait`` vs ``step.infeed.put``
  spans).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np

from shifu_tensorflow_tpu.data.reader import (
    ParsedBlock,
    RecordSchema,
    parse_buffer_split,
)
from shifu_tensorflow_tpu.utils import fs

Batch = dict[str, np.ndarray]  # {"x": (B,F), "y": (B,1), "w": (B,1)}


def resolve_stream_feature_dtype(setting: str | None, *,
                                 uses_feature_hashing: bool,
                                 has_normalization_stats: bool = True) -> str:
    """Streaming TRANSPORT dtype for features (conf key
    shifu.tpu.stream-feature-dtype), decoupled from the compute dtype.

    ``auto`` (the default) ships bf16 whenever it is safe: half the cache
    slab bytes and half the bytes over the host→device link (the rate is
    not measured on the attached chip); the jitted step widens back to
    the params' precision on device (train/trainer.py _widen_features), so
    an fp32 model still computes fp32 — bf16 is transport-only.

    Two unsafe cases keep ``auto`` at float32:

    - models that HASH feature columns (embedding / wide-cross): bucket
      ids are computed from raw float bits; bf16 rounding of category
      codes > 256 would re-bucket them, skewing training against the
      f32-hashing exported scorer.  An explicit bfloat16 request refuses
      loudly rather than silently skewing.  Rows of token ids
      (ModelType=hybrid_lm) are the same case — an id above 256 would
      round to another token — and callers pass
      ``TrainParams.features_carry_ids`` here;
    - no ZSCALE normalization stats (``has_normalization_stats=False``):
      z-scaled features are O(1) where bf16's 8-bit mantissa is plenty,
      but RAW features (un-normalized numeric codes, large-magnitude
      amounts fed densely) lose low-order digits with no warning — the
      KS-parity evidence behind the bf16 default only covers normalized
      pipelines.  An explicit ``bfloat16`` still forces it (the operator
      owns the precision claim); ``auto`` stays conservative.
    """
    s = (setting or "auto").lower()
    if s == "auto":
        if uses_feature_hashing or not has_normalization_stats:
            return "float32"
        return "bfloat16"
    if s == "bfloat16" and uses_feature_hashing:
        raise ValueError(
            "shifu.tpu.stream-feature-dtype=bfloat16 is unsafe with "
            "hashed feature columns or token ids: bucket ids are computed "
            "from raw float bits and ids index an embedding, and bf16 "
            "rounding moves integers > 256 — use auto (streams float32 "
            "for such models)"
        )
    if s not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown stream-feature-dtype {setting!r} "
            "(auto | float32 | bfloat16)"
        )
    return s

def make_batch(x: np.ndarray, y: np.ndarray, w: np.ndarray) -> Batch:
    return {"x": x, "y": y, "w": w}


def pad_to_batch(block: ParsedBlock, batch_size: int) -> ParsedBlock:
    """Zero-pad rows to a multiple of batch_size with weight=0 rows."""
    n = len(block)
    rem = n % batch_size
    if rem == 0 and n > 0:
        return block
    pad = batch_size - rem if n > 0 else batch_size
    # padding keeps the block's feature dtype: a float32 pad concatenated
    # onto bfloat16 features would silently promote the whole batch
    f = np.zeros((pad, block.features.shape[1]), block.features.dtype)
    z = np.zeros((pad, 1), np.float32)
    return ParsedBlock.concat([block, ParsedBlock(f, z, z)])


def iter_batches(block: ParsedBlock, batch_size: int, *, shuffle: bool = False,
                 seed: int = 0) -> Iterator[Batch]:
    """Slice an in-memory block into fixed-size batches."""
    if len(block) == 0:
        return
    if shuffle:
        perm = np.random.default_rng(seed).permutation(len(block))
        block = ParsedBlock(
            block.features[perm], block.targets[perm], block.weights[perm]
        )
    padded = pad_to_batch(block, batch_size)
    for i in range(0, len(padded), batch_size):
        sl = slice(i, i + batch_size)
        yield make_batch(padded.features[sl], padded.targets[sl], padded.weights[sl])


@dataclass
class InMemoryDataset:
    """Fully-loaded shard with deterministic train/valid split — the
    reference ``load_data`` contract (ssgd_monitor.py:348-454) for datasets
    that fit in host RAM (the demo / unit-test path)."""

    train: ParsedBlock
    valid: ParsedBlock
    schema: RecordSchema

    @classmethod
    def load(
        cls,
        paths: Sequence[str],
        schema: RecordSchema,
        valid_rate: float,
        salt: int = 0,
    ) -> "InMemoryDataset":
        train_blocks, valid_blocks = [], []
        for path in paths:
            with fs.open_maybe_gzip(path) as f:
                buf = f.read()
            tr, va = parse_buffer_split(buf, schema, valid_rate, salt)
            train_blocks.append(tr)
            valid_blocks.append(va)
        if not train_blocks:
            empty = ParsedBlock.empty(schema.num_features)
            return cls(empty, empty, schema)
        return cls(
            ParsedBlock.concat(train_blocks),
            ParsedBlock.concat(valid_blocks),
            schema,
        )

    def train_batches(self, batch_size: int, *, epoch: int = 0) -> Iterator[Batch]:
        return iter_batches(self.train, batch_size, shuffle=True, seed=epoch)

    def valid_batches(self, batch_size: int) -> Iterator[Batch]:
        return iter_batches(self.valid, batch_size)

    def train_batches_fixed(
        self, batch_size: int, steps: int, *, epoch: int = 0
    ) -> Iterator[Batch]:
        """Exactly ``steps`` batches (zero-weight padded) — SPMD epochs."""
        return fixed_step_batches(
            iter_batches(self.train, batch_size, shuffle=True, seed=epoch),
            batch_size, steps, self.schema.num_features,
        )

    def valid_batches_fixed(self, batch_size: int, steps: int) -> Iterator[Batch]:
        return fixed_step_batches(
            iter_batches(self.valid, batch_size),
            batch_size, steps, self.schema.num_features,
        )

    def steps_per_epoch(self, batch_size: int) -> int:
        return -(-len(self.train) // batch_size)

    def valid_steps(self, batch_size: int) -> int:
        return -(-len(self.valid) // batch_size)


def _zero_batch(batch_size: int, num_features: int,
                x_dtype=np.float32) -> Batch:
    """All-padding batch: weight 0 everywhere, so it contributes nothing to
    the weighted loss/gradient — pure barrier participation.  ``x_dtype``
    must match the real batches' feature dtype or SPMD processes would
    compile different programs for padded vs real steps."""
    z = np.zeros((batch_size, 1), np.float32)
    return make_batch(np.zeros((batch_size, num_features), x_dtype), z, z)


def fixed_step_batches(
    batches: Iterable[Batch],
    batch_size: int,
    steps: int,
    num_features: int,
    *,
    on_dropped: Callable[[int], None] | None = None,
    x_dtype=np.float32,
) -> Iterator[Batch]:
    """Adapt any batch iterator to EXACTLY ``steps`` batches of exactly
    ``batch_size`` rows.

    Under cross-process SPMD every process must execute the same number of
    identically-shaped steps per epoch or the collective deadlocks (XLA
    all-reduce is a barrier; the reference had the same constraint spread
    across SyncReplicasOptimizer's token queue, ssgd_monitor.py:136-142).
    Shards are rarely equal-sized, so the coordinator agrees on the MAX step
    count and shorter shards pad with zero-weight batches; a source yielding
    more than ``steps`` batches has the surplus dropped (``on_dropped``
    receives the dropped row count — callers log it; silent truncation reads
    as full coverage when it isn't).

    Returns a closeable iterator that remembers ``batches`` as its ROOT:
    ``close()`` closes the root stream FIRST (object-level, thread-safe —
    it can unwedge a pipelined-infeed put thread blocked inside this
    adapter's generator, whose own close() is refused while its frame is
    live on that thread) and then the generator.
    """
    return _RootedBatches(
        _fixed_step_gen(batches, batch_size, steps, num_features,
                        on_dropped=on_dropped, x_dtype=x_dtype),
        batches,
    )


class _RootedBatches:
    """A generator chain paired with the root stream object under it.

    Iterating delegates to the generator.  ``close()`` goes root-first:
    the root's object-level close is safe from any thread and releases
    the producer machinery (ShardStream contract), after which closing
    the generator itself (running its ``finally``) succeeds once no
    thread is executing its frame."""

    __slots__ = ("_gen", "root")

    def __init__(self, gen, root):
        self._gen = gen
        self.root = root

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._gen)

    def close(self) -> None:
        close_stream(self.root)
        close_stream(self._gen)


def _fixed_step_gen(
    batches: Iterable[Batch],
    batch_size: int,
    steps: int,
    num_features: int,
    *,
    on_dropped: Callable[[int], None] | None = None,
    x_dtype=np.float32,
) -> Iterator[Batch]:
    it = iter(batches)
    try:
        emitted = 0
        for batch in it:
            if emitted >= steps:
                dropped = int(batch["x"].shape[0])
                for extra in it:
                    dropped += int(extra["x"].shape[0])
                if on_dropped is not None and dropped:
                    on_dropped(dropped)
                return
            n = batch["x"].shape[0]
            if n != batch_size:  # pad a short (final) batch to the fixed shape
                pad = batch_size - n
                batch = {
                    k: np.concatenate(
                        [np.asarray(v), np.zeros((pad,) + v.shape[1:], v.dtype)]
                    )
                    for k, v in batch.items()
                }
            yield batch
            emitted += 1
        while emitted < steps:
            yield _zero_batch(batch_size, num_features, x_dtype)
            emitted += 1
    finally:
        # close-through: abandoning this adapter (step cap reached, caller
        # exception, rollback) must release the wrapped stream's producer
        # threads — the ShardStream close() contract
        close_stream(batches)


def close_stream(obj) -> None:
    """Close a batch source if it supports it (ShardStream, a generator,
    a pipelined prefetcher); quietly ignore sources that don't.  The one
    teardown helper every epoch path's ``finally`` uses.

    A generator whose frame is LIVE on another thread (a pipelined-infeed
    put thread blocked mid-``next()``) refuses ``close()`` with
    ValueError("generator already executing") — swallowed here: the
    abandonment paths close the ROOT stream too, whose stop signal is
    what actually releases that thread, and letting the ValueError fly
    out of an epoch ``finally`` would mask the original exception."""
    close = getattr(obj, "close", None)
    if callable(close):
        try:
            close()
        except ValueError as e:
            if "already executing" not in str(e):
                raise


class ShardStream:
    """Streaming reader: files → staged pull pipeline → fixed batches.

    A thin facade over ``data/pipeline.ShardPipeline`` — parallel shard
    readers (static round-robin shard→reader assignment), a decode/cast
    pool, an order-preserving pull sequencer, an optional seeded shuffle
    buffer, and fixed-shape batch formation.  Each file is served from the
    fastest available source, in order:

    1. **binary cache hit** (``cache_dir`` set, entry valid): finalized
       tensors are memory-mapped and batches are zero-copy views — ingest
       at page-cache speed, the steady-state multi-epoch path
       (data/cache.py);
    2. **fused native stream** (local file, native lib built): one C++ pass
       does read→inflate→parse (cpp/stpu_data.cc stpu_stream_*) with the
       GIL released; a cache entry is written as a side effect when
       ``cache_dir`` is set;
    3. **byte-chunk fallback** (remote schemes / no native lib): fs-layer
       reads, parsed in the decode pool.

    Determinism: the emitted batch sequence is a pure function of
    (path order, schema, salt, batch size, shuffle knobs) — INDEPENDENT of
    ``n_readers``, ``decode_workers``, queue depths, and thread
    interleaving (the sequencer merges per-reader queues in global shard
    order).  Parallel ingest is therefore safe to enable — and to
    autotune — without losing reproducibility; a fixed seed plus a fixed
    shard list replays the identical epoch (tests/test_ingest.py pins
    this across 1/2/4 readers and across chaos-drill resumes).

    Lifecycle: iterating to completion releases every pipeline thread; an
    abandoned iterator is released by ``close()`` (also available as a
    context manager), which the trainer's epoch paths call from their
    ``finally`` blocks.
    """

    def __init__(
        self,
        paths: Sequence[str],
        schema: RecordSchema,
        batch_size: int,
        *,
        valid_rate: float = 0.0,
        emit: str = "train",  # which side of the split to emit
        block_bytes: int = 4 << 20,
        block_rows: int = 1 << 16,
        queue_depth: int = 4,
        drop_remainder: bool = False,
        salt: int = 0,
        n_readers: int | None = None,
        cache_dir: str | None = None,
        feature_dtype: str = "float32",
        decode_workers: int | None = None,
        shuffle_rows: int = 0,
        shuffle_seed: int | None = None,
        retry_policy=None,
        stats_sink: "Callable | None" = None,
        traced: bool | None = None,
    ):
        self.paths = list(paths)
        self.schema = schema
        self.batch_size = batch_size
        self.valid_rate = valid_rate
        self.emit = emit
        self.block_bytes = block_bytes
        self.block_rows = block_rows  # native fused-stream rows per chunk
        # per-reader chunk-queue capacity: bounds read-ahead AND in-flight
        # decodes (futures live in the queue)
        self.queue_depth = queue_depth
        self.drop_remainder = drop_remainder
        self.salt = salt
        self.cache_dir = cache_dir
        # "float32" | "bfloat16": emitted batch x dtype; bf16 halves cache
        # slab reads and host->device transfer for bf16 training runs
        self.feature_dtype = feature_dtype or "float32"
        if n_readers is None:
            n_readers = 1
        self.n_readers = max(1, min(n_readers, max(1, len(self.paths))))
        self.decode_workers = max(1, decode_workers or 1)
        self.shuffle_rows = max(0, int(shuffle_rows))
        self.shuffle_seed = salt if shuffle_seed is None else int(shuffle_seed)
        self.retry_policy = retry_policy
        # called with the epoch's StageStats after each full iteration /
        # close — the autotuner's feedback channel (data/autotune.py)
        self.stats_sink = stats_sink
        # record ingest.* spans to the installed tracer?  None = auto:
        # train-side streams trace, valid-side streams don't — the eval
        # pass runs untraced by discipline (trainer.evaluate), and its
        # ingest work polluting the train epoch's journaled span budget
        # would point the hand-tuning decision table (docs/ingest.md) at
        # the wrong stage
        self.traced = (emit != "valid") if traced is None else bool(traced)
        self._live: list = []  # pipelines with threads possibly running

    def close(self) -> None:
        """Release every live pipeline (producer threads, decode pool,
        uncommitted cache writers).  Idempotent; the contract every
        consumer that may abandon the iterator mid-epoch must honor."""
        for pipe in list(self._live):
            pipe.close()

    def __enter__(self) -> "ShardStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __iter__(self) -> Iterator[Batch]:
        from shifu_tensorflow_tpu.data.pipeline import (
            ShardPipeline,
            StageStats,
            blocks_to_batches,
            route_blocks,
            shuffled_blocks,
        )

        from shifu_tensorflow_tpu.obs import datastats as obs_datastats
        from shifu_tensorflow_tpu.obs import trace as obs_trace

        stats = StageStats()
        tracer = obs_trace.active() if self.traced else None
        # data-observability tap (obs/datastats.py): TRAIN-emit streams
        # only — the exported feature baseline must describe what the
        # model trained on, not the validation split's reweighted view
        # (same per-emit discipline as the tracer above)
        stats_tap = (obs_datastats.train_active()
                     if self.emit != "valid" else None)
        pipe = ShardPipeline(
            self.paths, self.schema,
            salt=self.salt,
            n_readers=self.n_readers,
            decode_workers=self.decode_workers,
            queue_depth=self.queue_depth,
            block_bytes=self.block_bytes,
            block_rows=self.block_rows,
            cache_dir=self.cache_dir,
            feature_dtype=self.feature_dtype,
            need_hashes=self.valid_rate > 0.0,
            retry_policy=self.retry_policy,
            stats=stats,
            tracer=tracer,
        )
        self._live.append(pipe)
        try:
            routed = route_blocks(
                pipe.blocks(), emit=self.emit, valid_rate=self.valid_rate,
            )
            blocks = shuffled_blocks(routed, self.shuffle_rows,
                                     self.shuffle_seed, stats,
                                     tracer=tracer)
            yield from blocks_to_batches(
                blocks, self.batch_size, self.schema.num_features,
                drop_remainder=self.drop_remainder,
                stats_tap=stats_tap,
            )
        finally:
            pipe.close()
            if pipe in self._live:
                self._live.remove(pipe)
            if self.stats_sink is not None:
                try:
                    self.stats_sink(stats)
                except Exception:  # a broken sink must not kill training
                    pass


def prefetch_to_device(
    batches: Iterable[Batch],
    put: Callable[[Batch], Batch] | None = None,
    depth: int = 2,
    *,
    pipelined: bool = False,
    tracer=None,
    root=None,
):
    """Keep ``depth`` batches already transferred ahead of the consumer.

    ``put`` maps a host batch to device (default ``jax.device_put``); with a
    ``NamedSharding`` it lands shards directly on the mesh.

    Two modes:

    - **unthreaded** (default): a plain generator — ``put`` runs inline in
      the consumer thread while filling the deque, so placement time is
      consumer-visible.  The host-embedding path DEPENDS on this (its
      zero-staleness contract needs gather→update ordering in one thread —
      trainer.Trainer._apply_emb_grad).
    - **pipelined** (``pipelined=True``): a producer thread runs
      ``next(batches)`` + ``put`` and feeds a bounded queue, so host batch
      production AND device placement of batch k+1 overlap the dispatch of
      batch k — the double-buffered infeed stage of the ingest pipeline
      (docs/ingest.md).  The consumer's only stall is the queue wait.
      Span split: ``step.infeed.put`` (thread-side placement work) vs
      ``step.infeed.wait`` (consumer-side starvation) — ``obs summary``
      uses it to distinguish "starved" from "placement-slow".

    The returned object supports ``close()`` (no-op for the unthreaded
    generator beyond normal generator close) — epoch paths close it in
    ``finally`` so an abandoned epoch never leaks the put thread.

    ``root`` (pipelined mode only) is the epoch's ROOT stream object
    (e.g. the ShardStream) when ``batches`` is a generator chain over
    it.  ``close()`` closes the root FIRST: object-level closes are
    thread-safe, and signalling the underlying pipeline's stop event is
    the only thing that can unwedge a put thread blocked inside
    ``next()`` on a stalled stream — a generator whose frame is live on
    the put thread refuses ``close()`` outright (ValueError).
    """
    if pipelined:
        return _PipelinedPrefetch(batches, put, depth, tracer, root=root)
    return _sync_prefetch(batches, put, depth)


def _sync_prefetch(
    batches: Iterable[Batch],
    put: Callable[[Batch], Batch] | None,
    depth: int,
) -> Iterator[Batch]:
    import collections

    import jax

    if put is None:
        put = jax.device_put

    buf: "collections.deque" = collections.deque()
    it = iter(batches)
    try:
        while True:
            while len(buf) < depth:
                buf.append(put(next(it)))
            yield buf.popleft()
    except StopIteration:
        while buf:
            yield buf.popleft()


class _PipelinedPrefetch:
    """Threaded device-put stage: one producer thread pulls host batches,
    places them, and fills a bounded queue the consumer drains.

    Order-preserving (single thread, FIFO queue).  Errors from the source
    iterator or from ``put`` re-raise in the consumer.  ``close()`` stops
    the thread, drains the queue, joins, then closes the source — safe to
    call from the consumer's ``finally`` at any point mid-epoch.
    """

    _END = object()

    #: close() abandons the put thread past this deadline instead of
    #: hanging the caller; with a root stream attached the thread always
    #: unwedges well inside it (the root's stop signal propagates in
    #: ≤ one queue-poll interval), so this is a backstop, not a budget
    _JOIN_TIMEOUT_S = 10.0

    def __init__(self, batches, put, depth, tracer=None, root=None):
        import jax

        self._src = batches
        self._root = root
        put_fn = put if put is not None else jax.device_put
        # only the EXPLICIT tracer records (no fallback to the process
        # install): the eval pass runs untraced on purpose — its waits
        # must not inflate the train epoch's step budget.  Recording goes
        # through the tracer's SAMPLED seams because budget_fields scales
        # step.* spans back up by sample_every — an unsampled side
        # channel would overcount under obs-trace-sample > 1.
        self._tracer = tracer
        self._put_fn = (
            tracer.timed("step.infeed.put", put_fn)
            if tracer is not None else put_fn
        )
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="stpu-infeed-put", daemon=True
        )
        self._closed = False
        self._thread.start()

    # ---- producer ----
    def _run(self) -> None:
        try:
            it = iter(self._src)
            while not self._stop.is_set():
                try:
                    b = next(it)
                except StopIteration:
                    break
                d = self._put_fn(b)
                if not self._enqueue(d):
                    return
            self._enqueue(self._END)
        except BaseException as e:
            self._enqueue(_PrefetchError(e))

    def _enqueue(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # ---- consumer ----
    def __iter__(self) -> Iterator[Batch]:
        from shifu_tensorflow_tpu.obs import trace as obs_trace

        while True:
            with obs_trace.maybe_span(self._tracer, "step.infeed.wait"):
                item = self._dequeue()
            if item is self._END:
                return
            if isinstance(item, _PrefetchError):
                raise item.exc
            yield item

    def _dequeue(self):
        while True:
            try:
                return self._q.get(timeout=0.5)
            except queue.Empty:
                if not self._thread.is_alive():
                    # thread died without a terminal marker (should be
                    # unreachable — _run always posts one) — fail loudly
                    # rather than hang the epoch
                    raise RuntimeError("infeed put thread died silently")

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # unwedge the put thread FIRST: if it is blocked inside next() on
        # a stream whose producers stalled, only the source's own stop
        # signal releases it — this prefetcher's stop event is checked
        # only between batches.  The root's close() is object-level and
        # thread-safe (close_stream itself tolerates a generator root
        # whose frame is live on the put thread).
        close_stream(self._root)
        deadline = time.monotonic() + self._JOIN_TIMEOUT_S
        while self._thread.is_alive():
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
            if self._thread.is_alive() and time.monotonic() > deadline:
                break  # daemon thread; exits once its blocked call returns
        # the source is no longer being consumed; release ITS threads and
        # run the generator chain's finallys (stats sink, pipeline close).
        # Safe now that the thread is joined (frames suspended); in the
        # abandoned-thread case a live frame refuses close and
        # close_stream swallows it.
        close_stream(self._src)


class _PrefetchError:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc
