"""Benchmark: training rows/sec/chip on the flagship tabular workload.

Output contract: every stdout line is a valid JSON object; the LAST line
is the most complete result — {"metric": ..., "value": N, "unit": ...,
"vs_baseline": N} plus context fields (platform, streaming end-to-end
throughput, diagnostics).  Lines before the last are the same result at
earlier stages of completeness ("partial": true), printed the moment each
number is measured, so a bench killed mid-run still leaves a parseable
artifact in its caller's output tail.

Two measurements:

- ``training_rows_per_sec_per_chip`` (primary): steady-state jitted SPMD
  step throughput on a device-resident batch — the MXU ceiling.
- ``stream_rows_per_sec``: END-TO-END ingest — ShardStream (gzip PSV →
  native block parser → bounded queue) → prefetch_to_device → jitted step,
  on a generated multi-shard dataset.  This is SURVEY.md §7.2 item 1, the
  real 1B-row battle: the number the input pipeline can actually sustain.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
comparison is a measured stand-in for its execution model, run on this same
host — a feed-dict-style uncompiled numpy forward+backward at the
reference's batch 100 (ssgd_monitor.py:33).  Generous to the reference (no
gRPC PS round-trips, no Python 2); vs_baseline understates the real gap.

Robustness (round-3 lesson: BENCH_r03 was killed by its caller's timeout
having printed nothing):

- the parent process never touches jax — a chip belongs to one process,
  and the measurement runs in ONE SUBPROCESS on the ambient platform,
  under a hard timeout.  There is no re-run on another platform: a run
  that measured nothing prints the error stub and exits non-zero;
- the parent enforces a TOTAL wall-clock budget (``BENCH_TOTAL_BUDGET_S``,
  default 540s) so the final line prints before any plausible caller
  deadline;
- results stream: the child re-prints its cumulative result JSON after
  every completed section and self-skips sections that no longer fit its
  share of the budget ("skipped" field); the parent forwards each line as
  it arrives;
- SIGTERM at either level flushes the best result measured so far and
  exits 0 — a killed bench fails OPEN with a partial artifact, never
  closed with an empty tail;
- compiled programs persist in an XLA compilation cache
  (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``), so subsequent
  runs on the same machine skip the compiles.
"""

from __future__ import annotations

import gzip
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

NUM_FEATURES = 30
HIDDEN = [256, 128, 64]
BATCH = int(os.environ.get("BENCH_BATCH", 16384))
WARMUP_STEPS = 3
MEASURE_SECONDS = float(os.environ.get("BENCH_SECONDS", 10.0))
REF_SAMPLE_STEPS = 20
REF_BATCH = 100  # the reference's fixed batch size (ssgd_monitor.py:33)
STREAM_ROWS = int(os.environ.get("BENCH_STREAM_ROWS", 2_000_000))
STREAM_SHARDS = int(os.environ.get("BENCH_STREAM_SHARDS", 8))
STREAM_READERS = int(os.environ.get("BENCH_STREAM_READERS", 4))
# ingest-bound phases run larger device batches: host->device transfer has
# a fixed per-call latency that 16K-row batches leave unamortized
STREAM_BATCH = int(os.environ.get("BENCH_STREAM_BATCH", 65536))
SCAN_STEPS = int(os.environ.get("BENCH_SCAN_STEPS", 16))
DEVICE_EPOCH_ROWS = int(os.environ.get("BENCH_DEVICE_EPOCH_ROWS", 1_000_000))
DEVICE_EPOCH_EPOCHS = int(os.environ.get("BENCH_DEVICE_EPOCH_EPOCHS", 5))
# budget discipline (round-3 verdict): the WHOLE bench fits
# BENCH_TOTAL_BUDGET_S; the one measurement child is capped at
# BENCH_TPU_TIMEOUT (how long a complete battery takes on the attached
# chip is not measured).
TOTAL_BUDGET_S = float(os.environ.get("BENCH_TOTAL_BUDGET_S", 540.0))
TPU_TIMEOUT_S = float(os.environ.get("BENCH_TPU_TIMEOUT", 260.0))
#: grace between SIGTERM and SIGKILL when an attempt overruns
KILL_GRACE_S = 8.0
COMPILE_CACHE_DIR = os.environ.get(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
)


def _model_config():
    from shifu_tensorflow_tpu.config.model_config import ModelConfig

    return ModelConfig.from_json(
        {
            "train": {
                "numTrainEpochs": 1,
                "validSetRate": 0.1,
                "params": {
                    "NumHiddenLayers": 3,
                    "NumHiddenNodes": HIDDEN,
                    "ActivationFunc": ["relu", "relu", "tanh"],
                    "LearningRate": 0.05,
                    "Optimizer": "adam",
                },
            }
        }
    )


# --------------------------------------------------------------- measurement


def bench_step_rows_per_sec(dtype: str = "float32",
                            measure_seconds: float | None = None) -> float:
    """Steady-state jitted step throughput, device-resident batch."""
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer

    if measure_seconds is None:
        measure_seconds = MEASURE_SECONDS
    if dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown bench dtype {dtype!r}")
    # shard the batch over every local chip so the per-chip division below
    # is honest on multi-chip hosts; single chip gets a 1-device mesh
    mesh = make_mesh("data:-1")
    model_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    trainer = Trainer(_model_config(), NUM_FEATURES, mesh=mesh,
                      dtype=model_dtype)
    rng = np.random.default_rng(0)
    rows = trainer.align_batch_size(BATCH)
    x = rng.normal(size=(rows, NUM_FEATURES)).astype(np.float32)
    if dtype == "bfloat16":
        x = x.astype(jnp.bfloat16)
    batch = {
        "x": x,
        "y": (rng.random((rows, 1)) < 0.3).astype(np.float32),
        "w": np.ones((rows, 1), np.float32),
    }
    # function-local on purpose (here and in the other sections):
    # importing the package pulls jax, and bench.py's PARENT process must
    # never touch jax — a hanging PJRT plugin would take down the
    # orchestrator instead of one timed-out child
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    dev_batch = trainer._put(batch)
    step = trainer._train_step
    state = trainer.state
    for _ in range(WARMUP_STEPS):
        state, loss = step(state, dev_batch)
    true_sync(loss)

    # sync by VALUE FETCH (utils/profiling.true_sync).  The fetched loss
    # threads through the whole state chain, so one fetch proves every
    # step before it ran.
    n_steps = 0
    t0 = time.perf_counter()
    while True:
        state, loss = step(state, dev_batch)
        n_steps += 1
        if n_steps % 50 == 0:
            true_sync(loss)
            if time.perf_counter() - t0 >= measure_seconds:
                break
    true_sync(loss)
    elapsed = time.perf_counter() - t0
    rows_per_sec = n_steps * rows / elapsed
    return rows_per_sec / jax.local_device_count()


def bench_scan_rows_per_sec(measure_seconds: float) -> float:
    """Chunked-scan training throughput: SCAN_STEPS distinct device-resident
    batches per lax.scan dispatch (train/trainer.py make_scan_epoch) —
    dispatch latency amortized the XLA-idiomatic way."""
    import jax

    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer

    S = SCAN_STEPS
    mesh = make_mesh("data:-1")
    trainer = Trainer(_model_config(), NUM_FEATURES, mesh=mesh, scan_steps=S)
    rng = np.random.default_rng(0)
    rows = trainer.align_batch_size(BATCH)
    stacked = {
        "x": rng.normal(size=(S, rows, NUM_FEATURES)).astype(np.float32),
        "y": (rng.random((S, rows, 1)) < 0.3).astype(np.float32),
        "w": np.ones((S, rows, 1), np.float32),
    }
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    dev = trainer._put_stacked(stacked)
    scan = trainer._scan_epoch
    state = trainer.state
    for _ in range(2):
        state, losses = scan(state, dev)
    true_sync(losses)
    # value-fetch sync (see bench_step_rows_per_sec): the r04 open-window
    # run measured 1.42B rows/s here with block_until_ready — over 2× the
    # chip's physical peak FLOPs, i.e. pure enqueue rate
    n_calls = 0
    t0 = time.perf_counter()
    while True:
        state, losses = scan(state, dev)
        n_calls += 1
        if n_calls % 5 == 0:
            true_sync(losses)
            if time.perf_counter() - t0 >= measure_seconds:
                break
    true_sync(losses)
    elapsed = time.perf_counter() - t0
    return n_calls * S * rows / elapsed / jax.local_device_count()


def bench_device_epoch_rows_per_sec(measure_seconds: float) -> float:
    """Device-resident epochs (--device-resident): dataset lives in HBM,
    one compiled program per epoch (on-device shuffle + scanned steps).
    Measures the steady multi-epoch rate of the reference's all-in-RAM
    regime (ssgd_monitor.py:348-454) in its TPU-native form."""
    import jax

    from shifu_tensorflow_tpu.data.reader import ParsedBlock
    from shifu_tensorflow_tpu.data.dataset import InMemoryDataset
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer

    n = DEVICE_EPOCH_ROWS
    rng = np.random.default_rng(0)
    block = ParsedBlock(
        rng.normal(size=(n, NUM_FEATURES)).astype(np.float32),
        (rng.random((n, 1)) < 0.3).astype(np.float32),
        np.ones((n, 1), np.float32),
    )
    schema = RecordSchema(feature_columns=tuple(range(1, NUM_FEATURES + 1)),
                          target_column=0)
    ds = InMemoryDataset(block, ParsedBlock.empty(NUM_FEATURES), schema)
    mesh = make_mesh("data:-1")
    trainer = Trainer(_model_config(), NUM_FEATURES, mesh=mesh)
    # one call, many epochs: epoch 0 pays the transfer + compile; the
    # steady rate is the median of the later epochs' training_time_s
    history = trainer.fit_device_resident(
        ds, epochs=DEVICE_EPOCH_EPOCHS, batch_size=BATCH
    )
    tail = history[1:] if len(history) > 1 else history
    steady = float(np.median([h.training_time_s for h in tail]))
    _ = measure_seconds  # epoch count, not wall-clock, bounds this one
    return n / steady / jax.local_device_count()


def _write_stream_shards(root: str, total_rows: int, n_shards: int) -> list[str]:
    """Synthetic gzip PSV shards (target|f0..f29|weight).  One formatted
    block is written repeatedly — content repetition is irrelevant to
    ingest throughput, and generation stays seconds, not minutes."""
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
        # gzip level 1: realistic-enough compression without dominating
        # generation time
        with gzip.open(path, "wb", compresslevel=1) as f:
            for _ in range(reps):
                f.write(block)
        paths.append(path)
    return paths


def bench_stream_rows_per_sec() -> dict:
    """End-to-end ingest: ShardStream -> prefetch -> jitted step, rows/sec.

    Measured twice over the same shards:
    - **cold**: first pass parses gzip PSV (fused native read→inflate→parse)
      and writes the binary shard cache as a side effect;
    - **steady** (the headline ``stream_rows_per_sec``): later epochs serve
      memmap'd finalized tensors — the rate every epoch after the first
      actually runs at in multi-epoch training (the reference default
      trains many epochs over the same shards, so steady-state IS the
      training ingest rate; the cold number is reported alongside).

    A per-stage breakdown (inflate / parse / cache-drain / device_put) is
    attached so the binding constraint is visible in the artifact —
    round-2 verdict asked for exactly this.
    """
    import jax

    from shifu_tensorflow_tpu.data.dataset import ShardStream, prefetch_to_device
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mesh = make_mesh("data:-1")
    trainer = Trainer(_model_config(), NUM_FEATURES, mesh=mesh)
    # small-config runs (CPU fallback) must still see several measured
    # batches after the warmup one, or the rate degenerates to 0
    batch_size = trainer.align_batch_size(
        max(1024, min(STREAM_BATCH, STREAM_ROWS // 8))
    )
    schema = RecordSchema(
        feature_columns=tuple(range(1, NUM_FEATURES + 1)),
        target_column=0,
        weight_column=NUM_FEATURES + 1,
    )
    with tempfile.TemporaryDirectory(prefix="stpu-bench-") as root:
        t_gen = time.perf_counter()
        paths = _write_stream_shards(root, STREAM_ROWS, STREAM_SHARDS)
        gen_s = time.perf_counter() - t_gen
        cache_dir = os.path.join(root, "cache")

        def one_epoch(tr=trainer, feature_dtype="float32") -> float:
            stream = ShardStream(
                paths, schema, batch_size,
                valid_rate=0.0, emit="train", n_readers=STREAM_READERS,
                drop_remainder=True, cache_dir=cache_dir,
                feature_dtype=feature_dtype,
            )
            step = tr._train_step
            rows = 0
            # warmup/compile on the first batch, then measure wall-clock
            # over the rest of the stream; the state threads through
            # tr.state because the step may donate its input buffers
            from shifu_tensorflow_tpu.utils.profiling import true_sync

            it = prefetch_to_device(iter(stream), put=tr._put)
            tr.state, loss = step(tr.state, next(it))
            true_sync(loss)
            t0 = time.perf_counter()
            for batch in it:
                tr.state, loss = step(tr.state, batch)
                rows += batch_size
            # value fetch: the final loss depends on every step of the
            # epoch, so the elapsed window provably contains them all
            true_sync(loss)
            return rows / (time.perf_counter() - t0)

        cold = one_epoch()

        # bf16 variant: the MXU-native config — bf16 features halve cache
        # slab reads and host->device bytes (model + stream both bf16)
        import jax.numpy as jnp

        trainer16 = Trainer(_model_config(), NUM_FEATURES, mesh=mesh,
                            dtype=jnp.bfloat16)
        # cold bf16 epoch (parse + cast + bf16 cache build): the DEFAULT
        # production cold path since stream-feature-dtype=auto (r05) —
        # timed, because item 3's done-criterion compares it to fp32 cold
        cold_bf16 = one_epoch(trainer16, "bfloat16")
        # steady epochs ALTERNATE dtypes so slow drift on the shared host
        # (page-cache churn, other tenants of its cores) biases neither side
        # of the fp32-vs-bf16 comparison; best-of-2 each
        steady = steady_bf16 = 0.0
        for _ in range(2):
            steady = max(steady, one_epoch())
            steady_bf16 = max(steady_bf16,
                              one_epoch(trainer16, "bfloat16"))
        stages = _stream_stage_breakdown(paths, schema, cache_dir, trainer,
                                         batch_size)
    return {
        "stream_rows_per_sec": round(steady, 1),
        "stream_cold_rows_per_sec": round(cold, 1),
        "stream_cold_bf16_rows_per_sec": round(cold_bf16, 1),
        "stream_bf16_rows_per_sec": round(steady_bf16, 1),
        "stream_batch": batch_size,
        "stream_rows": STREAM_ROWS,
        "stream_readers": STREAM_READERS,
        "stream_gen_s": round(gen_s, 1),
        "stream_stage_breakdown": stages,
    }


def _stream_stage_breakdown(paths, schema, cache_dir, trainer,
                            batch_size) -> dict:
    """Isolate each ingest stage on this host (cheap: one shard each)."""
    import zlib as _zlib

    import jax

    from shifu_tensorflow_tpu.data import native
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.reader import wanted_columns

    out: dict = {"host_cpus": os.cpu_count()}
    p = paths[0]
    comp = open(p, "rb").read()
    t0 = time.perf_counter()
    text = _zlib.decompressobj(wbits=31).decompress(comp)
    out["gzip_inflate_mb_s"] = round(len(text) / (time.perf_counter() - t0) / 1e6, 1)

    if native.available():
        t0 = time.perf_counter()
        arr, _ = native.parse_buffer(text, wanted_columns(schema), "|",
                                     want_hashes=False, n_threads=1)
        dt = time.perf_counter() - t0
        out["native_parse_rows_s"] = round(arr.shape[0] / dt, 0)
        t0 = time.perf_counter()
        n = sum(a.shape[0] for a, _ in native.stream_blocks(
            p, wanted_columns(schema), "|", want_hashes=False))
        out["native_fused_stream_rows_s"] = round(
            n / (time.perf_counter() - t0), 0)

    # warm cache drain, host only (no device)
    stream = ShardStream(paths, schema, batch_size, valid_rate=0.0,
                         emit="train", cache_dir=cache_dir,
                         drop_remainder=True)
    t0 = time.perf_counter()
    rows = sum(b["x"].shape[0] for b in stream)
    out["cache_drain_rows_s"] = round(rows / (time.perf_counter() - t0), 0)

    # device transfer
    rng = np.random.default_rng(0)
    batch = {
        "x": rng.normal(size=(batch_size, NUM_FEATURES)).astype(np.float32),
        "y": np.zeros((batch_size, 1), np.float32),
        "w": np.ones((batch_size, 1), np.float32),
    }
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    true_sync(trainer._put(batch))
    t0 = time.perf_counter()
    reps = 20
    # enqueue all puts (overlapping, as training's prefetch does) and
    # chain one element of every leaf of every put into an on-device
    # accumulator; ONE final fetch proves all transfers landed inside
    # the elapsed window without serializing a round trip per put
    acc = None
    for _ in range(reps):
        for leaf in jax.tree_util.tree_leaves(trainer._put(batch)):
            probe = (leaf.reshape(-1)[0] if leaf.ndim else leaf)
            probe = probe.astype("float32")
            acc = probe if acc is None else acc + probe
    true_sync(acc)
    out["device_put_rows_s"] = round(
        reps * batch_size / (time.perf_counter() - t0), 0)
    return out


def bench_reference_style_rows_per_sec() -> float:
    """Feed-dict-style numpy loop: the reference's per-batch execution model
    (uncompiled forward+backward, batch 100, host-resident)."""
    rng = np.random.default_rng(0)
    sizes = [NUM_FEATURES] + HIDDEN + [1]
    Ws = [rng.normal(size=(a, b)).astype(np.float32) * 0.1
          for a, b in zip(sizes[:-1], sizes[1:])]
    bs = [np.zeros(b, np.float32) for b in sizes[1:]]
    X = rng.normal(size=(REF_BATCH, NUM_FEATURES)).astype(np.float32)
    Y = (rng.random((REF_BATCH, 1)) < 0.3).astype(np.float32)

    def step(lr=0.01):
        acts = [X]
        h = X
        for i, (W, b) in enumerate(zip(Ws, bs)):
            z = h @ W + b
            h = 1 / (1 + np.exp(-z)) if i == len(Ws) - 1 else np.maximum(z, 0)
            acts.append(h)
        grad = 2 * (h - Y) * h * (1 - h) / len(Y)
        for i in range(len(Ws) - 1, -1, -1):
            gW = acts[i].T @ grad
            gb = grad.sum(0)
            grad = (grad @ Ws[i].T) * (acts[i] > 0)
            Ws[i] -= lr * gW
            bs[i] -= lr * gb

    step()  # warmup
    t0 = time.perf_counter()
    for _ in range(REF_SAMPLE_STEPS):
        step()
    elapsed = time.perf_counter() - t0
    return REF_SAMPLE_STEPS * REF_BATCH / elapsed


class _Emitter:
    """Cumulative result that re-prints itself (one JSON line, flushed)
    after every update, and once more — without the partial flag — at the
    end.  A SIGTERM mid-run flushes the current state: partial evidence
    beats an empty tail."""

    def __init__(self):
        self.result: dict = {}
        # REENTRANT: the SIGTERM handler flushes from the same (main)
        # thread that may be holding the lock inside update() when the
        # signal lands — a plain Lock would deadlock the flush in exactly
        # the window it exists for
        self._lock = threading.RLock()

    def update(self, **kv) -> None:
        with self._lock:
            self.result.update(kv)
            out = dict(self.result)
            out["partial"] = True
        print(json.dumps(out), flush=True)

    def final(self) -> None:
        with self._lock:
            out = dict(self.result)
        print(json.dumps(out), flush=True)


def run_measurements(emit: _Emitter, budget_s: float) -> None:
    """Child-process entry: measure on whatever backend the env selects.

    The primary metric goes out first; each optional section runs only if
    it plausibly fits the remaining budget (generous static estimates —
    a warm compilation cache makes every section much cheaper than its
    estimate) and prints as soon as it lands.
    """
    t0 = time.monotonic()

    def remaining() -> float:
        return budget_s - (time.monotonic() - t0)

    import jax

    value = bench_step_rows_per_sec()
    ref = bench_reference_style_rows_per_sec()
    emit.update(
        metric="training_rows_per_sec_per_chip",
        value=round(value, 1),
        unit="rows/s/chip",
        vs_baseline=round(value / ref, 2),
        platform=jax.devices()[0].platform,
        device=str(jax.devices()[0].device_kind),
        n_devices=jax.local_device_count(),
        baseline="measured reference-style feeddict numpy loop, same host",
        baseline_rows_per_sec=round(ref, 1),
    )

    skipped: list[str] = []

    def fits(name: str, est_s: float) -> bool:
        if remaining() > est_s:
            return True
        skipped.append(name)
        emit.update(skipped=list(skipped))
        return False

    # section cost estimates: one fresh compile (~40s TPU, ~0 with a warm
    # cache) + its measurement window + slack
    if fits("stream", 60.0 + MEASURE_SECONDS):
        try:
            # END-TO-END ingest — the headline the 1B-row epoch runs at
            emit.update(**bench_stream_rows_per_sec())
        except Exception as e:  # streaming must not void the primary
            emit.update(stream_error=f"{type(e).__name__}: {e}")
    if fits("bf16", 40.0 + MEASURE_SECONDS / 2):
        try:
            # MXU-native variant: bf16 params + features; reported as
            # context, the primary stays float32 for cross-round
            # comparability
            emit.update(value_bf16=round(
                bench_step_rows_per_sec("bfloat16", MEASURE_SECONDS / 2), 1
            ))
        except Exception as e:
            emit.update(value_bf16_error=f"{type(e).__name__}: {e}")
    if fits("scan", 40.0 + MEASURE_SECONDS / 2):
        try:
            # chunked-scan path (shifu.tpu.scan-steps): SCAN_STEPS updates
            # per dispatch; the dispatch-amortized ceiling
            emit.update(
                value_scan=round(
                    bench_scan_rows_per_sec(MEASURE_SECONDS / 2), 1
                ),
                scan_steps=SCAN_STEPS,
            )
        except Exception as e:
            emit.update(value_scan_error=f"{type(e).__name__}: {e}")
    if fits("device_epoch", 60.0 + MEASURE_SECONDS):
        try:
            # all-in-HBM multi-epoch regime (--device-resident): one
            # compiled program per epoch, zero per-epoch batch transfer
            emit.update(device_epoch_rows_per_sec=round(
                bench_device_epoch_rows_per_sec(MEASURE_SECONDS), 1
            ))
        except Exception as e:
            emit.update(device_epoch_error=f"{type(e).__name__}: {e}")
    emit.update(bench_seconds=round(time.monotonic() - t0, 1))


# ------------------------------------------------------------- orchestration


def _child_main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    emit = _Emitter()

    def on_term(signum, frame):
        # os.write to fd 1, not print(): the handler may interrupt the
        # main thread mid-print, and CPython's buffered writer raises on
        # reentrant use — which would abort this flush with a traceback
        out = dict(emit.result)
        out["terminated"] = "SIGTERM mid-measurement"
        os.write(1, (json.dumps(out) + "\n").encode())
        os._exit(3)

    signal.signal(signal.SIGTERM, on_term)
    budget = float(os.environ.get("BENCH_CHILD_BUDGET_S", 1e9))
    run_measurements(emit, budget)
    emit.final()


#: in-flight measurement children, so the parent's signal handler can put
#: them down before exiting — an orphan would keep holding the TPU backend
#: into the next bench launch
_live_children: list = []


def _attempt(env_overrides: dict, timeout_s: float,
             forward) -> tuple[dict | None, str]:
    """Run the measurement child, streaming its stdout: every JSON line is
    handed to ``forward`` AS IT ARRIVES (so the parent's own stdout always
    carries the best evidence so far) and the last one parsed is returned.
    On timeout the child gets SIGTERM (it flushes a partial result), then
    SIGKILL — whatever it printed before dying still counts."""
    env = dict(os.environ)
    env.update(env_overrides)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)
    # leave the child headroom to finish a section before the hard kill
    env.setdefault("BENCH_CHILD_BUDGET_S", str(max(30.0, timeout_s - 15.0)))
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--run"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    _live_children.append(proc)
    # one-slot box, REBOUND not mutated: the parent's signal handler reads
    # it from another thread — rebinding is atomic, clear()+update() has a
    # window where the dict is empty
    parsed_box: list[dict | None] = [None]
    stderr_buf: list[bytes] = []

    def read_stdout():
        for raw in proc.stdout:
            line = raw.decode(errors="replace").strip()
            if not line.startswith("{"):
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            parsed_box[0] = obj
            forward(obj)

    def read_stderr():
        stderr_buf.append(proc.stderr.read())

    t_out = threading.Thread(target=read_stdout, daemon=True)
    t_err = threading.Thread(target=read_stderr, daemon=True)
    t_out.start()
    t_err.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.terminate()  # SIGTERM: child flushes its partial result
        try:
            proc.wait(timeout=KILL_GRACE_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    t_out.join(timeout=5.0)
    t_err.join(timeout=5.0)
    _live_children.remove(proc)
    last = parsed_box[0]
    result = dict(last) if last and last.get("value") else None
    if timed_out:
        state = "partial kept" if result else "nothing measured"
        return result, f"timeout after {timeout_s:.0f}s ({state})"
    if proc.returncode != 0 and result is None:
        err = b"".join(stderr_buf).decode(errors="replace")
        tail = err.strip().splitlines()[-3:]
        return None, f"rc={proc.returncode}: {' | '.join(tail)}"
    if result is None:
        return None, "child produced no JSON"
    return result, "ok" if proc.returncode == 0 else f"rc={proc.returncode}"


def _append_bench_history(name: str, artifact: str | None = None,
                          rc: int = 0, result: dict | None = None) -> None:
    """Append one line per bench run to ``BENCH_HISTORY.jsonl`` so the
    perf trajectory is a tracked series (`obs diff --bench` renders the
    delta between the last two entries of a bench).  The record carries
    a host fingerprint (numbers from different hosts must never be
    compared silently), the device the artifact names (``platform`` /
    ``device_kind`` — null where it names none), the artifact's scalar
    metrics, and a caller-supplied timestamp (``BENCH_TS`` — the driver
    pins run identity; wall clock otherwise).  Best-effort: history must never
    fail the bench that feeds it."""
    try:
        import platform as _platform
        import socket as _socket

        root = os.path.dirname(os.path.abspath(__file__))
        doc = result
        # a FAILED run must not re-read the artifact: the file on disk
        # is the PREVIOUS successful run's, and logging its numbers
        # under this run's timestamp would fake a clean data point —
        # the failure is recorded (rc field), its metrics are not
        if doc is None and artifact is not None and rc == 0:
            try:
                with open(os.path.join(root, artifact)) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                doc = None
        doc = doc or {}
        metrics = {
            k: v for k, v in doc.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        ts = os.environ.get("BENCH_TS") or round(time.time(), 3)
        rec = {
            "ts": ts,
            "name": name,
            "rc": rc,
            "artifact": artifact,
            "host": {
                "hostname": _socket.gethostname(),
                "platform": _platform.platform(terse=True),
                "machine": _platform.machine(),
                "cpus": os.cpu_count(),
            },
            "platform": doc.get("platform"),
            "device_kind": doc.get("device_kind", doc.get("device")),
            "metrics": metrics,
        }
        with open(os.path.join(root, "BENCH_HISTORY.jsonl"), "a") as f:
            f.write(json.dumps(rec, separators=(",", ":"),
                               default=str) + "\n")
    except Exception as e:
        print(f"bench history append failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def main() -> None:
    if "ingest" in sys.argv[1:]:
        # staged-ingest pipeline benchmark (python bench.py ingest):
        # cold parallel-reader scaling, traced dispatch occupancy, and
        # autotune-vs-grid, artifact BENCH_INGEST_PIPELINE.json —
        # implemented in scripts/bench_ingest_pipeline.py.  In-process
        # on the CPU backend (host ingest is the quantity under test),
        # so the parent's no-jax rule does not apply to this mode.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_ingest_pipeline

        rc = bench_ingest_pipeline.main()
        _append_bench_history('ingest', 'BENCH_INGEST_PIPELINE.json', rc=rc)
        sys.exit(rc)
    if "obs" in sys.argv[1:]:
        # observability-overhead benchmark (python bench.py obs):
        # obs-enabled vs disabled step time on the per-step epoch path,
        # artifact BENCH_OBS.json — implemented in scripts/bench_obs.py.
        # In-process on the CPU backend (the quantity under test is
        # host-side instrumentation cost), so the parent's no-jax rule
        # does not apply to this mode either.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_obs

        rc = bench_obs.main()
        _append_bench_history('obs', 'BENCH_OBS.json', rc=rc)
        sys.exit(rc)
    if "serve-tenants" in sys.argv[1:]:
        # multi-tenant serve benchmark (python bench.py serve-tenants):
        # N-model consolidation rows/s vs N single-model fleets at equal
        # total concurrency + p99 isolation under one-tenant overload,
        # artifact BENCH_SERVE_TENANTS.json — implemented in
        # scripts/bench_serve_tenants.py.  In-process on the CPU
        # backend, so the parent's no-jax rule does not apply.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_serve_tenants

        rc = bench_serve_tenants.main()
        _append_bench_history('serve-tenants', 'BENCH_SERVE_TENANTS.json', rc=rc)
        sys.exit(rc)
    if "elastic" in sys.argv[1:]:
        # elastic-fleet drill (python bench.py elastic [--quick]):
        # hot-standby takeover vs checkpoint restart on a real process
        # fleet — kill-a-worker mid-epoch, gate zero rollback on the
        # survivors (epoch monotonicity + bit-identical chief params vs
        # an unkilled control arm) and takeover-beats-relaunch latency,
        # artifact BENCH_ELASTIC.json — implemented in
        # scripts/bench_elastic.py.  Workers are subprocesses; the
        # submitter side is jax-light, so the parent's no-jax rule does
        # not apply to this mode.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_elastic

        rc = bench_elastic.main()
        _append_bench_history('elastic', 'BENCH_ELASTIC.json', rc=rc)
        sys.exit(rc)
    if "score" in sys.argv[1:]:
        # bulk scoring benchmark (python bench.py score [--quick]):
        # the batch plane vs HTTP /score on the same rows + bundle,
        # 1-vs-2 worker scaling (host_capped fallback on narrow hosts),
        # and the exactly-once kill drill — SIGKILL a scorer process
        # mid-lease under a torn-write plan, gate zero missing rows,
        # zero duplicate commit tokens, and bit-identical output vs the
        # unkilled arm; artifact BENCH_SCORE.json — implemented in
        # scripts/bench_score.py.  The driver side is jax-light and the
        # scorer fleet is subprocesses, so the parent's no-jax rule does
        # not apply to this mode.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_score

        rc = bench_score.main()
        _append_bench_history('score', 'BENCH_SCORE.json', rc=rc)
        sys.exit(rc)
    if "lifecycle" in sys.argv[1:]:
        # closed-loop lifecycle drill (python bench.py lifecycle
        # [--quick]): seeded drift on a live serving tenant →
        # journal-triggered retrain → shadow → weighted ramp → promote,
        # plus a poisoned-retrain arm (nan-loss fault plan) that must
        # auto-rollback with the parent generation still serving; gates
        # zero failed requests across the ramp and bit-identical
        # promoted scores, artifact BENCH_LIFECYCLE.json — implemented
        # in scripts/bench_lifecycle.py.  The serving fleet is
        # in-process on the CPU backend and retrains are subprocesses,
        # so the parent's no-jax rule does not apply to this mode.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_lifecycle

        rc = bench_lifecycle.main()
        _append_bench_history('lifecycle', 'BENCH_LIFECYCLE.json', rc=rc)
        sys.exit(rc)
    if "serve-aot" in sys.argv[1:]:
        # AOT executable shipping benchmark (python bench.py serve-aot):
        # 10-tenant fleet-restart admission, deserialize (shipped
        # executables) vs the PR-5 compile-warm baseline, plus the
        # fingerprint-mismatch fallback drill, artifact
        # BENCH_SERVE_AOT.json — implemented in
        # scripts/bench_serve_aot.py.  In-process on the CPU backend
        # (admission cost is the quantity under test), so the parent's
        # no-jax rule does not apply.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_serve_aot

        rc = bench_serve_aot.main()
        _append_bench_history('serve-aot', 'BENCH_SERVE_AOT.json', rc=rc)
        sys.exit(rc)
    if "serve-scale" in sys.argv[1:]:
        # serve-plane scale benchmark (python bench.py serve-scale):
        # bucket-ladder warm-up latency cliffs (cold start + hot-reload
        # admits, warm vs --no-warm) and SO_REUSEPORT --serve-workers
        # throughput scaling, artifact BENCH_SERVE_SCALE.json —
        # implemented in scripts/bench_serve_scale.py.  In-process on
        # the CPU backend, so the parent's no-jax rule does not apply.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_serve_scale

        rc = bench_serve_scale.main()
        _append_bench_history('serve-scale', 'BENCH_SERVE_SCALE.json', rc=rc)
        sys.exit(rc)
    if "serve-frame" in sys.argv[1:]:
        # frame wire-protocol benchmark (python bench.py serve-frame):
        # columnar binary frames vs /score JSON at equal in-flight
        # concurrency (gate: >= 2x rows/s, host_capped fallback),
        # bit-identical parity, and fleet occupancy at 2 workers with
        # the shared dispatch lane vs the fragmented private-batcher
        # baseline, artifact BENCH_SERVE_FRAME.json — implemented in
        # scripts/bench_serve_frame.py.  Fleets are CLI subprocesses;
        # the parent stays jax-free.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_serve_frame

        rc = bench_serve_frame.main()
        _append_bench_history('serve-frame', 'BENCH_SERVE_FRAME.json', rc=rc)
        sys.exit(rc)
    if "sharding" in sys.argv[1:]:
        # sharded-parameter SPMD benchmark (python bench.py sharding):
        # max trainable embedding rows under data:2,model:2 vs the
        # replicated ceiling at equal per-device params budget (the
        # memory accountant's params_dev_bytes bucket), step-time noise
        # bound, bit-identical sharded-vs-replicated eval through a
        # per-shard checkpoint migration, and a quiet storm detector —
        # artifact BENCH_SHARDING.json, implemented in
        # scripts/bench_sharding.py.  In-process on a 4-virtual-device
        # CPU backend (capacity is a bytes-placement property, not a
        # FLOPs one), so the parent's no-jax rule does not apply.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_sharding

        rc = bench_sharding.main()
        _append_bench_history('sharding', 'BENCH_SHARDING.json', rc=rc)
        sys.exit(rc)
    if "serve" in sys.argv[1:]:
        # serving benchmark (python bench.py serve): micro-batched vs
        # one-row-per-request scoring over HTTP, artifact
        # BENCH_SERVE.json — implemented in scripts/bench_serve.py.
        # Runs in-process on the CPU backend (force_cpu_backend inside),
        # so the parent's no-jax rule does not apply to this mode.
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import bench_serve

        rc = bench_serve.main()
        _append_bench_history('serve', 'BENCH_SERVE.json', rc=rc)
        sys.exit(rc)
    if "--run" in sys.argv:
        _child_main()
        return

    t_start = time.monotonic()
    deadline = t_start + TOTAL_BUDGET_S
    diagnostics: list[str] = []
    # one-slot box, rebound atomically by the reader thread; the signal
    # handler on the main thread reads it concurrently
    best_box: list[dict | None] = [None]

    def forward(obj: dict) -> None:
        # re-print child evidence immediately under the parent's pid —
        # if the parent is SIGKILLed this line is already in the caller's
        # output tail
        best_box[0] = obj
        print(json.dumps(obj), flush=True)

    def flush_and_exit(signum, frame):
        for child in list(_live_children):
            try:  # no orphans: a leaked child would hold the TPU backend
                child.kill()
            except Exception:
                pass
        best = best_box[0]
        out = dict(best) if best and best.get("value") else {
            "metric": "training_rows_per_sec_per_chip",
            "value": 0.0, "unit": "rows/s/chip", "vs_baseline": 0.0,
            "error": "terminated before any measurement completed",
        }
        if out.pop("partial", None):
            out["incomplete"] = True  # final lines are never "partial"
        out["diagnostics"] = diagnostics + [
            f"parent received signal {signum} at "
            f"{time.monotonic() - t_start:.0f}s"
        ]
        # os.write, not print: the buffered stdout writer is not
        # reentrant and the main thread may be mid-print right now
        os.write(1, (json.dumps(out) + "\n").encode())
        os._exit(0)

    signal.signal(signal.SIGTERM, flush_and_exit)
    signal.signal(signal.SIGINT, flush_and_exit)

    # overhead beyond the child timeout itself: SIGTERM→KILL grace (8s)
    # + two 5s reader joins + slack — the budget arithmetic must charge
    # it or the worst case overruns the total
    overhead = KILL_GRACE_S + 12.0
    # ONE attempt, on the ambient platform.  No second platform: a bench
    # that lost its chip must say so, not report the CPU's number.
    budget = min(TPU_TIMEOUT_S, deadline - time.monotonic() - overhead)
    result, diag = _attempt({}, budget, forward)
    diagnostics.append(f"attempt: {diag}")
    if result is None:
        print(json.dumps({
            "metric": "training_rows_per_sec_per_chip",
            "value": 0.0,
            "unit": "rows/s/chip",
            "vs_baseline": 0.0,
            "error": "nothing was measured",
            "diagnostics": diagnostics,
            "total_bench_s": round(time.monotonic() - t_start, 1),
        }), flush=True)
        _append_bench_history("train", rc=1)
        sys.exit(1)
    if result.pop("partial", None):
        # the kept result came from a timed-out child: say so — a clean-
        # looking artifact with silently missing sections would misread
        # as a complete run
        result["incomplete"] = True
    result["diagnostics"] = diagnostics
    result["total_bench_s"] = round(time.monotonic() - t_start, 1)
    print(json.dumps(result), flush=True)
    _append_bench_history("train", rc=0, result=result)


if __name__ == "__main__":
    main()
