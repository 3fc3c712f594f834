"""Benchmark: training rows/sec/chip on the flagship tabular workload.

Output contract: every stdout line is a valid JSON object; the LAST line
is the most complete result — {"metric": ..., "value": N, "unit": ...,
"vs_baseline": N} plus context fields (platform, device, diagnostics).
Lines before the last are the same result at earlier stages of
completeness ("partial": true), printed the moment each number is
measured, so a bench killed mid-run still leaves a parseable artifact in
its caller's output tail.

``training_rows_per_sec_per_chip``: steady-state jitted SPMD step
throughput on a device-resident batch.  The system's benchmark proper is
``benchmark/run.py`` over the cells of ``BENCHMARK.json``; this file is
the root contract its callers run.

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
- results stream: the child prints its result JSON as soon as it is
  measured, and the parent forwards each line as it arrives;
- SIGTERM at either level flushes the best result measured so far and
  exits 0 — a killed bench fails OPEN with a partial artifact, never
  closed with an empty tail;
- compiled programs persist in an XLA compilation cache
  (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``), so subsequent
  runs on the same machine skip the compiles.

Every run appends one line to ``BENCH_HISTORY.jsonl`` (``obs diff
--bench`` renders the last two).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
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
# budget discipline (round-3 verdict): the WHOLE bench fits
# BENCH_TOTAL_BUDGET_S; the one measurement child is capped at
# BENCH_TPU_TIMEOUT.
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


def bench_step_rows_per_sec() -> float:
    """Steady-state jitted step throughput, device-resident batch."""
    import jax

    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer

    # shard the batch over every local chip so the per-chip division below
    # is honest on multi-chip hosts; single chip gets a 1-device mesh
    mesh = make_mesh("data:-1")
    trainer = Trainer(_model_config(), NUM_FEATURES, mesh=mesh)
    rng = np.random.default_rng(0)
    rows = trainer.align_batch_size(BATCH)
    batch = {
        "x": rng.normal(size=(rows, NUM_FEATURES)).astype(np.float32),
        "y": (rng.random((rows, 1)) < 0.3).astype(np.float32),
        "w": np.ones((rows, 1), np.float32),
    }
    # function-local on purpose: importing the package pulls jax, and
    # bench.py's PARENT process must never touch jax — a hanging PJRT
    # plugin would take down the orchestrator instead of one timed-out
    # child
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
            if time.perf_counter() - t0 >= MEASURE_SECONDS:
                break
    true_sync(loss)
    elapsed = time.perf_counter() - t0
    rows_per_sec = n_steps * rows / elapsed
    return rows_per_sec / jax.local_device_count()


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


def run_measurements(emit: _Emitter) -> None:
    """Child-process entry: measure on whatever backend the env selects."""
    t0 = time.monotonic()

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
        bench_seconds=round(time.monotonic() - t0, 1),
    )


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
    run_measurements(emit)
    emit.final()


#: in-flight measurement children, so the parent's signal handler can put
#: them down before exiting — an orphan would keep holding the TPU backend
#: into the next bench launch
_live_children: list = []


def _attempt(timeout_s: float, forward) -> tuple[dict | None, str]:
    """Run the measurement child, streaming its stdout: every JSON line is
    handed to ``forward`` AS IT ARRIVES (so the parent's own stdout always
    carries the best evidence so far) and the last one parsed is returned.
    On timeout the child gets SIGTERM (it flushes a partial result), then
    SIGKILL — whatever it printed before dying still counts."""
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)
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


def _append_bench_history(name: str, rc: int = 0,
                          result: dict | None = None) -> None:
    """Append one line per bench run to ``BENCH_HISTORY.jsonl`` so the
    perf trajectory is a tracked series (`obs diff --bench` renders the
    delta between the last two entries of a bench).  The record carries
    a host fingerprint (numbers from different hosts must never be
    compared silently), the device the result names (``platform`` /
    ``device_kind`` — null where it names none), the result's scalar
    metrics, and a caller-supplied timestamp (``BENCH_TS`` — the driver
    pins run identity; wall clock otherwise).  Best-effort: history must never
    fail the bench that feeds it."""
    try:
        import platform as _platform
        import socket as _socket

        root = os.path.dirname(os.path.abspath(__file__))
        doc = result or {}
        metrics = {
            k: v for k, v in doc.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        }
        ts = os.environ.get("BENCH_TS") or round(time.time(), 3)
        rec = {
            "ts": ts,
            "name": name,
            "rc": rc,
            "host": {
                "hostname": _socket.gethostname(),
                "platform": _platform.platform(terse=True),
                "machine": _platform.machine(),
                "cpus": os.cpu_count(),
            },
            "platform": doc.get("platform"),
            "device_kind": doc.get("device"),
            "metrics": metrics,
        }
        with open(os.path.join(root, "BENCH_HISTORY.jsonl"), "a") as f:
            f.write(json.dumps(rec, separators=(",", ":"),
                               default=str) + "\n")
    except Exception as e:
        print(f"bench history append failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def main() -> None:
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
    result, diag = _attempt(budget, forward)
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
        # the kept result came from a child that never printed its final
        # line: say so, or the artifact would misread as a complete run
        result["incomplete"] = True
    result["diagnostics"] = diagnostics
    result["total_bench_s"] = round(time.monotonic() - t_start, 1)
    print(json.dumps(result), flush=True)
    _append_bench_history("train", rc=0, result=result)


if __name__ == "__main__":
    main()
