"""On-chip profiler trace: prove (or refute) infeed/compute overlap.

VERDICT r04 item 6: ``trace_if`` exists but no trace artifact does.  This
script traces ~N streaming steps (ShardStream -> prefetch_to_device ->
jitted step) AND a device-resident control loop under ``jax.profiler.trace``,
parses the XPlane protobuf, and writes a step-time vs device-busy breakdown
to ``BENCH_INFEED_TRACE.json``.

Methodology
-----------
- The **control** loop (device-resident batch, same jitted step) calibrates
  what "compute-bound" looks like in the trace: its device-busy fraction is
  the ceiling this tracer can report.
- The **streaming** loop runs the real ingest path.  Its device-busy
  fraction, normalized by the control's, is the overlap measure:
  ``stall_frac ~= 1 - busy_stream / busy_control``.  If the device is as
  busy streaming as it is device-resident, infeed fully overlaps; the gap
  is host-side stall (parse, queue, transfer).
- Busy time is the **union of event intervals per plane** (nesting-safe),
  restricted to the measured wall window.
- Wall-clock syncs use ``true_sync`` (value fetch,
  utils/profiling.py).

Reference surface: the reference has no profiler at all (SURVEY.md §5.1);
its epoch timer is ssgd_monitor.py:270-277.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root bench: shares workload + shard generator

NUM_FEATURES = bench.NUM_FEATURES


def _union_busy_s(events: list[tuple[float, float]],
                  w0: float, w1: float) -> float:
    """Union of [start, end) intervals clipped to [w0, w1], in seconds."""
    clipped = [(max(s, w0), min(e, w1)) for s, e in events
               if e > w0 and s < w1]
    if not clipped:
        return 0.0
    clipped.sort()
    total = 0.0
    cur_s, cur_e = clipped[0]
    for s, e in clipped[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    total += cur_e - cur_s
    return total


def parse_xplane(trace_dir: str) -> dict:
    """Per-plane busy-interval lists from the newest .xplane.pb under dir.

    Returns {plane_name: {"events": [(start_s, end_s)...], "n_events": int}}
    with timestamps in seconds since the plane's epoch (XPlane pico/nano
    offsets normalized).
    """
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    space = xplane_pb2.XSpace()
    with open(pbs[-1], "rb") as f:
        space.ParseFromString(f.read())

    planes: dict = {}
    for plane in space.planes:
        line_events: dict = {}
        for line in plane.lines:
            # line timestamps are ns since epoch; event offsets/durations ps
            base_ns = line.timestamp_ns
            evs = []
            for ev in line.events:
                s = base_ns * 1e-9 + ev.offset_ps * 1e-12
                e = s + ev.duration_ps * 1e-12
                if e > s:
                    evs.append((s, e))
            line_events.setdefault(line.name, []).extend(evs)
        planes[plane.name] = {
            "line_events": line_events,
            "n_events": sum(len(v) for v in line_events.values()),
            "lines": list(line_events),
        }
    return planes


def _compute_events(planes: dict) -> tuple[list[str], list]:
    """(selected sources, flat event list) for device compute.

    TPU: every line of the device planes (``/device:TPU:N`` etc.).
    CPU backend: there is no device plane — XLA compute runs on host
    threadpools that show up as ``tf_XLAEigen/...`` /
    ``tf_XLAPjRtCpuClient/...`` lines of ``/host:CPU``; their busy union
    is the compute-busy equivalent (observed shape of jax 0.8 CPU traces).
    """
    tpu = [n for n in planes if "TPU" in n and "Host" not in n]
    if tpu:
        events = [ev for n in tpu
                  for evs in planes[n]["line_events"].values()
                  for ev in evs]
        return tpu, events
    srcs, events = [], []
    for n, p in planes.items():
        for line, evs in p["line_events"].items():
            if line.startswith(("tf_XLAEigen", "tf_XLAPjRtCpuClient")):
                srcs.append(f"{n}:{line}")
                events.extend(evs)
    return srcs, events


def _note(msg: str) -> None:
    print(f"[trace_infeed] {msg}", file=sys.stderr, flush=True)


def traced_run(tag: str, run_fn, trace_root: str) -> dict:
    """Run ``run_fn`` under jax.profiler.trace; return busy breakdown."""
    import jax

    _note(f"tracing {tag}...")
    trace_dir = os.path.join(trace_root, tag)
    p0 = time.perf_counter()
    with jax.profiler.trace(trace_dir):
        run_fn()
    wall_s = time.perf_counter() - p0
    _note(f"{tag}: ran {wall_s:.1f}s, parsing xplane...")

    planes = parse_xplane(trace_dir)
    dev_names, dev_events = _compute_events(planes)
    # the busy window is the trace's own span: XPlane timestamps are not
    # host-epoch through every backend, so clipping to time.time() would
    # zero everything; the traced region wraps run_fn exactly, so the
    # all-plane event span ≈ wall_s (reported as trace_span_s to check)
    all_events = [ev for p in planes.values()
                  for evs in p["line_events"].values() for ev in evs]
    t0 = min((s for s, _ in all_events), default=0.0)
    t1 = max((e for _, e in all_events), default=0.0)
    dev_busy = _union_busy_s(dev_events, t0, t1)
    span = t1 - t0
    out = {
        "wall_s": round(wall_s, 3),
        "trace_span_s": round(span, 3),
        "device_planes": dev_names[:8],
        "device_busy_s": round(dev_busy, 3),
        "device_busy_frac": round(dev_busy / span, 4) if span else 0.0,
        "planes": {n: {"n_events": p["n_events"], "lines": p["lines"][:12]}
                   for n, p in planes.items()},
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if os.path.basename(os.path.dirname(os.path.abspath(__file__)))
        == "scripts" else ".", "BENCH_INFEED_TRACE.json"))
    ap.add_argument("--steps", type=int,
                    default=int(os.environ.get("TRACE_STEPS", 100)))
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("TRACE_STREAM_ROWS", 2_000_000)))
    ap.add_argument("--keep-trace", action="store_true",
                    help="keep the raw trace dir (large) instead of tmp")
    args = ap.parse_args()

    # fail fast if the XPlane proto is unavailable — discovering that
    # AFTER the traced run would burn a scarce TPU window for nothing
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: F401

    import jax

    from shifu_tensorflow_tpu.data.dataset import (ShardStream,
                                                   prefetch_to_device)
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.trainer import Trainer
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    mesh = make_mesh("data:-1")
    trainer = Trainer(bench._model_config(), NUM_FEATURES, mesh=mesh)
    batch_size = trainer.align_batch_size(
        int(os.environ.get("TRACE_BATCH", 65536)))
    # both traced loops must run the SAME step count: the busy-fraction
    # comparison is biased if fixed trace overhead weighs differently in
    # the two windows.  The stream yields floor(rows/batch) batches
    # (drop_remainder), so cap steps to what the data can actually serve.
    avail = args.rows // batch_size
    if avail < args.steps:
        _note(f"capping steps {args.steps} -> {avail} "
              f"({args.rows} rows / batch {batch_size})")
        args.steps = max(1, avail)
    rng = np.random.default_rng(0)
    warm = {
        "x": rng.normal(size=(batch_size, NUM_FEATURES)).astype(np.float32),
        "y": (rng.random((batch_size, 1)) < 0.3).astype(np.float32),
        "w": np.ones((batch_size, 1), np.float32),
    }
    step = trainer._train_step
    # compile + warm OUTSIDE the trace so the trace is steady-state
    _note("compiling train step...")
    dev_warm = trainer._put(warm)
    trainer.state, loss = step(trainer.state, dev_warm)
    true_sync(loss)
    _note("compiled")

    result: dict = {
        "metric": "infeed_trace",
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0].device_kind),
        "batch": batch_size,
        "steps": args.steps,
    }

    trace_root = (os.path.abspath("trace_infeed_out") if args.keep_trace
                  else tempfile.mkdtemp(prefix="stpu-trace-"))
    if not args.keep_trace:
        # raw XPlane traces are large and the watcher runs this on every
        # open window — clean up even on SIGTERM/timeout kills (the
        # SIGTERM handler routes through sys.exit so atexit fires; the
        # partial artifact is already flushed incrementally)
        import atexit
        import shutil
        import signal

        atexit.register(shutil.rmtree, trace_root, ignore_errors=True)
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(1))

    def flush() -> None:
        # incremental artifact writes: the watcher runs this under a hard
        # timeout — a kill after the control trace must still leave the
        # completed sections on disk (same discipline as bench_sequence)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)

    # ---- control: device-resident loop (compute-bound ceiling) ----
    def run_control():
        # thread the state back onto the trainer: the jitted step DONATES
        # its input state, so a later run reusing the old reference would
        # hit a deleted buffer
        st = trainer.state
        loss = None
        for _ in range(args.steps):
            st, loss = step(st, dev_warm)
        true_sync(loss)
        trainer.state = st

    result["control"] = traced_run("control", run_control, trace_root)
    flush()

    # ---- streaming: the real ingest path ----
    schema = RecordSchema(
        feature_columns=tuple(range(1, NUM_FEATURES + 1)),
        target_column=0, weight_column=NUM_FEATURES + 1,
    )
    with tempfile.TemporaryDirectory(prefix="stpu-trace-data-") as root:
        _note(f"generating {args.rows} rows...")
        paths = bench._write_stream_shards(root, args.rows,
                                           bench.STREAM_SHARDS)
        cache_dir = os.path.join(root, "cache")
        _note("building shard cache...")
        # build the shard cache outside the trace: we are measuring the
        # steady multi-epoch ingest regime (cold parse is its own bench)
        warm_stream = ShardStream(paths, schema, batch_size, valid_rate=0.0,
                                  emit="train", cache_dir=cache_dir,
                                  drop_remainder=True)
        for _ in warm_stream:
            pass

        def run_stream():
            stream = ShardStream(paths, schema, batch_size, valid_rate=0.0,
                                 emit="train", cache_dir=cache_dir,
                                 drop_remainder=True)
            it = prefetch_to_device(iter(stream), put=trainer._put)
            st = trainer.state
            loss = None
            n = 0
            for batch in it:
                st, loss = step(st, batch)
                n += 1
                if n >= args.steps:
                    break
            true_sync(loss)
            trainer.state = st
            result["stream_steps_run"] = n

        result["stream"] = traced_run("stream", run_stream, trace_root)
        flush()

    ctl = result["control"]["device_busy_frac"]
    stm = result["stream"]["device_busy_frac"]
    result["overlap"] = {
        # streaming device busyness relative to the compute-bound ceiling;
        # 1.0 = infeed fully hidden, 0.2 = device idle 80% waiting on host
        "stream_vs_control_busy": round(stm / ctl, 4) if ctl else None,
        "infeed_stall_frac": round(1 - stm / ctl, 4) if ctl else None,
        "note": ("control calibrates tracer fidelity: stall is "
                 "1 - stream_busy/control_busy, not 1 - stream_busy"),
    }
    if args.keep_trace:
        result["trace_dir"] = trace_root

    flush()
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("control", "stream")} |
                     {"control_busy": ctl, "stream_busy": stm}))


if __name__ == "__main__":
    main()
