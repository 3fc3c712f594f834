"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell is an entry of ``BENCHMARK.json``'s ``workloads``;
its configuration is the ``file`` of the ``configs`` entry it names; its
traffic mix is ``benchmark/workloads/<traffic>.json``, whose ``plane`` names
the module under ``benchmark/planes/`` that drives the program; a traced
run calls ``benchmark/metrics/<name>.py`` for every ``per_layer`` metric
that lists the cell (one with no ``workloads`` list is every cell's; one
that exists only in some cells, as the collectives do only across chips,
names them there).  A new cell, configuration, traffic mix or per-layer
metric is new files plus new ``BENCHMARK.json`` entries, and no edit here.

The last line printed is the result object; earlier lines are notes
(``{"note": ...}``).  Exit codes: 0 a result on a TPU; 3 no result (no
accelerator, too few chips, an unknown device kind, a compile inside the
window, a metric missing); 4 a rehearsal off the TPU (``--rehearse``: the
result is stamped ``"rehearsal": true`` and names the platform it ran on).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import subprocess
import sys
import time

_perf = time.perf_counter
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
EXIT_NO_RESULT = 3
EXIT_REHEARSAL = 4
#: jax.monitoring's event for one XLA backend compile (a persistent-cache
#: hit included) — the event ``obs/compile.py``'s ``CompileRecorder``
#: counts; read here directly, because an untraced run installs no
#: recorder (it is the program as users start it)
COMPILE_EVENT_SUFFIX = "backend_compile_duration"


def process_age_s() -> float:
    """Seconds since this process started, by the kernel's clock."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def note(**fields) -> None:
    print(json.dumps({"note": fields}, default=str), flush=True)


class NoResult(SystemExit):
    def __init__(self, why: str):
        print(f"benchmark: no result: {why}", file=sys.stderr, flush=True)
        super().__init__(EXIT_NO_RESULT)


class Window:
    """The measured window: opened on the host's clock, held open as the
    ``bench.window`` span of a traced run, with the compiles inside it
    counted."""

    def __init__(self):
        self.t_open = self.t_close = None
        self.compiles = 0
        self.setup_s = None

    def elapsed(self) -> float:
        return _perf() - self.t_open

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


@dataclasses.dataclass
class Ctx:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    work_dir: str
    out_dir: str
    cpu_workers: int
    state: dict = dataclasses.field(default_factory=dict)
    window_obj: Window | None = None
    compiles: int = 0  # XLA backend compiles seen so far in this process

    def ensure_native(self) -> None:
        """``make -B -C cpp`` where this machine has not built the native
        parser yet: git does not carry the ``.so``, and one copied from
        another machine was compiled with ``-march=native`` for that
        machine's CPU (it dies of SIGILL here).  A stamp under the work
        directory names the CPU the library was built on.  The child ends
        before this returns."""
        lib = os.path.join(REPO, "shifu_tensorflow_tpu", "_native",
                           "libstpu_data.so")
        with open("/proc/cpuinfo") as f:
            cpu = "".join(ln for ln in f.read().split("\n\n")[0].splitlines(True)
                          if ln.startswith(("model name", "flags")))
        stamp = os.path.join(REPO, ".bench_work", "native_built_on.txt")
        try:
            with open(stamp) as f:
                if os.path.exists(lib) and f.read() == cpu:
                    return
        except OSError:
            pass
        made = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "cpp")],
                              capture_output=True, text=True)
        if made.returncode != 0 or not os.path.exists(lib):
            raise NoResult(f"make -B -C cpp failed: {made.stderr[-2000:]}")
        os.makedirs(os.path.dirname(stamp), exist_ok=True)
        with open(stamp, "w") as f:
            f.write(cpu)

    def memory_peak(self) -> int | None:
        """The most HBM held on any device at the moments this is called:
        ``bytes_in_use + bytes_reserved`` now, or the largest seen at an
        earlier call.  ``bytes_reserved`` is what the runtime sets aside
        for a loaded program's temporaries: ``peak_bytes_in_use`` alone
        does not count it (PERF.md, Findings), and it is most of what a
        train step over a large table holds.  Planes call this after each
        phase; the last call is after the window."""
        import jax

        for d in jax.local_devices():
            st = d.memory_stats() or {}
            if "bytes_in_use" in st:
                held = max(st["bytes_in_use"] + st.get("bytes_reserved", 0),
                           st.get("peak_bytes_in_use", 0))
                self.state["memory_peak"] = max(
                    self.state.get("memory_peak", 0), held)
        return self.state.get("memory_peak")

    def listen_for_compiles(self) -> None:
        import jax.monitoring

        def on_duration(name: str, _secs: float, **_kw) -> None:
            if name.endswith(COMPILE_EVENT_SUFFIX):
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    @contextlib.contextmanager
    def window(self):
        """``with ctx.window() as win:`` — everything before is set-up."""
        import jax

        win = self.window_obj = Window()
        trace_dir = os.path.join(self.out_dir, "trace")
        annotation = contextlib.nullcontext()
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            from benchmark.xplane import WINDOW_SPAN

            annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        try:
            before = self.compiles
            win.setup_s = process_age_s()
            with annotation:
                win.t_open = _perf()
                try:
                    yield win
                finally:
                    win.t_close = _perf()
            win.compiles = self.compiles - before
        finally:
            if self.trace:
                jax.profiler.stop_trace()
                self.state["trace_dir"] = trace_dir


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, root: str, name: str):
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(
        root, os.path.dirname(os.path.dirname(entry["file"])), "workloads",
        cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_for(bench: dict, group: str, cell_name: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def device_info(ctx) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": ctx.memory_peak()}


def per_layer(ctx, bench: dict, result: dict, device: dict) -> tuple:
    """(metrics, breakdown, busy_s, window_s) of a traced run."""
    from benchmark import peaks, xplane

    names = set(result.get("span_names", ())) | {xplane.WINDOW_SPAN}
    path = xplane.find_xplane(ctx.state["trace_dir"])
    trace = xplane.load(path, host_names=names.__contains__)
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if keep:  # a directory: for looking at a trace by hand
        os.makedirs(keep, exist_ok=True)
        with open(os.path.join(keep, "describe.json"), "w") as f:
            json.dump(xplane.describe(path), f, indent=1)
        xplane.save_json(trace, os.path.join(keep, "trace.json.gz"))
    window = xplane.window_of(trace)
    win = ctx.window_obj
    reading = {
        "trace": trace, "window_ns": window, "window_s": win.seconds,
        "spans": result.get("spans", {}),
        "cell": ctx.cell, "config": ctx.config, "traffic": ctx.traffic,
        "device": device,
        "peaks": None if ctx.rehearsal else peaks.lookup(device["kind"]),
        "step_pattern": result.get("step_pattern"),
    }
    metrics = {}
    reported = {e["name"] for e in metrics_for(bench, "end_to_end",
                                               ctx.cell["name"])}
    for m in metrics_for(bench, "per_layer", ctx.cell["name"]):
        if m["moves"] not in reported:  # only where the metric it moves is
            continue
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        value = reader.read(reading)
        if value is None:  # left out of the line; the driver wants every
            # metric BENCHMARK.json lists for the cell, so say which is not
            print(f"benchmark: {m['name']}: nothing to read in "
                  f"{ctx.cell['name']}", file=sys.stderr, flush=True)
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    busy = xplane.busy_seconds(trace, window)
    busy_s = sum(busy.values()) / len(busy) if busy else 0.0
    breakdown = {"device_ops": xplane.top_ops(trace, window),
                 "idle_gaps": xplane.idle_gaps(trace, window)}
    window_s = ((window[1] - window[0]) / 1e9 if window else win.seconds)
    return metrics, breakdown, busy_s, window_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(REPO, "BENCHMARK.json"),
                    help="tests point this at a copy with cells of their "
                         "own; data files are found beside it")
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the TPU: exit 4, result stamped")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "shifu_tensorflow_tpu")):
        raise NoResult("the program (shifu_tensorflow_tpu/) is not here")
    sys.path.insert(0, REPO)
    bench = load_json(args.benchmark_json)
    root = os.path.dirname(os.path.abspath(args.benchmark_json))
    cell, config, traffic = find_cell(bench, root, args.workload)

    # caches inside the checkout, at fixed paths; the variable wins where
    # the machine sets it (obs/compile.apply_persistent_cache reads it)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    work = os.path.join(REPO, ".bench_work", cell["name"])
    out = os.path.join(work, "trace1" if args.trace else "trace0")
    os.makedirs(out, exist_ok=True)
    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              rehearsal=args.rehearse, work_dir=work, out_dir=out,
              cpu_workers=max(1, min(8, (os.cpu_count() or 2) - 1)))
    plane = importlib.import_module(f"benchmark.planes.{traffic['plane']}")
    plane.prepare(ctx)  # forks end here; JAX is not imported yet

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise NoResult(f"JAX found no TPU (platform {dev.platform!r})")
    if jax.device_count() < int(cell["chips"]):
        raise NoResult(f"the cell asks for {cell['chips']} chips, JAX has "
                       f"{jax.device_count()}")
    if dev.platform == "tpu":
        from benchmark import peaks

        peaks.lookup(dev.device_kind)  # an unknown kind is an error
    ctx.listen_for_compiles()

    result = plane.run(ctx)
    win = ctx.window_obj
    note(cell=cell["name"], seed=args.seed, trace=args.trace,
         window_s=win.seconds, setup_s=win.setup_s,
         compiles_in_window=win.compiles, info=result.get("info"))
    if win.compiles:
        raise NoResult(f"{win.compiles} compiles inside the window")

    device = device_info(ctx)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {},
            "device": device}
    if args.trace:
        metrics, breakdown, busy_s, window_s = per_layer(
            ctx, bench, result, device)
        line["metrics"] = metrics
        line["breakdown"] = breakdown
        device["busy_s"], device["window_s"] = busy_s, window_s
    else:
        values = dict(result["end_to_end"], setup_s=win.setup_s)
        for m in metrics_for(bench, "end_to_end", cell["name"]):
            if m["name"] not in values:
                raise NoResult(f"the plane gave no {m['name']}")
            line["metrics"][m["name"]] = {
                "value": float(values[m["name"]]), "unit": m["unit"]}
    if not line["metrics"]:
        raise NoResult("no metric to report")
    if args.rehearse:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return EXIT_REHEARSAL if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())
