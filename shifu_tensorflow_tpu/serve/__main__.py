"""Serving CLI — run the scoring server against an exported bundle.

    python -m shifu_tensorflow_tpu.serve \
        --model-dir ./model-export --port 8080

Config precedence matches the training CLI: built-in defaults →
``--globalconfig`` file(s) (Hadoop XML or JSON, ``shifu.tpu.serve-*``
keys) → explicit CLI flags.  On startup the server prints one JSON line
``{"state": "listening", "port": N, ...}`` (machine-readable for smoke
tests and supervisors), serves until SIGTERM/SIGINT, then drains and
prints a final JSON summary line.
"""

from __future__ import annotations

import argparse
import json
import signal
import sys

from shifu_tensorflow_tpu.config import keys as K
from shifu_tensorflow_tpu.config.conf import Conf
from shifu_tensorflow_tpu.serve.config import resolve_serve_config
from shifu_tensorflow_tpu.utils import retry as _retry_util


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shifu_tensorflow_tpu.serve",
        description="Serve an exported model over HTTP with micro-batched "
                    "scoring, hot reload, and shed-before-queue "
                    "backpressure.",
    )
    p.add_argument("--model-dir", default=None,
                   help="exported bundle dir (export_model output) — "
                        "single-model mode; exactly one of this and "
                        "--models-dir is required")
    p.add_argument("--models-dir", default=None, dest="models_dir",
                   help="multi-tenant mode (shifu.tpu.serve-models-dir): "
                        "every immediate subdirectory holding an export "
                        "bundle is a tenant, routed at /score/<model> "
                        "(GET /models lists them)")
    p.add_argument("--model-budget-mb", type=float, default=None,
                   dest="model_budget_mb",
                   help="admission budget in MB of bundle bytes "
                        "(shifu.tpu.serve-model-budget-mb); past it, "
                        "least-recently-used tenants evict.  0 = "
                        "unlimited")
    p.add_argument("--model-admit-wait", type=float, default=None,
                   dest="model_admit_wait",
                   help="cold-start guard seconds a request waits on an "
                        "in-flight admission before 503 + Retry-After "
                        "(shifu.tpu.serve-model-admit-wait)")
    p.add_argument("--tenant-weight", action="append", default=None,
                   dest="tenant_weight", metavar="MODEL=W",
                   help="weighted fair dispatch: device-rows weight for "
                        "one tenant (repeatable; CLI wins over "
                        "shifu.tpu.serve-tenant-weight-<model> keys)")
    p.add_argument("--globalconfig", action="append", default=[],
                   help="layered config file (XML or JSON); repeatable, "
                        "later wins")
    p.add_argument("--host", default=None)
    p.add_argument("--port", type=int, default=None,
                   help=f"0 = ephemeral (default "
                        f"{K.DEFAULT_SERVE_PORT})")
    p.add_argument("--backend", default=None,
                   choices=["native", "cpp", "saved_model"])
    p.add_argument("--max-batch", type=int, default=None, dest="max_batch",
                   help="rows per coalesced dispatch")
    p.add_argument("--max-delay-ms", type=float, default=None,
                   dest="max_delay_ms",
                   help="max wait for request coalescing")
    p.add_argument("--queue-rows", type=int, default=None, dest="queue_rows",
                   help="admission bound; beyond it requests shed with 429")
    p.add_argument("--retry-after", type=int, default=None,
                   dest="retry_after",
                   help="Retry-After seconds on shed responses")
    p.add_argument("--reload-poll-ms", type=int, default=None,
                   dest="reload_poll_ms",
                   help="export-dir poll cadence for hot reload; "
                        "0 disables")
    p.add_argument("--serve-workers", type=int, default=None,
                   dest="serve_workers",
                   help="scoring processes sharing the port via "
                        "SO_REUSEPORT (shifu.tpu.serve-workers); a parent "
                        "supervisor drains them on SIGTERM and restarts "
                        "crashes.  1 = single process (default)")
    p.add_argument("--serve-workers-max", type=int, default=None,
                   dest="serve_workers_max",
                   help="autoscaler ceiling (shifu.tpu.serve-workers-max):"
                        " with a value above --serve-workers, the "
                        "supervisor scales SO_REUSEPORT workers between "
                        "the two from the journaled SLO/shed signals "
                        "(sustained breach grows, sustained recovery "
                        "shrinks, single-tenant overload rebalances that "
                        "tenant's weight first).  Needs --obs-journal.  "
                        "0 = off (default)")
    p.add_argument("--autoscale-cooldown", type=float, default=None,
                   dest="autoscale_cooldown",
                   help="seconds the autoscaler holds still after any "
                        "decision (shifu.tpu.serve-autoscale-cooldown)")
    p.add_argument("--autoscale-poll", type=float, default=None,
                   dest="autoscale_poll",
                   help="autoscaler tick cadence in seconds "
                        "(shifu.tpu.serve-autoscale-poll)")
    p.add_argument("--supervisor-port", type=int, default=None,
                   dest="supervisor_port",
                   help="supervisor /metrics listener port "
                        "(shifu.tpu.serve-supervisor-port): scrapes "
                        "stpu_serve_scale_* gauges — live worker count, "
                        "ceiling, scale/rebalance totals, restart-budget "
                        "remaining and per-window burn.  0 = off")
    p.add_argument("--frame-port", type=int, default=None,
                   dest="frame_port",
                   help="binary wire-protocol listener "
                        "(shifu.tpu.serve-frame-port): length-prefixed "
                        "float32 frames on persistent connections, "
                        "replies multiplexed by rid — no JSON parse, no "
                        "per-row copies.  0 = off (default), -1 = "
                        "ephemeral (resolved port in the listening line)")
    p.add_argument("--frame-max-rows", type=int, default=None,
                   dest="frame_max_rows",
                   help="largest row count one frame may carry "
                        "(shifu.tpu.serve-frame-max-rows); bigger frames "
                        "get a typed 413 ERROR frame before the payload "
                        "is buffered")
    p.add_argument("--shared-lane", action="store_true", default=None,
                   dest="shared_lane",
                   help="with --serve-workers N>1, funnel every worker's "
                        "packed batches through ONE fleet-wide "
                        "DeviceScheduler on the lowest-index worker "
                        "(shifu.tpu.serve-shared-lane); siblings fall "
                        "back to private dispatch while the owner is "
                        "unreachable")
    p.add_argument("--lane-socket", default=None, dest="lane_socket",
                   help="(internal) shared-lane UNIX socket path; set by "
                        "the --serve-workers supervisor")
    p.add_argument("--no-warm", action="store_true", dest="no_warm",
                   help="skip the bucket-ladder pre-warm at startup and "
                        "on reload admits (diagnostic/benchmark arm: "
                        "exposes the first-request compile cliff)")
    p.add_argument("--worker-index", type=int, default=None,
                   dest="serve_worker_index",
                   help="(internal) index of this scoring process under "
                        "--serve-workers; set by the supervisor")
    p.add_argument("--obs-journal", default=None, dest="obs_journal",
                   help="observability journal path (shifu.tpu.obs-journal):"
                        " reload/shed lifecycle events append here; serve "
                        "workers write <path>.s<i> siblings; read "
                        "with `python -m shifu_tensorflow_tpu.obs`")
    p.add_argument("--obs-job", default=None, dest="obs_job",
                   help="(internal) fleet-wide job correlation id stamped "
                        "on journal events; set by the --serve-workers "
                        "supervisor so every worker journals the same id")
    p.add_argument("--compile-cache-dir", default=None,
                   dest="compile_cache_dir",
                   help="jax persistent compilation cache dir "
                        "(shifu.tpu.compile-cache-dir) — the middle "
                        "tier of the AOT fallback ladder: a bucket "
                        "that live-compiles (AOT mismatch, no AOT "
                        "shipped) persists its program here, so the "
                        "next worker/restart skips XLA.  "
                        "JAX_COMPILATION_CACHE_DIR wins where set; with "
                        "neither, <checkout>/.jax_cache")
    p.add_argument("--obs-baseline", default=None, dest="obs_baseline",
                   help="pinned baseline rollup (a .rollup.jsonl sidecar "
                        "or a journal base) for the cross-run regression "
                        "watchdog (shifu.tpu.obs-baseline); fires "
                        "perf_regression when live windows exceed it by "
                        "the shifu.tpu.slo-regression ratio")
    return p


def main(argv: list[str] | None = None) -> int:
    argv = list(argv) if argv is not None else sys.argv[1:]
    args = build_parser().parse_args(argv)
    conf = Conf()
    for path in args.globalconfig:
        conf.add_resource(path)
    _retry_util.set_default_policy(_retry_util.policy_from_conf(conf))
    try:
        config = resolve_serve_config(args, conf)
        # observability plane (shifu.tpu.obs-* / --obs-journal): the serve
        # process journals reload/shed lifecycle events beside the
        # training planes' — one fleet timeline across all three
        from shifu_tensorflow_tpu.obs import install_obs, resolve_obs_config

        obs_cfg = resolve_obs_config(args, conf)
        # job correlation id: minted once here, shared by the whole
        # serve fleet (the supervisor re-execs workers with --obs-job),
        # so the merged journal can attribute events job-wide
        import uuid as _uuid

        job_id = args.obs_job or _uuid.uuid4().hex[:8]
        needs_supervisor = (
            config.workers > 1
            # an autoscale ceiling needs the supervisor even at one
            # worker: the policy loop and the spawn/drain actuators
            # live there
            or (config.workers_max or 0) > config.workers
        )
        if needs_supervisor and args.serve_worker_index is None:
            # multi-process scale-out: this invocation becomes the
            # supervisor, each scoring process is a re-exec of this CLI
            # with --worker-index set (and the SAME argv otherwise, so
            # every knob — conf layers included — reaches the workers).
            # Every worker loads the model onto the device, the shared
            # lane's siblings included, so the fleet is a CPU topology.
            from shifu_tensorflow_tpu.utils.jaxenv import (
                refuse_processes_sharing_a_chip,
            )

            refuse_processes_sharing_a_chip(
                max(config.workers, config.workers_max or 0),
                "--serve-workers")
            return _supervise(argv, config, obs_cfg, job_id)
        install_obs(obs_cfg, plane="serve",
                    worker_index=args.serve_worker_index, job=job_id)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2

    from shifu_tensorflow_tpu.serve.model_store import ArtifactCorrupt
    from shifu_tensorflow_tpu.serve.server import ScoringServer

    try:
        server = ScoringServer(config, warm=not args.no_warm,
                               worker_index=args.serve_worker_index,
                               lane_socket=args.lane_socket)
    except (ArtifactCorrupt, ValueError) as e:
        # single-model: corrupt initial artifact fails fast; multi:
        # a missing/empty models dir does (per-tenant corruption only
        # refuses THAT tenant — the fleet still starts)
        where = config.model_dir or config.models_dir
        print(f"refusing to serve {where}: {e}", file=sys.stderr)
        return 3

    import threading

    stop = threading.Event()
    stopping: list[int] = []

    def on_signal(signum, frame):
        # only flag from the handler: HTTPServer.shutdown() BLOCKS until
        # the serve loop exits, so calling it here (on the main thread,
        # which may be the serve loop) would deadlock — the main loop
        # below does the actual teardown
        stopping.append(signum)
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    from shifu_tensorflow_tpu.obs import journal as _obs_journal

    server.start()
    if server.multi is not None:
        admitted = server.multi.admitted()
        _obs_journal.emit("serve_start", plane="serve", port=server.port,
                          models=admitted)
        ready = {
            "state": "listening",
            "host": config.host,
            "port": server.port,
            "backend": config.backend,
            "models": sorted(server.multi.models()),
            "models_admitted": admitted,
        }
    else:
        model = server.store.current()
        _obs_journal.emit("serve_start", plane="serve", port=server.port,
                          model_epoch=model.epoch,
                          model_digest=model.digest[:12])
        ready = {
            "state": "listening",
            "host": config.host,
            "port": server.port,
            "backend": config.backend,
            "model_epoch": model.epoch,
            "model_digest": model.digest[:12],
            "model_verified": model.verified,
        }
    if args.serve_worker_index is not None:
        ready["worker_index"] = args.serve_worker_index
    if server.frame_port:
        ready["frame_port"] = server.frame_port
    print(json.dumps(ready), flush=True)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        server.close()
        counters = server.metrics.counters()
        if server.multi is not None:
            # the stopped line aggregates across tenants (the unrouted
            # surface only carries pre-resolution errors)
            for k, v in server.multi.aggregate_counters().items():
                counters[k] = counters.get(k, 0) + v
        _obs_journal.emit("serve_stop", plane="serve",
                          requests_total=counters.get("requests_total", 0),
                          shed_total=counters.get("shed_total", 0))
        print(json.dumps({
            "state": "stopped",
            "signal": stopping[0] if stopping else None,
            **{k: v for k, v in sorted(counters.items())},
        }), flush=True)
    return 0


class _Worker:
    """One supervised scoring process: the subprocess handle plus the
    reader thread that captures its stdout JSON lines (forwarded to the
    supervisor's stderr so the supervisor's OWN stdout keeps the
    one-listening-line / one-stopped-line machine-readable contract)."""

    def __init__(self, index: int, argv: list[str], port: int,
                 job_id: str | None = None):
        import subprocess
        import threading

        self.index = index
        self.listening = threading.Event()
        self.last_json: dict = {}
        # re-exec this CLI: original argv first, the supervisor's
        # overrides LAST (argparse last-wins) — the resolved port must
        # replace a possible "--port 0", the index marks the child as a
        # worker so it does not recurse into supervision, and --obs-job
        # pins the fleet-wide journal correlation id
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "shifu_tensorflow_tpu.serve", *argv,
             "--port", str(port), "--worker-index", str(index),
             *(["--obs-job", job_id] if job_id else [])],
            stdout=subprocess.PIPE,
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            line = raw.decode(errors="replace").rstrip()
            print(f"[serve.s{self.index}] {line}", file=sys.stderr,
                  flush=True)
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                self.last_json = obj
                if obj.get("state") == "listening":
                    self.listening.set()


def _probe_port(host: str):
    """Resolve ``--port 0`` for the fleet: every worker must bind the
    SAME concrete port, so the supervisor picks an ephemeral one.  The
    probe socket is returned STILL BOUND (SO_REUSEPORT, not listening):
    closing it before the workers bind would open a window for any
    other process to take the port — held bound, the kernel reserves it,
    workers' SO_REUSEPORT binds coexist with it, and a bound
    non-listening socket receives no connections.  The caller closes it
    once every worker is listening."""
    import socket

    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        s.bind((host, 0))
    except BaseException:
        s.close()
        raise
    return s, int(s.getsockname()[1])


def _start_supervisor_metrics(host: str, port: int, render):
    """Tiny /metrics-only HTTP listener on the supervisor process: the
    fleet's control-loop state (worker count, scale totals, restart-
    budget remaining + per-window burn) as stpu_serve_scale_* gauges —
    the sliding-window restart budget was previously invisible until it
    exhausted at rc 4.  Returns (server, bound_port) or (None, 0)."""
    import http.server
    import socketserver
    import threading

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path != "/metrics":
                self.send_error(404)
                return
            body = render().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # stdout carries the JSON contract
            pass

    class Srv(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    srv = Srv((host, port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, int(srv.server_address[1])


def _supervise(argv: list[str], config, obs_cfg,
               job_id: str | None = None) -> int:
    """Parent of ``--serve-workers N``: spawn N scoring processes
    sharing one SO_REUSEPORT port, restart crashes (bounded), propagate
    SIGTERM as a fleet-wide drain, and aggregate the final summary.

    With ``serve-workers-max > serve-workers`` (and an obs journal) it
    ALSO runs the elastic control loop (serve/autoscale.py): the policy
    reads the fleet's own journaled SLO/shed signals and the supervisor
    applies its decisions — spawn another SO_REUSEPORT worker
    (``scale_up``), SIGTERM-drain one back (``scale_down``), or roll the
    fleet onto new ``--tenant-weight`` overrides (``rebalance``) —
    journaling every decision with its triggering evidence, so a dead
    fleet's scaling story reconstructs from the files alone."""
    import signal
    import threading
    import time as _time

    from shifu_tensorflow_tpu.obs import install_obs
    from shifu_tensorflow_tpu.obs import journal as obs_journal

    # the supervisor journals fleet lifecycle at the BASE path; workers
    # write <base>.s<i> siblings (install_obs plane="serve") stamped
    # with the same job id
    install_obs(obs_cfg, plane="serve", job=job_id)
    n = config.workers
    probe = None
    if config.port:
        port = config.port
    else:
        probe, port = _probe_port(config.host)
    # the wire-frame listener is fleet-shared too: -1 (ephemeral) must
    # resolve to ONE concrete port every worker SO_REUSEPORT-binds, so
    # the supervisor reserves it exactly like the HTTP port above
    frame_probe = None
    frame_port = config.frame_port
    if frame_port == -1:
        frame_probe, frame_port = _probe_port(config.host)
    # shared dispatch lane: the supervisor mints the fleet's UNIX socket
    # path so every spawn — initial, crash restart, scale_up, rolling
    # rebalance — agrees on it.  Worker 0 binds it (the lane owner:
    # crash restarts reuse the index and scale_down always evicts the
    # HIGHEST index, so ownership never migrates); siblings connect.
    lane_socket = None
    if config.shared_lane:
        import os as _os
        import tempfile as _tempfile

        lane_socket = _os.path.join(
            _tempfile.gettempdir(),
            f"stpu-lane-{job_id or _os.getpid()}.sock")
    # a crash loop (bad artifact, port stolen, OOM) must fail the fleet,
    # not respawn forever — but the budget is over a sliding WINDOW, not
    # the fleet's lifetime: sporadic single-worker deaths spaced hours
    # apart are transients a long-lived fleet must absorb, while a
    # crashing artifact burns through the window's budget in seconds
    restart_budget = max(5, 2 * max(n, config.workers_max or n))
    restart_window_s = 600.0
    recent_restarts: list[float] = []  # monotonic ts, pruned to window
    restarts = 0  # lifetime total, for the journal + summary only

    def budget_remaining() -> int:
        # read-only on purpose: /metrics scrapes call this from HTTP
        # threads, and a prune-by-assignment here could race the main
        # loop's append and erase a just-burned restart.  Only the main
        # loop (the sole appender) prunes.
        now = _time.monotonic()
        live = sum(1 for t in recent_restarts
                   if now - t < restart_window_s)
        return max(0, restart_budget - live)

    # ---- elastic control loop ----
    autoscale = bool(config.workers_max and config.workers_max > n)
    policy = None
    signals = None
    if autoscale:
        if not obs_cfg.journal_path:
            print(f"autoscale disabled: serve-workers-max="
                  f"{config.workers_max} needs an obs journal "
                  f"(--obs-journal) — the SLO/shed signals live there",
                  file=sys.stderr)
            autoscale = False
        else:
            from shifu_tensorflow_tpu.serve.autoscale import (
                AutoscaleConfig,
                AutoscalePolicy,
                JournalSignals,
            )

            policy = AutoscalePolicy(AutoscaleConfig(
                workers_min=n,
                workers_max=config.workers_max,
                ticks=config.autoscale_ticks,
                recovery_ticks=config.autoscale_recovery_ticks,
                cooldown_s=config.autoscale_cooldown_s,
            ))
            signals = JournalSignals(obs_cfg.journal_path)
    scale_totals = {"scale_up": 0, "scale_down": 0, "rebalance": 0}

    def worker_argv() -> list[str]:
        # the policy OWNS the weight-override state (observe() applies
        # the backoff/floor there); every spawn — scale_up, crash
        # restart, rolling rebalance — reads the one copy, so the
        # policy's view and the workers' flags cannot drift
        extra: list[str] = []
        if frame_port:
            # replaces a possible "--frame-port -1" (argparse last-wins)
            extra += ["--frame-port", str(frame_port)]
        if lane_socket:
            extra += ["--lane-socket", lane_socket]
        if policy is not None:
            for m, w in sorted(policy.weight_overrides.items()):
                # appended LAST so argparse's append-and-last-wins merge
                # lets the override beat any operator-passed weight
                extra += ["--tenant-weight", f"{m}={w:g}"]
        return [*argv, *extra]

    stop = threading.Event()
    stopping: list[int] = []

    def on_signal(signum, frame):
        stopping.append(signum)
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    # every exit path — spawn failure, barrier failure, SIGTERM
    # mid-startup, budget exhaustion, normal drain — goes through the
    # finally below: the fleet is always reaped and the one
    # machine-readable "stopped" line always prints (a consumer tailing
    # stdout must never see a run end without it).  Spawning INSIDE the
    # try matters: if worker k's fork fails, workers 0..k-1 are already
    # listening on the shared port and must not be orphaned.
    workers: list[_Worker] = []
    expected_exits: set = set()  # _Worker objects we terminated on purpose
    # rebalance rolling restart, advanced ONE step per monitor tick (a
    # blocking roll would stall crash detection for minutes: an
    # unrelated worker dying at the start of the roll must still be
    # restarted within one poll)
    roll_queue: list[int] = []   # worker indices still to roll
    roll_in_flight: "_Worker | None" = None  # replacement warming up
    roll_old: "_Worker | None" = None        # retiring copy, still serving
    roll_deadline = 0.0
    rc: int | None = None
    drain_rc = 0
    metrics_srv = None

    def render_metrics() -> str:
        from shifu_tensorflow_tpu.obs.registry import MetricsRegistry

        reg = MetricsRegistry()
        reg.set_gauge("scale_workers", len(workers))
        reg.set_gauge("scale_workers_min", n)
        reg.set_gauge("scale_workers_max", config.workers_max or n)
        reg.set_gauge("scale_autoscale_enabled", int(autoscale))
        reg.set_gauge("scale_ups_total", scale_totals["scale_up"])
        reg.set_gauge("scale_downs_total", scale_totals["scale_down"])
        reg.set_gauge("scale_rebalances_total",
                      scale_totals["rebalance"])
        if policy is not None:
            reg.set_gauge("scale_cooldown_remaining_s",
                          round(policy.cooldown_remaining_s(), 3))
        reg.set_gauge("restart_budget", restart_budget)
        reg.set_gauge("restart_budget_remaining", budget_remaining())
        reg.set_gauge("restart_budget_burn_window",
                      restart_budget - budget_remaining())
        reg.set_gauge("restarts_total", restarts)
        return reg.render_prometheus("stpu_serve_")

    def apply_decision(decision) -> None:
        nonlocal workers
        ev = {
            "reason": decision.reason,
            "workers": len(workers),
            "budget_remaining": budget_remaining(),
            **{f"evidence_{k}": v
               for k, v in decision.evidence.items()},
        }
        if decision.action == "scale_up":
            idx = min(i for i in range(len(workers) + 1)
                      if i not in {w.index for w in workers})
            w = _Worker(idx, worker_argv(), port, job_id)
            workers.append(w)
            scale_totals["scale_up"] += 1
            obs_journal.emit("scale_up", plane="serve", index=idx,
                             to_workers=len(workers), **ev)
            print(f"autoscale: scale_up -> {len(workers)} workers "
                  f"(worker {idx}; {decision.reason})", file=sys.stderr)
        elif decision.action == "scale_down":
            victim = max(workers, key=lambda w: w.index)
            workers = [w for w in workers if w is not victim]
            expected_exits.add(victim)
            if victim.proc.poll() is None:
                victim.proc.terminate()
            scale_totals["scale_down"] += 1
            obs_journal.emit("scale_down", plane="serve",
                             index=victim.index,
                             to_workers=len(workers), **ev)
            print(f"autoscale: scale_down -> {len(workers)} workers "
                  f"(drained worker {victim.index}; {decision.reason})",
                  file=sys.stderr)
        elif decision.action == "rebalance":
            # the policy already recorded the new weight in its
            # weight_overrides (the single owner worker_argv reads)
            scale_totals["rebalance"] += 1
            obs_journal.emit("rebalance", plane="serve",
                             model=decision.model,
                             weight=decision.weight, **ev)
            print(f"autoscale: rebalance tenant {decision.model} "
                  f"weight -> {decision.weight:g} (rolling restart; "
                  f"{decision.reason})", file=sys.stderr)
            # rolling restart onto the new weights: enqueued, not run
            # inline — the monitor loop advances it one worker per tick
            # (waiting for each replacement to listen before the next),
            # so crash detection keeps its 0.2s poll during the roll
            roll_queue[:] = sorted(w.index for w in workers)

    try:
        for i in range(n):
            workers.append(_Worker(i, worker_argv(), port, job_id))
        obs_journal.emit("serve_fleet_start", plane="serve", port=port,
                         workers=n, workers_max=config.workers_max or n,
                         autoscale=autoscale)
        # listening barrier: every worker up (or one dead = fail fast —
        # a fleet that can only half-listen mis-advertises its capacity)
        deadline = _time.monotonic() + 180.0
        ready = True
        for w in workers:
            while ready and not w.listening.wait(0.2):
                if stop.is_set():
                    ready = False  # drained below; signal rc wins
                elif w.proc.poll() is not None:
                    print(f"serve worker {w.index} exited rc="
                          f"{w.proc.returncode} before listening",
                          file=sys.stderr)
                    rc = 3
                    ready = False
                elif _time.monotonic() > deadline:
                    print(f"serve workers not listening after 180s",
                          file=sys.stderr)
                    rc = 3
                    ready = False
            if not ready:
                break
        if probe is not None:
            # the workers hold the port now (or the fleet is failing);
            # release the reservation either way
            probe.close()
            probe = None
        if frame_probe is not None:
            frame_probe.close()
            frame_probe = None
        if ready:
            if config.supervisor_port:
                metrics_srv, mport = _start_supervisor_metrics(
                    config.host, config.supervisor_port, render_metrics)
                print(f"[supervisor] /metrics on port {mport}",
                      file=sys.stderr)
            print(json.dumps({
                "state": "listening", "host": config.host, "port": port,
                "workers": n,
                "workers_max": config.workers_max or n,
                "autoscale": autoscale,
                **({"frame_port": frame_port} if frame_port else {}),
                **({"shared_lane": True} if lane_socket else {}),
            }), flush=True)
            next_tick = _time.monotonic() + (
                config.autoscale_poll_s if autoscale else 0.0)
            while not stop.wait(0.2):
                for i, w in enumerate(list(workers)):
                    if w.proc.poll() is None:
                        continue
                    # unprompted exit = crash (clean or not, a scoring
                    # process has no business leaving on its own)
                    obs_journal.emit("serve_worker_exit", plane="serve",
                                     index=w.index, rc=w.proc.returncode,
                                     budget_remaining=budget_remaining())
                    if budget_remaining() <= 0:
                        print(f"serve worker {w.index} died (rc="
                              f"{w.proc.returncode}) with the restart "
                              f"budget ({restart_budget} per "
                              f"{restart_window_s:.0f}s) exhausted; "
                              "stopping the fleet", file=sys.stderr)
                        rc = 4
                        stop.set()
                        break
                    restarts += 1
                    now = _time.monotonic()
                    # prune HERE, the sole appender (budget_remaining
                    # is read-only so /metrics threads can't race this)
                    recent_restarts[:] = [
                        t for t in recent_restarts
                        if now - t < restart_window_s
                    ] + [now]
                    _time.sleep(0.5)  # a crashing artifact busy-loops
                    workers[workers.index(w)] = _Worker(
                        w.index, worker_argv(), port, job_id)
                    obs_journal.emit("serve_worker_restart", plane="serve",
                                     index=w.index, restarts=restarts,
                                     budget_remaining=budget_remaining())
                    print(f"restarted serve worker {w.index} "
                          f"({restarts}/{restart_budget})", file=sys.stderr)
                # reap expected (scaled-down / rolled) workers quietly
                for w in list(expected_exits):
                    if w.proc.poll() is not None:
                        w._reader.join(timeout=5.0)
                        expected_exits.discard(w)
                # advance the rolling rebalance, one index at a time:
                # make-before-break over SO_REUSEPORT — spawn the
                # replacement on the new weights, wait for it to
                # listen, only then drain the old copy, so capacity
                # never dips mid-roll
                if roll_in_flight is not None:
                    if roll_in_flight.listening.is_set():
                        if roll_old.proc.poll() is None:
                            roll_old.proc.terminate()
                        roll_in_flight = roll_old = None
                    elif (roll_in_flight.proc.poll() is not None
                          or _time.monotonic() > roll_deadline):
                        # replacement crashed or wedged before
                        # listening: the crash path above owns its
                        # respawn (worker_argv already carries the new
                        # weights) — drain the old copy and abandon
                        # the rest of the roll rather than churn the
                        # fleet behind a broken spawn
                        print(f"rebalance roll aborted: replacement "
                              f"for worker {roll_old.index} never "
                              "listened", file=sys.stderr)
                        if roll_old.proc.poll() is None:
                            roll_old.proc.terminate()
                        roll_queue.clear()
                        roll_in_flight = roll_old = None
                if (roll_in_flight is None and roll_queue
                        and not stop.is_set()):
                    idx = roll_queue.pop(0)
                    old = next((w for w in workers if w.index == idx),
                               None)
                    if old is not None:
                        repl = _Worker(idx, worker_argv(), port, job_id)
                        workers[workers.index(old)] = repl
                        # retired but STILL SERVING until the
                        # replacement listens; the finally drain and
                        # the reap loop both know expected_exits
                        expected_exits.add(old)
                        roll_old = old
                        roll_in_flight = repl
                        roll_deadline = _time.monotonic() + 120.0
                if (autoscale and not stop.is_set()
                        and _time.monotonic() >= next_tick):
                    next_tick = (_time.monotonic()
                                 + config.autoscale_poll_s)
                    decision = policy.observe(signals.poll(),
                                              len(workers))
                    if decision is not None:
                        apply_decision(decision)
    finally:
        if probe is not None:
            probe.close()
        if frame_probe is not None:
            frame_probe.close()
        if metrics_srv is not None:
            metrics_srv.shutdown()
        # fleet-wide drain: SIGTERM each live worker (it stops
        # admitting, finishes queued dispatches, prints its summary);
        # expected exits (scale_down victims, rolled workers) drain too
        drainees = [*workers, *expected_exits]
        for w in drainees:
            if w.proc.poll() is None:
                w.proc.terminate()
        for w in drainees:
            try:
                wrc = w.proc.wait(timeout=60.0)
            except Exception:
                w.proc.kill()
                wrc = w.proc.wait()
            # wrc == -SIGTERM is OUR drain signal landing before the
            # worker installed its graceful handler (e.g. a just-
            # restarted worker still importing jax) — an expected drain
            # outcome, not a failure (and never for expected exits)
            if wrc not in (0, -signal.SIGTERM) and w not in expected_exits:
                drain_rc = drain_rc or wrc
            # the worker's final "stopped" JSON line may still be in
            # the pipe when wait() returns — let the reader drain it
            # before the aggregate summary reads last_json
            w._reader.join(timeout=10.0)
        obs_journal.emit("serve_fleet_stop", plane="serve",
                         restarts=restarts,
                         scale_ups=scale_totals["scale_up"],
                         scale_downs=scale_totals["scale_down"],
                         rebalances=scale_totals["rebalance"])
        totals: dict[str, int] = {}
        per_worker = []
        for w in workers:
            summary = (w.last_json
                       if w.last_json.get("state") == "stopped" else {})
            per_worker.append({"index": w.index, **{
                k: v for k, v in summary.items() if k != "state"}})
            for k, v in summary.items():
                if isinstance(v, (int, float)) and k != "signal":
                    totals[k] = totals.get(k, 0) + v
        stopped = {
            "state": "stopped",
            "signal": stopping[0] if stopping else None,
            "workers": len(workers) or n,
            "restarts": restarts,
            **{k: v for k, v in sorted(totals.items())},
            "per_worker": per_worker,
        }
        if any(scale_totals.values()):
            # NOTE: totals above sum the FINAL workers' counters; rolled
            # or drained workers' requests live in the journal/rollup
            # (exact monotonic counters, PR-13), not this line
            stopped["autoscale"] = dict(scale_totals)
        if lane_socket is not None:
            # the owner unlinks on clean close; a SIGKILLed owner leaves
            # the socket file behind — sweep it so the next fleet's
            # owner does not bind-fail on the stale path
            try:
                _os.unlink(lane_socket)
            except OSError:
                pass
        print(json.dumps(stopped), flush=True)
    return rc if rc is not None else (drain_rc or 0)


if __name__ == "__main__":
    sys.exit(main())
