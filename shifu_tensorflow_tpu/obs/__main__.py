"""Observability CLI — render a journal as budgets, timelines, causal
traces, and a live dashboard.

    python -m shifu_tensorflow_tpu.obs summary --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs tail    --journal /tmp/job.jsonl -n 40
    python -m shifu_tensorflow_tpu.obs tail    --journal ... --follow
    python -m shifu_tensorflow_tpu.obs trace 4f2a91b0c3d4e5f6 --journal ...
    python -m shifu_tensorflow_tpu.obs trace 0:3 --journal ...
    python -m shifu_tensorflow_tpu.obs top     --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs fleet   --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs compile --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs mem     --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs report  --journal /tmp/job.jsonl
    python -m shifu_tensorflow_tpu.obs diff /tmp/runA.jsonl /tmp/runB.jsonl
    python -m shifu_tensorflow_tpu.obs diff --bench
    python -m shifu_tensorflow_tpu.obs profile --journal ... --request \
        --dir /tmp/dump --seconds 5
    python -m shifu_tensorflow_tpu.obs profile --phases /tmp/dump

Works on a finished or a RUNNING job: readers never lock writers, and a
torn final line (writer killed mid-event) is skipped, not fatal.  The
``--journal`` path is the base the job was configured with
(``shifu.tpu.obs-journal``); fleet-worker siblings (``.w<k>`` train,
``.s<k>`` serve) and rotations (``.N``) are discovered and merged by
``(ts, writer, seq)``.

``trace`` reconstructs ONE causal story: a request id (as minted at
serve ingress / supplied via ``X-Request-Id``) or one worker's epoch
(``worker:epoch``) across every plane that touched it — rendered on a
fleet-aligned clock when the writers stamped ``offset=`` estimates
(obs/fleet.ClockSync; ``--json`` keeps the raw wall clocks).  ``fleet``
renders the per-rank skew table and straggler excursions
(``straggler_detect``/``straggler_clear``) the coordinator journaled.  ``top`` is a
live terminal dashboard (``--once`` for CI) that tails the journals and
optionally scrapes ``/metrics`` URLs.  ``compile`` renders the compile
flight recorder's history (per-callable costs, signatures, recompile
storms — which signature churned and when the storm started and
cleared), ``mem`` the device-memory accountant's bucket split and
high-water marks, and ``profile`` lists journaled ``jax.profiler``
captures, (``--request``) asks the running fleet for one, or
(``--phases <dump dir>``) splits a capture's train step by the program's
own phase names and, "between epochs", the device's wait from one epoch's
last step to the next one's first by the host span open over it.  Every
reading subcommand takes ``--json`` for machine-readable output —
scripts and the autoscaling supervisor must not screen-scrape the
human renderer.

stdlib-only and jax-free: this must run on an operator's laptop against
a journal scp'd out of a dead fleet.  (``profile --phases`` alone reads
the dump with ``jax.profiler.ProfileData``, imported inside the call.)
"""

from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from collections import defaultdict

from shifu_tensorflow_tpu.obs.journal import journal_files, read_events
from shifu_tensorflow_tpu.obs.rollup import (
    read_rollups,
    reconstruct,
    rollup_files,
)

#: stable top-level schema tags on every machine-readable document, so
#: downstream tooling can detect format drift instead of guessing from
#: key shapes (pinned by test)
SUMMARY_SCHEMA = "stpu.obs.summary/1"
REPORT_SCHEMA = "stpu.obs.report/1"
DIFF_SCHEMA = "stpu.obs.diff/1"

#: events that are high-signal fleet lifecycle (the timeline keeps every
#: event, but these get rendered even under --compact aggregation)
_STEP_PHASES = ("infeed", "host", "dispatch", "block")

#: per-dispatch request records — high-volume, elided from the fleet
#: timeline (trace/top still read them)
_BULK_EVENTS = ("step_breakdown", "serve_batch")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shifu_tensorflow_tpu.obs",
        description="Inspect a shifu.tpu.obs-journal event journal.",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    tail = sub.add_parser("tail", help="print the last N events")
    tail.add_argument("--journal", required=True,
                      help="journal base path (shifu.tpu.obs-journal)")
    tail.add_argument("-n", type=int, default=20, dest="count",
                      help="events to show (default 20)")
    tail.add_argument("--json", action="store_true", dest="as_json",
                      help="raw events, one JSON object per line")
    tail.add_argument("--follow", "-f", action="store_true",
                      help="live-tail: keep polling the journals and "
                           "print events as they land (rotation-aware; "
                           "re-reads only growing files)")
    tail.add_argument("--interval", type=float, default=1.0,
                      help="--follow poll seconds (default 1)")

    summ = sub.add_parser(
        "summary",
        help="per-step time budget + serve plane + fleet event timeline",
    )
    summ.add_argument("--journal", required=True,
                      help="journal base path (shifu.tpu.obs-journal)")
    summ.add_argument("--timeline-limit", type=int, default=200,
                      help="max timeline rows (default 200; 0 = all)")
    summ.add_argument("--json", action="store_true", dest="as_json",
                      help="machine-readable summary document")

    trace = sub.add_parser(
        "trace",
        help="causal timeline of one request (rid) or one step "
             "(worker:epoch) across every plane",
    )
    trace.add_argument("id",
                       help="a request correlation id (X-Request-Id / "
                            "minted rid), or worker:epoch (e.g. 0:3)")
    trace.add_argument("--journal", required=True,
                       help="journal base path (shifu.tpu.obs-journal)")
    trace.add_argument("--json", action="store_true", dest="as_json",
                       help="matching events, one JSON object per line")

    fleet = sub.add_parser(
        "fleet",
        help="per-rank skew table + straggler excursions (fleet_skew / "
             "straggler_detect / straggler_clear events)",
    )
    fleet.add_argument("--journal", required=True,
                       help="journal base path (shifu.tpu.obs-journal)")
    fleet.add_argument("--json", action="store_true", dest="as_json",
                       help="machine-readable fleet document")

    data = sub.add_parser(
        "data",
        help="per-feature train-baseline-vs-live-serve table, drift "
             "excursions (data_drift / data_drift_clear events)",
    )
    data.add_argument("--journal", required=True,
                      help="journal base path (shifu.tpu.obs-journal)")
    data.add_argument("--bundle", action="append", default=[],
                      dest="bundles",
                      help="an exported bundle dir (or a multi-tenant "
                           "models dir) whose feature_stats.json is the "
                           "train baseline (repeatable); without it the "
                           "baseline comes from journaled train-plane "
                           "data_stats events")
    data.add_argument("--features", type=int, default=20,
                      help="max feature rows per model, highest drift "
                           "score first (default 20; 0 = all)")
    data.add_argument("--json", action="store_true", dest="as_json",
                      help="machine-readable data document")

    rep = sub.add_parser(
        "report",
        help="one-run operator report from the rotation-exempt rollup "
             "sidecars: totals, per-tenant cost, utilization, "
             "excursions — survives journal rotation",
    )
    rep.add_argument("--journal", required=True,
                     help="journal base path (shifu.tpu.obs-journal) or "
                          "one .rollup.jsonl sidecar")
    rep.add_argument("--json", action="store_true", dest="as_json",
                     help="machine-readable report document")

    diff = sub.add_parser(
        "diff",
        help="compare two runs' rollup archives (noise-aware), or "
             "--bench: the last two BENCH_HISTORY.jsonl entries",
    )
    diff.add_argument("runs", nargs="*",
                      help="two journal bases (or .rollup.jsonl "
                           "sidecars); with --bench, at most one bench "
                           "name to filter the history by")
    diff.add_argument("--bench", action="store_true",
                      help="diff the last two BENCH_HISTORY.jsonl "
                           "entries of one bench instead of rollups")
    diff.add_argument("--history", default="BENCH_HISTORY.jsonl",
                      help="--bench history file "
                           "(default ./BENCH_HISTORY.jsonl)")
    diff.add_argument("--threshold", type=float, default=0.02,
                      help="relative-change floor below which a delta "
                           "is noise (default 0.02 = 2%%)")
    diff.add_argument("--json", action="store_true", dest="as_json",
                      help="machine-readable diff document")

    comp = sub.add_parser(
        "compile",
        help="compile flight-recorder history: per-callable compile "
             "costs, signatures, and recompile-storm excursions",
    )
    comp.add_argument("--journal", required=True,
                      help="journal base path (shifu.tpu.obs-journal)")
    comp.add_argument("--json", action="store_true", dest="as_json",
                      help="machine-readable compile document")

    mem = sub.add_parser(
        "mem",
        help="device-memory accounting: per-worker bucket split, "
             "high-water marks, per-model device bytes",
    )
    mem.add_argument("--journal", required=True,
                     help="journal base path (shifu.tpu.obs-journal)")
    mem.add_argument("--json", action="store_true", dest="as_json",
                     help="machine-readable memory document")

    prof = sub.add_parser(
        "profile",
        help="list journaled jax.profiler captures, --request one from "
             "the running fleet, or split a dump's train step by phase",
    )
    prof.add_argument("--journal",
                      help="journal base path (shifu.tpu.obs-journal)")
    prof.add_argument("--phases", metavar="DUMP_DIR",
                      help="reduce the newest capture under DUMP_DIR: the "
                           "train step's device time by the program's "
                           "named scopes, the host spans it holds, and "
                           "the device's idle between epochs by span")
    prof.add_argument("--request", action="store_true",
                      help="write a capture trigger beside the journal; "
                           "the fleet's next obs tick starts the window")
    prof.add_argument("--dir", dest="out_dir",
                      help="dump directory for --request")
    prof.add_argument("--seconds", type=float, default=5.0,
                      help="capture window length for --request "
                           "(default 5)")
    prof.add_argument("--worker", type=int, default=None,
                      help="pin --request to one worker index "
                           "(default: first poller wins)")
    prof.add_argument("--json", action="store_true", dest="as_json",
                      help="capture events, one JSON object per line")

    score = sub.add_parser(
        "score",
        help="reconstruct a bulk scoring job from its journal: shard "
             "commit state, per-worker commits, lease reclaims/"
             "duplicates, row totals",
    )
    score.add_argument("--journal", required=True,
                       help="journal base path the score driver wrote "
                            "(`score run --journal ...`)")
    score.add_argument("--json", action="store_true", dest="as_json",
                       help="machine-readable score-job document")

    life = sub.add_parser(
        "lifecycle",
        help="reconstruct closed-loop model lifecycle cycles from the "
             "journal: trigger evidence, retrain, shadow, ramp steps, "
             "the promote/rollback verdict and its latency",
    )
    life.add_argument("--journal", required=True,
                      help="journal base path shared by the serve fleet "
                           "and the lifecycle controller (.l writer)")
    life.add_argument("--model", default=None,
                      help="only cycles managing this tenant")
    life.add_argument("--json", action="store_true", dest="as_json",
                      help="machine-readable lifecycle document")

    top = sub.add_parser(
        "top",
        help="live dashboard: tail the journals (+ optionally scrape "
             "/metrics) and render fleet state",
    )
    top.add_argument("--journal", required=True,
                     help="journal base path (shifu.tpu.obs-journal)")
    top.add_argument("--metrics-url", action="append", default=[],
                     dest="metrics_urls",
                     help="a /metrics URL to scrape each refresh "
                          "(repeatable); failures are tolerated — the "
                          "journal alone still renders")
    top.add_argument("--interval", type=float, default=2.0,
                     help="refresh seconds (default 2)")
    top.add_argument("--once", action="store_true",
                     help="render one frame and exit (CI / dead fleets)")
    return p


# ---- shared rendering ----

def _fmt_event(ev: dict, t0: float) -> str:
    ts = ev.get("ts", t0)
    plane = ev.get("plane", "?")
    worker = ev.get("worker")
    who = f"{plane} w{worker}" if worker is not None else plane
    # offset is the writer's clock stamp, not event payload — rendered
    # timelines use it for alignment (trace) or the fleet table; the
    # raw value stays in --json output
    skip = {"ts", "event", "plane", "worker", "seq", "job", "offset"}
    detail = " ".join(
        f"{k}={_short(v)}" for k, v in ev.items() if k not in skip
    )
    return f"+{ts - t0:10.3f}s  {who:<14} {ev.get('event', '?'):<22} {detail}"


def _short(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    s = str(v)
    return s if len(s) <= 60 else s[:57] + "..."


def cmd_tail(args) -> int:
    if getattr(args, "follow", False):
        return _tail_follow(args)
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    shown = events[-args.count:]
    if args.as_json:
        for ev in shown:
            print(json.dumps(ev, separators=(",", ":"), default=str))
        return 0
    t0 = events[0].get("ts", 0.0)
    for ev in shown:
        print(_fmt_event(ev, t0))
    return 0


def _event_key(ev: dict) -> tuple:
    """Identity of one journal event for follow-mode dedup: (ts, writer
    coordinates, seq) — unique per event by the Journal's contract (one
    monotonic seq per writer).  Bounded memory: the journal itself is
    rotation-bounded, so the set of live keys is too."""
    return (ev.get("ts", 0.0), ev.get("plane"), ev.get("worker"),
            ev.get("seq"), ev.get("event"))


def _tail_follow(args) -> int:
    """Live tail: poll the journal set, print what's new.  Reuses the
    read_events parse cache, so each poll re-parses only files whose
    (size, mtime, inode) changed — the growing active file, not the
    whole rotation set (satellite of the PR-7 `obs top` cache)."""
    cache: dict = {}
    seen: set = set()
    t0 = None
    first = True
    while True:
        events = read_events(args.journal, cache=cache)
        if events and t0 is None:
            t0 = events[0].get("ts", 0.0)
        new = [ev for ev in events if _event_key(ev) not in seen]
        if first:
            # start like plain tail: the last N events, then the stream
            new = new[-args.count:]
            seen.update(_event_key(ev) for ev in events)
            first = False
        else:
            seen.update(_event_key(ev) for ev in new)
            if events:
                # prune keys that rotated out of the journal set — the
                # seen-set tracks the live window, not the whole run
                min_ts = events[0].get("ts", 0.0)
                if len(seen) > 4 * len(events):
                    seen = {k for k in seen if k[0] >= min_ts}
        for ev in new:
            if args.as_json:
                print(json.dumps(ev, separators=(",", ":"), default=str),
                      flush=True)
            else:
                print(_fmt_event(ev, t0 or 0.0), flush=True)
        try:
            time.sleep(max(0.1, args.interval))
        except KeyboardInterrupt:
            return 0


# ---- step budget (data + renderer) ----

def _budget_data(events: list[dict]) -> dict:
    """Aggregate step_breakdown (+ matching epoch) events into one
    budget record per worker: where each step's wall clock went."""
    acc: dict = defaultdict(lambda: {
        "epochs": 0, "steps": 0,
        "infeed_wait": 0.0, "infeed_put": 0.0, "host_produce": 0.0,
        **{p: 0.0 for p in _STEP_PHASES}, "spans": defaultdict(
            lambda: {"count": 0, "total_s": 0.0}),
    })
    epoch_wall: dict = defaultdict(float)  # worker -> train wall seconds
    for ev in events:
        w = ev.get("worker", 0) or 0
        if ev.get("event") == "step_breakdown":
            a = acc[w]
            a["epochs"] += 1
            a["steps"] += int(ev.get("steps", 0))
            for p in _STEP_PHASES:
                a[p] += float(ev.get(f"{p}_s", 0.0))
            a["infeed_wait"] += float(ev.get("infeed_wait_s", 0.0))
            a["infeed_put"] += float(ev.get("infeed_put_s", 0.0))
            a["host_produce"] += float(ev.get("host_produce_s", 0.0))
            for name, s in (ev.get("spans") or {}).items():
                a["spans"][name]["count"] += int(s.get("count", 0))
                a["spans"][name]["total_s"] += float(s.get("total_s", 0.0))
        elif ev.get("event") == "epoch":
            epoch_wall[w] += float(ev.get("train_time_s", 0.0))
    workers = {}
    for w in sorted(acc):
        a = acc[w]
        phase_total = sum(a[p] for p in _STEP_PHASES)
        wall = epoch_wall.get(w, 0.0) or phase_total
        denom = max(wall, phase_total) or 1.0
        other = max(0.0, denom - phase_total)
        workers[w] = {
            "epochs": a["epochs"], "steps": a["steps"],
            "wall_s": round(denom, 6),
            "step_ms": round(denom / a["steps"] * 1000.0, 3)
            if a["steps"] else 0.0,
            "pct": {
                **{p: round(100.0 * a[p] / denom, 1)
                   for p in _STEP_PHASES},
                "other": round(100.0 * other / denom, 1),
            },
            "infeed_wait_pct": round(100.0 * a["infeed_wait"] / denom, 1),
            "infeed_put_pct": round(100.0 * a["infeed_put"] / denom, 1),
            "host_produce_pct": round(100.0 * a["host_produce"] / denom, 1),
            "spans": {k: dict(v) for k, v in sorted(a["spans"].items())},
        }
    return workers


def _render_budget(workers: dict) -> list[str]:
    if not workers:
        return ["  (no step_breakdown events — was the run traced? "
                "set shifu.tpu.obs-enabled=true / --obs)"]
    lines = [
        "  worker  epochs  steps  step_ms   infeed%   host%  dispatch%"
        "  block%  other%"
    ]
    for w, a in workers.items():
        pct = a["pct"]
        lines.append(
            f"  {w:<7} {a['epochs']:<7} {a['steps']:<6} {a['step_ms']:<9.3f}"
            f" {pct['infeed']:<9.1f} {pct['host']:<6.1f}"
            f" {pct['dispatch']:<10.1f} {pct['block']:<7.1f}"
            f" {pct['other']:.1f}"
        )
        if a["infeed_wait_pct"] or a["infeed_put_pct"] \
                or a["host_produce_pct"]:
            # pipelined infeed: wait is the consumer's stall (part of the
            # infeed%% above); put and host-produce are work on the put
            # thread, overlapped with dispatch — wait-heavy means STARVED
            # (widen the ingest pipeline), put-heavy means PLACEMENT-SLOW
            # (transfer/pad cost; see docs/ingest.md)
            line = (
                f"          infeed split: wait "
                f"{a['infeed_wait_pct']:.1f}% of wall, put "
                f"{a['infeed_put_pct']:.1f}% (overlapped)"
            )
            if a["host_produce_pct"]:
                line += (f", host produce "
                         f"{a['host_produce_pct']:.1f}%"
                         f" (overlapped)")
            lines.append(line)
        span_bits = [
            f"{name} {s['count']}x {s['total_s']:.3f}s"
            for name, s in a["spans"].items()
        ]
        if span_bits:
            lines.append(f"          spans: {', '.join(span_bits)}")
    return lines


# ---- serve plane (data + renderer) ----

def _serve_data(events: list[dict]) -> dict:
    """Aggregate the serve plane's lifecycle events: request volume and
    rate per scoring process, shed pressure, reload outcomes, and the
    fleet split — what the SO_REUSEPORT fleet's per-process /metrics
    cannot show in one place."""
    serve = [e for e in events if e.get("plane") == "serve"]
    if not serve:
        return {}
    per: dict = defaultdict(lambda: {
        "start_ts": None, "stop_ts": None, "requests": None,
        "reloads": 0, "refused": 0, "shed_events": 0, "shed_total": 0,
        # multi-tenant shed events carry per-TENANT counters: the
        # worker total is the SUM of per-model maxima, not a max
        # across tenants (which would report only the hottest one)
        "_shed_max": {},
    })
    fleet = {"workers": None, "restarts": 0}
    # autoscaler decisions (scale_up / scale_down / rebalance), in
    # journal order with their evidence — the dead-fleet reconstruction
    # of the supervisor's control loop
    autoscale: list = []
    # per-MODEL aggregation (multi-tenant serve: events carry a `model`
    # dimension) — rows/batches from serve_batch, tenancy lifecycle
    # from model_admit/model_evict/model_admit_failed.  Rows
    # materialize ONLY in branches that count something: an event kind
    # this table doesn't track must not mint an all-zero row that
    # reads as "present and idle".
    models: dict = defaultdict(lambda: {
        "rows": 0, "bucket_rows": 0, "batches": 0, "sheds": 0,
        "reloads": 0, "refused": 0, "admits": 0, "evicts": 0,
    })
    # shared dispatch lane lifecycle (lane_owner / lane_degraded /
    # lane_restored), in journal order — the dead-fleet reconstruction
    # of who owned fleet dispatch and when siblings fell back to
    # private dispatch
    lane: list = []

    def mm_of(ev):
        mname = ev.get("model")
        return models[mname] if mname else None

    for ev in serve:
        kind = ev.get("event")
        w = ev.get("worker")
        a = per[w]
        if kind == "serve_start":
            a["start_ts"] = ev.get("ts")
        elif kind == "serve_stop":
            a["stop_ts"] = ev.get("ts")
            a["requests"] = ev.get("requests_total")
            a["shed_total"] = max(a["shed_total"],
                                  int(ev.get("shed_total", 0) or 0))
        elif kind == "reload":
            a["reloads"] += 1
            mm = mm_of(ev)
            if mm is not None:
                mm["reloads"] += 1
        elif kind in ("reload_refused", "model_admit_failed"):
            a["refused"] += 1
            mm = mm_of(ev)
            if mm is not None:
                mm["refused"] += 1
        elif kind == "shed":
            a["shed_events"] += 1
            key = ev.get("model")
            a["_shed_max"][key] = max(
                a["_shed_max"].get(key, 0),
                int(ev.get("shed_total", 0) or 0))
            mm = mm_of(ev)
            if mm is not None:
                mm["sheds"] += 1
        elif kind == "serve_batch":
            mm = mm_of(ev)
            if mm is not None:
                mm["batches"] += 1
                mm["rows"] += int(ev.get("rows", 0) or 0)
                # bucket = rows the DEVICE paid (useful + ladder
                # padding); rows/bucket_rows is the occupancy column
                mm["bucket_rows"] += int(
                    ev.get("bucket", ev.get("rows", 0)) or 0)
        elif kind == "model_admit":
            mm = mm_of(ev)
            if mm is not None:
                mm["admits"] += 1
        elif kind == "model_evict":
            mm = mm_of(ev)
            if mm is not None:
                mm["evicts"] += 1
        elif kind == "serve_fleet_start":
            fleet["workers"] = ev.get("workers")
            fleet["workers_max"] = ev.get("workers_max")
            fleet["autoscale"] = ev.get("autoscale")
        elif kind in ("serve_worker_restart",):
            fleet["restarts"] += 1
        elif kind in ("scale_up", "scale_down", "rebalance"):
            autoscale.append({
                "action": kind,
                "ts": ev.get("ts"),
                "to_workers": ev.get("to_workers"),
                "model": ev.get("model"),
                "weight": ev.get("weight"),
                "reason": ev.get("reason"),
            })
        elif kind in ("lane_owner", "lane_degraded", "lane_restored"):
            lane.append({
                "event": kind,
                "ts": ev.get("ts"),
                "worker": w,
                "redispatched": ev.get("redispatched"),
                "connects": ev.get("connects"),
            })
    rows = {}
    for w, a in per.items():
        if (a["start_ts"] is None and a["requests"] is None
                and not a["reloads"] and not a["refused"]
                and not a["shed_events"]):
            continue
        rate = None
        if (a["requests"] is not None and a["start_ts"] is not None
                and a["stop_ts"] is not None
                and a["stop_ts"] > a["start_ts"]):
            rate = round(a["requests"] / (a["stop_ts"] - a["start_ts"]), 1)
        # the stop line's counter is the worker-wide aggregate; shed
        # events each carry one tenant's counter, so their per-model
        # maxima SUM to the worker total — take whichever saw more
        a["shed_total"] = max(a["shed_total"],
                              sum(a["_shed_max"].values()))
        rows[w] = {**{k: v for k, v in a.items()
                      if k not in ("start_ts", "stop_ts", "_shed_max")},
                   "req_per_s": rate}
    return {"fleet": fleet, "workers": rows,
            "models": {m: dict(v) for m, v in sorted(models.items())},
            "autoscale": autoscale, "lane": lane}


def _render_serve(data: dict) -> list[str]:
    if not data:
        return []
    fleet, rows = data["fleet"], data["workers"]
    models = data.get("models") or {}
    lines = []
    if fleet["workers"]:
        line = f"  fleet: {fleet['workers']} workers"
        if fleet.get("autoscale") and fleet.get("workers_max"):
            line += f" (autoscaling up to {fleet['workers_max']})"
        if fleet["restarts"]:
            line += f", {fleet['restarts']} restart(s)"
        lines.append(line)
    for d in data.get("autoscale") or []:
        if d["action"] == "rebalance":
            what = (f"tenant {d['model']} weight -> {d['weight']:g}"
                    if d.get("weight") is not None else "weights")
        else:
            what = f"-> {d['to_workers']} workers"
        lines.append(f"  autoscale: {d['action']} {what}"
                     + (f"  ({d['reason']})" if d.get("reason") else ""))
    for d in data.get("lane") or []:
        # shared dispatch-lane lifecycle in journal order: owner bind,
        # sibling joins, degradations — who owned fleet dispatch when
        who = "-" if d.get("worker") is None else str(d["worker"])
        if d["event"] == "lane_owner":
            what = "owns the fleet dispatch lane"
        elif d["event"] == "lane_restored":
            what = (f"joined the lane (connect "
                    f"#{d.get('connects') or '?'})")
        else:
            what = (f"lane degraded -> private dispatch "
                    f"({d.get('redispatched') or 0} in-flight "
                    f"re-dispatched)")
        lines.append(f"  lane: worker {who} {what}")
    if not rows:
        # a fleet whose workers all died before serve_start (crash
        # loop: bad artifact, stolen port) has no per-worker rows, but
        # the fleet line above — workers + restart count — is exactly
        # what the operator diagnosing it needs; never hide it
        if fleet["workers"]:
            lines.append("  (no worker reached serve_start)")
        return lines
    lines.append(
        "  worker  requests  req/s    shed   reloads  refused")
    for w in sorted(rows, key=lambda k: (k is None, k)):
        a = rows[w]
        who = "-" if w is None else str(w)
        reqs = a["requests"]
        rate = "" if a["req_per_s"] is None else f"{a['req_per_s']}"
        lines.append(
            f"  {who:<7} {('?' if reqs is None else reqs):<9} "
            f"{rate or '?':<8} {a['shed_total']:<6} {a['reloads']:<8} "
            f"{a['refused']}"
        )
    if models:
        # the multi-tenant split: which model the rows/sheds/tenancy
        # churn belong to — journal-only (the per-process /metrics
        # can't aggregate a fleet; this table can)
        lines.append(
            "  model          rows     batches  shed-ev  reloads  "
            "refused  admits  evicts  occup")
        for m, v in models.items():
            # useful rows / device (bucket) rows across this model's
            # journaled dispatches — the fleet-coalescing health number
            # (fragmented fleets pad more, so this falls)
            occ = (f"{v['rows'] / v['bucket_rows']:.3f}"
                   if v.get("bucket_rows") else "-")
            lines.append(
                f"  {m:<14} {v['rows']:<8} {v['batches']:<8} "
                f"{v['sheds']:<8} {v['reloads']:<8} {v['refused']:<8} "
                f"{v['admits']:<7} {v['evicts']:<7} {occ}"
            )
    return lines


# ---- slo plane (data + renderer) ----

def _slo_data(events: list[dict]) -> dict:
    """Last-known SLO state per signal from the journaled breach /
    recover / anomaly transitions (obs/slo.py)."""
    signals: dict = {}
    for ev in events:
        kind = ev.get("event")
        if kind not in ("slo_breach", "slo_recover", "slo_anomaly"):
            continue
        name = ev.get("signal", "?")
        s = signals.setdefault(name, {
            "breaches": 0, "recovers": 0, "anomalies": 0,
            "breached": False, "last_value": None, "target": None,
            "last_ts": None, "worker": ev.get("worker"),
        })
        s["last_ts"] = ev.get("ts")
        s["last_value"] = ev.get("value")
        if kind == "slo_breach":
            s["breaches"] += 1
            s["breached"] = True
            s["target"] = ev.get("target")
            s["window"] = ev.get("window")
        elif kind == "slo_recover":
            s["recovers"] += 1
            s["breached"] = False
            s["target"] = ev.get("target")
            s["breach_s"] = ev.get("breach_s")
        else:
            s["anomalies"] += 1
            s["last_z"] = ev.get("z")
    return signals


def _render_slo(signals: dict, t0: float) -> list[str]:
    if not signals:
        return []
    lines = ["  signal            state      value      target   "
             "breaches  anomalies"]
    for name in sorted(signals):
        s = signals[name]
        state = "BREACHED" if s["breached"] else "ok"
        val = "?" if s["last_value"] is None else f"{s['last_value']:.4g}"
        tgt = "-" if not s.get("target") else f"{s['target']:.4g}"
        lines.append(
            f"  {name:<17} {state:<10} {val:<10} {tgt:<8} "
            f"{s['breaches']:<9} {s['anomalies']}"
        )
    return lines


# ---- summary ----

def _build_summary(base: str, cache: dict | None = None) -> dict | None:
    files = journal_files(base)
    events = read_events(base, cache=cache)
    if not events:
        return None
    t0 = events[0].get("ts", 0.0)
    t1 = events[-1].get("ts", t0)
    counts: dict = defaultdict(int)
    for ev in events:
        counts[ev.get("event", "?")] += 1
    return {
        "schema": SUMMARY_SCHEMA,
        "journal": base,
        "files": files,
        "events": len(events),
        "t0": t0,
        "t1": t1,
        "span_s": round(t1 - t0, 3),
        "jobs": sorted({e["job"] for e in events if "job" in e}),
        "counts": dict(sorted(counts.items())),
        "budget": _budget_data(events),
        "serve": _serve_data(events),
        "slo": _slo_data(events),
        "fleet": _fleet_data(events),
        "data": _data_summary(events),
        "mesh": _mesh_data(events),
        "_events": events,  # stripped before --json output
    }


def _mesh_data(events: list[dict]) -> dict:
    """The fleet's resolved device-mesh layout: the LAST ``mesh`` event
    per worker (each worker journals one at start; a fleet restart's
    re-journal supersedes) — rendered as one summary line so an
    operator reads the data×model split without grepping the journal."""
    per_worker: dict = {}
    for ev in events:
        if ev.get("event") != "mesh":
            continue
        per_worker[ev.get("worker")] = {
            "shape": ev.get("shape"),
            "coord": ev.get("coord"),
            "fingerprint": ev.get("fingerprint"),
            "devices": ev.get("devices"),
        }
    if not per_worker:
        return {}
    any_rec = next(iter(per_worker.values()))
    return {
        "shape": any_rec.get("shape"),
        "fingerprint": any_rec.get("fingerprint"),
        "devices": any_rec.get("devices"),
        "workers": {
            str(w): rec.get("coord")
            for w, rec in sorted(
                per_worker.items(), key=lambda kv: str(kv[0]))
        },
    }


def _render_mesh(m: dict) -> list[str]:
    if not m:
        return []
    shape = m.get("shape") or {}
    spec = ",".join(f"{n}:{s}" for n, s in shape.items()) or "?"
    line = (f"  mesh {spec} ({m.get('devices', '?')} device(s), "
            f"fingerprint {m.get('fingerprint', '?')})")
    coords = {w: c for w, c in (m.get("workers") or {}).items()
              if c is not None}
    out = [line]
    if coords:
        out.append("  rank coordinates: " + ", ".join(
            f"{w}→({', '.join(f'{k}={v}' for k, v in c.items())})"
            for w, c in sorted(coords.items())))
    return out


def _data_summary(events: list[dict]) -> dict:
    """The data leg's compact summary (the full per-feature table is
    ``obs data``'s job): per-model live rows + drift score, train
    sketch presence, excursion counts."""
    d = _data_data(events)
    if not d:
        return {}
    return {
        "train_workers": sorted(d["train"], key=lambda w: (w is None, w)),
        "models": {
            m: {
                "live_rows": v["stats"].get("rows"),
                "drift_score": v.get("drift_score"),
                "drifting": v.get("drifting") or 0,
            }
            for m, v in d["serve"].items()
        },
        "excursions": len(d["excursions"]),
        "open_excursions": sum(
            1 for e in d["excursions"] if e["clear_ts"] is None),
    }


def _render_data_brief(d: dict) -> list[str]:
    if not d:
        return []
    lines = []
    for m, v in sorted(d["models"].items()):
        score = v.get("drift_score")
        lines.append(
            f"  model {m}: live {v['live_rows']} rows"
            + (f", drift score {score:.3g}" if score is not None else "")
            + (f", {v['drifting']} feature(s) DRIFTING"
               if v["drifting"] else "")
        )
    if d["train_workers"]:
        lines.append(f"  train sketches from worker(s) "
                     f"{d['train_workers']}")
    lines.append(f"  drift excursions: {d['excursions']} "
                 f"({d['open_excursions']} open)  — `obs data` for the "
                 f"per-feature table")
    return lines


def cmd_summary(args) -> int:
    data = _build_summary(args.journal)
    if data is None:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    events = data.pop("_events")
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0
    t0 = data["t0"]
    print(f"journal {args.journal}: {data['events']} events in "
          f"{len(data['files'])} file(s), spanning {data['span_s']:.1f}s"
          + (f"  [job {', '.join(data['jobs'])}]" if data["jobs"] else ""))
    print("  " + ", ".join(
        f"{name} x{n}" for name, n in data["counts"].items()))
    print()
    mesh_lines = _render_mesh(data.get("mesh") or {})
    if mesh_lines:
        print("device mesh")
        for line in mesh_lines:
            print(line)
        print()
    print("per-step time budget")
    for line in _render_budget(data["budget"]):
        print(line)
    print()
    serve_lines = _render_serve(data["serve"])
    if serve_lines:
        print("serve plane")
        for line in serve_lines:
            print(line)
        print()
    slo_lines = _render_slo(data["slo"], t0)
    if slo_lines:
        print("slo")
        for line in slo_lines:
            print(line)
        print()
    fleet_lines = _render_fleet(data["fleet"], t0)
    if fleet_lines:
        print("fleet skew")
        for line in fleet_lines:
            print(line)
        print()
    data_lines = _render_data_brief(data["data"])
    if data_lines:
        print("data plane")
        for line in data_lines:
            print(line)
        print()
    print("fleet timeline")
    timeline = [e for e in events if e.get("event") not in _BULK_EVENTS]
    limit = args.timeline_limit
    shown = timeline if not limit else timeline[-limit:]
    if len(shown) < len(timeline):
        print(f"  ... {len(timeline) - len(shown)} earlier events elided "
              f"(--timeline-limit {limit})")
    for ev in shown:
        print(" " + _fmt_event(ev, t0))
    return 0


# ---- trace ----

_COORD_RE = re.compile(r"^(\d+):(\d+)$")


def _match_rid(ev: dict, rid: str) -> bool:
    if ev.get("rid") == rid:
        return True
    rids = ev.get("rids")
    return isinstance(rids, list) and rid in rids


def _match_step(ev: dict, worker: int, epoch: int) -> bool:
    if ev.get("epoch") != epoch:
        return False
    w = ev.get("worker")
    # coordinator-plane records of the same epoch (epoch_summary,
    # rollback directives) carry no worker, or the arbitrating one —
    # they belong to every worker's story for that epoch
    return w is None or w == worker or ev.get("plane") == "coordinator"


def cmd_trace(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r}", file=sys.stderr)
        return 1
    m = _COORD_RE.match(args.id)
    if m:
        worker, epoch = int(m.group(1)), int(m.group(2))
        sel = [e for e in events if _match_step(e, worker, epoch)]
        what = f"worker {worker} epoch {epoch}"
        if not sel:
            # the serve sanitizer strips ':' from rids, but journals
            # written by older builds (or by hand) may carry one — a
            # missed worker:epoch query falls back to a rid match
            # rather than refusing an id that is demonstrably present
            sel = [e for e in events if _match_rid(e, args.id)]
            if sel:
                what = f"rid {args.id}"
    else:
        sel = [e for e in events if _match_rid(e, args.id)]
        what = f"rid {args.id}"
    if not sel:
        print(f"no events for {what} under {args.journal!r} "
              f"(is the journal's rotation window past it?)",
              file=sys.stderr)
        return 1
    if args.as_json:
        # raw wall clocks preserved: each event keeps its writer's ts
        # (and its offset= estimate, when stamped) untouched
        for ev in sel:
            print(json.dumps(ev, separators=(",", ":"), default=str))
        return 0
    # offset-aligned rendering: workers stamp offset= (coordinator clock
    # minus theirs, obs/fleet.ClockSync), so ts + offset maps every
    # writer onto ONE clock — the merged timeline then reflects
    # causality across machines, not whose wall clock ran fast.  Events
    # without a stamp (the coordinator's own, pre-offset builds) align
    # at offset 0.
    aligned = any(e.get("offset") for e in sel)
    if aligned:
        sel = sorted(
            sel, key=lambda e: (e.get("ts", 0.0)
                                + float(e.get("offset", 0.0) or 0.0)))
    t0 = sel[0].get("ts", 0.0) + float(sel[0].get("offset", 0.0) or 0.0)
    planes = sorted({e.get("plane", "?") for e in sel})
    jobs = sorted({e["job"] for e in sel if "job" in e})
    print(f"trace {what}: {len(sel)} event(s) across "
          f"plane(s) {', '.join(planes)}"
          + (f"  [job {', '.join(jobs)}]" if jobs else ""))
    if aligned:
        print("  (timestamps offset-aligned to the coordinator clock; "
              "--json keeps raw wall clocks)")
    for ev in sel:
        if aligned and ev.get("offset"):
            ev = {**ev, "ts": (ev.get("ts", 0.0)
                               + float(ev.get("offset") or 0.0))}
            ev.pop("offset", None)
        print(" " + _fmt_event(ev, t0))
    # the request's phase split, when a serve_batch dispatch carried it
    for ev in sel:
        if ev.get("event") == "serve_batch":
            print(f"  -> coalesced into a {ev.get('rows', '?')}-row "
                  f"dispatch (bucket {ev.get('bucket', '?')}, "
                  f"{ev.get('requests', '?')} request(s)): waited "
                  f"{ev.get('queue_delay_s', 0.0):.4f}s, device "
                  f"{ev.get('dispatch_s', 0.0):.4f}s")
    return 0


# ---- rollup report (data + renderer) ----

def _report_doc(base: str) -> dict | None:
    """One-run document reconstructed from the rotation-exempt rollup
    sidecars alone — no journal read, so it survives rotation AND runs
    against a single scp'd ``.rollup.jsonl``."""
    records = read_rollups(base)
    if not records:
        return None
    doc = reconstruct(records)
    doc["schema"] = REPORT_SCHEMA
    doc["journal"] = base
    doc["files"] = ([base] if base.endswith(".rollup.jsonl")
                    else rollup_files(base))
    return doc


def _tenant_cost_table(doc: dict) -> dict[str, dict]:
    """Per-tenant cost rows: device-seconds / padded-row-seconds / rows
    / bytes from the cost leg's counters (exact — counter deltas), with
    the journal-fold serve volume as the fallback when the cost leg was
    off, plus request/shed counts from the serve counters."""
    counters = doc.get("counters") or {}
    cost = counters.get("cost") or {}
    serve_c = counters.get("serve") or {}
    fold = doc.get("serve") or {}
    models: set[str] = set(fold)
    for k in cost:
        if ":" in k and not k.startswith("train_"):
            models.add(k.split(":", 1)[1])
    for k in serve_c:
        if ":" in k:
            models.add(k.split(":", 1)[1])
    out: dict[str, dict] = {}
    for m in sorted(models):
        f = fold.get(m) or {}
        out[m] = {
            "device_s": round(cost.get(f"device_seconds:{m}",
                                       f.get("dispatch_s", 0.0)), 6),
            "padded_row_s": round(
                cost.get(f"padded_row_seconds:{m}", 0.0), 3),
            "rows": int(cost.get(f"rows:{m}", f.get("rows", 0))),
            "bytes": int(cost.get(f"bytes:{m}", 0)),
            "requests": int(serve_c.get(f"requests_total:{m}",
                                        f.get("requests", 0))),
            "shed": int(serve_c.get(f"shed_total:{m}", 0)),
        }
    total_dev = sum(r["device_s"] for r in out.values())
    for r in out.values():
        r["share_pct"] = (round(100.0 * r["device_s"] / total_dev, 1)
                          if total_dev else 0.0)
    return out


def _lane_utilization(doc: dict) -> dict | None:
    """Device-lane busy wall vs the run's wall clock.  With several
    serve workers each lane contributes its own busy seconds, so the
    fraction is lane-seconds per wall-second (can exceed 1)."""
    cost = (doc.get("counters") or {}).get("cost") or {}
    busy = cost.get("device_busy_seconds")
    if busy is None:
        return None
    span = (doc.get("t1") or 0.0) - (doc.get("t0") or 0.0)
    out = {"busy_s": round(float(busy), 3)}
    if span > 0:
        out["wall_s"] = round(span, 3)
        out["busy_frac"] = round(float(busy) / span, 4)
        out["idle_frac"] = round(max(0.0, 1.0 - float(busy) / span), 4)
    return out


def _fmt_excursion(e: dict, t0: float) -> str:
    start = e.get("start_ts")
    start_s = "?" if start is None else f"+{start - t0:.1f}s"
    end = e.get("end_ts")
    if end is not None:
        dur = "" if start is None else f" ({end - start:.1f}s)"
        span = f"{start_s} .. +{end - t0:.1f}s{dur}"
    else:
        span = f"{start_s} .. STILL OPEN"
    writer = f"  [{e['writer']}]" if e.get("writer") else ""
    return (f"  {e.get('kind', '?'):<11} {e.get('name', '?'):<24} "
            f"{span}{writer}")


def _render_report(doc: dict) -> list[str]:
    lines: list[str] = []
    t0 = doc.get("t0") or 0.0
    span = (doc.get("t1") or t0) - t0
    lines.append(
        f"run: {doc['windows']} rollup window(s) spanning {span:.1f}s, "
        f"writer(s) {', '.join(doc['writers']) or '?'}"
        + (f"  [job {', '.join(doc['jobs'])}]" if doc["jobs"] else ""))
    serve_c = (doc.get("counters") or {}).get("serve") or {}
    base_c = {k: v for k, v in serve_c.items() if ":" not in k}
    if base_c:
        order = ("requests_total", "rows_total", "batches_total",
                 "shed_total", "errors_total", "nan_rows_total")
        bits = [f"{k.removesuffix('_total')} {int(base_c[k])}"
                for k in order if base_c.get(k)]
        bits += [f"{k} {int(v)}" for k, v in sorted(base_c.items())
                 if k not in order and v]
        lines.append("totals (monotonic counters): " + ", ".join(bits))
    util = _lane_utilization(doc)
    if util is not None and "busy_frac" in util:
        lines.append(
            f"device lane: busy {util['busy_s']:.1f}s of "
            f"{util['wall_s']:.1f}s wall — utilization "
            f"{100 * util['busy_frac']:.1f}%, idle headroom "
            f"{100 * util['idle_frac']:.1f}%")
    tenants = _tenant_cost_table(doc)
    if tenants:
        lines.append("per-tenant cost (device attribution)")
        lines.append(
            "  model          device_s  share%  padded_row_s  rows"
            "      requests  shed    bytes")
        for m, r in tenants.items():
            lines.append(
                f"  {m:<14} {r['device_s']:<9.3f} {r['share_pct']:<7} "
                f"{r['padded_row_s']:<13.1f} {r['rows']:<9} "
                f"{r['requests']:<9} {r['shed']:<7} {_fmt_bytes(r['bytes'])}"
            )
    cost_c = (doc.get("counters") or {}).get("cost") or {}
    train_rows = {k.split(":w", 1)[1]: v for k, v in cost_c.items()
                  if k.startswith("train_device_seconds:w")}
    train_fold = doc.get("train") or {}
    if train_rows or train_fold:
        lines.append("train device time")
        lines.append("  worker  device_s   steps     epochs")
        workers = sorted(set(train_rows) | set(train_fold),
                         key=lambda w: (not w.isdigit(),
                                        int(w) if w.isdigit() else w))
        for w in workers:
            f = train_fold.get(w) or {}
            dev = train_rows.get(w, f.get("dispatch_s", 0.0))
            steps = int(cost_c.get(f"train_steps:w{w}",
                                   f.get("steps", 0)))
            lines.append(f"  {w:<7} {dev:<10.3f} {steps:<9} "
                         f"{int(f.get('epochs', 0))}")
    digests = doc.get("digests") or {}
    if digests:
        lines.append("windowed digests (count-weight merged)")
        lines.append("  signal                 stat   value      mean"
                     "       max        count")
        for sig in sorted(digests):
            s = digests[sig]
            stat = s.get("stat") or "mean"
            val = s.get(stat)
            lines.append(
                f"  {sig:<22} {stat:<6} "
                f"{'?' if val is None else f'{val:.4g}':<10} "
                f"{s.get('mean', 0.0):<10.4g} {s.get('max', 0.0):<10.4g} "
                f"{s['count']}")
    comp = doc.get("compile") or {}
    gauges = doc.get("gauges") or {}
    if comp or gauges:
        bits = []
        if comp:
            bits.append(f"{int(comp.get('compiles', 0))} compile(s), "
                        f"{comp.get('compile_s', 0.0):.2f}s total, "
                        f"max {comp.get('max_s', 0.0):.2f}s")
            if comp.get("aot_loads"):
                # the dead-fleet report says what admission actually
                # did: deserialized shipped executables vs fallbacks
                bits.append(
                    f"{int(comp['aot_loads'])} AOT load(s)"
                    + (f", {int(comp['aot_fallbacks'])} fallback(s)"
                       if comp.get("aot_fallbacks") else ""))
        if gauges.get("total_bytes"):
            bits.append(
                f"devmem high-water {_fmt_bytes(gauges['total_bytes'])}"
                + (f" ({100 * gauges['devmem_frac']:.1f}% of limit)"
                   if gauges.get("devmem_frac") else ""))
        lines.append("device/compiler: " + "; ".join(bits))
    excs = (doc.get("excursions") or []) + (doc.get("open_excursions")
                                            or [])
    if excs:
        lines.append("excursions")
        for e in excs:
            lines.append(_fmt_excursion(e, t0))
    else:
        lines.append("no excursions")
    return lines


def cmd_report(args) -> int:
    doc = _report_doc(args.journal)
    if doc is None:
        print(f"no rollup records under {args.journal!r} "
              f"(files: {rollup_files(args.journal) or 'none'}) — "
              "rollups write beside the journal once obs is enabled "
              "(shifu.tpu.obs-rollup, on by default with a journal)",
              file=sys.stderr)
        return 1
    if args.as_json:
        print(json.dumps(doc, indent=2, default=str))
        return 0
    print(f"rollup report — {args.journal}")
    for line in _render_report(doc):
        print(line)
    return 0


# ---- cross-run diff ----

#: noise-discount scale for count-backed metrics (the datastats ~3/√n
#: small-sample discipline): a delta must clear k/√min(n) on top of the
#: relative floor before it can be called significant
_DIFF_NOISE_K = 3.0


def _delta_row(metric: str, va: float, vb: float, na: int, nb: int,
               floor: float, lower_is_better: bool) -> dict:
    rel = (vb - va) / abs(va)
    n = min(na or 0, nb or 0)
    noise = _DIFF_NOISE_K / math.sqrt(n) if n > 0 else 0.0
    bar = max(floor, noise)
    significant = abs(rel) >= bar
    worse = (rel > 0) == lower_is_better
    verdict = ("~same" if not significant
               else ("REGRESSED" if worse else "improved"))
    return {
        "metric": metric,
        "a": round(va, 6), "b": round(vb, 6),
        "delta_pct": round(100.0 * rel, 2),
        "count_a": na, "count_b": nb,
        "noise_floor_pct": round(100.0 * bar, 2),
        "significant": significant,
        "verdict": verdict,
    }


def _diff_rows(a: dict, b: dict, floor: float) -> list[dict]:
    rows: list[dict] = []
    da, db = a.get("digests") or {}, b.get("digests") or {}
    for sig in sorted(set(da) & set(db)):
        sa, sb = da[sig], db[sig]
        stat = sb.get("stat") or sa.get("stat") or "mean"
        va, vb = sa.get(stat), sb.get(stat)
        if va is None or vb is None or va <= 0:
            continue
        rows.append(_delta_row(
            f"{sig}.{stat}", va, vb, int(sa.get("count", 0)),
            int(sb.get("count", 0)), floor,
            # every digest-backed signal here is a latency/time/ratio:
            # smaller is better
            lower_is_better=True))

    def rate_of(doc, key):
        c = (doc.get("counters") or {}).get("serve") or {}
        span = (doc.get("t1") or 0.0) - (doc.get("t0") or 0.0)
        v = c.get(key)
        if not v or span <= 0:
            return None, 0
        return float(v) / span, int(v)

    for key, label in (("requests_total", "serve_requests_per_s"),
                       ("rows_total", "serve_rows_per_s")):
        (ra, na), (rb, nb) = rate_of(a, key), rate_of(b, key)
        if ra and rb:
            rows.append(_delta_row(label, ra, rb, na, nb, floor,
                                   lower_is_better=False))

    def cost_per_krow(doc):
        cost = (doc.get("counters") or {}).get("cost") or {}
        dev = sum(v for k, v in cost.items()
                  if k.startswith("device_seconds:"))
        n = sum(v for k, v in cost.items() if k.startswith("rows:"))
        return (dev / n * 1000.0, int(n)) if n else (None, 0)

    (ca, na), (cb, nb) = cost_per_krow(a), cost_per_krow(b)
    if ca and cb:
        rows.append(_delta_row("device_s_per_krow", ca, cb, na, nb,
                               floor, lower_is_better=True))
    return rows


def _diff_runs(args) -> int:
    if len(args.runs) != 2:
        print("obs diff needs exactly two runs (journal bases or "
              ".rollup.jsonl sidecars), or --bench", file=sys.stderr)
        return 2
    docs = []
    for run in args.runs:
        records = read_rollups(run)
        if not records:
            print(f"no rollup records under {run!r}", file=sys.stderr)
            return 1
        docs.append(reconstruct(records))
    a, b = docs
    rows = _diff_rows(a, b, args.threshold)
    doc = {
        "schema": DIFF_SCHEMA,
        "mode": "rollup",
        "a": {"run": args.runs[0], "t0": a.get("t0"), "t1": a.get("t1"),
              "windows": a.get("windows"), "jobs": a.get("jobs")},
        "b": {"run": args.runs[1], "t0": b.get("t0"), "t1": b.get("t1"),
              "windows": b.get("windows"), "jobs": b.get("jobs")},
        "metrics": rows,
        "regressions": [r["metric"] for r in rows
                        if r["verdict"] == "REGRESSED"],
    }
    if args.as_json:
        print(json.dumps(doc, indent=2, default=str))
        return 0
    print(f"rollup diff — A: {args.runs[0]}  vs  B: {args.runs[1]}")
    if not rows:
        print("  no comparable metrics (both runs need rollup digests "
              "or counters)")
        return 1
    print("  metric                     A          B          Δ%        "
          "noise%   verdict")
    for r in rows:
        print(f"  {r['metric']:<26} {r['a']:<10.4g} {r['b']:<10.4g} "
              f"{r['delta_pct']:<+10.2f} {r['noise_floor_pct']:<8.2f} "
              f"{r['verdict']}")
    if doc["regressions"]:
        print(f"  REGRESSED: {', '.join(doc['regressions'])}")
    return 0


def _diff_bench(args) -> int:
    entries: list[dict] = []
    try:
        with open(args.history) as f:
            for raw in f:
                try:
                    e = json.loads(raw)
                except ValueError:
                    continue
                if isinstance(e, dict) and e.get("name"):
                    entries.append(e)
    except OSError:
        print(f"no bench history at {args.history!r} — `python bench.py "
              "<name>` appends one line per run", file=sys.stderr)
        return 1
    # failed runs (rc != 0) carry no trustworthy metrics — they stay in
    # the history as the record of the failure, but a diff must compare
    # two runs that actually measured something
    entries = [e for e in entries if not e.get("rc")]
    name = args.runs[0] if args.runs else None
    if name is None and entries:
        name = entries[-1]["name"]
    entries = [e for e in entries if e.get("name") == name]
    if len(entries) < 2:
        print(f"need at least two {name!r} entries in {args.history!r} "
              f"to diff (have {len(entries)})", file=sys.stderr)
        return 1
    a, b = entries[-2], entries[-1]
    rows = []
    ma, mb = a.get("metrics") or {}, b.get("metrics") or {}
    for k in sorted(set(ma) & set(mb)):
        va, vb = ma[k], mb[k]
        if (isinstance(va, (int, float)) and isinstance(vb, (int, float))
                and not isinstance(va, bool) and va):
            rel = (vb - va) / abs(va)
            rows.append({
                "metric": k, "a": va, "b": vb,
                "delta_pct": round(100.0 * rel, 2),
                "significant": abs(rel) >= args.threshold,
            })
    doc = {
        "schema": DIFF_SCHEMA,
        "mode": "bench",
        "name": name,
        "a": {k: a.get(k) for k in ("ts", "host", "artifact")},
        "b": {k: b.get(k) for k in ("ts", "host", "artifact")},
        "metrics": rows,
    }
    if args.as_json:
        print(json.dumps(doc, indent=2, default=str))
        return 0
    host_a = (a.get("host") or {}).get("hostname", "?")
    host_b = (b.get("host") or {}).get("hostname", "?")
    print(f"bench diff — {name}: {a.get('ts')} ({host_a}) -> "
          f"{b.get('ts')} ({host_b})")
    if not rows:
        print("  no shared numeric metrics between the two entries")
        return 1
    for r in rows:
        mark = "  *" if r["significant"] else ""
        print(f"  {r['metric']:<34} {r['a']:<12.6g} -> {r['b']:<12.6g} "
              f"({r['delta_pct']:+.2f}%){mark}")
    return 0


def cmd_diff(args) -> int:
    if args.bench:
        return _diff_bench(args)
    return _diff_runs(args)


# ---- fleet skew (data + renderer) ----

def _fleet_data(events: list[dict]) -> dict:
    """Per-rank skew state + straggler excursions from the coordinator's
    ``fleet_skew`` / ``straggler_detect`` / ``straggler_clear`` events,
    plus the per-epoch ``comm`` drains, standby-promotion takeovers
    (``standby_promote`` / ``standby_claim``) and elastic re-splits —
    entirely from journal files, so a dead fleet's straggler AND
    takeover story reconstructs on a jax-free laptop."""
    ranks: dict = {}
    excursions: list[dict] = []
    open_exc: dict = {}
    comm: dict = defaultdict(lambda: {"calls": 0, "bytes": 0})
    promotions: list[dict] = []
    resplits: list[dict] = []
    standbys: set = set()
    epochs = 0
    straggler = None
    max_skew = None
    for ev in events:
        kind = ev.get("event")
        if kind == "fleet_skew":
            epochs += 1
            straggler = ev.get("straggler")
            max_skew = ev.get("max_skew")
            for w, r in (ev.get("ranks") or {}).items():
                ranks[w] = dict(r)
        elif kind == "straggler_detect":
            rec = {
                "worker": ev.get("worker"),
                "phase": ev.get("phase"),
                "skew": ev.get("skew"),
                "step_s": ev.get("step_s"),
                "fleet_step_s": ev.get("fleet_step_s"),
                "detect_ts": ev.get("ts"),
                "detect_epoch": ev.get("epoch"),
                "clear_ts": None,
                "clear_epoch": None,
                "straggler_s": None,
            }
            excursions.append(rec)
            open_exc[ev.get("worker")] = rec
        elif kind == "straggler_clear":
            rec = open_exc.pop(ev.get("worker"), None)
            if rec is not None:
                rec["clear_ts"] = ev.get("ts")
                rec["clear_epoch"] = ev.get("epoch")
                rec["straggler_s"] = ev.get("straggler_s")
        elif kind == "comm":
            for k, v in (ev.get("kinds") or {}).items():
                comm[k]["calls"] += int(v.get("calls", 0) or 0)
                comm[k]["bytes"] += int(v.get("bytes", 0) or 0)
        elif kind == "standby_register":
            standbys.add(ev.get("worker_id"))
        elif kind == "standby_promote":
            promotions.append({
                "worker": ev.get("worker"),
                "standby_id": ev.get("worker_id"),
                "old_id": ev.get("old_worker_id"),
                "epoch": ev.get("epoch"),
                "why": ev.get("why"),
                "hb_age_s": ev.get("hb_age_s"),
                "promote_ts": ev.get("ts"),
                "latency_s": None,
            })
        elif kind == "standby_claim":
            for p in reversed(promotions):
                if (p["standby_id"] == ev.get("worker_id")
                        and p["latency_s"] is None):
                    p["latency_s"] = ev.get("latency_s")
                    break
        elif kind == "resplit":
            resplits.append({
                "split_generation": ev.get("split_generation"),
                "ranks": ev.get("ranks"),
                "n_files": ev.get("n_files"),
                "why": ev.get("why"),
                "ts": ev.get("ts"),
            })
    if (not ranks and not excursions and not comm and not promotions
            and not resplits and not standbys):
        return {}
    def rank_key(kv):
        # ranks are JSON string keys: numeric order, not "0,1,10,11,2"
        try:
            return (0, int(kv[0]))
        except (TypeError, ValueError):
            return (1, kv[0])

    return {
        "ranks": dict(sorted(ranks.items(), key=rank_key)),
        "excursions": excursions,
        "epochs": epochs,
        "straggler": straggler,
        "max_skew": max_skew,
        "comm": {k: dict(v) for k, v in sorted(comm.items())},
        "standbys": sorted(s for s in standbys if s),
        "promotions": promotions,
        "resplits": resplits,
    }


def _render_fleet(data: dict, t0: float) -> list[str]:
    if not data:
        return []
    lines = []
    if data["ranks"]:
        lines.append(
            "  rank  step_s    skew    phase      barrier_s  offset_s"
            "    state")
        for w, r in data["ranks"].items():
            state = "STRAGGLER" if r.get("straggler") else "ok"
            barrier = r.get("barrier_s")
            offset = r.get("offset_s")
            lines.append(
                f"  {w:<5} {r.get('step_s', 0.0):<9.4f} "
                f"{r.get('skew', 1.0):<7.3f} "
                f"{r.get('phase', '?'):<10} "
                f"{('-' if barrier is None else f'{barrier:.4f}'):<10} "
                f"{('-' if offset is None else f'{offset:+.6f}'):<11}"
                f"{state}"
            )
    for e in data["excursions"]:
        start = (e["detect_ts"] or t0) - t0
        if e["clear_ts"] is not None:
            span = (f"+{start:.1f}s .. +{e['clear_ts'] - t0:.1f}s "
                    f"({e['straggler_s']:.1f}s, epochs "
                    f"{e['detect_epoch']}..{e['clear_epoch']})")
        else:
            span = f"+{start:.1f}s .. STILL STRAGGLING"
        lines.append(
            f"  straggler: worker {e['worker']}  {span}  "
            f"skew {e.get('skew', 0.0):.2f}  dominant phase "
            f"{e.get('phase', '?')}")
    if not data["excursions"] and data["ranks"]:
        lines.append("  no straggler excursions")
    # elastic fleet: standby promotions render beside the straggler
    # excursions — rank, epoch, takeover latency, and why
    if data.get("standbys"):
        lines.append(f"  standbys registered: "
                     f"{', '.join(data['standbys'])}")
    for p in data.get("promotions") or []:
        when = ""
        if p.get("promote_ts") is not None:
            when = f"+{p['promote_ts'] - t0:.1f}s  "
        lat = ("takeover pending" if p.get("latency_s") is None
               else f"takeover {p['latency_s']:.2f}s")
        lines.append(
            f"  promotion: rank {p['worker']} <- {p['standby_id']}  "
            f"{when}@epoch {p.get('epoch')}  {lat}  ({p.get('why')})")
    for r in data.get("resplits") or []:
        lines.append(
            f"  resplit: generation {r['split_generation']} over ranks "
            f"{r['ranks']} ({r['n_files']} file(s); {r.get('why')})")
    if data["comm"]:
        lines.append("  collective      calls     bytes")
        for k, v in data["comm"].items():
            lines.append(
                f"  {k:<15} {v['calls']:<9} {_fmt_bytes(v['bytes'])}")
    return lines


def cmd_fleet(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _fleet_data(events)
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0 if data else 1
    if not data:
        print("no fleet events — the coordinator journals fleet_skew / "
              "straggler_detect once workers attach phase summaries "
              "(obs enabled on a multi-worker run)")
        return 1
    t0 = events[0].get("ts", 0.0)
    n = len(data["ranks"])
    print(f"fleet skew — {n} rank(s), {data['epochs']} fleet epoch(s)"
          + (f", max skew {data['max_skew']:.2f}"
             if data.get("max_skew") is not None else ""))
    for line in _render_fleet(data, t0):
        print(line)
    return 0


# ---- data distribution (train baseline vs live serve) ----

def _data_data(events: list[dict]) -> dict:
    """Aggregate the data leg's journal: per-worker train sketches
    (``data_stats`` plane=train), per-model live windowed sketches
    (``data_stats`` plane=serve), drift excursions, and any
    ``config_stats_missing`` records — entirely from journal files."""
    train: dict = {}
    serve: dict = {}
    excursions: list[dict] = []
    open_: dict = {}
    stats_missing: list[dict] = []
    for ev in events:
        kind = ev.get("event")
        if kind == "data_stats":
            stats = ev.get("stats")
            if not isinstance(stats, dict):
                continue
            if ev.get("plane") == "train":
                train[ev.get("worker")] = {
                    "stats": stats, "epoch": ev.get("epoch"),
                    "ts": ev.get("ts"),
                }
            else:
                serve[ev.get("model") or "default"] = {
                    "stats": stats, "ts": ev.get("ts"),
                    "drift_score": ev.get("drift_score"),
                    "drifting": ev.get("drifting"),
                }
        elif kind == "data_drift":
            key = (ev.get("model"), ev.get("feature"))
            exc = {
                "model": ev.get("model"), "feature": ev.get("feature"),
                "column": ev.get("column"), "stat": ev.get("stat"),
                "score": ev.get("score"), "detect_ts": ev.get("ts"),
                "clear_ts": None, "drift_s": None,
            }
            open_[key] = exc
            excursions.append(exc)
        elif kind == "data_drift_clear":
            exc = open_.pop((ev.get("model"), ev.get("feature")), None)
            if exc is not None:
                exc["clear_ts"] = ev.get("ts")
                exc["drift_s"] = ev.get("drift_s")
        elif kind == "config_stats_missing":
            stats_missing.append({
                "columns": ev.get("columns"),
                "missing": ev.get("missing"),
                "selected": ev.get("selected"),
            })
    if not (train or serve or excursions):
        return {}
    return {"train": train, "serve": serve, "excursions": excursions,
            "config_stats_missing": stats_missing}


def _merged_train_stats(train: dict) -> dict | None:
    """One train baseline out of the per-worker journal snapshots —
    count-weighted merge when numpy is importable (obs/datastats.py),
    else the biggest worker's snapshot (this CLI stays usable on a
    box with nothing but the stdlib)."""
    snaps = [v["stats"] for v in train.values() if v.get("stats")]
    if not snaps:
        return None
    if len(snaps) == 1:
        return snaps[0]
    try:
        from shifu_tensorflow_tpu.obs.datastats import merge_snapshots

        return merge_snapshots(snaps)
    except Exception:
        return max(snaps, key=lambda s: s.get("rows", 0))


def _bundle_baselines(paths: list[str]) -> dict[str, dict]:
    """feature_stats.json baselines out of export dirs: each ``--bundle``
    is either one bundle (name "default") or a multi-tenant models dir
    (one baseline per tenant subdirectory)."""
    import os

    out: dict[str, dict] = {}

    def load(path):
        try:
            with open(path) as f:
                doc = json.load(f)
            return doc.get("stats") or None
        except (OSError, ValueError):
            return None

    for p in paths:
        single = os.path.join(p, "feature_stats.json")
        if os.path.isfile(single):
            stats = load(single)
            if stats:
                out["default"] = stats
            continue
        try:
            names = sorted(os.listdir(p))
        except OSError:
            continue
        for name in names:
            sub = os.path.join(p, name, "feature_stats.json")
            if os.path.isfile(sub):
                stats = load(sub)
                if stats:
                    out[name] = stats
    return out


def _fmt_stat(snap: dict, j: int) -> str:
    mean = snap["mean"][j]
    std = snap["std"][j]
    if mean is None:
        return "-"
    return f"{mean:.4g}±{0.0 if std is None else std:.3g}"


def _pct(snap: dict, key: str, j: int) -> str:
    rates = snap.get(key) or []
    v = rates[j] if j < len(rates) else None
    return "-" if v is None else f"{100.0 * v:.3g}%"


def _render_data(data: dict, baselines: dict, t0: float,
                 max_features: int = 20) -> list[str]:
    lines: list[str] = []
    train_stats = _merged_train_stats(data.get("train") or {})
    open_excs = {(e["model"], e["feature"])
                 for e in data.get("excursions", [])
                 if e["clear_ts"] is None}
    models = sorted(data.get("serve") or {})
    for model in models:
        live_doc = data["serve"][model]
        live = live_doc["stats"]
        base = baselines.get(model)
        base_src = "bundle"
        if base is None and len(baselines) == 1 and len(models) == 1:
            base = next(iter(baselines.values()))
        if base is None:
            base, base_src = train_stats, "journal"
        score = live_doc.get("drift_score")
        lines.append(
            f"  model {model}: live window {live['rows']} rows"
            + (f", baseline {base['rows']} rows [{base_src}]"
               if base else ", NO BASELINE")
            + (f", drift score {score:.3g}" if score is not None else "")
            + (f", {live_doc['drifting']} drifting"
               if live_doc.get("drifting") else "")
        )
        if base is None or base.get("num_features") != live.get(
                "num_features"):
            continue
        rows = []
        try:
            from shifu_tensorflow_tpu.obs.datastats import drift_components
        except Exception:
            drift_components = None
        for j in range(live["num_features"]):
            score_j, stat_j = None, "-"
            if drift_components is not None:
                comps = drift_components(base, live, j)
                stat_j, score_j = max(comps.items(), key=lambda kv: kv[1])
            rows.append((j, score_j, stat_j))
        rows.sort(key=lambda r: -(r[1] or 0.0))
        shown = rows if not max_features else rows[:max_features]
        lines.append(
            "    feat  base mean±std     live mean±std     base p50"
            "   live p50   miss%      score   stat          state")
        bq = (base.get("quantiles") or {}).get("0.5") or []
        lq = (live.get("quantiles") or {}).get("0.5") or []
        for j, score_j, stat_j in shown:
            bp50 = bq[j] if j < len(bq) and bq[j] is not None else None
            lp50 = lq[j] if j < len(lq) and lq[j] is not None else None
            state = ("DRIFTING" if (model, j) in open_excs else "ok")
            lines.append(
                f"    {j:<5} {_fmt_stat(base, j):<17} "
                f"{_fmt_stat(live, j):<17} "
                f"{'-' if bp50 is None else f'{bp50:.4g}':<10} "
                f"{'-' if lp50 is None else f'{lp50:.4g}':<10} "
                f"{_pct(base, 'missing_rate', j)}/"
                f"{_pct(live, 'missing_rate', j):<7} "
                f"{'-' if score_j is None else f'{score_j:.3g}':<7} "
                f"{stat_j:<13} {state}"
            )
        if len(shown) < len(rows):
            lines.append(f"    ... {len(rows) - len(shown)} more features "
                         f"(--features 0 for all)")
    if not models and train_stats:
        lines.append(
            f"  train baseline only: {train_stats['rows']} rows, "
            f"{train_stats['num_features']} features (no serve-plane "
            "data_stats journaled)")
    for e in data.get("excursions", []):
        start = (e["detect_ts"] or t0) - t0
        where = f"model {e['model']} feature {e['feature']}"
        if e.get("column") is not None:
            where += f" (column {e['column']})"
        if e["clear_ts"] is not None:
            span = (f"+{start:.1f}s .. +{e['clear_ts'] - t0:.1f}s "
                    f"({(e['drift_s'] or 0.0):.1f}s)")
        else:
            span = f"+{start:.1f}s .. STILL DRIFTING"
        lines.append(f"  drift: {where}  {span}  stat {e['stat']}  "
                     f"score {e['score']:.3g}")
    if models and not data.get("excursions"):
        lines.append("  no drift excursions")
    for m in data.get("config_stats_missing", []):
        lines.append(
            f"  config: {m['missing']}/{m['selected']} selected columns "
            f"had no columnStats (ZSCALE substituted mean=0/std=1): "
            f"{m['columns']}")
    return lines


def cmd_data(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _data_data(events)
    baselines = _bundle_baselines(args.bundles)
    if args.as_json:
        doc = dict(data) if data else {}
        doc["baselines"] = baselines
        doc["train_merged"] = _merged_train_stats(
            (data or {}).get("train") or {})
        print(json.dumps(doc, indent=2, default=str))
        return 0 if (data or baselines) else 1
    if not data and not baselines:
        print("no data-plane events — the train sketch journals "
              "data_stats per epoch and the serve drift monitor per "
              "window once obs is enabled (shifu.tpu.obs-*)")
        return 1
    t0 = events[0].get("ts", 0.0)
    n_models = len((data or {}).get("serve") or {})
    print(f"data distribution — {n_models} serving model(s), "
          f"{len((data or {}).get('train') or {})} train worker sketch(es), "
          f"{len(baselines)} bundle baseline(s)")
    for line in _render_data(data or {}, baselines, t0,
                             max_features=args.features):
        print(line)
    return 0


# ---- compile flight recorder (data + renderer) ----

def _compile_data(events: list[dict]) -> dict:
    """Aggregate `compile` + `recompile_storm[_clear]` events into the
    per-callable cost table and the storm excursion list — entirely from
    journal files (a dead fleet's included)."""
    per: dict = defaultdict(lambda: {
        "compiles": 0, "compile_s": 0.0, "max_s": 0.0, "wall_s": 0.0,
        "signatures": set(), "warm": 0, "aot_loads": 0,
        "aot_fallbacks": 0, "workers": set(),
        "flops_max": None, "code_bytes": 0,
    })
    storms: list[dict] = []
    open_storms: dict = {}  # (plane, worker) -> storm record
    for ev in events:
        kind = ev.get("event")
        if kind == "compile":
            a = per[ev.get("name", "?")]
            a["signatures"].add(ev.get("signature", "?"))
            if ev.get("worker") is not None:
                a["workers"].add(ev["worker"])
            if ev.get("kind") == "aot_load":
                # a deserialized shipped executable — admission did a
                # LOAD, not a compile; counted in its own column so the
                # table says what admission actually did
                a["aot_loads"] += 1
                a["wall_s"] += float(ev.get("wall_s", 0.0) or 0.0)
                continue
            a["compiles"] += 1
            s = float(ev.get("compile_s", 0.0) or 0.0)
            a["compile_s"] += s
            a["max_s"] = max(a["max_s"], s)
            a["wall_s"] += float(ev.get("wall_s", 0.0) or 0.0)
            if ev.get("kind") == "warm":
                a["warm"] += 1
            elif ev.get("kind") == "aot_fallback":
                a["aot_fallbacks"] += 1
            if ev.get("flops") is not None:
                a["flops_max"] = max(a["flops_max"] or 0.0,
                                     float(ev["flops"]))
            if ev.get("code_bytes"):
                a["code_bytes"] = max(a["code_bytes"],
                                      int(ev["code_bytes"]))
        elif kind == "recompile_storm":
            rec = {
                "started_ts": ev.get("ts"),
                "cleared_ts": None,
                "storm_s": None,
                "culprit": ev.get("culprit"),
                "signature": ev.get("signature"),
                "compiles_in_window": ev.get("compiles_in_window"),
                "plane": ev.get("plane"),
                "worker": ev.get("worker"),
            }
            storms.append(rec)
            open_storms[(ev.get("plane"), ev.get("worker"))] = rec
        elif kind == "recompile_storm_clear":
            rec = open_storms.pop((ev.get("plane"), ev.get("worker")),
                                  None)
            if rec is not None:
                rec["cleared_ts"] = ev.get("ts")
                rec["storm_s"] = ev.get("storm_s")
    callables = {
        name: {
            "compiles": a["compiles"],
            "warm": a["warm"],
            "aot_loads": a["aot_loads"],
            "aot_fallbacks": a["aot_fallbacks"],
            "signatures": len(a["signatures"]),
            "compile_s": round(a["compile_s"], 4),
            "max_s": round(a["max_s"], 4),
            "workers": sorted(a["workers"]),
            **({"flops_max": a["flops_max"]}
               if a["flops_max"] is not None else {}),
            **({"code_bytes": a["code_bytes"]}
               if a["code_bytes"] else {}),
        }
        for name, a in sorted(per.items())
    }
    return {"callables": callables, "storms": storms}


def cmd_compile(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _compile_data(events)
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0
    t0 = events[0].get("ts", 0.0)
    if not data["callables"]:
        print("no compile events — was the run traced with obs enabled "
              "on a jax build that reports compile durations?")
        return 1
    total_s = sum(a["compile_s"] for a in data["callables"].values())
    total_n = sum(a["compiles"] for a in data["callables"].values())
    total_aot = sum(a["aot_loads"] for a in data["callables"].values())
    aot_note = (f", {total_aot} AOT executable load(s)"
                if total_aot else "")
    print(f"compile flight recorder — {total_n} compilation(s), "
          f"{total_s:.2f}s total compile time{aot_note}")
    print("  callable                 compiles  warm  aot   fb    "
          "signatures  compile_s  max_s")
    for name, a in data["callables"].items():
        print(f"  {name:<24} {a['compiles']:<9} {a['warm']:<5} "
              f"{a['aot_loads']:<5} {a['aot_fallbacks']:<5} "
              f"{a['signatures']:<11} {a['compile_s']:<10.3f} "
              f"{a['max_s']:.3f}")
    if data["storms"]:
        print()
        print("recompile storms")
        for s in data["storms"]:
            start = (s["started_ts"] or t0) - t0
            if s["cleared_ts"] is not None:
                span = (f"+{start:.1f}s .. +{s['cleared_ts'] - t0:.1f}s "
                        f"({s['storm_s']:.1f}s)")
            else:
                span = f"+{start:.1f}s .. STILL ACTIVE"
            print(f"  {span}  worker {s['worker']}  "
                  f"{s['compiles_in_window']} compiles/window")
            print(f"    churning: {s['culprit']}  last signature "
                  f"{s['signature']}")
    else:
        print("\n  no recompile storms")
    return 0


# ---- device memory (data + renderer) ----

def _mem_data(events: list[dict]) -> dict:
    """Latest + high-water device-memory state per (plane, worker) from
    `device_mem` events, plus the per-model last-known device bytes."""
    per: dict = {}
    models: dict = {}
    for ev in events:
        if ev.get("event") == "model_evict":
            # the eviction's post-release snapshot omits the tenant; a
            # merge-only table would show its bytes forever — exactly
            # inverting the leak diagnosis the snapshot exists for.  A
            # re-admission's device_mem re-adds it below.
            models.pop(ev.get("model"), None)
            continue
        if ev.get("event") != "device_mem":
            continue
        key = f"{ev.get('plane', '?')}/w{ev.get('worker')}" \
            if ev.get("worker") is not None else ev.get("plane", "?")
        a = per.setdefault(key, {"snapshots": 0, "hwm_bytes": 0,
                                 "hwm_ts": None, "last": None})
        a["snapshots"] += 1
        # the allocator's held bytes where the backend reports them
        # (in use + reserved), the live arrays' total otherwise
        held = int(ev.get("bytes_held") or ev.get("total_bytes") or 0)
        if held >= a["hwm_bytes"]:
            a["hwm_bytes"] = held
            a["hwm_ts"] = ev.get("ts")
        a["last"] = {
            k: ev.get(k) for k in (
                "ts", "total_bytes", "params_bytes", "opt_bytes",
                "infeed_bytes", "exec_bytes", "other_bytes", "arrays",
                "bytes_in_use", "bytes_held", "bytes_limit", "devmem_frac",
                "epoch")
            if ev.get(k) is not None
        }
        for m, b in (ev.get("models") or {}).items():
            models[m] = int(b)
    return {"workers": per, "models": models}


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024.0 or unit == "GiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024.0
    return f"{n:.1f}GiB"


def cmd_mem(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _mem_data(events)
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0
    if not data["workers"]:
        print("no device_mem events — the device-memory accountant "
              "snapshots per train epoch and per serve admission; was "
              "obs enabled?")
        return 1
    print("device memory accountant")
    print("  writer          snaps  high-water  last-total  params    "
          "opt       infeed    other")
    for key, a in sorted(data["workers"].items()):
        last = a["last"] or {}
        print(
            f"  {key:<15} {a['snapshots']:<6} "
            f"{_fmt_bytes(a['hwm_bytes']):<11} "
            f"{_fmt_bytes(last.get('total_bytes')):<11} "
            f"{_fmt_bytes(last.get('params_bytes')):<9} "
            f"{_fmt_bytes(last.get('opt_bytes')):<9} "
            f"{_fmt_bytes(last.get('infeed_bytes')):<9} "
            f"{_fmt_bytes(last.get('other_bytes'))}"
        )
        if last.get("bytes_limit"):
            print(f"                  backend: "
                  f"{_fmt_bytes(last.get('bytes_in_use'))} in use, "
                  f"{_fmt_bytes(last.get('bytes_held'))} held of "
                  f"{_fmt_bytes(last['bytes_limit'])} limit "
                  f"({100.0 * (last.get('devmem_frac') or 0):.1f}%)")
    if data["models"]:
        print("  model           device-bytes")
        for m, b in sorted(data["models"].items()):
            print(f"  {m:<15} {_fmt_bytes(b)}")
    return 0


# ---- profile captures ----

def cmd_profile(args) -> int:
    if args.phases:
        return _cmd_profile_phases(args)
    if not args.journal:
        print("profile needs --journal (or --phases <dump dir>)",
              file=sys.stderr)
        return 2
    if args.request:
        from shifu_tensorflow_tpu.obs import profile as obs_profile

        if not args.out_dir:
            print("--request needs --dir (where the profiler dump "
                  "should land)", file=sys.stderr)
            return 2
        path = obs_profile.request(args.journal, args.out_dir,
                                   seconds=args.seconds,
                                   worker=args.worker)
        print(f"capture requested: trigger {path} "
              f"({args.seconds:.1f}s window -> {args.out_dir}); the "
              "fleet's next obs tick starts it")
        return 0
    events = read_events(args.journal)
    caps = [e for e in events if e.get("event") == "profile_capture"]
    if args.as_json:
        for ev in caps:
            print(json.dumps(ev, separators=(",", ":"), default=str))
        return 0 if caps else 1
    if not caps:
        print(f"no profile_capture events under {args.journal!r}; "
              "request one with: obs profile --journal ... --request "
              "--dir <dump-dir>", file=sys.stderr)
        return 1
    t0 = events[0].get("ts", 0.0)
    print(f"profiler captures ({len(caps)} event(s))")
    for ev in caps:
        print(" " + _fmt_event(ev, t0))
    return 0


def _cmd_profile_phases(args) -> int:
    from shifu_tensorflow_tpu.obs import profile as obs_profile

    out = obs_profile.phases(args.phases)
    if args.as_json:
        print(json.dumps(out, separators=(",", ":")))
        return 0 if out else 1
    if not out:
        print(f"no capture under {args.phases!r} in which "
              f"{obs_profile.STEP_PROGRAM} ran on a TPU", file=sys.stderr)
        return 1
    step_ms = out["step_ms"]
    print(f"{out['step']}: {step_ms:.3f} ms/step (median of "
          f"{out['steps']} step(s), {out['devices']} device(s))  "
          f"{out['xplane']}")
    print(f"  {'phase':<22} {'ms':>9} {'share':>7}")
    for name, ms in out["phases_ms"].items():
        print(f"  {name:<22} {ms:>9.3f} {100 * ms / step_ms:>6.1f}%")
    busy = sum(out["phases_ms"].values())
    print(f"  {'(sum)':<22} {busy:>9.3f} {100 * busy / step_ms:>6.1f}%")
    if out["unscoped_ops_ms"]:
        print("  unscoped ops (ms): " + ", ".join(
            f"{n} {ms:.3f}" for n, ms in out["unscoped_ops_ms"].items()))
    if out["host_spans"]:
        print(f"  {'host span':<22} {'count':>7} {'total s':>10}")
        for name, h in out["host_spans"].items():
            print(f"  {name:<22} {h['count']:>7} {h['total_s']:>10.4f}")
    if out["boundaries"]:
        _print_boundaries(out["boundaries"])
    return 0


def _print_boundaries(b: dict) -> None:
    """The "between epochs" block of ``profile --phases``
    (``obs.profile.boundaries``)."""
    n = b["boundaries"]
    print(f"  between epochs: {n} {'boundary' if n == 1 else 'boundaries'}, "
          f"{b['devices']} device(s); gap {b['gap_ms']['median']:.3f} ms "
          f"(largest {b['gap_ms']['max']:.3f}), of it the device idle "
          f"{b['idle_ms']['median']:.3f} ms (largest "
          f"{b['idle_ms']['max']:.3f}; all of them {b['idle_ms']['sum']:.3f})")
    print(f"  {'idle under':<22} {'median ms':>10} {'largest':>10}")
    for name, ms in b["idle_split_ms"].items():
        print(f"  {name:<22} {ms['median']:>10.3f} {ms['max']:>10.3f}")
    for name, ms in b["first_ms"].items():
        steady = b["steady_ms"].get(name)
        print(f"  an epoch's first {name}: {ms['median']:.3f} ms (largest "
              f"{ms['max']:.3f})" + ("" if steady is None else
                                     f"; the others' median {steady:.3f}"))
    edges = b["edges_ms"]
    print(f"  the window's edges: idle {edges['open']['idle']:.3f} ms before "
          f"the first step, {edges['close']['idle']:.3f} after the last; "
          f"inside and between steps {b['in_steps_idle_ms']:.3f}")


# ---- top ----

def _scrape(url: str, timeout: float = 2.0) -> dict[str, float]:
    """One /metrics scrape → {metric_name: value} (labels stripped; the
    last sample of a name wins).  Any failure returns {} — top renders
    from the journal alone."""
    import urllib.request

    try:
        text = urllib.request.urlopen(url, timeout=timeout).read().decode()
    except Exception:
        return {}
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        try:
            key, val = line.rsplit(" ", 1)
            name = key.split("{", 1)[0]
            out[name] = float(val)
        except ValueError:
            continue
    return out


def _render_top(base: str, urls: list[str],
                cache: dict | None = None) -> list[str] | None:
    data = _build_summary(base, cache=cache)
    if data is None:
        return None
    events = data.pop("_events")
    now = time.time()
    scraped: dict[str, float] = {}
    reachable = 0
    for url in urls:
        got = _scrape(url)
        if got:
            reachable += 1
            scraped.update(got)
    lines = []
    age = now - data["t1"]
    lines.append(
        f"obs top — {base}  |  {data['events']} events, last {age:.1f}s ago"
        + (f"  |  job {', '.join(data['jobs'])}" if data["jobs"] else "")
        + (f"  |  scraped {reachable}/{len(urls)} targets" if urls else "")
    )
    lines.append("")
    # slo: journaled transitions + live gauges when a scrape answered
    slo = data["slo"]
    gauge_signals = sorted({
        m[len("stpu_slo_"):].removesuffix("_breached").removesuffix(
            "_target").removesuffix("_z")
        for m in scraped if m.startswith("stpu_slo_")
    })
    if slo or gauge_signals:
        lines.append("slo")
        names = sorted(set(slo) | set(gauge_signals))
        lines.append("  signal            state      value      target")
        for name in names:
            s = slo.get(name, {})
            value = scraped.get(f"stpu_slo_{name}", s.get("last_value"))
            target = scraped.get(f"stpu_slo_{name}_target", s.get("target"))
            live = scraped.get(f"stpu_slo_{name}_breached")
            breached = bool(live) if live is not None \
                else s.get("breached", False)
            lines.append(
                f"  {name:<17} {'BREACHED' if breached else 'ok':<10} "
                f"{'?' if value is None else f'{value:.4g}':<10} "
                f"{'-' if not target else f'{target:.4g}'}"
            )
        lines.append("")
    # train budget
    budget = data["budget"]
    if budget:
        lines.append("train")
        lines.append("  worker  epochs  steps  step_ms   infeed%  other%")
        for w, a in budget.items():
            lines.append(
                f"  {w:<7} {a['epochs']:<7} {a['steps']:<6} "
                f"{a['step_ms']:<9.3f} {a['pct']['infeed']:<8.1f} "
                f"{a['pct']['other']:.1f}"
            )
        lines.append("")
    # fleet panel: per-rank skew + straggler state beside the serve
    # panel (journal-fed; the live stpu_fleet_* gauges ride the
    # coordinator metrics op, which top's --metrics-url can scrape)
    fleet = data.get("fleet") or {}
    if fleet.get("ranks") or fleet.get("excursions"):
        lines.append("fleet")
        for line in _render_fleet(fleet, data["t0"]):
            lines.append(line)
        lines.append("")
    # data panel: per-model drift state from the journaled windowed
    # sketches (live stpu_data_* gauges ride the same /metrics scrape
    # as everything else when --metrics-url is given)
    data_leg = data.get("data") or {}
    if data_leg:
        lines.append("data")
        for line in _render_data_brief(data_leg):
            lines.append(line)
        lines.append("")
    # serve plane: journal rows, live counters when scraped
    serve = data["serve"]
    if serve and (serve["workers"] or serve["fleet"]["workers"]):
        lines.append("serve")
        for line in _render_serve(serve):
            lines.append(line)
        live_reqs = scraped.get("stpu_serve_requests_total")
        if live_reqs is not None:
            lines.append(
                f"  live: requests {int(live_reqs)}, shed "
                f"{int(scraped.get('stpu_serve_shed_total', 0))}, queue "
                f"{int(scraped.get('stpu_serve_queue_rows', 0))} rows "
                f"(one scraped worker's view)"
            )
        lines.append("")
    lines.append("recent events")
    t0 = data["t0"]
    timeline = [e for e in events if e.get("event") not in _BULK_EVENTS]
    for ev in timeline[-8:]:
        lines.append(" " + _fmt_event(ev, t0))
    return lines


# ---- bulk scoring job reconstruction ----

SCORE_SCHEMA = "stpu.obs.score/1"


def _score_data(events: list[dict]) -> dict:
    """One score job's story out of the journal: the driver emits
    ``score_job_start``/``score_job_finished`` and the lease table
    emits every ``lease_*`` / ``shard_commit`` / duplicate transition —
    enough to reconstruct shard ownership history, per-worker commit
    counts, and the exactly-once audit (committed vs duplicate tokens)
    from a dead fleet's files alone."""
    jobs: dict = {}
    # the lease table emits its events without a job field (it predates
    # nothing — it simply doesn't know the id); attribute them to the
    # most recently STARTED job, which is correct because one driver
    # runs one job at a time and events are merged time-ordered
    current: list = [None]

    def job(ev) -> dict:
        key = ev.get("job") or current[0] or "?"
        return jobs.setdefault(key, {
            "job": key, "start_ts": None, "finish_ts": None,
            "shards": None, "noop": False, "rows": None,
            "committed": {}, "duplicates": [], "grants": 0,
            "expiries": [], "reclaims": [], "workers": {},
            "timeline": [],
        })

    for ev in events:
        kind = ev.get("event")
        if kind == "score_job_start":
            current[0] = ev.get("job") or current[0]
            j = job(ev)
            j["start_ts"] = ev.get("ts")
            j["shards"] = ev.get("shards")
            j["noop"] = bool(ev.get("noop"))
            j["timeline"].append(ev)
        elif kind == "score_job_finished":
            j = job(ev)
            j["finish_ts"] = ev.get("ts")
            j["rows"] = ev.get("rows")
            j["noop"] = bool(ev.get("noop")) or j["noop"]
            j["timeline"].append(ev)
        elif kind in ("lease_grant", "lease_expire", "lease_reclaim",
                      "shard_commit", "shard_discarded_duplicate"):
            j = job(ev)
            j["timeline"].append(ev)
            if kind == "lease_grant":
                j["grants"] += 1
            elif kind == "lease_expire":
                j["expiries"].append(ev)
            elif kind == "lease_reclaim":
                j["reclaims"].append(ev)
            elif kind == "shard_commit":
                j["committed"][ev.get("shard")] = ev
                w = ev.get("worker") or "?"
                j["workers"][w] = j["workers"].get(w, 0) + 1
            else:
                j["duplicates"].append(ev)
    out = [j for j in jobs.values() if j["timeline"]]
    if not out:
        return {}
    for j in out:
        j["committed_rows"] = sum(
            int(e.get("rows") or 0) for e in j["committed"].values())
        tokens = [e.get("lease") for e in j["committed"].values()]
        j["duplicate_committed_tokens"] = len(tokens) - len(set(tokens))
    return {"schema": SCORE_SCHEMA, "jobs": out}


def _render_score(data: dict, t0: float) -> list[str]:
    lines: list[str] = []
    for j in data["jobs"]:
        n_committed = len(j["committed"])
        total = j["shards"] if j["shards"] is not None else "?"
        state = ("no-op (already sealed)" if j["noop"]
                 else "finished" if j["finish_ts"] is not None
                 else "RUNNING/DEAD")
        lines.append(f"score job {j['job']} — {state}: "
                     f"{n_committed}/{total} shard(s) committed, "
                     f"{j['committed_rows']} row(s)")
        lines.append(f"  grants {j['grants']}  expiries "
                     f"{len(j['expiries'])}  reclaims "
                     f"{len(j['reclaims'])}  duplicates discarded "
                     f"{len(j['duplicates'])}  duplicate committed "
                     f"tokens {j['duplicate_committed_tokens']}")
        if j["workers"]:
            per = "  ".join(f"{w}={n}" for w, n in
                            sorted(j["workers"].items()))
            lines.append(f"  commits by worker: {per}")
        for ev in j["timeline"]:
            lines.append(" " + _fmt_event(ev, t0))
    return lines


def cmd_score(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _score_data(events)
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0 if data else 1
    if not data:
        print("no score-plane events — run the job with "
              "`python -m shifu_tensorflow_tpu.score run --journal ...`")
        return 1
    t0 = events[0].get("ts", 0.0)
    for line in _render_score(data, t0):
        print(line)
    return 0


# ---- lifecycle reconstruction ----

LIFECYCLE_SCHEMA = "stpu.obs.lifecycle/1"

#: controller-plane events that open/advance/close a lifecycle cycle
_CYCLE_EVENTS = (
    "lifecycle_trigger", "retrain_start", "retrain_done", "shadow_admit",
    "ramp_step", "promote", "rollback",
)


def _lifecycle_data(events: list[dict],
                    model: str | None = None) -> dict:
    """Lifecycle cycles out of the journal: the controller's ``.l``
    writer emits every transition with its evidence, the serve workers
    echo ``lifecycle_ctl_applied`` / ``weight_change`` as they converge
    on the ctl intent — together enough to reconstruct each cycle
    (trigger → retrain → shadow → ramp → verdict), its wall-clock
    latency, and whether the fleet actually actuated each step, from a
    dead fleet's files alone."""
    cycles: list = []
    open_by_model: dict = {}

    def cycle_for(ev, *, open_new: bool) -> dict | None:
        m = ev.get("model") or "?"
        c = open_by_model.get(m)
        if c is None and open_new:
            c = {
                "model": m, "trigger_ts": None, "verdict": None,
                "verdict_ts": None, "generation": None,
                "evidence": None, "retrain": None, "ramp_steps": [],
                "ctl_applied": [], "weight_changes": [],
                "timeline": [],
            }
            open_by_model[m] = c
            cycles.append(c)
        return c

    for ev in events:
        kind = ev.get("event")
        m = ev.get("model")
        if model is not None and m is not None and m != model \
                and not str(m).startswith(f"{model}."):
            continue
        if kind == "lifecycle_trigger":
            # a trigger while a cycle is open means the previous
            # controller died verdict-less: close it as such
            stale = open_by_model.pop(m or "?", None)
            if stale is not None and stale["verdict"] is None:
                stale["verdict"] = "abandoned"
            c = cycle_for(ev, open_new=True)
            c["trigger_ts"] = ev.get("ts")
            c["evidence"] = ev.get("evidence") or ev.get("signals")
            c["timeline"].append(ev)
        elif kind in _CYCLE_EVENTS:
            c = cycle_for(ev, open_new=True)
            c["timeline"].append(ev)
            if kind == "retrain_start":
                c["generation"] = ev.get("generation", c["generation"])
            elif kind == "retrain_done":
                c["retrain"] = {
                    "ok": bool(ev.get("ok")), "rc": ev.get("rc"),
                    "why": ev.get("why"),
                    "duration_s": ev.get("duration_s"),
                }
            elif kind == "ramp_step":
                c["ramp_steps"].append(ev.get("fraction"))
            elif kind in ("promote", "rollback"):
                c["verdict"] = kind
                c["verdict_ts"] = ev.get("ts")
                if kind == "rollback":
                    c["rollback_reason"] = ev.get("reason")
                open_by_model.pop(c["model"], None)
        elif kind == "lifecycle_ctl_applied":
            for c in cycles:
                if c["verdict"] is None:
                    c["ctl_applied"].append(ev)
                    c["timeline"].append(ev)
        elif kind == "weight_change":
            for c in cycles:
                if c["verdict"] is None:
                    c["weight_changes"].append(ev)
                    c["timeline"].append(ev)
    if model is not None:
        cycles = [c for c in cycles if c["model"] == model]
    if not cycles:
        return {}
    for c in cycles:
        if c["trigger_ts"] is not None and c["verdict_ts"] is not None:
            c["latency_s"] = round(c["verdict_ts"] - c["trigger_ts"], 3)
        else:
            c["latency_s"] = None
        if c["verdict"] is None:
            c["verdict"] = "in-flight"
    return {"schema": LIFECYCLE_SCHEMA, "cycles": cycles}


def _render_lifecycle(data: dict, t0: float) -> list[str]:
    lines: list[str] = []
    for i, c in enumerate(data["cycles"]):
        gen = (f" gen {c['generation']}"
               if c["generation"] is not None else "")
        lat = (f" in {c['latency_s']}s"
               if c["latency_s"] is not None else "")
        lines.append(f"cycle {i} — model {c['model']}{gen}: "
                     f"{c['verdict'].upper()}{lat}")
        if c.get("evidence"):
            lines.append(f"  trigger evidence: {_short(c['evidence'])}")
        r = c.get("retrain")
        if r:
            state = "ok" if r["ok"] else f"FAILED ({r.get('why')})"
            dur = (f" in {r['duration_s']:.1f}s"
                   if isinstance(r.get("duration_s"), (int, float))
                   else "")
            lines.append(f"  retrain: {state} rc={r.get('rc')}{dur}")
        if c["ramp_steps"]:
            lines.append("  ramp: " + " -> ".join(
                f"{f:g}" for f in c["ramp_steps"] if f is not None))
        if c.get("rollback_reason"):
            lines.append(f"  rollback reason: {c['rollback_reason']}")
        lines.append(f"  fleet actuation: {len(c['ctl_applied'])} ctl "
                     f"apply(s), {len(c['weight_changes'])} weight "
                     f"change(s)")
        for ev in c["timeline"]:
            lines.append(" " + _fmt_event(ev, t0))
    return lines


def cmd_lifecycle(args) -> int:
    events = read_events(args.journal)
    if not events:
        print(f"no journal events under {args.journal!r} "
              f"(files: {journal_files(args.journal) or 'none'})",
              file=sys.stderr)
        return 1
    data = _lifecycle_data(events, model=args.model)
    if args.as_json:
        print(json.dumps(data, indent=2, default=str))
        return 0 if data else 1
    if not data:
        print("no lifecycle events — run the controller with "
              "`python -m shifu_tensorflow_tpu.lifecycle run ...` "
              "against this journal")
        return 1
    t0 = events[0].get("ts", 0.0)
    for line in _render_lifecycle(data, t0):
        print(line)
    return 0


def cmd_top(args) -> int:
    # per-file parse cache: rotated journal files are immutable, so each
    # refresh re-reads only the growing active files, not the whole
    # rotation set ("tail", not "re-read everything, every 2 seconds")
    cache: dict = {}
    while True:
        frame = _render_top(args.journal, args.metrics_urls, cache)
        if frame is None:
            print(f"no journal events under {args.journal!r} "
                  f"(files: {journal_files(args.journal) or 'none'})",
                  file=sys.stderr)
            return 1
        if args.once:
            print("\n".join(frame))
            return 0
        # ANSI clear + home: a plain terminal dashboard, no curses dep
        sys.stdout.write("\x1b[2J\x1b[H" + "\n".join(frame) + "\n")
        sys.stdout.flush()
        try:
            time.sleep(max(0.2, args.interval))
        except KeyboardInterrupt:
            return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    try:
        if args.cmd == "tail":
            return cmd_tail(args)
        if args.cmd == "trace":
            return cmd_trace(args)
        if args.cmd == "top":
            return cmd_top(args)
        if args.cmd == "fleet":
            return cmd_fleet(args)
        if args.cmd == "report":
            return cmd_report(args)
        if args.cmd == "diff":
            return cmd_diff(args)
        if args.cmd == "data":
            return cmd_data(args)
        if args.cmd == "compile":
            return cmd_compile(args)
        if args.cmd == "mem":
            return cmd_mem(args)
        if args.cmd == "profile":
            return cmd_profile(args)
        if args.cmd == "score":
            return cmd_score(args)
        if args.cmd == "lifecycle":
            return cmd_lifecycle(args)
        return cmd_summary(args)
    except KeyboardInterrupt:
        return 0
    except BrokenPipeError:
        # `... | head` closes our stdout mid-timeline; that is the
        # reader's prerogative, not an error
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0


if __name__ == "__main__":
    sys.exit(main())
