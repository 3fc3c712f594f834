"""Bulk scoring CLI.

    # drive a whole job (plans, serves leases, spawns the scan fleet,
    # audits, seals _SUCCESS; re-run of a finished job is a no-op):
    python -m shifu_tensorflow_tpu.score run \
        --input /data/eval --models /models --output /data/scored \
        --workers 2 --journal /tmp/score.jsonl

    # one scorer process (normally spawned by `run`; exposed for the
    # kill drills and for pointing extra workers at a live driver):
    python -m shifu_tensorflow_tpu.score worker \
        --coordinator 127.0.0.1:41333 --worker-id scorer-9

Output: ``part-<shard>.psv`` + digest sidecars + ``_SUCCESS`` in
``--output``; rows are ``|``-joined per-tenant scores in sorted-tenant
order.  See docs/scoring.md for the lease/commit protocol and the
re-run/resume runbook.
"""

from __future__ import annotations

import argparse
import json
import sys

from shifu_tensorflow_tpu.config import keys as K


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shifu_tensorflow_tpu.score",
        description="Exactly-once bulk scoring over the worker fleet.",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="drive one scoring job end to end")
    run.add_argument("--input", required=True,
                     help="input data dir (PSV feature rows; dot/underscore"
                          "-prefixed files are invisible)")
    run.add_argument("--models", required=True,
                     help="models dir: one export bundle, or a multi-tenant"
                          " dir of bundles — every tenant scores the scan")
    run.add_argument("--output", required=True,
                     help="output dir (part-*.psv + sidecars + _SUCCESS)")
    run.add_argument("--tenants", default=None,
                     help="comma-separated tenant subset (default: all "
                          "discovered bundles)")
    run.add_argument("--workers", type=int, default=K.DEFAULT_SCORE_WORKERS,
                     help=f"scan fleet size (shifu.tpu.score-workers; "
                          f"default {K.DEFAULT_SCORE_WORKERS})")
    run.add_argument("--max-shards", type=int,
                     default=K.DEFAULT_SCORE_MAX_SHARDS,
                     help="cap the shard plan (0 = one shard per file)")
    run.add_argument("--lease-ttl-s", type=float,
                     default=K.DEFAULT_SCORE_LEASE_TTL_S,
                     help="lease ttl seconds (shifu.tpu.score-lease-ttl)")
    run.add_argument("--speculate-factor", type=float,
                     default=K.DEFAULT_SCORE_SPECULATE_FACTOR,
                     help="straggler speculation trigger, x median shard "
                          "duration (0 disables)")
    run.add_argument("--batch-rows", type=int,
                     default=K.DEFAULT_SCORE_BATCH_ROWS,
                     help="rows per compute_batch dispatch")
    run.add_argument("--backend", default="native")
    run.add_argument("--worker-mode", choices=("process", "thread"),
                     default="process")
    run.add_argument("--timeout-s", type=float, default=600.0)
    run.add_argument("--journal", default=None,
                     help="obs journal base path — job/lease/commit events "
                          "land here for `obs score` reconstruction")
    run.add_argument("--json", action="store_true", dest="as_json",
                     help="print the job summary as JSON")

    w = sub.add_parser("worker", help="one scorer process")
    w.add_argument("--coordinator", required=True, help="host:port")
    w.add_argument("--worker-id", required=True)
    w.add_argument("--backend", default="native")
    w.add_argument("--poll-s", type=float, default=0.2)
    return p


def cmd_run(args) -> int:
    from shifu_tensorflow_tpu.score.job import run_job

    if args.journal:
        from shifu_tensorflow_tpu.obs import journal as obs_journal

        obs_journal.install(obs_journal.Journal(args.journal, plane="score"))
    tenants = ([t for t in args.tenants.split(",") if t]
               if args.tenants else None)
    summary = run_job(
        args.input, args.models, args.output,
        workers=args.workers, tenants=tenants,
        max_shards=args.max_shards, ttl_s=args.lease_ttl_s,
        speculate_factor=args.speculate_factor,
        batch_rows=args.batch_rows, backend=args.backend,
        worker_mode=args.worker_mode, timeout_s=args.timeout_s,
    )
    if args.as_json:
        print(json.dumps(summary, indent=2))
    else:
        print(f"score job {summary['job_id']}: "
              + ("no-op (already sealed); " if summary["noop"] else "")
              + f"{summary['shards']} shard(s), {summary['rows']} row(s), "
                f"{summary['duplicates']} duplicate(s), "
                f"{summary['reclaims']} reclaim(s)")
    return 0


def cmd_worker(args) -> int:
    from shifu_tensorflow_tpu.coordinator.coordinator import CoordinatorClient
    from shifu_tensorflow_tpu.score.worker import run_worker

    host, port = args.coordinator.rsplit(":", 1)
    client = CoordinatorClient(host, int(port), timeout_s=60.0)
    counters = run_worker(client, args.worker_id, backend=args.backend,
                          poll_s=args.poll_s)
    print(json.dumps({"worker": args.worker_id, **counters}))
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # the coordinator process stays jax-free: the setting reaches its
    # scoring workers through the environment
    from shifu_tensorflow_tpu.obs.compile import apply_persistent_cache

    apply_persistent_cache()
    if args.cmd == "run":
        return cmd_run(args)
    return cmd_worker(args)


if __name__ == "__main__":
    sys.exit(main())
