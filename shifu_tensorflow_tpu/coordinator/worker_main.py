"""Subprocess worker entry: ``python -m shifu_tensorflow_tpu.coordinator.worker_main``.

The reference launched each worker as a real OS process in a YARN container
(AMRMCallbackHandler.java:159-182) with its configuration passed through
environment variables and localized files
(TensorflowTaskExecutor.java:200-238).  This is the equivalent launch shim:
the submitter writes the WorkerConfig as JSON (file or inline), spawns this
module, and consumes the process exit code — which makes kill-based fault
tolerance real (SIGKILL the process, watch checkpoint-restart recover),
something thread workers cannot model.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="worker_main")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--config-file", help="path to a WorkerConfig JSON file")
    g.add_argument("--config-json", help="inline WorkerConfig JSON")
    g.add_argument("--config-stdin", action="store_true",
                   help="read WorkerConfig JSON from stdin (remote launch: "
                        "no shared filesystem required)")
    p.add_argument("--fail-at-epoch", type=int, default=None,
                   help="fault injection: abort at this epoch (tests)")
    p.add_argument("--run-tag", default=None,
                   help="opaque marker on the command line; the remote "
                        "launcher kills by matching it (pkill -f)")
    args = p.parse_args(argv)

    if args.config_file:
        with open(args.config_file) as f:
            payload = json.load(f)
    elif args.config_stdin:
        payload = json.loads(sys.stdin.read())
    else:
        payload = json.loads(args.config_json)

    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig, run_worker
    from shifu_tensorflow_tpu.obs.compile import apply_persistent_cache

    cfg = WorkerConfig.from_json(payload)
    # a remote (ssh) worker inherits no environment from the submitter,
    # so the configured directory rides the obs dict
    apply_persistent_cache((cfg.obs or {}).get("compile_cache_dir", ""))
    return run_worker(cfg, fail_at_epoch=args.fail_at_epoch)


if __name__ == "__main__":
    sys.exit(main())
