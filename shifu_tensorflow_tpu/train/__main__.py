"""Training CLI — the client surface of the framework.

Parity surface: the reference's entry point is ``TensorflowClient`` — parse
``-globalconfig``/CLI args, merge the layered XML config, stage
ModelConfig.json/ColumnConfig.json, submit the job, and tail per-epoch
progress to the console (TensorflowClient.java:211-290,333-403,625-658).
Here the same surface is one command:

    python -m shifu_tensorflow_tpu.train \
        --training-data-path /data/train \
        --model-config ModelConfig.json --column-config ColumnConfig.json \
        --workers 2 --export-dir ./model-export

Config precedence (reference three-layer merge, conf.Conf): built-in
defaults → ``--globalconfig`` file(s) → explicit CLI flags.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from shifu_tensorflow_tpu.config import keys as K
from shifu_tensorflow_tpu.config.conf import Conf
from shifu_tensorflow_tpu.config.model_config import ColumnConfig, ModelConfig
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.utils import retry as _retry_util


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shifu_tensorflow_tpu.train",
        description="Train a config-driven tabular model on TPU (or CPU).",
    )
    p.add_argument("--training-data-path", help="file/dir of PSV(.gz) shards")
    p.add_argument("--globalconfig", action="append", default=[],
                   help="layered config file (XML or JSON); repeatable, later wins")
    p.add_argument("--model-config", help="ModelConfig.json path")
    p.add_argument("--column-config", help="ColumnConfig.json path")
    # schema overrides (when no ColumnConfig.json)
    p.add_argument("--feature-columns", help="comma-separated column indices")
    p.add_argument("--target-column", type=int, default=None)
    p.add_argument("--weight-column", type=int, default=None)
    p.add_argument("--delimiter", default=None)
    p.add_argument("--zscale", action="store_true",
                   help="apply ZSCALE normalization from ColumnConfig stats")
    # run shape
    p.add_argument("--workers", type=int, default=None,
                   help="worker count; >1 runs the coordinator/submitter path")
    p.add_argument("--launcher", choices=["process", "thread"],
                   default="process",
                   help="multi-worker launch mode: real OS processes "
                        "(default; required for SPMD) or in-process threads")
    spmd = p.add_mutually_exclusive_group()
    spmd.add_argument("--spmd", dest="spmd", action="store_true", default=None,
                      help="train ONE model across workers via "
                           "jax.distributed gradient all-reduce (default "
                           "with --launcher process)")
    spmd.add_argument("--no-spmd", dest="spmd", action="store_false",
                      help="independent per-worker models; only the chief's "
                           "checkpoint is exported")
    p.add_argument("--standby-workers", type=int, default=None,
                   dest="standby_workers",
                   help="hot standbys launched beside the fleet "
                        "(shifu.tpu.standby-workers): each pre-builds "
                        "its model (compile warm, no shard) and takes "
                        "over a dead rank on promotion instead of the "
                        "fleet restarting from checkpoint.  Default 0")
    elastic = p.add_mutually_exclusive_group()
    elastic.add_argument("--elastic", dest="elastic", action="store_true",
                         default=None,
                         help="shrink instead of failing when a rank "
                              "dies with no standby and no restart "
                              "budget left: the data re-splits "
                              "deterministically over the survivors "
                              "(shifu.tpu.elastic; non-SPMD fleets)")
    elastic.add_argument("--no-elastic", dest="elastic",
                         action="store_false",
                         help="fail the job on budget exhaustion (the "
                              "default)")
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--valid-rate", type=float, default=None)
    p.add_argument("--mesh", default=None,
                   help='mesh spec, e.g. "data:-1" or "data:4,model:2"')
    p.add_argument("--stream", action="store_true",
                   help="stream shards (1B-row path) instead of loading to RAM")
    p.add_argument("--readers", type=int, default=None,
                   help="parallel shard-reader threads for --stream "
                        "(shifu.tpu.data-readers; default auto: the "
                        "ingest autotuner sizes it between epochs; an "
                        "explicit value pins the dimension.  Batch order "
                        "is reproducible at any reader count)")
    p.add_argument("--decode-workers", type=int, default=None,
                   help="parse/finalize/cast pool width for --stream "
                        "(shifu.tpu.data-decode-workers; default auto/"
                        "autotuned; explicit value pins it)")
    p.add_argument("--data-prefetch", type=int, default=None,
                   help="device-put pipeline depth for --stream "
                        "(shifu.tpu.data-prefetch; default auto: starts "
                        "at shifu.tpu.prefetch-depth, then autotuned; "
                        "explicit value pins it)")
    tune = p.add_mutually_exclusive_group()
    tune.add_argument("--data-autotune", dest="data_autotune",
                      action="store_true", default=None,
                      help="size readers/decode/prefetch from live stage "
                           "span ratios between epochs (the default; "
                           "shifu.tpu.data-autotune)")
    tune.add_argument("--no-data-autotune", dest="data_autotune",
                      action="store_false",
                      help="freeze the ingest knobs at their resolved "
                           "values")
    p.add_argument("--shuffle-rows", type=int, default=None,
                   help="seeded shuffle-buffer window for --stream, in "
                        "rows (shifu.tpu.data-shuffle-rows; default 0 = "
                        "off).  Deterministic per seed at any "
                        "parallelism")
    p.add_argument("--cache-dir", default=None,
                   help="binary shard cache dir: text shards parse once, "
                        "later epochs stream memory-mapped tensors")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="compute dtype (default float32; bfloat16 feeds the "
                        "MXU at full rate on TPU)")
    p.add_argument("--device-resident", action="store_true",
                   help="keep the whole dataset in device memory and run "
                        "each epoch as ONE compiled program (on-device "
                        "shuffle + scanned steps); single-process, "
                        "dataset must fit in HBM")
    p.add_argument("--scan-steps", type=int, default=None,
                   help="batches per lax.scan dispatch (default 1 = one "
                        "dispatch per step; raise to amortize dispatch "
                        "latency when steps are short)")
    p.add_argument("--accum-steps", type=int, default=None,
                   help="microbatches per optimizer update (default 1 = "
                        "off); the update equals one step on the "
                        "concatenated batch — effective batch sizes "
                        "beyond device memory")
    p.add_argument("--early-stop-ks", type=float, default=None,
                   help="stop once validation KS reaches this target "
                        "(default 0 = off); multi-worker fleets stop "
                        "coordinated via the epoch barrier")
    p.add_argument("--early-stop-patience", type=int, default=None,
                   help="stop after N epochs without validation-loss "
                        "improvement (default 0 = off); multi-worker "
                        "fleets stop coordinated via the epoch barrier")
    p.add_argument("--keep-best", default=None,
                   choices=["valid_loss", "ks"],
                   help="snapshot params at the best validation epoch and "
                        "export THAT model instead of the last epoch's; "
                        "fleets persist the chief's snapshot beside the "
                        "shared checkpoints")
    # artifacts
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--export-dir", default=None)
    p.add_argument("--export-aot", action="store_true", default=None,
                   dest="export_aot",
                   help="compile the serve bucket ladder at export and "
                        "ship serialized XLA executables in the bundle "
                        "(shifu.tpu.export-aot): serve admission then "
                        "DESERIALIZES instead of compiling, falling "
                        "back per bucket on environment mismatch")
    p.add_argument("--export-aot-rows", type=int, default=None,
                   dest="export_aot_rows",
                   help="pre-compile the ladder covering batches up to "
                        "this many rows (shifu.tpu.export-aot-rows; "
                        "default matches the serve plane's warm set, "
                        "ladder(serve-queue-rows))")
    p.add_argument("--export-parent-sha", default=None,
                   dest="export_parent_sha",
                   help="generation lineage: the weights sha256 of the "
                        "bundle this retrain descends from, stamped "
                        "into the export manifest (the lifecycle "
                        "controller's rollback target); omit for a "
                        "root export")
    p.add_argument("--export-generation", type=int, default=None,
                   dest="export_generation",
                   help="generation lineage: monotonic generation "
                        "number stamped into the export manifest "
                        "(default: absent — legacy readers treat it "
                        "as 0)")
    p.add_argument("--compile-cache-dir", default=None,
                   dest="compile_cache_dir",
                   help="jax persistent compilation cache dir "
                        "(shifu.tpu.compile-cache-dir): programs that "
                        "do compile persist here, so the next "
                        "process/restart on this host skips XLA.  "
                        "JAX_COMPILATION_CACHE_DIR wins where set; with "
                        "neither, <checkout>/.jax_cache")
    p.add_argument("--board-path", default=None,
                   help="metrics board file (reference console-board parity)")
    p.add_argument("--profile-dir", default=None,
                   help="write jax.profiler traces for the run here")
    # observability plane (shifu.tpu.obs-*): step-phase tracing + the
    # fleet event journal; --obs-journal implies --obs
    p.add_argument("--obs", action="store_true", default=None,
                   help="enable the observability plane: per-epoch "
                        "infeed/host/dispatch/block step breakdown and "
                        "lifecycle spans")
    p.add_argument("--obs-journal", default=None, dest="obs_journal",
                   help="event-journal base path (implies --obs); fleet "
                        "workers write <path>.w<i>; read with "
                        "`python -m shifu_tensorflow_tpu.obs summary`")
    return p


def resolve_lineage(args: argparse.Namespace) -> dict | None:
    """The manifest lineage stamp from the CLI flags, or None when
    neither was given (a root export — the manifest then carries no
    ``lineage`` key, exactly like every pre-lifecycle bundle)."""
    if args.export_parent_sha is None and args.export_generation is None:
        return None
    lineage: dict = {}
    if args.export_parent_sha is not None:
        lineage["parent_sha256"] = args.export_parent_sha
    if args.export_generation is not None:
        lineage["generation"] = int(args.export_generation)
    return lineage


def load_conf(args: argparse.Namespace) -> Conf:
    conf = Conf()
    for path in args.globalconfig:
        conf.add_resource(path)
    # CLI flags overlay the file layers (the reference's "programmatic" layer)
    overlay = {
        K.TRAINING_DATA_PATH: args.training_data_path,
        K.EPOCHS: args.epochs,
        K.BATCH_SIZE: args.batch_size,
        K.MESH_SHAPE: args.mesh,
        K.instances_key(K.WORKER_JOB_NAME): args.workers,
        K.MODEL_CONF: args.model_config,
        K.COLUMN_CONF: args.column_config,
        K.TMP_MODEL_PATH: args.checkpoint_dir,
        K.FINAL_MODEL_PATH: args.export_dir,
        K.TMP_LOG_PATH: args.board_path,
        K.CACHE_DIR: args.cache_dir,
        K.DTYPE: args.dtype,
    }
    conf.update({k: v for k, v in overlay.items() if v is not None},
                source="<cli>")
    return conf


def resolve_schema(
    args: argparse.Namespace, model_config: ModelConfig
) -> tuple[RecordSchema, ColumnConfig | None]:
    """ColumnConfig.json drives column selection when given (the reference's
    Java client derived SELECTED/TARGET/WEIGHT column env vars from it,
    TensorflowClient.java:378-382); explicit flags override."""
    cc = ColumnConfig.load(args.column_config) if args.column_config else None
    if args.feature_columns:
        features = tuple(int(c) for c in args.feature_columns.split(","))
    elif cc is not None:
        features = tuple(cc.selected_column_nums)
    else:
        raise SystemExit(
            "need --feature-columns or --column-config to define the schema"
        )
    target = (
        args.target_column
        if args.target_column is not None
        else (cc.target_column_num if cc else K.DEFAULT_TARGET_COLUMN_NUM)
    )
    weight = (
        args.weight_column
        if args.weight_column is not None
        else (cc.weight_column_num if cc else K.DEFAULT_WEIGHT_COLUMN_NUM)
    )
    schema = RecordSchema(
        feature_columns=features,
        target_column=target,
        weight_column=weight,
        delimiter=args.delimiter or model_config.delimiter,
    )
    if args.zscale:
        if cc is None:
            raise SystemExit("--zscale needs --column-config for the stats")
        means, stds = cc.zscale_stats(features)
        schema = schema.with_zscale(means, stds)
    return schema, cc


def trainer_extras(args, conf: Conf) -> dict:
    """Trainer kwargs resolved through the conf layer: the CLI flag wins,
    then the conf key, then the built-in default — so a globalconfig can
    set shifu.tpu.dtype / shifu.tpu.prefetch-depth without flags."""
    import jax.numpy as jnp

    dtype_name = args.dtype or conf.get(K.DTYPE, K.DEFAULT_DTYPE)
    try:
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[dtype_name]
    except KeyError:
        raise SystemExit(
            f"unsupported {K.DTYPE}={dtype_name!r} (float32 | bfloat16)"
        )
    return {
        "dtype": dtype,
        "dtype_name": dtype_name,
        "prefetch_depth": conf.get_int(K.PREFETCH_DEPTH,
                                       K.DEFAULT_PREFETCH_DEPTH),
        "scan_steps": resolve_scan_steps(args, conf),
        "accum_steps": resolve_accum_steps(args, conf),
        "keep_best": resolve_keep_best(args, conf),
        "health": resolve_health(conf),
    }


def resolve_ingest(args, conf: Conf) -> dict:
    """shifu.tpu.data-* -> staged-ingest knob values with the usual
    CLI-wins precedence.  0/None = auto (the autotuner sizes the
    dimension between epochs); an explicit value pins its dimension
    (data/autotune.resolve_ingest_knobs).  ONE resolver for both run
    paths and the wiring tests."""
    def pick(cli, key, default):
        if cli is not None:
            return cli
        return conf.get_int(key, default)

    autotune = (args.data_autotune if getattr(args, "data_autotune", None)
                is not None
                else conf.get_bool(K.DATA_AUTOTUNE, K.DEFAULT_DATA_AUTOTUNE))
    return {
        "readers": pick(getattr(args, "readers", None),
                        K.DATA_READERS, K.DEFAULT_DATA_READERS),
        "decode_workers": pick(getattr(args, "decode_workers", None),
                               K.DATA_DECODE_WORKERS,
                               K.DEFAULT_DATA_DECODE_WORKERS),
        "prefetch": pick(getattr(args, "data_prefetch", None),
                         K.DATA_PREFETCH, K.DEFAULT_DATA_PREFETCH),
        "autotune": bool(autotune),
        "shuffle_rows": pick(getattr(args, "shuffle_rows", None),
                             K.DATA_SHUFFLE_ROWS,
                             K.DEFAULT_DATA_SHUFFLE_ROWS),
    }


def resolve_obs(args, conf: Conf):
    """shifu.tpu.obs-* -> ObsConfig with the usual CLI-wins precedence —
    ONE resolver for both run paths (and the wiring tests), so a fleet
    can never trace under a different policy than a single-process run
    reading the same conf."""
    from shifu_tensorflow_tpu.obs import resolve_obs_config

    return resolve_obs_config(args, conf)


def resolve_health(conf: Conf):
    """shifu.tpu.health-* -> HealthConfig for the single-process run
    paths (run_multi carries the same keys per worker through the
    WorkerConfig JSON bridge, worker_runtime_kwargs)."""
    from shifu_tensorflow_tpu.train.trainer import HealthConfig

    return HealthConfig(
        check_finite=conf.get_bool(K.HEALTH_CHECK_FINITE,
                                   K.DEFAULT_HEALTH_CHECK_FINITE),
        spike_factor=conf.get_float(K.HEALTH_SPIKE_FACTOR,
                                    K.DEFAULT_HEALTH_SPIKE_FACTOR),
        spike_min_epochs=conf.get_int(K.HEALTH_SPIKE_MIN_EPOCHS,
                                      K.DEFAULT_HEALTH_SPIKE_MIN_EPOCHS),
        hang_timeout_s=conf.get_int(
            K.HEALTH_HANG_TIMEOUT_MS, K.DEFAULT_HEALTH_HANG_TIMEOUT_MS
        ) / 1000.0,
    )


def resolve_keep_best(args, conf: Conf) -> str:
    """shifu.tpu.keep-best with the usual CLI-wins precedence.  Validated
    HERE so a typo'd conf value (the CLI flag has argparse choices, the
    conf key does not) is one clean pre-launch error in both run paths —
    not an N-worker crash cascade inside Trainer.__init__."""
    if getattr(args, "keep_best", None) is not None:
        value = args.keep_best
    else:
        value = conf.get(K.KEEP_BEST, K.DEFAULT_KEEP_BEST) or ""
    if value not in ("", "valid_loss", "ks"):
        raise SystemExit(
            f"unknown {K.KEEP_BEST} value {value!r} (valid_loss | ks)"
        )
    return value


def worker_runtime_kwargs(args, conf: Conf) -> dict:
    """WorkerConfig runtime fields resolved through the conf layer — the
    run_multi analogue of trainer_extras, extracted so the wiring tests can
    pin each key to the field it drives (no dead keys)."""
    ing = resolve_ingest(args, conf)
    return {
        "prefetch_depth": conf.get_int(K.PREFETCH_DEPTH,
                                       K.DEFAULT_PREFETCH_DEPTH),
        "scan_steps": resolve_scan_steps(args, conf),
        "accum_steps": resolve_accum_steps(args, conf),
        "keep_best": resolve_keep_best(args, conf),
        "async_checkpoint": conf.get_bool(K.ASYNC_CHECKPOINT,
                                          K.DEFAULT_ASYNC_CHECKPOINT),
        "flat_checkpoint": conf.get_bool(K.FLAT_CHECKPOINT,
                                         K.DEFAULT_FLAT_CHECKPOINT),
        "cache_dir": conf.get(K.CACHE_DIR),
        # staged-ingest knobs (shifu.tpu.data-*): 0 = auto/autotuned, an
        # explicit value pins its dimension (data/autotune.py); carried
        # per worker through the WorkerConfig JSON bridge.  n_readers
        # keeps its legacy None-means-auto WorkerConfig encoding
        "n_readers": ing["readers"] or None,
        "decode_workers": ing["decode_workers"],
        "data_prefetch": ing["prefetch"],
        "data_autotune": ing["autotune"],
        "data_shuffle_rows": ing["shuffle_rows"],
        "stream_feature_dtype": conf.get(K.STREAM_FEATURE_DTYPE,
                                         K.DEFAULT_STREAM_FEATURE_DTYPE),
        # subprocess workers inherit the submit-side retry envelope
        # (shifu.tpu.retry-*) through the WorkerConfig JSON bridge
        "retry": _retry_util.policy_from_conf(conf).to_dict(),
        # training-health guard (shifu.tpu.health-*): each worker detects
        # its own divergence/hangs; the coordinator arbitrates rollbacks.
        # ONE resolver (resolve_health) for both run paths, so a worker
        # fleet can never apply a different health policy than a
        # single-process run reading the same conf.
        **_health_worker_kwargs(conf),
        # observability plane (shifu.tpu.obs-*): subprocess workers
        # inherit the submit-side config through the JSON bridge and
        # journal to <path>.w<index> siblings
        **_obs_worker_kwargs(args, conf),
    }


def _obs_worker_kwargs(args, conf: Conf) -> dict:
    obs_cfg = resolve_obs(args, conf)
    return {"obs": obs_cfg.to_json() if obs_cfg.enabled else None}


def _health_worker_kwargs(conf: Conf) -> dict:
    hc = resolve_health(conf)
    return {
        "health_check_finite": hc.check_finite,
        "health_spike_factor": hc.spike_factor,
        "health_spike_min_epochs": hc.spike_min_epochs,
        "health_hang_timeout_s": hc.hang_timeout_s,
    }


def resolve_scan_steps(args, conf: Conf) -> int:
    """CLI flag wins when given (None = unset, so an explicit
    ``--scan-steps 0/1`` forces the per-step path even if the conf raises
    the key); then the conf key; then the default."""
    if getattr(args, "scan_steps", None) is not None:
        return args.scan_steps
    return conf.get_int(K.SCAN_STEPS, K.DEFAULT_SCAN_STEPS)


def resolve_accum_steps(args, conf: Conf) -> int:
    """Same precedence as resolve_scan_steps, for shifu.tpu.accum-steps."""
    if getattr(args, "accum_steps", None) is not None:
        return args.accum_steps
    return conf.get_int(K.ACCUM_STEPS, K.DEFAULT_ACCUM_STEPS)


def resolve_valid_rate(args, model_config: ModelConfig) -> float:
    """--valid-rate wins; else the ModelConfig's validSetRate.  ONE
    resolver shared by both run paths' preflights and fit loops, so a
    guard can never judge a different rate than training uses."""
    return (
        args.valid_rate if args.valid_rate is not None
        else model_config.valid_set_rate
    )


def reject_unfireable_validation_configs(args, conf: Conf,
                                         valid_rate: float,
                                         early_stop=None) -> None:
    """Shared preflight: early stopping and keep-best both need validation
    data to ever act; with a zero validation rate they would silently do
    nothing (or worse, keep-best=ks would crown the FIRST epoch).  One
    clean error up front beats a silent no-op — in a fleet, beats N
    workers burning the full budget.  ``early_stop``: pass the already-
    resolved stopper to avoid re-resolving; None resolves here."""
    if valid_rate > 0:
        return
    if early_stop is None:
        early_stop = resolve_early_stop(args, conf)
    if early_stop is not None:
        raise SystemExit(
            f"{K.EARLY_STOP_KS}/{K.EARLY_STOP_PATIENCE} need validation "
            "data to ever fire, but the validation rate is 0 — raise "
            "validSetRate/--valid-rate or drop the early-stop keys "
            "(silently training the full budget is not what you asked for)"
        )
    if resolve_keep_best(args, conf):
        raise SystemExit(
            f"{K.KEEP_BEST} needs validation data to rank epochs, but the "
            "validation rate is 0 — with keep-best=ks every epoch ties at "
            "0.0 and the FIRST epoch would be exported as 'best'; raise "
            "validSetRate/--valid-rate or drop the key"
        )


def resolve_early_stop(args, conf: Conf):
    """shifu.tpu.early-stop-ks / early-stop-patience -> EarlyStopper (or
    None when both are off).  CLI flags win with the usual precedence."""
    from shifu_tensorflow_tpu.train.trainer import EarlyStopper

    ks = (args.early_stop_ks if getattr(args, "early_stop_ks", None)
          is not None
          else conf.get_float(K.EARLY_STOP_KS, K.DEFAULT_EARLY_STOP_KS))
    patience = (args.early_stop_patience
                if getattr(args, "early_stop_patience", None) is not None
                else conf.get_int(K.EARLY_STOP_PATIENCE,
                                  K.DEFAULT_EARLY_STOP_PATIENCE))
    if ks <= 0 and patience <= 0:
        return None
    return EarlyStopper(target_ks=ks, patience=patience)


def job_spec_kwargs(conf: Conf) -> dict:
    """JobSpec fields driven by conf keys — the reference's backup-instance
    and heartbeat tunables (GlobalConfigurationKeys.java:75-79,148-150)
    mapped onto the TPU-native recovery model."""
    return {
        # backup instances -> spare restart budget: hot standbys have no
        # SPMD analogue; the same capacity buys extra relaunches
        "spare_restarts": conf.num_backup_instances(),
        "heartbeat_interval_ms": conf.get_int(
            K.TASK_HEARTBEAT_INTERVAL_MS, K.DEFAULT_TASK_HEARTBEAT_INTERVAL_MS
        ),
        "max_missed_heartbeats": conf.get_int(
            K.TASK_MAX_MISSED_HEARTBEATS, K.DEFAULT_TASK_MAX_MISSED_HEARTBEATS
        ),
        "sync_epochs": conf.get_bool(K.SYNC_EPOCHS, K.DEFAULT_SYNC_EPOCHS),
        # training-health rollback policy (coordinator side)
        "health_lr_backoff": conf.get_float(K.HEALTH_LR_BACKOFF,
                                            K.DEFAULT_HEALTH_LR_BACKOFF),
        "health_max_rollbacks": conf.get_int(K.HEALTH_MAX_ROLLBACKS,
                                             K.DEFAULT_HEALTH_MAX_ROLLBACKS),
        "health_skip_window": conf.get_int(K.HEALTH_SKIP_WINDOW,
                                           K.DEFAULT_HEALTH_SKIP_WINDOW),
    }


def elastic_spec_kwargs(args, conf: Conf) -> dict:
    """JobSpec fields for the elastic fleet (hot standbys + shrink-on-
    exhaustion re-split), CLI-wins over the shifu.tpu.standby-workers /
    shifu.tpu.elastic keys."""
    standby = (args.standby_workers
               if getattr(args, "standby_workers", None) is not None
               else conf.get_int(K.STANDBY_WORKERS,
                                 K.DEFAULT_STANDBY_WORKERS))
    el = (args.elastic if getattr(args, "elastic", None) is not None
          else conf.get_bool(K.ELASTIC, K.DEFAULT_ELASTIC))
    out = {"standby_workers": max(0, int(standby)), "elastic": bool(el)}
    if out["elastic"]:
        # shrink/release and re-split directives are delivered through
        # the per-epoch barrier: elastic forces it on over whatever the
        # conf key says (same rule as early stopping — the invariant
        # lives where the spec is built)
        out["sync_epochs"] = True
    return out


def early_stop_spec_kwargs(args, conf: Conf) -> dict:
    """JobSpec fields for fleet-coordinated early stopping (the
    coordinator evaluates quorum aggregates; the barrier delivers the
    decision fleet-wide)."""
    es = resolve_early_stop(args, conf)
    if es is None:
        return {}
    return {
        "early_stop_ks": es.target_ks,
        "early_stop_patience": es.patience,
        # the invariant lives where the spec is BUILT: the stop decision
        # rides the per-epoch barrier, so it must be on
        "sync_epochs": True,
    }


def prune_cache_if_configured(conf: Conf) -> None:
    """Cache eviction to the shifu.tpu.cache-max-bytes budget (accepts
    memory strings: "2g", "512m", plain bytes).  Runs in the CLI's finally
    paths — a failing job must not grow the cache past budget forever."""
    cache_dir = conf.get(K.CACHE_DIR)
    try:
        max_bytes = conf.get_memory(K.CACHE_MAX_BYTES,
                                    K.DEFAULT_CACHE_MAX_BYTES) or 0
    except ValueError as e:
        print(f"ignoring {K.CACHE_MAX_BYTES}: {e}", file=sys.stderr)
        return
    if cache_dir and max_bytes > 0:
        from shifu_tensorflow_tpu.data import cache as shard_cache

        removed = shard_cache.prune_cache(cache_dir, max_bytes)
        if removed:
            print(f"cache: evicted {removed} entries to fit "
                  f"{max_bytes} bytes", flush=True)


def _print_epoch(stats) -> None:
    print(
        f"epoch {stats.current_epoch}: train_loss={stats.training_loss:.6f} "
        f"valid_loss={stats.valid_loss:.6f} ks={stats.ks:.4f} "
        f"auc={stats.auc:.4f} epoch_time={stats.training_time_s:.2f}s "
        f"valid_time={stats.valid_time_s:.2f}s step={stats.global_step}",
        flush=True,
    )


def run_single(args, conf, model_config: ModelConfig, schema: RecordSchema) -> int:
    from shifu_tensorflow_tpu.data.dataset import InMemoryDataset, ShardStream
    from shifu_tensorflow_tpu.data.splitter import list_data_files
    from shifu_tensorflow_tpu.export.saved_model import export_model
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import Checkpointer
    from shifu_tensorflow_tpu.train.trainer import TrainingUnhealthy
    from shifu_tensorflow_tpu.utils.profiling import trace_if

    device_resident = args.device_resident or conf.get_bool(
        K.DEVICE_RESIDENT, K.DEFAULT_DEVICE_RESIDENT
    )
    if device_resident and args.stream:
        raise SystemExit(
            "--stream and --device-resident conflict: streaming exists for "
            "datasets that do NOT fit in memory; drop one of them "
            "(or unset shifu.tpu.device-resident)"
        )
    if device_resident and model_config.params.algorithm == "sagn":
        # knowable before any data I/O; a raw NotImplementedError after a
        # minutes-long dataset load would say the same thing rudely
        raise SystemExit(
            "Algorithm=sagn does not support --device-resident (the scanned "
            "epoch runs plain-SSGD updates, not SAGN windows); drop one"
        )
    if device_resident and resolve_accum_steps(args, conf) > 1:
        raise SystemExit(
            f"--device-resident does not support {K.ACCUM_STEPS}; raise "
            "the batch size instead (the dataset already fits in device "
            "memory)"
        )
    valid_rate = resolve_valid_rate(args, model_config)
    early_stop = resolve_early_stop(args, conf)
    reject_unfireable_validation_configs(args, conf, valid_rate,
                                         early_stop=early_stop)
    data_path = conf.get(K.TRAINING_DATA_PATH)
    paths = list_data_files(data_path)
    if not paths:
        print(f"no training files under {data_path}", file=sys.stderr)
        return 2

    mesh_spec = conf.get(K.MESH_SHAPE, K.DEFAULT_MESH_SHAPE)
    mesh = make_mesh(mesh_spec) if mesh_spec != "none" else None
    # observability plane: installed BEFORE make_trainer so the trainer
    # picks the tracer up at construction (obs/trace.active()).  The job
    # correlation id stamps every journal event this run writes.
    import uuid as _uuid

    from shifu_tensorflow_tpu.obs import install_obs

    install_obs(resolve_obs(args, conf), plane="train",
                job=_uuid.uuid4().hex[:8])
    if mesh is not None:
        # one mesh event per run: the RESOLVED layout (-1 axes solved),
        # rendered by `obs summary`
        from shifu_tensorflow_tpu.obs import journal as _obs_journal
        from shifu_tensorflow_tpu.parallel.mesh import mesh_shape_fingerprint

        _obs_journal.emit(
            "mesh", plane="train",
            shape={n: int(s) for n, s in mesh.shape.items()},
            fingerprint=mesh_shape_fingerprint(mesh),
            devices=int(mesh.devices.size),
        )
    # make_trainer dispatches on train.params.Algorithm (ssgd | sagn) —
    # the reference selected between its two programs by script path
    extras = trainer_extras(args, conf)
    dtype_name = extras.pop("dtype_name")
    trainer = make_trainer(
        model_config,
        schema.num_features,
        feature_columns=schema.feature_columns,
        mesh=mesh,
        seed=args.seed,
        **extras,
    )
    epochs = conf.get_int(K.EPOCHS, model_config.num_train_epochs)
    batch_size = trainer.align_batch_size(
        conf.get_int(K.BATCH_SIZE, model_config.batch_size)
    )
    # valid_rate and early_stop were resolved once in the preflight block

    checkpointer = None
    start_epoch = 0
    if args.checkpoint_dir:
        # model-sharded runs (mesh with model axis > 1) checkpoint
        # through the flat npz format: it saves one npz PER model
        # coordinate and restores by re-sharding onto the current mesh
        # without a full-parameter gather — the orbax path would
        # materialize the global arrays.  flat-checkpoint opts plain
        # runs into the same format.
        from shifu_tensorflow_tpu.parallel.mesh import model_axis_size
        from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

        use_flat = model_axis_size(mesh) > 1 or conf.get_bool(
            K.FLAT_CHECKPOINT, K.DEFAULT_FLAT_CHECKPOINT)
        ckpt_cls = NpzCheckpointer if use_flat else Checkpointer
        checkpointer = ckpt_cls(
            args.checkpoint_dir,
            every_epochs=conf.get_int(K.CHECKPOINT_EVERY_EPOCHS,
                                      K.DEFAULT_CHECKPOINT_EVERY_EPOCHS),
        )
        start_epoch = trainer.restore(checkpointer)
        if start_epoch:
            print(f"resuming at epoch {start_epoch}", flush=True)

    t0 = time.time()
    try:
        with trace_if(args.profile_dir):
            if args.stream:
                cache_dir = conf.get(K.CACHE_DIR)
                # streaming transport dtype (decoupled from compute): bf16
                # by default, f32 when hashed columns need raw float bits
                from shifu_tensorflow_tpu.data.dataset import (
                    resolve_stream_feature_dtype,
                )

                feature_dtype = resolve_stream_feature_dtype(
                    conf.get(K.STREAM_FEATURE_DTYPE,
                             K.DEFAULT_STREAM_FEATURE_DTYPE),
                    uses_feature_hashing=(
                        model_config.params.features_carry_ids),
                    has_normalization_stats=bool(schema.means),
                )
                # staged-ingest knobs (shifu.tpu.data-*): explicit values
                # pin their dimension; the rest start at defaults and the
                # autotuner (on by default) resizes them between epochs
                # from the live stage span ratios — one shared wiring
                # helper with the fleet worker path (data/autotune.py)
                from shifu_tensorflow_tpu.data.autotune import (
                    install_ingest_autotuner,
                )

                ing = resolve_ingest(args, conf)
                _widths, _stats_sink = install_ingest_autotuner(
                    trainer, ing["readers"], ing["decode_workers"],
                    ing["prefetch"], autotune=ing["autotune"],
                    fallback_prefetch=trainer.prefetch_depth,
                )

                history = trainer.fit_stream(
                    lambda epoch: ShardStream(
                        paths, schema, batch_size,
                        valid_rate=valid_rate, emit="train", salt=args.seed,
                        cache_dir=cache_dir,
                        feature_dtype=feature_dtype,
                        shuffle_rows=ing["shuffle_rows"],
                        shuffle_seed=args.seed + epoch,
                        stats_sink=_stats_sink,
                        **_widths(),
                    ),
                    (lambda: ShardStream(
                        paths, schema, batch_size,
                        valid_rate=valid_rate, emit="valid", salt=args.seed,
                        cache_dir=cache_dir,
                        feature_dtype=feature_dtype,
                        **_widths(),
                    )) if valid_rate > 0 else None,
                    epochs=epochs,
                    on_epoch=_print_epoch,
                    checkpointer=checkpointer,
                    start_epoch=start_epoch,
                    early_stop=early_stop,
                )
            else:
                dataset = InMemoryDataset.load(
                    paths, schema, valid_rate, salt=args.seed
                )
                print(
                    f"loaded {len(dataset.train)} train / "
                    f"{len(dataset.valid)} valid rows from {len(paths)} files",
                    flush=True,
                )
                fit = (
                    trainer.fit_device_resident
                    if device_resident
                    else trainer.fit
                )
                history = fit(
                    dataset,
                    epochs=epochs,
                    batch_size=batch_size,
                    on_epoch=_print_epoch,
                    checkpointer=checkpointer,
                    start_epoch=start_epoch,
                    early_stop=early_stop,
                )
    except TrainingUnhealthy as e:
        # divergence caught by the health guard BEFORE the diverged epoch
        # was checkpointed: single-process runs have no coordinator to
        # arbitrate a rollback, so fail fast with the diagnostics (resume
        # from the last verified checkpoint restarts below the bad epoch)
        print(json.dumps({
            "state": "unhealthy",
            "reason": e.reason,
            "epoch": e.epoch,
            "bad_steps": list(e.bad_steps),
            "diagnostics": e.diag,
        }), flush=True)
        print(
            f"training unhealthy: {e.reason} — the last verified "
            f"checkpoint (if any) was NOT overwritten; re-run to resume "
            f"below the diverged epoch, lower the learning rate, or "
            f"disable the guard via {K.HEALTH_CHECK_FINITE}=false",
            file=sys.stderr,
        )
        return 3
    finally:
        if checkpointer is not None:
            checkpointer.close()
        prune_cache_if_configured(conf)
    wall = time.time() - t0

    if args.export_dir:
        from shifu_tensorflow_tpu.export.aot import resolve_aot_buckets

        wrote = export_model(
            args.export_dir,
            trainer,
            feature_columns=schema.feature_columns,
            zscale_means=schema.means or None,
            zscale_stds=schema.stds or None,
            aot_buckets=resolve_aot_buckets(args, conf),
            lineage=resolve_lineage(args),
        )
        print(f"exported to {args.export_dir}: {wrote}", flush=True)
    import jax as _jax

    devices = _jax.devices()
    summary = {
        "state": "finished",
        "epochs_run": len(history),
        "wall_time_s": round(wall, 2),
        "final_valid_loss": history[-1].valid_loss if history else None,
        "final_ks": history[-1].ks if history else None,
        # which device actually trained — scripts wrapping the CLI (e.g.
        # chip_smoke) record it in their artifacts
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    if trainer.stop_reason:
        summary["stopped_early"] = trainer.stop_reason
    if trainer.keep_best and trainer.best_epoch is not None:
        summary["best_epoch"] = trainer.best_epoch
        summary["best_metric"] = trainer.best_metric
    print(json.dumps(summary), flush=True)
    return 0


def run_multi(args, conf, model_config: ModelConfig, schema: RecordSchema) -> int:
    from shifu_tensorflow_tpu.coordinator.coordinator import JobState
    from shifu_tensorflow_tpu.coordinator.submitter import (
        JobSubmitter,
        make_job_spec,
    )
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig

    n_workers = conf.get_int(K.instances_key(K.WORKER_JOB_NAME), 1)
    epochs = conf.get_int(K.EPOCHS, model_config.num_train_epochs)
    # preflight config HERE: a bad shifu.tpu.dtype or an invalid
    # scan/accum combination must be one clean error before launch, not
    # an N-worker crash cascade after cluster bring-up
    extras = trainer_extras(args, conf)
    if extras["scan_steps"] > 1 and extras["accum_steps"] > 1:
        raise SystemExit(
            f"{K.SCAN_STEPS} and {K.ACCUM_STEPS} are mutually exclusive: "
            "one chunks UPDATES per dispatch, the other chunks "
            "microbatches per UPDATE — drop one"
        )
    if extras["accum_steps"] > 1 and model_config.params.algorithm == "sagn":
        raise SystemExit(
            f"Algorithm=sagn does not compose with {K.ACCUM_STEPS}: the "
            "SAGN window already defines its own accumulation semantics "
            "(UpdateWindow)"
        )
    if extras["accum_steps"] > 1 and model_config.params.update_window > 1:
        raise SystemExit(
            f"{K.ACCUM_STEPS} does not compose with "
            "train.params.UpdateWindow > 1: both define gradient "
            "accumulation — drop one"
        )
    # fleet early stopping is COORDINATED: the coordinator evaluates the
    # criteria on full-quorum epoch aggregates and delivers the decision
    # through the per-epoch barrier (which it force-enables), so every
    # worker stops after the same epoch — see JobSpec.early_stop_*
    reject_unfireable_validation_configs(
        args, conf, resolve_valid_rate(args, model_config)
    )
    if extras["keep_best"]:
        # supported for fleets: the CHIEF persists its best snapshot
        # beside the shared checkpoints (keep-best.npz), and the export
        # trainer restores it
        if not args.checkpoint_dir:
            # without a shared checkpoint dir the snapshot has nowhere to
            # live: the chief's in-memory best dies with its process and
            # keep-best would be a silent no-op
            raise SystemExit(
                f"{K.KEEP_BEST} with --workers>1 needs --checkpoint-dir: "
                "the chief persists the best snapshot beside the shared "
                "checkpoints"
            )
    if args.device_resident or conf.get_bool(K.DEVICE_RESIDENT,
                                             K.DEFAULT_DEVICE_RESIDENT):
        # silently training a different mode than requested is a bug; the
        # multi-worker path feeds per-process shards via fit/fit_stream
        raise SystemExit(
            "--device-resident is single-process (the whole dataset lives "
            "in one process's device memory); multi-worker jobs load or "
            "stream per-worker shards — drop --workers or the flag/key"
        )
    # SPMD (one model across workers) is the default for real process
    # launches — the reference's defining capability; thread workers can't
    # host it (one process cannot be N jax.distributed participants)
    use_spmd = args.spmd if args.spmd is not None else args.launcher == "process"
    if args.launcher == "process":
        # every worker is a JAX process on THIS host (one process per
        # host is the multi-host shape; hosts are joined by the API's
        # ssh launcher, not by this flag)
        from shifu_tensorflow_tpu.utils.jaxenv import (
            refuse_processes_sharing_a_chip,
        )

        refuse_processes_sharing_a_chip(
            n_workers, "--workers with --launcher process")
    # merged dict (not two ** expansions): early-stop forces sync_epochs
    # True over whatever the conf key says — a keyword collision otherwise
    spec_kw = {**job_spec_kwargs(conf), **elastic_spec_kwargs(args, conf),
               **early_stop_spec_kwargs(args, conf)}
    # declared fleet mesh (only when the operator set the key — a
    # defaulted data:-1 must not push every plain worker onto the mesh
    # path): the coordinator hands every rank (and every promoted
    # standby) its row-major coordinate at registration, and elastic
    # resizes validate the reshape against the model axis
    mesh_spec = conf.get(K.MESH_SHAPE)
    if mesh_spec and mesh_spec != "none":
        spec_kw["mesh_spec"] = mesh_spec
    # one job correlation id for the whole fleet: the coordinator stamps
    # it on its journal events and hands it to every worker at
    # registration (the workers' .w<i> journal siblings carry the same id)
    import uuid as _uuid

    job_id = _uuid.uuid4().hex[:8]
    spec_kw["job_id"] = job_id
    spec = make_job_spec(
        conf.get(K.TRAINING_DATA_PATH),
        n_workers,
        epochs=epochs,
        board_path=args.board_path,
        spmd=use_spmd,
        **spec_kw,
    )

    def make_cfg(worker_id: str, addr) -> WorkerConfig:
        return WorkerConfig(
            worker_id=worker_id,
            coordinator_host=addr[0],
            coordinator_port=addr[1],
            model_config=model_config,
            schema=schema,
            batch_size=conf.get_int(K.BATCH_SIZE, model_config.batch_size),
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every_epochs=conf.get_int(
                K.CHECKPOINT_EVERY_EPOCHS, K.DEFAULT_CHECKPOINT_EVERY_EPOCHS
            ),
            # both halves of the heartbeat pipe come from the SAME key: the
            # coordinator's expiry window is interval*misses, so a worker
            # sending at a different hardcoded rate would be expired while
            # healthy
            heartbeat_interval_s=conf.get_int(
                K.TASK_HEARTBEAT_INTERVAL_MS,
                K.DEFAULT_TASK_HEARTBEAT_INTERVAL_MS,
            ) / 1000.0,
            # the RESOLVED rate, so the worker trains at exactly what the
            # preflight judged (its own None-fallback stays for direct
            # WorkerConfig users)
            valid_rate=resolve_valid_rate(args, model_config),
            seed=args.seed,
            dtype=args.dtype or conf.get(K.DTYPE, K.DEFAULT_DTYPE),
            mesh_spec=conf.get(K.MESH_SHAPE),
            stream=bool(args.stream),
            **worker_runtime_kwargs(args, conf),
        )

    # observability plane for the CONTROL side: the coordinator/submitter
    # journal lifecycle events (register, restarts, rollbacks) to the
    # base path; workers (launched with the obs dict in their
    # WorkerConfig) write <path>.w<index> siblings
    from shifu_tensorflow_tpu.obs import install_obs

    install_obs(resolve_obs(args, conf), plane="coordinator", job=job_id)
    submitter = JobSubmitter(spec, make_cfg, launcher=args.launcher)
    timeout_ms = conf.get_int(K.APPLICATION_TIMEOUT, K.DEFAULT_APPLICATION_TIMEOUT)
    result = submitter.run(
        timeout_s=timeout_ms / 1000.0 if timeout_ms > 0 else 86400.0
    )
    for s in result.epoch_summaries:
        print(s.board_line(), end="", flush=True)

    def print_summary() -> None:
        # the JSON summary is the last line of output — a stable contract
        # for scripts wrapping the CLI
        summary = {
            "state": result.state.value,
            "failure_reason": result.failure_reason,
            "epochs_run": len(result.epoch_summaries),
            "restarts_used": result.restarts_used,
            "wall_time_s": round(result.wall_time_s, 2),
        }
        if result.rollbacks_used:
            # a health rollback is an operational event the run record
            # must show — not just epochs silently running twice
            summary["rollbacks_used"] = result.rollbacks_used
        if result.promotions_used:
            # ditto for standby takeovers: an elastic recovery is part
            # of the run record, not an invisible non-event
            summary["promotions_used"] = result.promotions_used
        if result.diagnostics is not None:
            summary["diagnostics"] = result.diagnostics
        if result.stop_reason:
            summary["stopped_early"] = result.stop_reason
        print(json.dumps(summary), flush=True)

    prune_cache_if_configured(conf)
    if result.state != JobState.FINISHED:
        print_summary()
        return 1

    if args.export_dir:
        # chief-export parity: restore the latest checkpoint into a fresh
        # trainer and export (reference: ssgd_monitor.py:304-341)
        if not args.checkpoint_dir:
            print("--export-dir with --workers>1 needs --checkpoint-dir",
                  file=sys.stderr)
            print_summary()
            return 2
        from shifu_tensorflow_tpu.export.saved_model import export_model
        from shifu_tensorflow_tpu.train import make_trainer
        from shifu_tensorflow_tpu.train.checkpoint import (
            Checkpointer,
            NpzCheckpointer,
        )

        trainer = make_trainer(
            model_config,
            schema.num_features,
            feature_columns=schema.feature_columns,
            seed=args.seed,
            # restore() then also loads the chief's persisted best
            # snapshot, and export_model serves it over the last epoch
            # (extras: single resolution — the export trainer must agree
            # with the fleet on the metric, or _restore_best rejects the
            # snapshot)
            keep_best=extras["keep_best"],
        )
        # SPMD (and flat-checkpoint-opted) jobs checkpoint through the
        # flat-file format (see NpzCheckpointer); restore with the
        # matching reader
        use_flat = use_spmd or conf.get_bool(K.FLAT_CHECKPOINT,
                                             K.DEFAULT_FLAT_CHECKPOINT)
        ckpt_cls = NpzCheckpointer if use_flat else Checkpointer
        with ckpt_cls(args.checkpoint_dir) as ckpt:
            trainer.restore(ckpt)
        # bundle-shipped drift baseline for the FLEET path: the data
        # flowed through the workers' processes, not this submitter —
        # their per-epoch journaled data_stats sketches merge into the
        # feature_stats.json this export ships (obs/datastats.py)
        feature_stats = None
        obs_cfg = resolve_obs(args, conf)
        if obs_cfg.enabled and obs_cfg.journal_path:
            from shifu_tensorflow_tpu.obs import datastats as obs_datastats

            feature_stats = obs_datastats.baseline_from_journal(
                obs_cfg.journal_path)
            if feature_stats is not None and \
                    feature_stats.get("num_features") != schema.num_features:
                feature_stats = None
        from shifu_tensorflow_tpu.export.aot import resolve_aot_buckets

        wrote = export_model(
            args.export_dir,
            trainer,
            feature_columns=schema.feature_columns,
            zscale_means=schema.means or None,
            zscale_stds=schema.stds or None,
            feature_stats=feature_stats,
            aot_buckets=resolve_aot_buckets(args, conf),
            lineage=resolve_lineage(args),
        )
        print(f"exported to {args.export_dir}: {wrote}", flush=True)
    print_summary()
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    conf = load_conf(args)
    # install the conf-resolved retry envelope as the process default so
    # the fs backends / RPC client / checkpointer (which auto-construct
    # with no conf in scope) all honor shifu.tpu.retry-* keys
    _retry_util.set_default_policy(_retry_util.policy_from_conf(conf))
    if not conf.get(K.TRAINING_DATA_PATH):
        print("--training-data-path (or a globalconfig providing "
              f"{K.TRAINING_DATA_PATH}) is required", file=sys.stderr)
        return 2

    mc_path = conf.get(K.MODEL_CONF)
    model_config = ModelConfig.load(mc_path) if mc_path else ModelConfig.from_json({})
    # resolve path-valued settings back out of the merged conf so a
    # --globalconfig file can provide them too (the CLI overlay already won
    # if both were given — the documented precedence)
    args.column_config = args.column_config or conf.get(K.COLUMN_CONF)
    args.checkpoint_dir = conf.get(K.TMP_MODEL_PATH)
    args.export_dir = conf.get(K.FINAL_MODEL_PATH)
    args.board_path = conf.get(K.TMP_LOG_PATH)
    schema, _ = resolve_schema(args, model_config)

    n_workers = conf.get_int(K.instances_key(K.WORKER_JOB_NAME), 1)
    if n_workers > 1:
        return run_multi(args, conf, model_config, schema)
    return run_single(args, conf, model_config, schema)


if __name__ == "__main__":
    sys.exit(main())
