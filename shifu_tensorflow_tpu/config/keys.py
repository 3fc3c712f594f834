"""Typed configuration keys and defaults.

Parity surface: the reference keeps every tunable under a flat ``shifu.*``
namespace with per-role templating (reference:
shifu-tensorflow-on-yarn/.../util/GlobalConfigurationKeys.java:113-154 and
util/Constants.java:87-94).  We keep the same namespace so existing Shifu
``global.xml`` files parse unchanged, and add a ``shifu.tpu.*`` sub-namespace
for mesh/topology keys that have no YARN analogue.

Unlike the reference — where role resources were matched to containers by
*exact* (memory, vcores) equality, an implicit invariant
(TensorflowSession.java:300-318) — roles here are explicit: a worker is a
host process addressing TPU chips, and the topology is declared, not
inferred from container shapes.
"""

from __future__ import annotations

SHIFU_PREFIX = "shifu."
APP_PREFIX = SHIFU_PREFIX + "application."

# ---- application-level keys (names shared with the reference) ----
APPLICATION_NAME = APP_PREFIX + "name"
DEFAULT_APPLICATION_NAME = "ShifuTpuApplication"
APPLICATION_TIMEOUT = APP_PREFIX + "timeout"  # ms; 0 = no timeout
DEFAULT_APPLICATION_TIMEOUT = 0

TRAINING_DATA_PATH = APP_PREFIX + "training-data-path"
WEIGHT_COLUMN_NUM = APP_PREFIX + "weight-column-number"
TARGET_COLUMN_NUM = APP_PREFIX + "target-column-number"
SELECTED_COLUMN_NUMS = APP_PREFIX + "selected-column-numbers"
SELECTED_NUMERIC_COLUMN_NUMS = APP_PREFIX + "selected-numeric-column-numbers"
SELECTED_CATEGORY_COLUMN_NUMS = APP_PREFIX + "selected-category-column-numbers"
TOTAL_TRAINING_DATA_NUM = APP_PREFIX + "total-training-data-number"
DEFAULT_WEIGHT_COLUMN_NUM = -1
DEFAULT_TARGET_COLUMN_NUM = 0
TMP_MODEL_PATH = APP_PREFIX + "tmp-model-path"
FINAL_MODEL_PATH = APP_PREFIX + "final-model-path"
TMP_LOG_PATH = APP_PREFIX + "tmp-log-path"
MODEL_CONF = APP_PREFIX + "model-conf"
COLUMN_CONF = APP_PREFIX + "column-conf"
EPOCHS = APP_PREFIX + "epochs"

# ---- task / liveness keys (reference: GlobalConfigurationKeys.java:75-79) ----
TASK_PREFIX = SHIFU_PREFIX + "task."
TASK_HEARTBEAT_INTERVAL_MS = TASK_PREFIX + "heartbeat-interval"
DEFAULT_TASK_HEARTBEAT_INTERVAL_MS = 1000
TASK_MAX_MISSED_HEARTBEATS = TASK_PREFIX + "max-missed-heartbeats"
DEFAULT_TASK_MAX_MISSED_HEARTBEATS = 25
# per-epoch fleet barrier for non-SPMD multi-worker jobs (SPMD is
# implicitly synchronous; this key re-creates the reference's lockstep
# epochs for independent-model mode)
SYNC_EPOCHS = TASK_PREFIX + "sync-epochs"
DEFAULT_SYNC_EPOCHS = False

# ---- role templating (reference: getInstancesKey etc. :123-150) ----
# NOTE: there is no "ps" role — the PS architecture has no TPU analogue
# (variables are replicated and gradients all-reduced, SURVEY.md §7.0);
# shifu.ps.* keys in legacy configs parse (Conf stores any key) and are
# simply never read.
WORKER_JOB_NAME = "worker"


def instances_key(job_name: str) -> str:
    return f"{SHIFU_PREFIX}{job_name}.instances"


def backup_instances_key(job_name: str) -> str:
    return f"{SHIFU_PREFIX}{job_name}.instances.backup"


def memory_key(job_name: str) -> str:
    return f"{SHIFU_PREFIX}{job_name}.memory"


def vcores_key(job_name: str) -> str:
    return f"{SHIFU_PREFIX}{job_name}.vcores"


DEFAULT_WORKER_INSTANCES = 1
DEFAULT_BACKUP_INSTANCES = 0

# ---- TPU-native topology keys (no YARN analogue) ----
TPU_PREFIX = SHIFU_PREFIX + "tpu."
MESH_SHAPE = TPU_PREFIX + "mesh-shape"  # e.g. "data:8" or "data:4,model:2"
DEFAULT_MESH_SHAPE = "data:-1"  # -1 = all local devices on the data axis
NUM_PROCESSES = TPU_PREFIX + "num-processes"
COORDINATOR_ADDRESS = TPU_PREFIX + "coordinator-address"
PROCESS_ID = TPU_PREFIX + "process-id"
BATCH_SIZE = TPU_PREFIX + "batch-size"  # global batch size
DEFAULT_BATCH_SIZE = 100  # parity with reference BATCH_SIZE (ssgd_monitor.py:33)
DTYPE = TPU_PREFIX + "dtype"
DEFAULT_DTYPE = "float32"  # tabular nets are tiny; bf16 is opt-in
# streaming TRANSPORT dtype for features (decoupled from compute dtype):
# "auto" ships bf16 over the host->device link whenever it is SAFE — no
# column feeds a hash AND ZSCALE normalization stats exist (raw
# un-normalized magnitudes would lose mantissa silently) — at 4.6x the
# fp32 device_put rate (BENCH_TRANSFER.json); the jitted step widens back
# to the params' precision on device; "float32"/"bfloat16" force it
STREAM_FEATURE_DTYPE = TPU_PREFIX + "stream-feature-dtype"
DEFAULT_STREAM_FEATURE_DTYPE = "auto"
PREFETCH_DEPTH = TPU_PREFIX + "prefetch-depth"
DEFAULT_PREFETCH_DEPTH = 2
# chunked-scan epochs: batches per lax.scan dispatch (1 = per-step path).
# Amortizes per-step dispatch latency; worth raising when steps are much
# shorter than dispatch (small models driven from a Python loop)
SCAN_STEPS = TPU_PREFIX + "scan-steps"
DEFAULT_SCAN_STEPS = 1
# gradient accumulation: microbatches per optimizer update (1 = off).
# The update equals a single step on the concatenated batch — effective
# batch sizes beyond HBM.  Mutually exclusive with scan-steps (which
# chunks UPDATES per dispatch, not microbatches per update).
ACCUM_STEPS = TPU_PREFIX + "accum-steps"
DEFAULT_ACCUM_STEPS = 1
# early stopping.  early-stop-ks: stop once validation KS reaches the
# target (the BASELINE.md north star is wall-clock TO KS, so keep
# training past it only if you ask to); early-stop-patience: stop after
# N epochs without validation-loss improvement.  0 disables each.
# Single-process fits stop locally; multi-worker fleets stop
# COORDINATED — the coordinator evaluates quorum epoch aggregates and
# the per-epoch barrier (force-enabled) delivers one decision to every
# worker, because an uncoordinated stop would hang SPMD collectives.
EARLY_STOP_KS = TPU_PREFIX + "early-stop-ks"
DEFAULT_EARLY_STOP_KS = 0.0
EARLY_STOP_PATIENCE = TPU_PREFIX + "early-stop-patience"
DEFAULT_EARLY_STOP_PATIENCE = 0
# keep-best ("" = off; "valid_loss" | "ks"): snapshot params at the best
# validation epoch; export serves that epoch instead of the last.  In a
# fleet the CHIEF persists its snapshot beside the shared checkpoints
# (keep-best.npz) and the export trainer restores it; needs validation
# data, and --export-dir with workers>1 additionally needs
# --checkpoint-dir (both preflighted).
KEEP_BEST = TPU_PREFIX + "keep-best"
DEFAULT_KEEP_BEST = ""
CHECKPOINT_EVERY_EPOCHS = TPU_PREFIX + "checkpoint-every-epochs"
DEFAULT_CHECKPOINT_EVERY_EPOCHS = 1
# background-thread checkpoint writes for the flat-file (SPMD) path: the
# epoch loop pays only the device->host fetch, the (possibly remote) file
# write overlaps the next epoch.  The orbax path is already async.
ASYNC_CHECKPOINT = TPU_PREFIX + "async-checkpoint"
DEFAULT_ASYNC_CHECKPOINT = False
# all-in-HBM training (--device-resident): dataset transfers once, each
# epoch is one compiled program (on-device shuffle + scanned steps)
DEVICE_RESIDENT = TPU_PREFIX + "device-resident"
DEFAULT_DEVICE_RESIDENT = False
# binary shard cache directory (data/cache.py): parse text shards once,
# stream later epochs from memory-mapped finalized tensors
CACHE_DIR = TPU_PREFIX + "cache-dir"
# cache size budget in bytes; oldest entries evicted after training
# (0 = unbounded)
CACHE_MAX_BYTES = TPU_PREFIX + "cache-max-bytes"
DEFAULT_CACHE_MAX_BYTES = 0

# ---- streaming-ingest pipeline (data/pipeline.py + data/autotune.py) ----
# Stage widths for the staged pull pipeline behind --stream.  0 = auto:
# the autotuner (on by default) sizes the dimension from live stage span
# ratios between epochs (tf.data-style; docs/ingest.md).  An EXPLICIT
# value both sets the dimension and PINS it — the operator's number wins
# and the tuner stops adjusting that dimension (the others keep adapting).
# Batch order is reproducible at ANY width (ordered sequencer), so these
# are pure throughput knobs.
DATA_READERS = TPU_PREFIX + "data-readers"  # parallel shard readers
DEFAULT_DATA_READERS = 0
DATA_DECODE_WORKERS = TPU_PREFIX + "data-decode-workers"  # parse/cast pool
DEFAULT_DATA_DECODE_WORKERS = 0
# device-put pipeline depth (batches placed ahead of dispatch); 0 = auto
# (starts from shifu.tpu.prefetch-depth, then autotuned)
DATA_PREFETCH = TPU_PREFIX + "data-prefetch"
DEFAULT_DATA_PREFETCH = 0
DATA_AUTOTUNE = TPU_PREFIX + "data-autotune"
DEFAULT_DATA_AUTOTUNE = True
# seeded shuffle-buffer stage: window of rows permuted per seeded RNG
# before batching (0 = off).  Deterministic for a fixed seed regardless
# of reader/decode width — the streaming analogue of the in-memory
# loader's per-epoch shuffle.
DATA_SHUFFLE_ROWS = TPU_PREFIX + "data-shuffle-rows"
DEFAULT_DATA_SHUFFLE_ROWS = 0

# ---- elastic fleet (coordinator standby promotion + membership
# re-split; docs/resilience.md) ----
# Hot-standby workers launched BESIDE the fleet (the reference's backup
# instances, weakupBackup/TensorflowSession.java:748-781, made real):
# each registers with role=standby, pre-builds its model/optimizer
# (compile warm, no data shard), and heartbeats like any worker.  When a
# rank dies, the coordinator PROMOTES the freshest-heartbeat standby
# into the dead rank — same index, same shard, current generation —
# instead of restarting the fleet from checkpoint, so surviving ranks
# never roll back and promotion costs no restart budget.
STANDBY_WORKERS = TPU_PREFIX + "standby-workers"
DEFAULT_STANDBY_WORKERS = 0
# Elastic membership: when a rank fails with no standby left AND the
# restart budget exhausted, re-split the training data deterministically
# over the surviving ranks (data/splitter is a pure function of
# paths x n_workers) and continue rather than failing the job.  Also
# unlocks the coordinator's explicit resize op (grow/shrink).  Off by
# default: shrinking changes shard->rank assignment mid-job, which an
# operator must opt into.
ELASTIC = TPU_PREFIX + "elastic"
DEFAULT_ELASTIC = False

# flat-file (npz) checkpointing with sidecar-manifest verification for
# NON-SPMD workers too (SPMD always uses it — orbax's collective
# barriers deadlock under chief-writes/everyone-reads)
FLAT_CHECKPOINT = TPU_PREFIX + "flat-checkpoint"
DEFAULT_FLAT_CHECKPOINT = False

# ---- training-health watchdog (train/trainer.py HealthGuard;
# coordinator.report_unhealthy for the fleet rollback policy) ----
# On-device isfinite check on the per-step loss and (per-step path)
# global gradient norm, cross-referenced against host-side real-row
# bookkeeping so the NaN-as-padding marker never trips it.
HEALTH_CHECK_FINITE = TPU_PREFIX + "health-check-finite"
DEFAULT_HEALTH_CHECK_FINITE = True
# EMA loss-spike divergence detector: trip when a finite epoch loss
# exceeds factor x EMA of previous epochs (0 disables).
HEALTH_SPIKE_FACTOR = TPU_PREFIX + "health-spike-factor"
DEFAULT_HEALTH_SPIKE_FACTOR = 0.0
HEALTH_SPIKE_MIN_EPOCHS = TPU_PREFIX + "health-spike-min-epochs"
DEFAULT_HEALTH_SPIKE_MIN_EPOCHS = 2
# wall-clock per-step hang watchdog (ms; 0 disables): catches a wedged
# device call the liveness monitor is blind to (the heartbeat THREAD
# keeps beating while the training thread hangs).
HEALTH_HANG_TIMEOUT_MS = TPU_PREFIX + "health-hang-timeout"
DEFAULT_HEALTH_HANG_TIMEOUT_MS = 0
# fleet rollback policy: LR multiplier applied per rollback, the hard cap
# on rollbacks (they ALSO share the crash-restart budget), and the skip
# window — each reported bad step plus (window - 1) steps BEFORE it is
# skipped on the replay (the guard's report already covers the trailing
# side: it lists the first bad step and its non-finite successors).
HEALTH_LR_BACKOFF = TPU_PREFIX + "health-rollback-lr-backoff"
DEFAULT_HEALTH_LR_BACKOFF = 0.5
HEALTH_MAX_ROLLBACKS = TPU_PREFIX + "health-max-rollbacks"
DEFAULT_HEALTH_MAX_ROLLBACKS = 2
HEALTH_SKIP_WINDOW = TPU_PREFIX + "health-skip-window"
DEFAULT_HEALTH_SKIP_WINDOW = 1

# ---- online serving (serve/: micro-batched scoring server) ----
# The reference's L6 was a batch-only Java scorer; the serve subsystem
# puts an HTTP front in front of the same exported artifact.  All knobs
# resolve through serve/__main__.resolve_serve_config with the usual
# CLI-wins precedence and land in ServeConfig (serve/config.py).
SERVE_HOST = TPU_PREFIX + "serve-host"
DEFAULT_SERVE_HOST = "127.0.0.1"
SERVE_PORT = TPU_PREFIX + "serve-port"  # 0 = ephemeral (tests)
DEFAULT_SERVE_PORT = 8080
# scoring backend behind the server: native (jitted flax) | cpp |
# saved_model — the same EvalModel backends offline eval uses
SERVE_BACKEND = TPU_PREFIX + "serve-backend"
DEFAULT_SERVE_BACKEND = "native"
# micro-batcher: coalesce concurrent requests into one device dispatch of
# at most max-batch rows, waiting at most max-delay for peers to arrive.
# Dispatch shapes pad to the export/bucketing.py power-of-two ladder, so
# the jitted scorer compiles once per bucket, not once per batch length.
SERVE_MAX_BATCH = TPU_PREFIX + "serve-max-batch"
DEFAULT_SERVE_MAX_BATCH = 256
SERVE_MAX_DELAY_MS = TPU_PREFIX + "serve-max-delay"  # ms
DEFAULT_SERVE_MAX_DELAY_MS = 5.0
# backpressure: the admission queue is bounded at this many rows; a
# request that would overflow it is SHED with 429 + Retry-After instead
# of queued (unbounded queues collapse latency long before they reject)
SERVE_QUEUE_ROWS = TPU_PREFIX + "serve-queue-rows"
DEFAULT_SERVE_QUEUE_ROWS = 4096
SERVE_RETRY_AFTER_S = TPU_PREFIX + "serve-retry-after"  # seconds, int
DEFAULT_SERVE_RETRY_AFTER_S = 1
# hot reload: poll the export dir's manifest at this cadence; a changed
# artifact is admitted only after manifest verification (size + CRC32 +
# SHA-256) passes, and swaps atomically.  0 disables reload.
SERVE_RELOAD_POLL_MS = TPU_PREFIX + "serve-reload-poll"
DEFAULT_SERVE_RELOAD_POLL_MS = 2000
# multi-process scale-out: N scoring processes share ONE port via
# SO_REUSEPORT (the kernel load-balances connections), each with its own
# ModelStore/batcher/GIL and an obs journal sibling (<base>.s<i>).  A
# parent supervisor propagates SIGTERM drain and restarts crashed
# workers.  1 = the single-process server (no supervisor).
SERVE_WORKERS = TPU_PREFIX + "serve-workers"
DEFAULT_SERVE_WORKERS = 1

# ---- zero-copy columnar wire protocol (serve/wire/: binary frames on a
# persistent streaming connection; docs/serving.md "Wire protocol") ----
# Second listener speaking length-prefixed binary frames: the float32
# feature matrix lands as one buffer handed straight to the pack stage —
# no per-row JSON float parsing, no per-request concat copies — and
# concurrent requests multiplex on one connection, matched back by rid.
# 0 (default) = frame listener off; -1 = ephemeral port (tests/bench;
# the bound port rides the "listening" status line); >0 = fixed port,
# shared via SO_REUSEPORT when --serve-workers > 1.
SERVE_FRAME_PORT = TPU_PREFIX + "serve-frame-port"
DEFAULT_SERVE_FRAME_PORT = 0
# upper bound on rows in ONE frame, enforced BEFORE the payload is
# buffered (the length prefix is checked against it, so an oversized
# frame is refused with a typed 413 ERROR frame without allocating).
# Defaults to the admission bound — a frame the batcher could never
# admit is refused at the wire.  0 = track serve-queue-rows (whatever
# it resolves to), so shrinking the queue never silently leaves the
# wire accepting frames the batcher must refuse.
SERVE_FRAME_MAX_ROWS = TPU_PREFIX + "serve-frame-max-rows"
DEFAULT_SERVE_FRAME_MAX_ROWS = 0
# fleet-wide shared dispatch lane: with --serve-workers N > 1, exactly
# one worker (the lowest index, re-elected by the supervisor on worker
# death) owns device dispatch; siblings forward their packed per-tenant
# batches over a local UDS handoff and scatter the replies by rid, so
# DRR weights and coalescing apply across the whole fleet instead of
# fragmenting the device into N uncoordinated batchers.  Siblings fall
# back to their private dispatch path whenever the lane owner is
# unreachable (journaled lane_degraded/lane_restored).
SERVE_SHARED_LANE = TPU_PREFIX + "serve-shared-lane"
DEFAULT_SERVE_SHARED_LANE = False

# ---- SLO-driven serve autoscaling (serve/autoscale.py, run by the
# --serve-workers supervisor; docs/serving.md) ----
# Ceiling for the autoscaler: with serve-workers-max > serve-workers the
# supervisor runs a policy loop over the fleet's journaled SLO/shed
# events — sustained serve_p99/shed-rate breach adds an SO_REUSEPORT
# worker (up to this many), sustained recovery shrinks back toward
# serve-workers, and a single-tenant overload REBALANCES that tenant's
# DRR weight down before any scaling.  0 (default) disables the loop;
# it also needs an obs journal (the signals live there).
SERVE_WORKERS_MAX = TPU_PREFIX + "serve-workers-max"
DEFAULT_SERVE_WORKERS_MAX = 0
# seconds after any scale/rebalance decision during which the policy
# holds still (anti-flap; breach/recover hysteresis applies on top)
SERVE_AUTOSCALE_COOLDOWN_S = TPU_PREFIX + "serve-autoscale-cooldown"
DEFAULT_SERVE_AUTOSCALE_COOLDOWN_S = 60.0
# consecutive breached policy ticks before acting (the slo_breach events
# feeding the loop are already hysteretic; this is the policy's own
# debounce on top)
SERVE_AUTOSCALE_TICKS = TPU_PREFIX + "serve-autoscale-ticks"
DEFAULT_SERVE_AUTOSCALE_TICKS = 2
# consecutive CLEAN (recovered, non-empty) ticks before scaling back
# down — shrink must be much lazier than grow
SERVE_AUTOSCALE_RECOVERY_TICKS = TPU_PREFIX + "serve-autoscale-recovery-ticks"
DEFAULT_SERVE_AUTOSCALE_RECOVERY_TICKS = 6
# policy tick cadence in seconds
SERVE_AUTOSCALE_POLL_S = TPU_PREFIX + "serve-autoscale-poll"
DEFAULT_SERVE_AUTOSCALE_POLL_S = 5.0
# supervisor scrape surface: a /metrics-only HTTP listener on the parent
# supervisor process exposing the stpu_serve_scale_* gauges (worker
# count, ceiling, scale/rebalance totals, restart-budget remaining and
# per-window burn — the PR-5 sliding-window budget was invisible until
# it exhausted at rc 4).  0 (default) = off; the same numbers always
# ride the journal events either way.
SERVE_SUPERVISOR_PORT = TPU_PREFIX + "serve-supervisor-port"
DEFAULT_SERVE_SUPERVISOR_PORT = 0

# ---- AOT executable shipping (export/aot.py: compile once at export,
# serve everywhere) ----
# Serialize the bucket ladder's compiled executables into the export
# bundle (aot/ subdir, digested into the manifest like any artifact) so
# serve admission DESERIALIZES instead of compiling: a fleet restart
# cold-starts in deserialize time instead of tenants x buckets compile
# time, and every SO_REUSEPORT worker loads the same shipped programs.
# Loadable only on a matching compile environment (jax/jaxlib/backend/
# device-kind fingerprint stamped in the bundle); any mismatch falls
# back PER BUCKET to a live compile — AOT never refuses a bundle that
# can still compile live.
EXPORT_AOT = TPU_PREFIX + "export-aot"
DEFAULT_EXPORT_AOT = False
# the ladder to pre-compile covers every bucket reachable under this
# many rows (export/bucketing.ladder); default matches the serve
# plane's warm set, ladder(serve-queue-rows)
EXPORT_AOT_ROWS = TPU_PREFIX + "export-aot-rows"
DEFAULT_EXPORT_AOT_ROWS = DEFAULT_SERVE_QUEUE_ROWS
# jax persistent compilation cache dir — the middle tier of the AOT
# fallback ladder (shipped executable -> this cache -> live compile): a
# fingerprint-mismatched bucket that live-compiles populates it, so the
# NEXT worker/restart on this host still skips XLA.  The cache is always
# on: JAX_COMPILATION_CACHE_DIR places it where set (this key is then
# ignored), else this key, else <checkout>/.jax_cache
# (obs/compile.py apply_persistent_cache).
COMPILE_CACHE_DIR = TPU_PREFIX + "compile-cache-dir"
DEFAULT_COMPILE_CACHE_DIR = ""

# ---- multi-tenant serving (serve/tenancy/: one endpoint, many models) ----
# A models DIR turns the server multi-tenant: every immediate
# subdirectory holding an exported bundle is a tenant named by the
# subdirectory, routed at /score/<model>.  Mutually exclusive with the
# single-model --model-dir; empty (the default) keeps single-model mode.
SERVE_MODELS_DIR = TPU_PREFIX + "serve-models-dir"
DEFAULT_SERVE_MODELS_DIR = ""
# admission budget in MB of bundle bytes (a proxy for resident model
# memory: weights + compiled ladder scale with the artifact).  Admitting
# past it evicts least-recently-used tenants first; a single bundle
# larger than the whole budget is refused.  0 = unlimited.
SERVE_MODEL_BUDGET_MB = TPU_PREFIX + "serve-model-budget-mb"
DEFAULT_SERVE_MODEL_BUDGET_MB = 0.0
# cold-start guard: how long a request for an evicted-but-admittable
# model waits on the in-flight admission (verify + warm ladder) before
# 503 + Retry-After.  The admission itself always runs to completion in
# the background — a timed-out caller retries into a warm model.
SERVE_MODEL_ADMIT_WAIT_S = TPU_PREFIX + "serve-model-admit-wait"
DEFAULT_SERVE_MODEL_ADMIT_WAIT_S = 30.0
# weighted fair dispatch: per-tenant weight under the shared device
# scheduler's deficit round-robin (serve/tenancy/scheduler.py).  Append
# the model name: shifu.tpu.serve-tenant-weight-<model> = 2.0 gives
# <model> 2x the device rows of a weight-1 tenant under contention;
# idle tenants cost nothing (work-conserving).
SERVE_TENANT_WEIGHT_PREFIX = TPU_PREFIX + "serve-tenant-weight-"
DEFAULT_SERVE_TENANT_WEIGHT = 1.0

# ---- observability plane (obs/: registry + trace + journal) ----
# Off-by-default-cheap: with every key unset the instrumented seams cost
# one is-None check.  Enabling turns on step-phase span timing
# (infeed/host/dispatch/block per epoch) and — with a journal path — the
# append-only JSONL event journal all three planes (train, coordinator,
# serve) write lifecycle events into.  All knobs resolve through
# obs/config.resolve_obs_config with the usual CLI-wins precedence and
# ride the WorkerConfig JSON bridge into subprocess workers.
OBS_ENABLED = TPU_PREFIX + "obs-enabled"
DEFAULT_OBS_ENABLED = False
# journal base path ("" = no journal).  Fleet workers write
# <path>.w<index> siblings; the obs CLI merges the set.
OBS_JOURNAL = TPU_PREFIX + "obs-journal"
DEFAULT_OBS_JOURNAL = ""
# per-writer rotation: the active file rotates past this size (memory
# string: "8m", "512k", plain bytes), keeping obs-journal-max-files
# files — disk footprint is bounded at max-bytes x max-files per writer
OBS_JOURNAL_MAX_BYTES = TPU_PREFIX + "obs-journal-max-bytes"
DEFAULT_OBS_JOURNAL_MAX_BYTES = 8 << 20
OBS_JOURNAL_MAX_FILES = TPU_PREFIX + "obs-journal-max-files"
DEFAULT_OBS_JOURNAL_MAX_FILES = 4
# span sampling: measure every Nth event per span name (1 = all).
# Ratios in the step budget stay unbiased; the (already sub-2%) cost
# divides by N
OBS_TRACE_SAMPLE = TPU_PREFIX + "obs-trace-sample"
DEFAULT_OBS_TRACE_SAMPLE = 1
# latency-histogram bucket bounds for the registry-backed scrape
# surfaces, comma-separated seconds ("" = the built-in ~100µs..60s
# ladder, obs/registry.DEFAULT_BOUNDS)
OBS_HIST_BUCKETS = TPU_PREFIX + "obs-hist-buckets"
DEFAULT_OBS_HIST_BUCKETS = ""
# compile flight recorder (obs/compile.py) analysis depth: "full" adds
# compiled.memory_analysis() bytes to each journaled compile event at
# the price of a SECOND backend compile per new signature (negligible on
# CPU, seconds per program on real accelerators); "cost" keeps the cheap
# Lowered.cost_analysis() flops/bytes fields only; "off" journals timing
# alone.  "auto" (default) resolves per plane: full on train/coordinator
# (compiles are rare and off any request path), cost on serve — a
# request-path compile there runs under the compute lock on the dispatch
# thread, and doubling it would double the very latency cliff the storm
# detector exists to diagnose.
OBS_COMPILE_ANALYSIS = TPU_PREFIX + "obs-compile-analysis"
DEFAULT_OBS_COMPILE_ANALYSIS = "auto"
# recompile-storm threshold: this many NON-warm compiles inside one
# slo-window opens a storm (journals recompile_storm naming the churning
# callable+signature; clears at half the threshold).  Warm-ladder
# compiles never count — pre-warming is the cure, not the disease.
OBS_COMPILE_STORM = TPU_PREFIX + "obs-compile-storm"
DEFAULT_OBS_COMPILE_STORM = 8
# ---- rollup archive (obs/rollup.py: the obs plane's time axis) ----
# The journal is rotation-bounded (max-bytes x max-files per writer), so
# a multi-day job loses its own history.  With a journal configured, a
# per-writer compactor folds events + monotonic-counter deltas + digest
# snapshots into one downsampled record per obs-rollup-window appended
# to a <journal>.rollup.jsonl sidecar EXEMPT from rotation — hours of
# history cost KBs, and `obs report` reconstructs a dead fleet's full
# run from the sidecars alone.  obs-rollup=false turns the compactor off.
OBS_ROLLUP = TPU_PREFIX + "obs-rollup"
DEFAULT_OBS_ROLLUP = True
OBS_ROLLUP_WINDOW_S = TPU_PREFIX + "obs-rollup-window"  # seconds
DEFAULT_OBS_ROLLUP_WINDOW_S = 60.0
# pinned baseline for cross-run regression detection: a rollup sidecar
# (or journal base whose sidecars exist) from a known-good run.  The
# regression watchdog compares live windowed digests against the
# baseline's merged digests ("" = no baseline, watchdog off).
OBS_BASELINE = TPU_PREFIX + "obs-baseline"
DEFAULT_OBS_BASELINE = ""
# regression threshold: live/baseline ratio at or above which the
# watchdog journals perf_regression naming the metric and magnitude
# (hysteretic, like every other slo state machine; clears below the
# threshold via perf_regression_clear).  Must be > 1 when set — a run
# always sits at ~1 against its own baseline; 0 = disabled even with a
# baseline pinned.
SLO_REGRESSION = TPU_PREFIX + "slo-regression"
DEFAULT_SLO_REGRESSION = 0.0

# ---- SLO watchdog (obs/slo.py: windowed quantile digests + breach
# events) ----
# Evaluated over a sliding window of this many seconds; targets of 0
# leave a signal untargeted (gauges + EWMA-z anomaly detection still
# run).  Breach/recover transitions are hysteretic — a signal must hold
# its state for slo-hysteresis consecutive evaluations before the
# journal records slo_breach / slo_recover — and every /metrics surface
# appends the stpu_slo_* gauges, so an autoscaling supervisor can read
# the same signal the journal records.
SLO_WINDOW_S = TPU_PREFIX + "slo-window"  # seconds
DEFAULT_SLO_WINDOW_S = 60.0
SLO_SERVE_P99_MS = TPU_PREFIX + "slo-serve-p99"  # ms; 0 = no target
DEFAULT_SLO_SERVE_P99_MS = 0.0
# shed fraction of scoring attempts over the window (0..1; 0 = no target)
SLO_SERVE_SHED_RATE = TPU_PREFIX + "slo-serve-shed-rate"
DEFAULT_SLO_SERVE_SHED_RATE = 0.0
SLO_STEP_TIME_MS = TPU_PREFIX + "slo-step-time"  # ms; 0 = no target
DEFAULT_SLO_STEP_TIME_MS = 0.0
# infeed-wait fraction of the step budget (0..1; 0 = no target)
SLO_INFEED_FRAC = TPU_PREFIX + "slo-infeed-frac"
DEFAULT_SLO_INFEED_FRAC = 0.0
SLO_HYSTERESIS = TPU_PREFIX + "slo-hysteresis"  # consecutive evaluations
DEFAULT_SLO_HYSTERESIS = 2
# EWMA-z anomaly threshold in sigmas (0 disables anomaly detection)
SLO_ANOMALY_SIGMA = TPU_PREFIX + "slo-anomaly-sigma"
DEFAULT_SLO_ANOMALY_SIGMA = 6.0
# device/compiler leg (PR 10).  slo-compile-s: window MAX of journaled
# backend-compile seconds (one slow compile is the breach); 0 = no
# target.  slo-devmem-frac: device bytes-in-use / bytes-limit from the
# backend's memory_stats (absent on backends that don't report a limit,
# e.g. CPU — the signal is then absent, never zero); 0 = no target.
SLO_COMPILE_S = TPU_PREFIX + "slo-compile-s"  # seconds; 0 = no target
DEFAULT_SLO_COMPILE_S = 0.0
SLO_DEVMEM_FRAC = TPU_PREFIX + "slo-devmem-frac"  # 0..1; 0 = no target
DEFAULT_SLO_DEVMEM_FRAC = 0.0
# fleet leg (obs/fleet.py).  slo-straggler-skew: watchdog target on the
# window MAX of per-rank relative step-time skew (rank window mean over
# the median of its peers'); 0 = no target — the straggler detect/clear
# events below still fire.  Must be > 1 when set: a fleet at parity has
# skew exactly 1.
SLO_STRAGGLER_SKEW = TPU_PREFIX + "slo-straggler-skew"
DEFAULT_SLO_STRAGGLER_SKEW = 0.0
# straggler detection threshold: a rank whose relative skew holds at or
# above this for slo-hysteresis consecutive epochs journals
# straggler_detect (naming the rank and its dominant phase);
# straggler_clear on the same count of clean epochs.  Relative, so a
# uniformly slow fleet never alarms.
FLEET_SKEW_THRESHOLD = TPU_PREFIX + "fleet-skew-threshold"
DEFAULT_FLEET_SKEW_THRESHOLD = 1.5
# data leg (obs/datastats.py).  slo-data-drift: watchdog target on the
# window MAX of per-model drift scores (live windowed feature sketch vs
# the bundle-shipped feature_stats.json baseline); 0 = no target — the
# per-feature data_drift/data_drift_clear events below still fire.
SLO_DATA_DRIFT = TPU_PREFIX + "slo-data-drift"
DEFAULT_SLO_DATA_DRIFT = 0.0
# per-feature drift detection threshold: a feature whose drift score
# (max of mean/std/quantile displacement in baseline-spread units and
# 4x the missing/inf-rate deltas) holds at or above this for
# slo-hysteresis consecutive evaluations journals data_drift naming the
# model, feature, and offending statistic; data_drift_clear on the same
# count of clean evaluations.  1.0 ≈ "the live mean moved one baseline
# sigma" — a real shift, not batch noise.
DATA_DRIFT_THRESHOLD = TPU_PREFIX + "data-drift-threshold"
DEFAULT_DATA_DRIFT_THRESHOLD = 1.0

# ---- transient-fault retry envelope (utils/retry.py) ----
# The reference inherited retry from YARN/ZooKeeper/DFSClient; our stdlib
# network planes (WebHDFS/GCS clients, coordinator RPC, remote checkpoint
# writes) carry their own classify-retry-with-backoff discipline, tuned
# here.  retry-max-attempts=1 disables retries (the chaos drill's control
# arm); retry-deadline caps one call's CUMULATIVE BACKOFF SLEEP — the
# stall the retry layer itself adds — NOT the attempts' own blocking time
# (a long-blocking barrier RPC keeps its reconnect budget), so bounding a
# seam against the liveness monitor's patience also needs per-request
# socket timeouts.
RETRY_MAX_ATTEMPTS = TPU_PREFIX + "retry-max-attempts"
DEFAULT_RETRY_MAX_ATTEMPTS = 5
RETRY_BASE_DELAY_MS = TPU_PREFIX + "retry-base-delay"  # ms, backoff base
DEFAULT_RETRY_BASE_DELAY_MS = 50
RETRY_MAX_DELAY_MS = TPU_PREFIX + "retry-max-delay"  # ms, per-sleep cap
DEFAULT_RETRY_MAX_DELAY_MS = 2000
# ms, cap on a call's CUMULATIVE backoff sleep (the stall retry itself
# adds) — not on the attempts' own runtime, so long-blocking barrier RPCs
# keep their reconnect budget
RETRY_DEADLINE_MS = TPU_PREFIX + "retry-deadline"
DEFAULT_RETRY_DEADLINE_MS = 60_000

# ---- bulk scoring plane (score/; docs/scoring.md) ----
# score-workers: scan fleet size the driver spawns (each worker is an
# admission-free AOT-admitted scorer process; elastic — a killed worker's
# leases expire and peers finish the job).
SCORE_WORKERS = TPU_PREFIX + "score-workers"
DEFAULT_SCORE_WORKERS = 2
# score-lease-ttl: seconds a shard lease lives without renewal (workers
# renew at ttl/3).  The recovery latency for a SIGKILLed scorer's shard
# is bounded by this plus one driver reclaim tick (ttl/4).
SCORE_LEASE_TTL_S = TPU_PREFIX + "score-lease-ttl"
DEFAULT_SCORE_LEASE_TTL_S = 10.0
# score-speculate-factor: when no shard is PENDING, an idle worker may
# steal (early-reclaim) the longest-running lease once it has outlived
# factor x the median committed-shard duration — straggler speculation
# on the reclaim path; first-commit-wins keeps it exactly-once.
# 0 disables.
SCORE_SPECULATE_FACTOR = TPU_PREFIX + "score-speculate-factor"
DEFAULT_SCORE_SPECULATE_FACTOR = 4.0
# score-max-shards: cap on the shard plan; 0 = one shard per input file,
# else size-aware grouping (splitter LPT) down to at most this many.
SCORE_MAX_SHARDS = TPU_PREFIX + "score-max-shards"
DEFAULT_SCORE_MAX_SHARDS = 0
# score-batch-rows: rows per decoded block = rows per compute_batch
# dispatch in the scan loop (bucket-ladder padding applies per call).
SCORE_BATCH_ROWS = TPU_PREFIX + "score-batch-rows"
DEFAULT_SCORE_BATCH_ROWS = 4096

# ---- closed-loop model lifecycle (lifecycle/; docs/lifecycle.md) ----
# lifecycle-model: the serving tenant the controller manages (drift on
# it triggers retrain; its bundle is the parent generation).
LIFECYCLE_MODEL = TPU_PREFIX + "lifecycle-model"
DEFAULT_LIFECYCLE_MODEL = ""
# lifecycle-poll: seconds between controller ticks (journal poll +
# policy evaluation).  Every hysteresis/cooldown below counts TICKS of
# this cadence or wall seconds as documented per key.
LIFECYCLE_POLL_S = TPU_PREFIX + "lifecycle-poll"
DEFAULT_LIFECYCLE_POLL_S = 1.0
# lifecycle-trigger-hysteresis: consecutive ticks with an open
# data_drift/perf_regression before a retrain triggers — one drifted
# window must not launch a fleet.
LIFECYCLE_TRIGGER_HYSTERESIS = TPU_PREFIX + "lifecycle-trigger-hysteresis"
DEFAULT_LIFECYCLE_TRIGGER_HYSTERESIS = 3
# lifecycle-cooldown: seconds after a retrain LAUNCH before drift may
# trigger another (covers the whole shadow/ramp evaluation of the
# previous generation plus a margin).
LIFECYCLE_COOLDOWN_S = TPU_PREFIX + "lifecycle-cooldown"
DEFAULT_LIFECYCLE_COOLDOWN_S = 300.0
# lifecycle-shadow-min-rows: mirrored rows the shadow generation must
# have scored before its score distribution is comparable at all.
LIFECYCLE_SHADOW_MIN_ROWS = TPU_PREFIX + "lifecycle-shadow-min-rows"
DEFAULT_LIFECYCLE_SHADOW_MIN_ROWS = 256
# lifecycle-divergence-threshold: parent-vs-shadow score-distribution
# divergence (drift_components max over the 1-wide score column,
# dimensionless, ~1.0 = clearly diverged) above which promotion is
# blocked and a ramping generation rolls back.
LIFECYCLE_DIVERGENCE_THRESHOLD = TPU_PREFIX + "lifecycle-divergence-threshold"
DEFAULT_LIFECYCLE_DIVERGENCE_THRESHOLD = 1.0
# lifecycle-ramp-steps: comma-separated traffic fractions the candidate
# walks through before promotion (each held for lifecycle-ramp-interval
# and gated on SLO + divergence before the next).
LIFECYCLE_RAMP_STEPS = TPU_PREFIX + "lifecycle-ramp-steps"
DEFAULT_LIFECYCLE_RAMP_STEPS = "0.05,0.25,0.5"
# lifecycle-ramp-interval: seconds each ramp step must hold clean
# before advancing.
LIFECYCLE_RAMP_INTERVAL_S = TPU_PREFIX + "lifecycle-ramp-interval"
DEFAULT_LIFECYCLE_RAMP_INTERVAL_S = 30.0
# lifecycle-rollback-hysteresis: consecutive BAD ticks (SLO breach on
# the managed model, or divergence past the threshold) during
# shadow/ramp before the candidate rolls back — the mirror image of the
# trigger hysteresis, so one noisy window cannot kill a good candidate.
LIFECYCLE_ROLLBACK_HYSTERESIS = TPU_PREFIX + "lifecycle-rollback-hysteresis"
DEFAULT_LIFECYCLE_ROLLBACK_HYSTERESIS = 2
# lifecycle-retrain-timeout: wall-second budget for the retrain job; a
# job past it is killed and verdicts as a failed retrain (back to IDLE
# under cooldown, parent keeps serving).
LIFECYCLE_RETRAIN_TIMEOUT_S = TPU_PREFIX + "lifecycle-retrain-timeout"
DEFAULT_LIFECYCLE_RETRAIN_TIMEOUT_S = 1800.0

# ---- fault-tolerance envelope (reference: Constants.java:87-89; the ps
# threshold has no analogue — there is no PS role) ----
WORKER_FAULT_TOLERANCE_THRESHOLD = 0.1
MIN_WORKERS_START_TRAINING_THRESHOLD = 0.95
REGISTRATION_SOFT_TIMEOUT_S = 6 * 60  # partial-start wait
REGISTRATION_HARD_TIMEOUT_S = 20 * 60  # hard abort

# ---- file-name constants (reference: Constants.java:34-39) ----
GLOBAL_DEFAULT_FILE = "global-default.xml"
GLOBAL_FINAL_FILE = "global-final.xml"
MODEL_CONFIG_FILE = "ModelConfig.json"
COLUMN_CONFIG_FILE = "ColumnConfig.json"
GENERIC_MODEL_CONFIG_FILE = "GenericModelConfig.json"
