"""Worker-side runtime: register → barrier → train → report → complete.

Parity surface: the reference's in-container executor chain —
``TensorflowTaskExecutor`` registering on ZK, awaiting the final cluster,
then exec'ing the Python trainer whose epoch loop pushes metrics to the
local socket server (TensorflowTaskExecutor.java:93-111,300-317,
ssgd_monitor.py:268-293).  Here the whole chain is one process: the worker
registers with the coordinator, blocks on the start barrier, streams its
shard into the Trainer, reports per-epoch stats and heartbeats in-band, and
completes with an exit code the coordinator's failure policy consumes.

Cross-process SPMD (``JobSpec.spmd``): the fleet is ONE ``jax.distributed``
job — the worker initializes the jax coordination service from the
coordinator's cluster info (chief host + reserved port), builds the global
mesh spanning every process's devices, and feeds only its local slice of
the global batch; XLA all-reduces gradients across processes.  That is the
TPU-native replacement for the reference's PS + SyncReplicasOptimizer
(ssgd_monitor.py:136-142): N workers train ONE model.

Recovery: on start the worker always tries to restore the shared
checkpoint; a relaunched worker therefore resumes at the right epoch with
its sticky shard (replaces backup wake-up, and fixes the epoch-budget gap
acknowledged at backup.py:30).  SPMD recovery is fleet-wide — the
coordinator bumps the generation, the submitter kills + relaunches every
process, and sync_plan agrees the restore epoch.
"""

from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Callable

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.coordinator.coordinator import (
    RESTART_EXIT_CODE,
    UNHEALTHY_EXIT_CODE,
    CoordinatorClient,
)
from shifu_tensorflow_tpu.data.dataset import (
    InMemoryDataset,
    ShardStream,
    fixed_step_batches,
)
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.checkpoint import Checkpointer, NpzCheckpointer
from shifu_tensorflow_tpu.train.trainer import HealthConfig, TrainingUnhealthy
from shifu_tensorflow_tpu.utils import logs

log = logs.get("worker")


@dataclass
class WorkerConfig:
    worker_id: str
    coordinator_host: str
    coordinator_port: int
    model_config: ModelConfig
    schema: RecordSchema
    # pins this worker to a cluster slot; None lets the coordinator pick
    worker_index: int | None = None
    # "worker" | "standby": a standby registers rankless, pre-builds its
    # model/optimizer (compile warm, no data shard), heartbeats, and
    # long-polls the coordinator until a rank failure promotes it — then
    # runs the normal worker lifecycle as that rank (docs/resilience.md)
    role: str = "worker"
    batch_size: int = 100
    checkpoint_dir: str | None = None
    checkpoint_every_epochs: int = 1
    valid_rate: float | None = None  # None -> model_config.valid_set_rate
    heartbeat_interval_s: float = 0.5
    mesh_spec: str | None = None
    seed: int = 0
    dtype: str | None = None  # "float32" | "bfloat16"; None -> float32
    # cross-process SPMD membership (one model across the fleet)
    spmd: bool = False
    host: str = "127.0.0.1"  # this worker's address for peers
    # streaming input (1B-row path): stream the shard instead of loading it
    stream: bool = False
    # staged-ingest knobs (shifu.tpu.data-* keys; data/pipeline.py):
    # None/0 = auto — the per-worker autotuner sizes the dimension
    # between epochs; an explicit value pins it (data/autotune.py)
    n_readers: int | None = None
    decode_workers: int | None = None
    data_prefetch: int | None = None
    data_autotune: bool = True
    # seeded shuffle-buffer window in rows (0 = off); deterministic per
    # (seed, epoch) at any reader/decode width
    data_shuffle_rows: int = 0
    # device-infeed lookahead (conf key shifu.tpu.prefetch-depth)
    prefetch_depth: int = 2
    # batches per lax.scan dispatch (conf key shifu.tpu.scan-steps)
    scan_steps: int = 1
    # microbatches per optimizer update (conf key shifu.tpu.accum-steps)
    accum_steps: int = 1
    # keep-best metric ("" = off; conf key shifu.tpu.keep-best); the
    # chief persists its best snapshot beside the shared checkpoints
    keep_best: str = ""
    # background checkpoint writes (conf key shifu.tpu.async-checkpoint)
    async_checkpoint: bool = False
    # use the flat-file NpzCheckpointer (sidecar-manifest-verified save /
    # quarantine-and-fall-back restore) even for non-SPMD workers; SPMD
    # always uses it (orbax's collective barriers deadlock there)
    flat_checkpoint: bool = False
    # binary shard cache directory (data/cache.py); None = no caching
    cache_dir: str | None = None
    # streaming transport dtype for features (conf key
    # shifu.tpu.stream-feature-dtype): auto = bf16 unless hashing or
    # un-normalized features (no ZSCALE stats)
    stream_feature_dtype: str = "auto"
    # transient-fault retry envelope (shifu.tpu.retry-* keys) as a
    # RetryPolicy dict; None keeps the process default.  Carried in the
    # JSON transport so subprocess workers inherit the submit-side conf.
    retry: dict | None = None
    # training-health guard (shifu.tpu.health-* keys): on-device
    # isfinite checks on loss/grad-norm, EMA loss-spike detection, and
    # the wall-clock per-step hang watchdog (0 = off)
    health_check_finite: bool = True
    health_spike_factor: float = 0.0
    health_spike_min_epochs: int = 2
    health_hang_timeout_s: float = 0.0
    # observability plane (shifu.tpu.obs-* keys) as an ObsConfig dict;
    # None keeps obs off.  Carried in the JSON transport so subprocess
    # workers inherit the submit-side conf — each worker journals to
    # <journal_path>.w<index> (one writer per file, obs/journal.py)
    obs: dict | None = None

    def to_json(self) -> dict:
        """JSON transport for subprocess workers (worker_main)."""
        from dataclasses import asdict

        d = {
            k: getattr(self, k)
            for k in (
                "worker_id", "coordinator_host", "coordinator_port",
                "worker_index", "role", "batch_size", "checkpoint_dir",
                "checkpoint_every_epochs", "valid_rate",
                "heartbeat_interval_s", "mesh_spec", "seed", "dtype",
                "spmd", "host", "stream", "n_readers", "decode_workers",
                "data_prefetch", "data_autotune", "data_shuffle_rows",
                "prefetch_depth",
                "scan_steps", "accum_steps", "keep_best",
                "async_checkpoint", "flat_checkpoint", "cache_dir",
                "stream_feature_dtype",
                "retry", "health_check_finite", "health_spike_factor",
                "health_spike_min_epochs", "health_hang_timeout_s",
                "obs",
            )
        }
        d["model_config"] = dict(self.model_config.raw)
        d["schema"] = asdict(self.schema)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "WorkerConfig":
        d = dict(d)
        mc = ModelConfig.from_json(d.pop("model_config") or {})
        s = d.pop("schema")
        schema = RecordSchema(
            feature_columns=tuple(s["feature_columns"]),
            target_column=s["target_column"],
            weight_column=s.get("weight_column", -1),
            delimiter=s.get("delimiter", "|"),
            means=tuple(s.get("means") or ()),
            stds=tuple(s.get("stds") or ()),
        )
        return cls(model_config=mc, schema=schema, **d)


class _HeartbeatThread(threading.Thread):
    """``generation=None`` disables the fleet-restart watch: standbys
    keep heartbeating across generation bumps — they are not collective
    participants, and their promotion reply carries whatever generation
    is current."""

    def __init__(
        self,
        client: CoordinatorClient,
        worker_id: str,
        interval_s: float,
        generation: int | None = 0,
    ):
        super().__init__(daemon=True)
        self.client = client
        self.worker_id = worker_id
        self.interval_s = interval_s
        self.generation = generation
        self.abort = threading.Event()
        self.restart = threading.Event()
        # the coordinator wrote this rank off (standby promoted into it,
        # or an elastic shrink re-split its rows away) while this
        # process was merely FLAPPED: exit cooperatively at the next
        # epoch boundary instead of training a shard someone else owns
        self.released = threading.Event()
        self._stop = threading.Event()

    def run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                resp = self.client.heartbeat(self.worker_id)
                if resp.get("abort"):
                    self.abort.set()
                    return
                if resp.get("released"):
                    self.released.set()
                    return
                if self.generation is None:
                    continue
                if int(resp.get("generation", self.generation)) != self.generation:
                    # fleet restarted without us (we may be about to be
                    # killed by the submitter; exit cooperatively first)
                    self.restart.set()
                    return
            except Exception:
                # coordinator unreachable: keep trying; the trainer decides
                # nothing — the coordinator's liveness policy decides for us
                continue

    def stop(self) -> None:
        self._stop.set()


def _stream_step_estimate(
    total_lines: int, rate: float, batch_size: int
) -> int:
    """Step count covering a hash-split stream of ``total_lines`` rows at
    split ``rate`` with overwhelming probability.

    Row→train/valid membership is per-row content hashing, so the actual
    split size is Binomial(lines, rate): mean ``lines*rate``, sd at most
    ``sqrt(lines)/2``.  Overshooting steps costs only zero-weight padding
    batches; undershooting silently drops rows — so budget mean + 8 sd.
    """
    if rate <= 0.0:
        return 0
    bound = total_lines * rate + 4.0 * math.sqrt(max(total_lines, 1))
    return max(1, int(math.ceil(min(bound, total_lines) / batch_size)))


def _health_from_cfg(cfg: WorkerConfig, lr_scale: float = 1.0,
                     skip: dict | None = None) -> HealthConfig:
    """HealthConfig from the worker's knobs plus the coordinator's
    rollback directive — one resolver shared by the normal lifecycle and
    the standby pre-build, so the two cannot drift."""
    skip = skip or {}
    return HealthConfig(
        check_finite=cfg.health_check_finite,
        spike_factor=cfg.health_spike_factor,
        spike_min_epochs=cfg.health_spike_min_epochs,
        hang_timeout_s=cfg.health_hang_timeout_s,
        lr_scale=lr_scale,
        skip_epoch=(int(skip["epoch"]) if skip.get("epoch") is not None
                    else None),
        skip_steps=tuple(int(s) for s in (skip.get("steps") or ())),
    )


def _build_trainer(cfg: WorkerConfig, model_config, health, *,
                   worker_index: int, mesh=None, topology=None):
    """The one trainer-construction site (normal lifecycle AND standby
    pre-build build through here)."""
    extra = {}
    if cfg.dtype:
        import jax.numpy as jnp

        extra["dtype"] = {"float32": jnp.float32,
                          "bfloat16": jnp.bfloat16}[cfg.dtype]
    # feature_columns must match what the export trainer will use, or
    # wide/embedding column positions (and so the param tree) diverge
    # between the trained checkpoint and the restored export model
    return make_trainer(
        model_config,
        cfg.schema.num_features,
        feature_columns=cfg.schema.feature_columns,
        mesh=mesh,
        worker_index=worker_index,
        seed=cfg.seed,
        topology=topology,
        prefetch_depth=cfg.prefetch_depth,
        scan_steps=cfg.scan_steps,
        accum_steps=cfg.accum_steps,
        keep_best=cfg.keep_best,
        health=health,
        **extra,
    )


def _standby_phase(cfg: WorkerConfig, client: CoordinatorClient):
    """Hot-standby lifecycle until promotion: register rankless, pre-build
    the model/optimizer and compile-warm the step functions (no data
    shard touched), heartbeat, and long-poll ``standby_wait``.

    Returns ``(promotion_reply, prebuilt_trainer_or_None)``; ``(None,
    None)`` when the job ends without this standby being needed.  The
    prebuild is best-effort — any failure just means the promoted rank
    builds cold, exactly like a relaunched worker.
    """
    reg = client.register(cfg.worker_id, host=cfg.host, role="standby")
    if not reg.get("ok"):
        log.error("standby registration rejected: %s", reg.get("error"))
        return None, None
    hb = _HeartbeatThread(
        client, cfg.worker_id, cfg.heartbeat_interval_s, generation=None
    )
    hb.start()
    trainer = None
    try:
        if not bool(reg.get("spmd", cfg.spmd)):
            # SPMD standbys stay un-built: the mesh spans processes that
            # only exist once the (restarted) fleet forms; their compile
            # warmth comes from the persistent compile cache instead
            try:
                mesh = None
                if cfg.mesh_spec:
                    from shifu_tensorflow_tpu.parallel.mesh import make_mesh

                    mesh = make_mesh(cfg.mesh_spec)
                trainer = _build_trainer(
                    cfg, cfg.model_config, _health_from_cfg(cfg),
                    worker_index=-1, mesh=mesh,
                )
                warmed = trainer.warm_step(
                    cfg.batch_size,
                    x_dtype=_np_feature_dtype(cfg) if cfg.stream else None,
                )
                log.info("standby %s pre-built and warmed %s",
                         cfg.worker_id, warmed)
            except Exception:
                log.exception(
                    "standby pre-build failed (%s); promotion will build "
                    "cold", cfg.worker_id)
                trainer = None
        while True:
            if hb.abort.is_set():
                return None, None
            try:
                resp = client.standby_wait(cfg.worker_id, timeout_s=10.0)
            except Exception:
                # coordinator unreachable past the retry envelope: the
                # job is gone — a standby exits quietly, it was never a
                # rank anyone is waiting on
                log.exception("standby %s lost the coordinator; exiting",
                              cfg.worker_id)
                return None, None
            if resp.get("promoted"):
                log.warning(
                    "standby %s promoted into rank %s (generation %s)",
                    cfg.worker_id, resp.get("worker_index"),
                    resp.get("generation"),
                )
                return resp, trainer
            if not resp.get("ok"):
                # terminal job state (or we were never admitted)
                return None, None
    finally:
        hb.stop()


def run_worker(cfg: WorkerConfig, *,
               fail_at_epoch: int | None = None) -> int:
    """Full worker lifecycle; returns the exit code it reported.

    ``fail_at_epoch`` is the built-in fault-injection hook (the reference
    only had a commented-out kill-PS-after-80s hack,
    CommonUtils.java:265-273): the worker aborts mid-job at that epoch.

    ``cfg.role == "standby"`` prepends the hot-standby phase: register
    rankless, pre-build + compile-warm, wait for a promotion — then run
    this very lifecycle as the promoted rank (the re-registration is
    sticky: the coordinator moved the standby's record into the dead
    rank's slot, so the register below returns that rank's shard, epoch
    state, and health directive).
    """
    from shifu_tensorflow_tpu.parallel import distributed as dist

    logs.set_worker(cfg.worker_id)
    if cfg.retry is not None:
        # subprocess workers inherit the submit-side retry envelope; the
        # fs backends and checkpointer resolve the default lazily per call
        from shifu_tensorflow_tpu.utils import retry as retry_util

        retry_util.set_default_policy(
            retry_util.RetryPolicy.from_dict(cfg.retry)
        )
    client = CoordinatorClient(cfg.coordinator_host, cfg.coordinator_port)
    prebuilt = None
    promoted_from_standby = False
    if cfg.role == "standby":
        promoted_from_standby = True
        promo, prebuilt = _standby_phase(cfg, client)
        if promo is None:
            # never promoted: the job ended (or refused us) — a clean,
            # budget-free exit the coordinator logs as standby_exit
            try:
                client.complete(cfg.worker_id, 0)
            except Exception:
                pass
            return 0
        import dataclasses as _dc

        # fall into the normal lifecycle AS the promoted rank: the
        # sticky re-registration below returns the rank's shard/state
        cfg = _dc.replace(cfg, role="worker",
                          worker_index=int(promo["worker_index"]))
    # reserve a port for the jax coordination service up front: only the
    # chief's is used, but index assignment happens at registration.  The
    # reservation is HELD (socket open) until just before initialize binds
    # it — round 2's flaky recovery traced to this port being stolen in the
    # registration window under load.
    port_hold = dist.ReservedPort(cfg.host) if cfg.spmd else None
    reg = client.register(
        cfg.worker_id, cfg.worker_index, host=cfg.host,
        jax_port=port_hold.port if port_hold else None,
    )
    if not reg.get("ok"):
        if port_hold is not None:
            port_hold.release()
        log.error("registration rejected: %s", reg.get("error"))
        return 1  # never registered; the coordinator doesn't know us
    worker_index = reg["worker_index"]
    private_tracer = None
    if cfg.obs:
        # installed AFTER registration so the journal file carries the
        # ASSIGNED index (a pinned cfg.worker_index may be None); the
        # trainer picks the tracer up at construction below
        from shifu_tensorflow_tpu.obs import ObsConfig, install_obs
        from shifu_tensorflow_tpu.obs import journal as _obs_journal
        from shifu_tensorflow_tpu.obs import trace as _obs_trace

        obs_cfg = ObsConfig.from_json(cfg.obs)
        if _obs_journal.active() is None and _obs_trace.active() is None:
            # subprocess worker: this process is ours to instrument.
            # The job correlation id rode the register reply, so every
            # worker journals the id the coordinator minted — one merged
            # journal, one job key across all planes.
            install_obs(obs_cfg, worker_index=worker_index, plane="train",
                        job=reg.get("job"))
        elif obs_cfg.enabled:
            # thread launcher: we SHARE the submitter's process, whose
            # journal/tracer are already installed — replacing them
            # would misattribute coordinator events and leak the open
            # journal.  Events flow into the shared journal (explicit
            # worker/plane fields keep attribution right); the step
            # phases get a PRIVATE per-worker tracer below so
            # take_summary() in one worker thread cannot drain
            # another's epoch.
            private_tracer = _obs_trace.Tracer(
                worker_index=worker_index,
                sample_every=obs_cfg.trace_sample,
            )
        _obs_journal.emit("worker_start", plane="train",
                          worker=worker_index,
                          worker_id=cfg.worker_id,
                          generation=int(reg.get("generation", 0)),
                          promoted=promoted_from_standby)
        if promoted_from_standby:
            _obs_journal.emit("standby_takeover", plane="train",
                              worker=worker_index,
                              worker_id=cfg.worker_id,
                              prebuilt=prebuilt is not None,
                              generation=int(reg.get("generation", 0)))
    shard_paths = reg["shard"]
    epochs = reg.get("epochs") or cfg.model_config.num_train_epochs
    sync_epochs = bool(reg.get("sync_epochs", False))
    spmd = bool(reg.get("spmd", cfg.spmd))
    generation = int(reg.get("generation", 0))
    # coordinator rollback directive: after a health rollback every worker
    # trains at the backed-off LR and skips the offending batch window —
    # identical values fleet-wide (they rode the same register reply)
    directive = reg.get("health") or {}
    lr_scale = float(directive.get("lr_scale") or 1.0)
    skip = directive.get("skip") or {}
    model_config = cfg.model_config
    if lr_scale != 1.0:
        import dataclasses as _dc

        p = model_config.params
        model_config = _dc.replace(
            model_config,
            params=_dc.replace(p, learning_rate=p.learning_rate * lr_scale),
        )
        log.warning(
            "health rollback directive: learning rate scaled x%g -> %g "
            "(rollback %s)", lr_scale,
            model_config.params.learning_rate, directive.get("rollbacks"),
        )
    health = _health_from_cfg(cfg, lr_scale=lr_scale, skip=skip)

    hb = _HeartbeatThread(
        client, cfg.worker_id, cfg.heartbeat_interval_s, generation
    )
    hb.start()
    exit_code = 0
    checkpointer = None
    trainer = None
    try:
        started = client.await_start()
        if not started.get("ok"):
            raise _JobAborted()
        valid_rate = (
            cfg.valid_rate
            if cfg.valid_rate is not None
            else cfg.model_config.valid_set_rate
        )

        # the declared fleet mesh rode the register reply (spec + THIS
        # rank's row-major coordinate — a promoted standby inherits the
        # dead rank's coordinate with its index); a locally configured
        # spec still wins so single-process runs need no coordinator
        mesh_info = reg.get("mesh") or {}
        mesh_spec = cfg.mesh_spec or mesh_info.get("spec")
        topology = None
        mesh = None
        if spmd:
            topology = dist.ProcessTopology.from_cluster_info(
                started.get("cluster") or {}, worker_index,
                local_host=cfg.host,
            )
            if port_hold is not None:
                port_hold.release()  # chief: initialize rebinds it NOW
            try:
                dist.initialize(topology)
            except Exception:
                # canonical cause: the chief's port was stolen anyway, or a
                # peer died mid-bring-up.  A fresh generation (fresh port,
                # full re-registration) cures both — request ONE budgeted
                # fleet restart attributed to this root cause instead of
                # dying opaquely and making the coordinator untangle the
                # cascade.
                log.exception(
                    "jax.distributed.initialize failed (worker_index=%s); "
                    "requesting fleet restart", worker_index,
                )
                try:
                    client.request_restart(
                        cfg.worker_id, "jax.distributed.initialize failed"
                    )
                except Exception:
                    pass
                raise _FleetRestart()
            mesh = dist.global_mesh(mesh_spec or "data:-1")
        elif mesh_spec:
            from shifu_tensorflow_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(mesh_spec)
        if mesh is not None:
            # ONE mesh event per worker start: the resolved layout (not
            # the spec string — `-1` axes are solved by now), this
            # rank's coordinate when the coordinator assigned one, and
            # the fingerprint artifacts stamp — `obs summary` renders it
            from shifu_tensorflow_tpu.obs import journal as _obs_journal
            from shifu_tensorflow_tpu.parallel.mesh import (
                mesh_shape_fingerprint,
            )

            _obs_journal.emit(
                "mesh", plane="train", worker=worker_index,
                shape={n: int(s) for n, s in mesh.shape.items()},
                coord=mesh_info.get("coord"),
                fingerprint=mesh_shape_fingerprint(mesh),
                devices=int(mesh.devices.size),
            )

        if (prebuilt is not None and not spmd and lr_scale == 1.0
                and not skip):
            # promoted standby, clean directive: the pre-built trainer's
            # construction arguments are identical to what _build_trainer
            # would produce here (same cfg, same health resolver), so the
            # warm executables carry straight into the takeover.  A
            # rollback directive (scaled LR / skip window) changes the
            # construction inputs — build fresh then.
            trainer = prebuilt
            trainer.worker_index = worker_index
            if trainer.health_guard is not None:
                trainer.health_guard.worker_index = worker_index
            # the standby built before install_obs ran: pick the plane up
            # now, exactly like construction would have
            from shifu_tensorflow_tpu.obs import trace as _obs_trace

            trainer.tracer = _obs_trace.active()
            from shifu_tensorflow_tpu.obs import slo as _obs_slo

            trainer.slo = _obs_slo.active()
        else:
            trainer = _build_trainer(
                cfg, model_config, health,
                worker_index=worker_index, mesh=mesh, topology=topology,
            )
        if private_tracer is not None:
            trainer.tracer = private_tracer
        if trainer.health_guard is not None:
            # hang watchdog → coordinated recovery: the wedged training
            # thread cannot raise, so the watchdog thread reports the
            # hang; the coordinator rolls the fleet back (SPMD: the
            # submitter SIGKILLs this very process on the generation
            # bump; non-SPMD: the submitter kills it via pending_kills)
            def _on_hang(reason: str, diag: dict) -> None:
                try:
                    client.report_unhealthy(
                        cfg.worker_id, diag.get("epoch", -1), reason,
                        diag=diag, hung=True,
                    )
                except Exception:
                    log.exception("could not report hung step")

            trainer.health_guard.on_hang = _on_hang

        if cfg.checkpoint_dir:
            # SPMD uses the flat-file checkpointer: orbax's internal
            # cross-process barriers deadlock under chief-writes/all-read.
            # flat_checkpoint opts non-SPMD workers into it too, for the
            # manifest-verified save/restore chain.
            if spmd or cfg.flat_checkpoint:
                checkpointer = NpzCheckpointer(
                    cfg.checkpoint_dir,
                    every_epochs=cfg.checkpoint_every_epochs,
                    async_save=cfg.async_checkpoint,
                )
            else:
                checkpointer = Checkpointer(
                    cfg.checkpoint_dir,
                    every_epochs=cfg.checkpoint_every_epochs,
                )

        if spmd:
            exit_code = _run_spmd_training(
                cfg, client, trainer, hb, checkpointer,
                worker_index=worker_index,
                shard_paths=shard_paths,
                epochs=epochs,
                valid_rate=valid_rate,
                fail_at_epoch=fail_at_epoch,
                shard_lines=reg.get("shard_lines"),
                sync_epochs=sync_epochs,
            )
        else:
            exit_code = _run_local_training(
                cfg, client, trainer, hb, checkpointer,
                worker_index=worker_index,
                shard_paths=shard_paths,
                epochs=epochs,
                valid_rate=valid_rate,
                sync_epochs=sync_epochs,
                fail_at_epoch=fail_at_epoch,
            )
    except TrainingUnhealthy as e:
        # divergence detected at epoch end, BEFORE the diverged state was
        # checkpointed or reported: hand the coordinator the evidence and
        # let it arbitrate one fleet-wide rollback
        log.warning(
            "health guard tripped (worker_index=%s, epoch %d): %s",
            worker_index, e.epoch, e.reason,
        )
        try:
            resp = client.report_unhealthy(
                cfg.worker_id, e.epoch, e.reason,
                bad_steps=list(e.bad_steps), diag=e.diag,
            )
        except Exception:
            log.exception("could not report unhealthy state")
            resp = {}
        if resp.get("fleet"):
            exit_code = RESTART_EXIT_CODE
        elif resp.get("ok"):
            exit_code = UNHEALTHY_EXIT_CODE
        else:
            exit_code = 42  # budget gone / job failed: cooperative abort
    except _InjectedFault:
        log.warning("injected fault fired (worker_index=%s, "
                    "fail_at_epoch=%s)", worker_index, fail_at_epoch)
        exit_code = 43
    except _FleetRestart:
        log.info("exiting for fleet restart (worker_index=%s)", worker_index)
        exit_code = RESTART_EXIT_CODE
    except _Released:
        # elastic resize released this rank: a clean exit, not a failure
        log.info("released by elastic resize (worker_index=%s)",
                 worker_index)
        exit_code = 0
    except _JobAborted:
        log.warning("job aborted by coordinator (worker_index=%s)",
                    worker_index)
        exit_code = 42
    except Exception:
        # the per-worker log file (submitter) must carry the root cause —
        # round 2's flaky recovery was undiagnosable because this path
        # swallowed the traceback
        log.exception("worker failed (worker_index=%s)", worker_index)
        exit_code = 1
    finally:
        if port_hold is not None:
            port_hold.release()
        # stop the hang watchdog FIRST: left armed, it could fire a
        # spurious unhealthy report for a worker that is already exiting
        if trainer is not None and trainer.health_guard is not None:
            try:
                trainer.health_guard.close()
            except Exception:
                pass
        # always release the checkpoint manager: leaked orbax async writer
        # threads abort the interpreter at teardown
        if checkpointer is not None:
            try:
                checkpointer.close()
            except Exception:
                pass
        hb.stop()
        try:
            client.complete(cfg.worker_id, exit_code)
        except Exception:
            pass
        from shifu_tensorflow_tpu.obs import journal as _obs_journal

        _obs_journal.emit("worker_exit", plane="train",
                          worker=worker_index,
                          worker_id=cfg.worker_id, exit_code=exit_code)
    return exit_code


class _FleetStopSignal:
    """Adapter between the coordinator's fleet early-stop decision and the
    fit loops' ``early_stop`` hook: the epoch-barrier reply fills it in
    (same value for every worker at the same barrier), and the loop then
    breaks through its normal path — AFTER the epoch's checkpoint save,
    with ``trainer.stop_reason`` recorded — instead of via an exception
    that would skip both."""

    def __init__(self):
        self.stop_after: int | None = None
        self.reason: str | None = None

    def should_stop(self, stats) -> str | None:
        if self.stop_after is not None and stats.current_epoch >= self.stop_after:
            return self.reason or "fleet early stop"
        return None


class _ShardState:
    """Mutable view of this worker's shard assignment: the streaming
    epoch factories read ``paths`` per epoch, so an elastic re-split
    delivered at the epoch barrier takes effect at the very next epoch
    without restarting the worker.  ``split_generation`` is echoed on
    every barrier call — the coordinator compares (never stores), so a
    lost resplit reply just redelivers at the next barrier."""

    def __init__(self, paths):
        self.paths = list(paths)
        self.split_generation = 0

    def apply(self, directive: dict) -> None:
        self.paths[:] = list(directive.get("shard") or self.paths)
        self.split_generation = int(directive.get("split_generation", 0))


def _epoch_callback(
    cfg: WorkerConfig,
    client: CoordinatorClient,
    hb: _HeartbeatThread,
    *,
    sync_epochs: bool,
    fail_at_epoch: int | None,
    fleet_stop: "_FleetStopSignal | None" = None,
    shard_state: "_ShardState | None" = None,
) -> Callable:
    def on_epoch(stats) -> None:
        if hb.abort.is_set():
            raise _JobAborted()
        if hb.restart.is_set():
            raise _FleetRestart()
        if hb.released.is_set():
            # heartbeat-borne release (the barrier-borne one below only
            # reaches sync_epochs fleets)
            raise _Released()
        if fail_at_epoch is not None and stats.current_epoch >= fail_at_epoch:
            raise _InjectedFault()
        client.report_epoch(stats)
        if sync_epochs:
            resp = client.epoch_barrier(
                cfg.worker_id, stats.current_epoch,
                split_generation=(shard_state.split_generation
                                  if shard_state is not None else None),
            )
            if resp.get("abort"):
                raise _JobAborted()
            if resp.get("released"):
                # resize shrink: this rank left the membership — stop
                # cleanly instead of training a shard someone else owns
                raise _Released()
            if not resp.get("ok"):
                raise RuntimeError(resp.get("error", "epoch barrier failed"))
            directive = resp.get("resplit")
            if directive and shard_state is not None:
                shard_state.apply(directive)
                log.warning(
                    "elastic re-split applied (split generation %d): "
                    "%d path(s); takes effect next epoch",
                    shard_state.split_generation, len(shard_state.paths),
                )
                from shifu_tensorflow_tpu.obs import journal as _obs_journal

                _obs_journal.emit(
                    "resplit_applied", plane="train",
                    worker=stats.worker_index,
                    split_generation=shard_state.split_generation,
                    n_paths=len(shard_state.paths),
                    n_workers=directive.get("n_workers"),
                )
            if fleet_stop is not None and "stop_after_epoch" in resp:
                fleet_stop.stop_after = int(resp["stop_after_epoch"])
                fleet_stop.reason = resp.get("stop_reason")

    return on_epoch


def _run_local_training(
    cfg, client, trainer, hb, checkpointer, *,
    worker_index, shard_paths, epochs, valid_rate, sync_epochs,
    fail_at_epoch,
) -> int:
    """Independent-model path (non-SPMD): each worker trains on its shard;
    only the chief's checkpoint is exported.

    The shard lives in a mutable _ShardState: an elastic re-split
    delivered at the epoch barrier re-points the STREAMING epoch
    factories at the new shard from the next epoch on (the in-memory
    path loaded its data up front — it picks a re-split up on relaunch,
    its coordinator record already carries the new shard)."""
    shard_state = _ShardState(shard_paths)
    fleet_stop = _FleetStopSignal() if sync_epochs else None
    on_epoch = _epoch_callback(
        cfg, client, hb, sync_epochs=sync_epochs,
        fail_at_epoch=fail_at_epoch, fleet_stop=fleet_stop,
        shard_state=shard_state,
    )
    start_epoch = 0
    if checkpointer is not None:
        start_epoch = trainer.restore(checkpointer)
    save_ckpt = checkpointer if worker_index == 0 else None

    if cfg.stream:
        batch_size = trainer.align_batch_size(cfg.batch_size)
        widths, stats_sink = _ingest_setup(cfg, trainer)
        trainer.fit_stream(
            lambda epoch: ShardStream(
                list(shard_state.paths), cfg.schema, batch_size,
                valid_rate=valid_rate, emit="train", salt=cfg.seed,
                cache_dir=cfg.cache_dir,
                feature_dtype=_feature_dtype_for(cfg),
                shuffle_rows=cfg.data_shuffle_rows,
                shuffle_seed=cfg.seed + epoch,
                stats_sink=stats_sink, **widths(),
            ),
            (lambda: ShardStream(
                list(shard_state.paths), cfg.schema, batch_size,
                valid_rate=valid_rate, emit="valid", salt=cfg.seed,
                cache_dir=cfg.cache_dir,
                feature_dtype=_feature_dtype_for(cfg),
                **widths(),
            )) if valid_rate > 0 else None,
            epochs=epochs,
            on_epoch=on_epoch,
            checkpointer=save_ckpt,
            start_epoch=start_epoch,
            early_stop=fleet_stop,
        )
    else:
        dataset = InMemoryDataset.load(
            shard_paths, cfg.schema, valid_rate, salt=cfg.seed
        )
        trainer.fit(
            dataset,
            epochs=epochs,
            batch_size=cfg.batch_size,
            on_epoch=on_epoch,
            checkpointer=save_ckpt,
            start_epoch=start_epoch,
            early_stop=fleet_stop,
        )
    if save_ckpt is not None:
        # surface a failed background write of the FINAL checkpoint here,
        # on the success path — run_worker's cleanup close() swallows
        # exceptions, so without this the job would report success with
        # the checkpoint missing
        save_ckpt.wait()
    return 0


def _ingest_setup(cfg, trainer):
    """Resolve this worker's staged-ingest knobs (shifu.tpu.data-*) and
    install the per-worker autotuner on its trainer — the shared wiring
    helper (data/autotune.install_ingest_autotuner) run_single uses too,
    so fleet and single-process paths cannot drift."""
    from shifu_tensorflow_tpu.data.autotune import install_ingest_autotuner

    return install_ingest_autotuner(
        trainer, cfg.n_readers, cfg.decode_workers, cfg.data_prefetch,
        autotune=cfg.data_autotune, fallback_prefetch=cfg.prefetch_depth,
    )


def _np_feature_dtype(cfg):
    from shifu_tensorflow_tpu.data.cache import feature_np_dtype

    return feature_np_dtype(_feature_dtype_for(cfg))


def _feature_dtype_for(cfg) -> str:
    """Streaming transport dtype — bf16 when safe (compact transfer, the
    jitted step widens on device), float32 when any column feeds a hash or
    the schema carries no ZSCALE stats (raw-magnitude features would lose
    precision); see data/dataset.py resolve_stream_feature_dtype."""
    from shifu_tensorflow_tpu.data.dataset import resolve_stream_feature_dtype

    return resolve_stream_feature_dtype(
        cfg.stream_feature_dtype,
        uses_feature_hashing=cfg.model_config.params.features_carry_ids,
        has_normalization_stats=bool(cfg.schema.means),
    )


def _run_spmd_training(
    cfg, client, trainer, hb, checkpointer, *,
    worker_index, shard_paths, epochs, valid_rate, fail_at_epoch,
    shard_lines=None, sync_epochs=False,
) -> int:
    """One-model path: this process is one SPMD participant.  Every process
    must execute identical step sequences, so the fleet agrees per-epoch
    step counts and the restore epoch through the coordinator's sync_plan
    barrier before training starts.

    ``sync_epochs`` engages the coordinator's per-epoch barrier here too:
    SPMD collectives already keep steps in lockstep, but fleet-level
    per-epoch DECISIONS (early stopping) need a rendezvous where every
    process sees the same answer at the same epoch — without it, a worker
    whose report completed the quorum could stop while a peer that
    reported earlier has already entered the next epoch's collectives."""
    local_batch = trainer.align_batch_size(cfg.batch_size)
    num_features = cfg.schema.num_features

    counted_lines = None
    if cfg.stream:
        # the register reply carries the coordinator-cached count (seeded at
        # submit or from a previous launch's report) — a relaunched fleet
        # must not re-read a 1B-row shard just to size its epochs
        lines = shard_lines
        if lines is None:
            from shifu_tensorflow_tpu.data.splitter import total_line_count

            lines = counted_lines = total_line_count(shard_paths)
        train_steps = _stream_step_estimate(
            lines, 1.0 - valid_rate, local_batch
        )
        valid_steps = _stream_step_estimate(lines, valid_rate, local_batch)
        dataset = None
    else:
        dataset = InMemoryDataset.load(
            shard_paths, cfg.schema, valid_rate, salt=cfg.seed
        )
        train_steps = dataset.steps_per_epoch(local_batch)
        valid_steps = dataset.valid_steps(local_batch)

    # report only VERIFIED generations into the fleet agreement: the
    # coordinator's min-over-workers must land on an epoch every worker
    # can actually restore — a corrupt-but-present generation reported
    # here would wedge the whole fleet on an unrestorable point.
    # Upgrade path: a checkpoint dir written before manifests existed has
    # restorable-but-unverifiable (legacy) generations; discarding hours
    # of progress over a missing sidecar would be worse than trusting the
    # npz-parse guard, so fall back to latest_epoch() (which itself
    # quarantines cheap-corrupt generations) when nothing is verified.
    latest = None
    if checkpointer is not None:
        latest = getattr(
            checkpointer, "latest_verified_epoch",
            lambda: None,
        )()
        if latest is None:
            latest = checkpointer.latest_epoch()
    plan_payload = {
        "train_steps": train_steps,
        "valid_steps": valid_steps,
        "ckpt_epoch": -1 if latest is None else int(latest),
    }
    if counted_lines is not None:
        plan_payload["shard_lines"] = counted_lines
    plan = client.sync_plan(cfg.worker_id, plan_payload)
    if plan.get("restart"):
        raise _FleetRestart()
    if not plan.get("ok"):
        if plan.get("abort"):
            raise _JobAborted()
        raise RuntimeError(plan.get("error", "sync_plan failed"))
    train_steps = int(plan["train_steps"])
    valid_steps = int(plan["valid_steps"])
    agreed_epoch = int(plan.get("ckpt_epoch", -1))

    start_epoch = 0
    if checkpointer is not None and agreed_epoch >= 0:
        state, start_epoch = checkpointer.restore_epoch(
            agreed_epoch, trainer.state
        )
        trainer.state = state
    if cfg.keep_best and checkpointer is not None:
        # resumed fleets compete against the TRUE best, not
        # best-since-restart (trainer.restore does this for the non-SPMD
        # path; SPMD restores through restore_epoch).  Unconditional on
        # the agreed epoch: a relaunch BEFORE the first checkpoint
        # (agreed_epoch -1) may still have a persisted best from the
        # previous generation's epoch 0 — restarting the race would let
        # a worse post-relaunch epoch overwrite it.
        trainer._restore_best(checkpointer.directory)

    def _warn_dropped(rows: int) -> None:
        log.warning(
            "fixed-step epoch dropped %d surplus rows (agreed %d steps)",
            rows, train_steps,
        )

    if cfg.stream:
        x_dtype = _np_feature_dtype(cfg)
        widths, stats_sink = _ingest_setup(cfg, trainer)

        def make_train(epoch: int):
            return fixed_step_batches(
                ShardStream(
                    shard_paths, cfg.schema, local_batch,
                    valid_rate=valid_rate, emit="train", salt=cfg.seed,
                    cache_dir=cfg.cache_dir,
                    feature_dtype=_feature_dtype_for(cfg),
                    shuffle_rows=cfg.data_shuffle_rows,
                    shuffle_seed=cfg.seed + epoch,
                    stats_sink=stats_sink, **widths(),
                ),
                local_batch, train_steps, num_features,
                on_dropped=_warn_dropped, x_dtype=x_dtype,
            )

        def make_valid():
            return fixed_step_batches(
                ShardStream(
                    shard_paths, cfg.schema, local_batch,
                    valid_rate=valid_rate, emit="valid", salt=cfg.seed,
                    cache_dir=cfg.cache_dir,
                    feature_dtype=_feature_dtype_for(cfg),
                    **widths(),
                ),
                local_batch, valid_steps, num_features, x_dtype=x_dtype,
            )
    else:
        def make_train(epoch: int):
            return dataset.train_batches_fixed(
                local_batch, train_steps, epoch=epoch
            )

        def make_valid():
            return dataset.valid_batches_fixed(local_batch, valid_steps)

    fleet_stop = _FleetStopSignal() if sync_epochs else None
    on_epoch = _epoch_callback(
        cfg, client, hb, sync_epochs=sync_epochs,
        fail_at_epoch=fail_at_epoch, fleet_stop=fleet_stop,
    )
    trainer.fit_stream(
        make_train,
        make_valid if valid_steps > 0 else None,
        epochs=epochs,
        on_epoch=on_epoch,
        checkpointer=checkpointer if worker_index == 0 else None,
        start_epoch=start_epoch,
        early_stop=fleet_stop,
    )
    if worker_index == 0 and checkpointer is not None:
        checkpointer.wait()  # see _run_local_training: no silent ckpt loss
    return 0


class _InjectedFault(RuntimeError):
    pass


class _JobAborted(RuntimeError):
    pass


class _FleetRestart(RuntimeError):
    pass


class _Released(RuntimeError):
    """Elastic resize removed this rank from the membership."""
