"""The training engine: jitted SPMD step + epoch loop.

This replaces the reference's entire PS-architecture hot loop — per-batch
``sess.run`` feed_dict marshalling, worker→PS gRPC parameter pulls/grad
pushes, SyncReplicasOptimizer token-queue barrier, chief init dance
(reference: ssgd_monitor.py:202-293, SURVEY.md §3.4) — with one compiled
XLA program: the batch is sharded over the mesh 'data' axis, parameters are
replicated, and XLA inserts the gradient all-reduce over ICI.  Synchronous
SGD is the *default semantics* of the program, not a protocol.

Epoch-level behavior parity:
- per-epoch train loss, valid loss, epoch wall time, valid wall time are
  reported through a metrics callback — the same fields the reference
  pushed through its Python→Java socket → ZK → AM pipeline
  (SocketServer.java:71-89, TrainingIntermediateResult);
- checkpoint every N epochs with correct global-step/epoch accounting so
  resume actually works (the reference punted: backup.py:30 TODO);
- a StopAtStep-style cap (reference used StopAtStepHook(numTrainEpochs)).
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from functools import partial, wraps
from typing import Any, Callable, Iterable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax.training import train_state

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.data.dataset import (
    Batch,
    InMemoryDataset,
    _zero_batch,
    close_stream,
    prefetch_to_device,
)
from shifu_tensorflow_tpu.models.factory import build_model, family_loss
from shifu_tensorflow_tpu.obs import journal as obs_journal
from shifu_tensorflow_tpu.obs import compile as obs_compile
from shifu_tensorflow_tpu.obs import fleet as _obs_fleet
from shifu_tensorflow_tpu.obs import trace as obs_trace
from shifu_tensorflow_tpu.ops import metrics as M
from shifu_tensorflow_tpu.ops.losses import get_loss, l2_penalty
from shifu_tensorflow_tpu.train.optimizers import make_optimizer


class TrainState(train_state.TrainState):
    """flax TrainState (params/tx/opt_state/step) — step is the global
    update counter, parity with the reference's ``global_step`` variable
    (ssgd_monitor.py:123-127)."""


@dataclass
class EpochStats:
    """Per-epoch record — field parity with TrainingIntermediateResult
    (TrainingIntermediateResult.java:35-45)."""

    worker_index: int
    current_epoch: int
    training_loss: float
    valid_loss: float
    training_time_s: float
    valid_time_s: float
    global_step: int
    ks: float = 0.0
    auc: float = 0.0
    # per-epoch step-phase summary (host/infeed/dispatch/block seconds,
    # steps, barrier wait, clock offset) attached by Trainer._obs_epoch
    # from the same budget_fields drain its journal gets — rides the
    # epoch-report RPC so the coordinator's FleetMonitor can attribute
    # skew to a phase (obs/fleet.py).  None when obs is off.
    phases: dict | None = None


MetricsCallback = Callable[[EpochStats], None]


@dataclass
class EarlyStopper:
    """Epoch-loop stop criteria for the single-controller fit paths.

    Two independent criteria, either disabled at 0:

    - ``target_ks``: stop once validation KS reaches the target — the
      BASELINE.md north star is wall-clock **to KS≥0.45**, so a job that
      has reached the target should stop burning chip time (the reference
      always trained its full fixed epoch budget, ssgd_monitor.py:274);
    - ``patience``: stop after this many consecutive epochs without
      validation-loss improvement (> ``min_delta``).  Epochs with NaN
      validation loss (no validation data) don't count toward patience —
      otherwise a valid-rate-0 job would spuriously stop.

    Multi-worker fleets must NOT use this per-worker/uncoordinated: one
    worker stopping while peers enter the next epoch's collectives hangs
    the fleet.  run_multi instead passes the criteria to the COORDINATOR
    (JobSpec.early_stop_*), which evaluates them on full-quorum epoch
    aggregates and delivers the decision through the per-epoch barrier;
    workers receive it as a _FleetStopSignal through this same
    ``early_stop`` hook (coordinator/worker.py).
    """

    target_ks: float = 0.0
    patience: int = 0
    min_delta: float = 0.0
    _best: float = float("inf")
    _bad_epochs: int = 0

    def should_stop(self, stats: EpochStats) -> str | None:
        """Returns the stop reason, or None to continue."""
        if self.target_ks > 0 and stats.ks >= self.target_ks:
            return (
                f"validation KS {stats.ks:.4f} reached target "
                f"{self.target_ks:g} at epoch {stats.current_epoch}"
            )
        if self.patience > 0 and not np.isnan(stats.valid_loss):
            if stats.valid_loss < self._best - self.min_delta:
                self._best = stats.valid_loss
                self._bad_epochs = 0
            else:
                self._bad_epochs += 1
                if self._bad_epochs >= self.patience:
                    return (
                        f"no validation-loss improvement in "
                        f"{self.patience} epochs (best {self._best:.6g})"
                    )
        return None


@dataclass
class HealthConfig:
    """Training-health guard settings (conf keys ``shifu.tpu.health-*``).

    - ``check_finite``: on-device ``isfinite`` check on the per-step loss
      and (per-step path) global gradient norm.  DISTINCT from the
      NaN-as-padding marker: the guard cross-references each loss with a
      host-side "did this batch have nonzero-weight rows" record, so a
      padding batch's contractual NaN never trips it while a NaN from a
      real batch always does.
    - ``spike_factor``: trip when a finite epoch loss exceeds
      ``factor × EMA`` of previous epoch losses (divergence that has not
      yet reached NaN); 0 disables.
    - ``hang_timeout_s``: wall-clock per-step watchdog — a training step
      (or evaluation batch) making no progress for this long fires the
      hang callback from a watchdog thread; 0 disables.
    - ``lr_scale`` / ``skip_epoch`` / ``skip_steps``: the coordinator's
      rollback directive — relaunched workers train at a backed-off
      learning rate and skip the batch window that tripped the guard
      (see coordinator.report_unhealthy).
    """

    check_finite: bool = True
    spike_factor: float = 0.0
    spike_min_epochs: int = 2
    hang_timeout_s: float = 0.0
    ema_decay: float = 0.7
    lr_scale: float = 1.0
    skip_epoch: int | None = None
    skip_steps: tuple[int, ...] = ()

    @classmethod
    def from_dict(cls, d: dict | None) -> "HealthConfig | None":
        if d is None:
            return None
        d = dict(d)
        if d.get("skip_steps") is not None:
            d["skip_steps"] = tuple(int(s) for s in d["skip_steps"])
        return cls(**d)


class TrainingUnhealthy(RuntimeError):
    """The health guard tripped: divergence (non-finite loss/grad,
    loss spike) detected at epoch end, BEFORE the epoch's checkpoint save
    and metrics report — diverged parameters must never be published as a
    restore point.  Carries the diagnostics the coordinator bundles into
    its rollback decision (and into the failure report when the rollback
    budget is gone)."""

    def __init__(self, reason: str, epoch: int,
                 bad_steps: tuple[int, ...] = (), diag: dict | None = None):
        super().__init__(reason)
        self.reason = reason
        self.epoch = epoch
        self.bad_steps = tuple(bad_steps)
        self.diag = diag or {}


class StepWatchdog:
    """Wall-clock per-step hang detector.

    The liveness monitor cannot catch a hung step: the worker's heartbeat
    THREAD keeps beating while the training thread is wedged inside a
    device call (the reference's monitor had the same blindspot — and its
    kill action was commented out anyway, SURVEY.md §5.2).  This watchdog
    lives beside the training loop, is ticked once per consumed batch,
    and fires ``on_hang(elapsed_s)`` from its own thread when no tick
    lands within the timeout — once, ever: the hung thread cannot be
    un-hung, so the single report hands recovery to the coordinator."""

    def __init__(self, timeout_s: float,
                 on_hang: Callable[[float], None]):
        self.timeout_s = float(timeout_s)
        self.on_hang = on_hang
        self._last = time.monotonic()
        self._armed = False
        self.fired = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def arm(self) -> None:
        self._last = time.monotonic()
        self._armed = True
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="stpu-step-watchdog"
            )
            self._thread.start()

    def tick(self) -> None:
        self._last = time.monotonic()

    def disarm(self) -> None:
        self._armed = False

    def _run(self) -> None:
        poll = max(0.01, min(self.timeout_s / 4.0, 0.5))
        while not self._stop.wait(poll):
            if not self._armed or self.fired:
                continue
            elapsed = time.monotonic() - self._last
            if elapsed > self.timeout_s:
                self.fired = True
                try:
                    self.on_hang(elapsed)
                except Exception:  # the watchdog must never die silently
                    from shifu_tensorflow_tpu.utils import logs

                    logs.get("health").exception("hang callback failed")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


class HealthGuard:
    """Per-trainer health state machine (built from :class:`HealthConfig`).

    The fit loops call ``begin_epoch`` / ``check_epoch`` around each
    epoch; ``filter_batches`` wraps the batch stream to (a) record which
    steps carried real (nonzero-weight) rows — the host-side half of the
    NaN-vs-padding disambiguation, (b) apply the coordinator's rollback
    skip-window, and (c) host the ``health.nan-loss`` fault-injection
    seam; the epoch paths feed their fetched loss (and, per-step, grad
    norm) arrays back through ``note_losses``.
    """

    def __init__(self, cfg: HealthConfig, worker_index: int = 0):
        import collections

        self.cfg = cfg
        self.worker_index = worker_index
        self._epoch = -1
        self._epochs_seen = 0
        self._ema: float | None = None
        self._steps_real: list[tuple[int, bool]] = []
        self._n_real = 0
        self._bad_steps: list[int] = []
        self._count_bad: str | None = None
        self._skip_set = set(cfg.skip_steps)
        self.skipped_steps = 0
        self.injected_nans = 0
        self.last_losses: "collections.deque" = collections.deque(maxlen=16)
        self.last_grad_norms: "collections.deque" = collections.deque(
            maxlen=16)
        #: hook for the worker runtime: called as ``on_hang(reason, diag)``
        #: from the watchdog thread; default just logs
        self.on_hang: Callable[[str, dict], None] | None = None
        self.watchdog = (
            StepWatchdog(cfg.hang_timeout_s, self._hang)
            if cfg.hang_timeout_s > 0 else None
        )

    def scale_watchdog(self, dispatch_steps: int, why: str) -> None:
        """The watchdog is ticked once per DEVICE DISPATCH; when one
        dispatch covers many optimizer steps (scan/accum chunking), the
        configured per-step timeout must stretch accordingly or a
        legitimately long dispatch reads as a hang."""
        if self.watchdog is not None and dispatch_steps > 1:
            from shifu_tensorflow_tpu.utils import logs

            self.watchdog.timeout_s *= dispatch_steps
            logs.get("health").info(
                "hang watchdog timeout scaled x%d to %.1fs (%s)",
                dispatch_steps, self.watchdog.timeout_s, why,
            )

    def disable_watchdog(self, why: str) -> None:
        """Paths with no per-step tick granularity (device-resident: one
        dispatch IS the epoch) cannot distinguish a hang from work — stop
        the watchdog instead of firing spuriously."""
        if self.watchdog is not None:
            from shifu_tensorflow_tpu.utils import logs

            logs.get("health").warning(
                "hang watchdog disabled: %s (shifu.tpu.health-hang-timeout "
                "has no per-step tick to measure here)", why,
            )
            self.watchdog.stop()
            self.watchdog = None

    # ---- epoch lifecycle ----
    def begin_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        self._steps_real = []
        self._n_real = 0
        self._bad_steps = []
        self._count_bad = None
        if self.watchdog is not None:
            self.watchdog.arm()

    def tick(self) -> None:
        if self.watchdog is not None:
            self.watchdog.tick()

    def _hang(self, elapsed: float) -> None:
        from shifu_tensorflow_tpu.utils import logs

        reason = (
            f"hung step: no training progress in {elapsed:.1f}s "
            f"(shifu.tpu.health-hang-timeout={self.cfg.hang_timeout_s:g}s, "
            f"epoch {self._epoch})"
        )
        logs.get("health").error("%s", reason)
        if self.on_hang is not None:
            self.on_hang(reason, self.diagnostics())

    # ---- batch stream instrumentation ----
    def filter_batches(self, batches: Iterable[Batch]) -> Iterable[Batch]:
        from shifu_tensorflow_tpu.utils import faults, logs

        epoch = self._epoch
        step = 0
        plan_active = faults.active() is not None
        for b in batches:
            real = bool(np.any(np.asarray(b["w"]) != 0.0))
            if (real and epoch == self.cfg.skip_epoch
                    and step in self._skip_set):
                # coordinator rollback directive: this batch window tripped
                # the guard last generation — skip it instead of replaying
                # the divergence deterministically
                self.skipped_steps += 1
                logs.get("health").warning(
                    "skipping epoch %d step %d (coordinated-rollback "
                    "directive)", epoch, step,
                )
                step += 1
                continue
            if real and plan_active and faults.poll(
                f"health.nan-loss.e{epoch}", index=step
            ):
                b = dict(b)
                x = np.array(b["x"], copy=True)
                x.flat[0] = np.nan
                b["x"] = x
                self.injected_nans += 1
            self._steps_real.append((step, real))
            if real:
                self._n_real += 1
            step += 1
            yield b

    # ---- loss bookkeeping ----
    def note_losses(self, vals, grad_norms=None,
                    mode: str = "aligned") -> None:
        """Feed one epoch's fetched loss array (+ optional per-step grad
        norms).  ``mode``: "aligned" — vals[i] pairs with the i-th yielded
        batch (per-step / host-emb paths; precise bad-step indices);
        "counted" — order lost but one loss per batch (scan path; finite
        count must cover every real batch); "loose" — losses are
        per-group (accum / SAGN windows; only inf and the epoch-mean NaN
        check apply)."""
        vals = np.asarray(vals, np.float64).reshape(-1)
        for v in vals[np.isfinite(vals)][-8:]:
            self.last_losses.append(float(v))
        if grad_norms is not None:
            g = np.asarray(grad_norms, np.float64).reshape(-1)
            for v in g[np.isfinite(g)][-8:]:
                self.last_grad_norms.append(float(v))
        if not self.cfg.check_finite:
            return
        if mode == "aligned":
            g = (np.asarray(grad_norms, np.float64).reshape(-1)
                 if grad_norms is not None else None)
            for i, (step, real) in enumerate(self._steps_real):
                if not real or i >= len(vals):
                    continue
                if not np.isfinite(vals[i]) or (
                    g is not None and i < len(g) and not np.isfinite(g[i])
                ):
                    self._bad_steps.append(step)
        elif mode == "counted":
            n_finite = int(np.isfinite(vals).sum())
            if n_finite < self._n_real:
                self._count_bad = (
                    f"{self._n_real - n_finite} of {self._n_real} real "
                    f"batches produced non-finite losses"
                )
        if np.isinf(vals).any():
            self._count_bad = self._count_bad or "infinite loss observed"

    def bad_steps(self) -> tuple[int, ...]:
        return tuple(self._bad_steps)

    def diagnostics(self) -> dict:
        return {
            "worker_index": self.worker_index,
            "epoch": self._epoch,
            "last_losses": list(self.last_losses),
            "last_grad_norms": list(self.last_grad_norms),
            "bad_steps": list(self._bad_steps),
            "skipped_steps": self.skipped_steps,
            "injected_nans": self.injected_nans,
        }

    def check_epoch(self, stats: EpochStats) -> str | None:
        """End-of-epoch verdict: a reason string when unhealthy, else
        None.  Runs BEFORE the epoch's checkpoint/report so diverged
        state is never published."""
        if self.watchdog is not None:
            self.watchdog.disarm()
        e = stats.current_epoch
        if self.cfg.check_finite:
            if self._bad_steps:
                shown = self._bad_steps[:4]
                return (
                    f"non-finite loss/grad-norm at epoch {e} step(s) "
                    f"{shown}{'...' if len(self._bad_steps) > 4 else ''}"
                )
            if self._count_bad:
                return f"divergence at epoch {e}: {self._count_bad}"
            if self._n_real > 0 and not np.isfinite(stats.training_loss):
                return (
                    f"divergence at epoch {e}: every real batch produced "
                    f"a non-finite loss (epoch mean NaN)"
                )
        if (
            self.cfg.spike_factor > 0
            and np.isfinite(stats.training_loss)
        ):
            if (
                self._ema is not None
                and self._epochs_seen >= self.cfg.spike_min_epochs
                and stats.training_loss
                > self.cfg.spike_factor * self._ema + 1e-12
            ):
                return (
                    f"loss spike at epoch {e}: {stats.training_loss:.6g} > "
                    f"{self.cfg.spike_factor:g} x EMA {self._ema:.6g}"
                )
            d = self.cfg.ema_decay
            self._ema = (
                stats.training_loss if self._ema is None
                else d * self._ema + (1 - d) * stats.training_loss
            )
            self._epochs_seen += 1
        return None

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.stop()


def _fault_lagged(batches: "Iterable[Batch]", worker_index: int):
    """Straggler-drill chaos seam: consult the fault plan once per host
    batch at ``train.step.w<index>`` (the `slow` kind sleeps there; an
    exception kind raises, like any other seam).  Installed by the epoch
    loop only while a plan is active."""
    from shifu_tensorflow_tpu.utils import faults

    site = f"train.step.w{worker_index}"
    for batch in batches:
        faults.check(site)
        yield batch


def _unbox_params(tree):
    """Strip flax partitioning boxes so host snapshots are plain arrays."""
    from flax.core import meta as flax_meta

    return jax.tree_util.tree_map(
        lambda x: x.unbox() if isinstance(x, flax_meta.AxisMetadata) else x,
        tree,
        is_leaf=lambda x: isinstance(x, flax_meta.AxisMetadata),
    )


def _widen_features(params, x):
    """Compact-transport seam: the streaming default ships features bf16
    over the host→device link (half the bytes of fp32; the rate is not
    measured on the attached chip) and widens HERE, on device, inside
    the jitted step, so an fp32 model still computes fp32 throughout.
    bf16 is transport-only: the quantization happened on the host; this
    cast just keeps every matmul/accumulation at the params' precision.  A bf16 model keeps bf16 x (no-op).  Dtypes are static at
    trace time, so the branch costs nothing."""
    p_dtype = jax.tree_util.tree_leaves(params)[0].dtype
    if x.dtype == jnp.bfloat16 and p_dtype == jnp.float32:
        return x.astype(jnp.float32)
    return x


def apply_if_rows(state, grads, has_rows):
    """``state.apply_gradients(grads=grads)`` where the batch has rows, the
    state as it was (every leaf, bit for bit) where it is all padding.

    Computes the update unconditionally and selects per leaf: the select
    fuses into the optimizer's element-wise fusion and the leaves keep
    the layout they rest in.  Called by :func:`apply_update` alone."""
    new = state.apply_gradients(grads=grads)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(has_rows, n, o), new, state
    )


def apply_update(state, grads, loss, w, *, grad_norm: bool = False,
                 counters=None, emb_grad=None):
    """The tail of every step builder here and of train/sagn.py, and the
    one place that knows what the update receives: count the rows of the
    weights ``w``, apply ``grads`` where there are any, and return
    ``(state, aux)``.  ``aux`` is the one auxiliary shape of a step, a
    mapping: ``loss`` always; ``grad_norm`` (the global norm of ``grads``)
    where asked for; ``counters`` and ``emb_grad`` where handed in.

    An all-padding (weight-0) batch must be a true no-op: the data loss
    is 0 but the l2 term still has gradients, and Adam-style momentum
    produces nonzero updates even from zero grads — either would let the
    fixed-step SPMD padding batches (data/dataset.py fixed_step_batches)
    drift parameters.  The count is over the GLOBAL batch, so every SPMD
    process selects the same.  The loss reports NaN for such batches so
    epoch means (nanmean) skip them instead of being biased toward zero;
    the norm and the embedding gradient report 0.  The guard is a select
    (apply_if_rows), not a lax.cond: a branch computation's parameters
    get XLA's default layout, so a conditional makes every step copy a
    big table and its moments into that layout and back."""
    has_rows = jnp.sum(w != 0.0) > 0
    with jax.named_scope("optimizer.update"):
        state = apply_if_rows(state, grads, has_rows)
    aux = {"loss": jnp.where(has_rows, loss, jnp.nan)}
    if grad_norm:
        import optax

        aux["grad_norm"] = jnp.where(has_rows, optax.global_norm(grads), 0.0)
    if counters is not None:
        aux["counters"] = counters
    if emb_grad is not None:
        aux["emb_grad"] = jnp.where(has_rows, emb_grad, 0.0)
    return state, aux


def prediction_loss(apply_fn, loss_name: str = "mse"):
    """The loss of the families that predict one ``(B, 1)`` score a row, in
    the form the step builders differentiate: ``(params, batch) -> (loss,
    prediction, None)`` with ``ops/losses.py``'s ``loss_name`` on
    ``apply_fn``'s prediction.  A family that brings its own loss
    (models/factory.py ``family_loss``) hands in the same form, with the
    per-row loss for a prediction and its step counters for ``None``."""
    loss_fn = get_loss(loss_name)

    def batch_loss(params, batch):
        pred = apply_fn({"params": params},
                        _widen_features(params, batch["x"]))
        with jax.named_scope("loss"):
            return loss_fn(pred, batch["y"], batch["w"]), pred, None

    return batch_loss


def make_train_step_body(apply_fn, loss_name: str = "mse", l2: float = 0.0,
                         with_grad_norm: bool = False, batch_loss=None):
    """The un-jitted (state, batch) -> (state, aux) transition — jitted
    per-batch by make_train_step, lax.scan'ed over stacked batches by
    make_scan_epoch.  One definition, so the two paths cannot drift.
    ``aux`` is :func:`apply_update`'s mapping.

    ``with_grad_norm=True`` (health guard, shifu.tpu.health-check-finite)
    adds ``grad_norm`` — a cheap on-device reduction over gradients the
    step already computed, letting the guard catch an exploding/NaN
    gradient before the loss itself goes non-finite.

    ``batch_loss`` is the seam for a family that brings its own loss
    (:func:`prediction_loss` says the form; models/factory.py
    ``family_loss`` finds it): it is differentiated in place of
    ``loss_name`` on ``apply_fn``'s ``(B, 1)`` prediction, and ``aux``
    gains its ``counters``.  Guard, norm and update are the same.
    """
    if batch_loss is None:
        batch_loss = prediction_loss(apply_fn, loss_name)

    def compute_loss(params, batch):
        loss, _, counters = batch_loss(params, batch)
        if l2:
            with jax.named_scope("loss"):
                loss = loss + l2_penalty(params, l2)
        return loss, counters

    def train_step(state: TrainState, batch: Batch):
        (loss, counters), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(state.params, batch)
        return apply_update(state, grads, loss, batch["w"],
                            grad_norm=with_grad_norm, counters=counters)

    return train_step


def make_train_step(apply_fn, loss_name: str = "mse", l2: float = 0.0,
                    with_grad_norm: bool = False, batch_loss=None):
    """Build the jitted SPMD train step.

    state is donated (its device buffers are reused in place — every
    jitted program below does the same, so whoever keeps an old state
    must copy it first); with a sharded batch the grad all-reduce is
    inserted by XLA — no explicit psum needed under jit (shard_map users
    would write it; we stay at the jit level so the same step runs
    single-chip and multi-chip).
    """
    body = make_train_step_body(apply_fn, loss_name, l2,
                                with_grad_norm=with_grad_norm,
                                batch_loss=batch_loss)
    return obs_compile.observe(jax.jit(body, donate_argnums=(0,)),
                               "train.step")


def make_host_emb_train_step(apply_fn, raw_width: int,
                             loss_name: str = "mse", l2: float = 0.0):
    """Train step for host-resident embeddings (EmbeddingPlacement=host):
    ``batch["x"]`` arrives as ``[raw features | host-gathered embeddings]``
    and the step's ``emb_grad`` is dLoss/d(embedding slice) so the host
    can apply the sparse Adagrad update (models/host_embedding.py).  Same
    no-op gate for all-padding batches as make_train_step — zero-weight
    rows produce zero embedding grads, so padded rows update nothing."""
    loss_fn = get_loss(loss_name)

    def compute(params, x, batch):
        pred = apply_fn({"params": params}, _widen_features(params, x))
        with jax.named_scope("loss"):
            loss = loss_fn(pred, batch["y"], batch["w"])
            if l2:
                loss = loss + l2_penalty(params, l2)
        return loss

    @partial(jax.jit, donate_argnums=(0,))
    def step(state: TrainState, batch: Batch):
        x = batch["x"]
        loss, (gp, gx) = jax.value_and_grad(compute, argnums=(0, 1))(
            state.params, x, batch
        )
        return apply_update(state, gp, loss, batch["w"],
                            emb_grad=gx[:, raw_width:])

    return obs_compile.observe(step, "train.host_emb_step")


def make_scan_epoch(apply_fn, loss_name: str = "mse", l2: float = 0.0):
    """Compiled multi-step run: lax.scan the train-step body over a stacked
    chunk ``{"x": (S,B,F), "y": (S,B,1), "w": (S,B,1)}`` — S sequential
    optimizer updates in ONE dispatch.

    The per-step path pays one host→device dispatch per update; where
    the step is shorter than its dispatch (any Python-driven loop at
    small step times) that overhead bounds throughput.  Scanning is the
    XLA-idiomatic fix — data-independent control flow compiled once,
    identical update semantics (same body, same order).  SURVEY.md §3.4's hot-loop finding, taken one step further
    than per-batch jit.
    """
    body = make_train_step_body(apply_fn, loss_name, l2)

    @partial(jax.jit, donate_argnums=(0,))
    def scan_epoch(state: TrainState, stacked: Batch):
        return jax.lax.scan(body, state, stacked)

    return obs_compile.observe(scan_epoch, "train.scan_epoch")


def make_accum_step(apply_fn, loss_name: str = "mse", l2: float = 0.0):
    """Gradient accumulation: A microbatches -> ONE optimizer update,
    mathematically equal to a single step on the concatenated batch.

    The TPU-idiomatic route to effective batch sizes beyond HBM: the
    stacked chunk ``{"x": (A, B, F), ...}`` is scanned on-device, each
    microbatch contributing its SUM-form data loss (the weighted loss
    times its nonzero-weight count — both losses normalize by that count,
    ops/losses.py) and gradients; the totals divide by the union's
    nonzero count, so the update equals the big-batch step exactly (up to
    float associativity) — unlike SAGN's local-SGD windows (train/sagn.py),
    which intentionally change update semantics.  Zero-weight padding
    microbatches contribute nothing, so short tail groups stay exact.
    """
    loss_fn = get_loss(loss_name)

    def sum_form(params, mb):
        pred = apply_fn({"params": params}, _widen_features(params, mb["x"]))
        n = jnp.sum((mb["w"] != 0.0).astype(jnp.float32))
        with jax.named_scope("loss"):
            loss = loss_fn(pred, mb["y"], mb["w"])
        # loss is sum/count; recover the sum (0 for all-padding micros,
        # where loss is 0/max(count,1) = 0 already, but guard anyway)
        return jnp.where(n > 0, loss * n, 0.0), n

    grad_fn = jax.value_and_grad(sum_form, has_aux=True)

    @partial(jax.jit, donate_argnums=(0,))
    def accum_step(state: TrainState, stacked: Batch):
        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p), state.params
        )

        def body(carry, mb):
            g_acc, s_acc, n_acc = carry
            (s, n), g = grad_fn(state.params, mb)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            return (g_acc, s_acc + s, n_acc + n), None

        (g_sum, s_tot, n_tot), _ = jax.lax.scan(
            body, (zeros, jnp.asarray(0.0), jnp.asarray(0.0)), stacked
        )
        denom = jnp.maximum(n_tot, 1.0)  # a count: 0 for all padding
        grads = jax.tree_util.tree_map(lambda g: g / denom, g_sum)
        loss = s_tot / denom
        if l2:
            # once per UPDATE, like the big-batch step — not per microbatch
            l2_loss, l2_g = jax.value_and_grad(
                lambda p: l2_penalty(p, l2)
            )(state.params)
            grads = jax.tree_util.tree_map(jnp.add, grads, l2_g)
            loss = loss + l2_loss
        return apply_update(state, grads, loss, stacked["w"])

    return obs_compile.observe(accum_step, "train.accum_step")


def make_eval_step_body(apply_fn, loss_name: str = "mse", batch_loss=None):
    """Un-jitted (params, batch) -> (loss, pred) — shared by the per-batch
    eval step and the device-resident scanned eval, so the all-padding
    NaN contract cannot drift between them.  With a family's own
    ``batch_loss`` (make_train_step_body) ``pred`` is the per-row loss
    ``(B, 1)``: such a family has no score to rank."""
    if batch_loss is None:
        batch_loss = prediction_loss(apply_fn, loss_name)

    def eval_step(params, batch: Batch):
        loss, pred, _ = batch_loss(params, batch)
        has_rows = jnp.sum(batch["w"] != 0.0) > 0
        return jnp.where(has_rows, loss, jnp.nan), pred

    return eval_step


def _sketch_fit_scope(fn):
    """Bracket a Trainer fit method with the train data sketch's
    ``begin_fit``/``end_fit`` generation markers: concurrent fits
    (thread-launcher fleet workers) share the sketch, while a fit
    starting after every previous fit ended is a NEW training in the
    same process and resets it — so a second same-width training can
    never export a baseline blended with the first one's data
    (obs/datastats.TrainDataSketch)."""
    @wraps(fn)
    def wrapper(self, *args, **kwargs):
        from shifu_tensorflow_tpu.obs import datastats as _obs_ds

        sk = _obs_ds.train_active()
        if sk is not None:
            sk.begin_fit(id(self))
        try:
            return fn(self, *args, **kwargs)
        finally:
            if sk is not None:
                sk.end_fit(id(self))
    return wrapper


def make_eval_step(apply_fn, loss_name: str = "mse", batch_loss=None):
    return obs_compile.observe(
        jax.jit(make_eval_step_body(apply_fn, loss_name, batch_loss)),
        "train.eval_step")


class Unit(NamedTuple):
    """What one ``step.dispatch`` takes.  ``data`` is its host side until
    the path's ``put`` has run, its device side after."""

    data: Any
    rows: int  # the real (unpadded) rows in it: what the step timer counts
    batches: int = 1  # the host batches it stands for
    tail: bool = False  # put and stepped by the path's ``tail``
    note: Any = None  # from ``put`` to ``after_step``, host side


def _one_each(batches: Iterable[Batch]) -> Iterator[Unit]:
    for batch in batches:
        yield Unit(batch, batch["x"].shape[0])


def _placing(put: Callable[[Batch], Batch]) -> Callable[[Unit], Unit]:
    """A path's ``put`` from a placement of the unit's data alone."""
    return lambda unit: unit._replace(data=put(unit.data))


@dataclass(frozen=True)
class EpochPath:
    """One way through an epoch, as data.  ``Trainer.__init__`` chooses it
    once (``_choose_path``) and ``Trainer._run_epoch`` is the one loop that
    runs it; ``warm_step`` runs one all-padding unit of it.  The loop owns
    everything the paths share — the straggler seam, the guard's filter,
    span names, infeed, ``step.dispatch``, ticks, the step timer, the
    closes, the ``step.block`` fetch, the NaN-skipping mean — and reads
    here what differs."""

    #: the compiled step's ``obs_compile.observe`` name
    name: str
    #: ``(state, unit.data) -> (state, aux)``, ``aux`` the mapping of
    #: :func:`apply_update`
    step: Callable
    #: host unit -> the unit with its data on the device
    put: Callable[[Unit], Unit]
    #: the keys of ``aux``, exactly
    aux: tuple[str, ...] = ("loss",)
    #: host batches -> dispatch units
    units: Callable[[Iterable[Batch]], Iterator[Unit]] = _one_each
    #: host batches in a full unit
    group: int = 1
    #: how ``HealthGuard.note_losses`` pairs losses with batches
    loss_mode: str = "aligned"
    #: whether the infeed may run on the put thread; an unthreaded path
    #: records ``step.infeed`` and ``step.host``
    threaded: bool = True
    #: infeed lookahead where the path pins it, else ``prefetch_depth``
    depth: int | None = None
    #: ``(aux, unit)`` after each dispatch, before the tick
    after_step: Callable[[dict, Unit], None] | None = None
    #: the epoch mean weighs a unit's loss by its ``batches``
    weighted: bool = False
    #: the path of the units marked ``tail``
    tail: "EpochPath | None" = None


class Trainer:
    """Single-controller trainer: one process driving all local devices
    (or, under ``jax.distributed``, one of N identical SPMD processes)."""

    def __init__(
        self,
        model_config: ModelConfig,
        num_features: int,
        *,
        feature_columns: tuple[int, ...] | None = None,
        mesh: jax.sharding.Mesh | None = None,
        loss: str = "mse",
        seed: int = 0,
        worker_index: int = 0,
        dtype=jnp.float32,
        topology: "Any | None" = None,
        prefetch_depth: int = 2,
        scan_steps: int = 1,
        accum_steps: int = 1,
        keep_best: str = "",
        health: "HealthConfig | None" = None,
    ):
        # validate the cheap invariants FIRST: a bad combination must
        # fail in microseconds, not after model build + param init +
        # mesh sharding
        self.scan_steps = max(1, int(scan_steps))
        self.accum_steps = max(1, int(accum_steps))
        if self.scan_steps > 1 and self.accum_steps > 1:
            raise ValueError(
                "scan_steps and accum_steps are mutually exclusive: one "
                "chunks UPDATES per dispatch, the other chunks "
                "microbatches per UPDATE (shifu.tpu.scan-steps / "
                "shifu.tpu.accum-steps)"
            )
        if self.accum_steps > 1 and model_config.params.update_window > 1:
            # MultiSteps would wrap each accumulated group's apply in a
            # SECOND accumulation window — nested semantics nobody
            # configured, and the equal-weight window mean breaks the
            # exact big-batch equality accum-steps promises
            raise ValueError(
                "accum_steps does not compose with UpdateWindow > 1: both "
                "define gradient accumulation (shifu.tpu.accum-steps / "
                "train.params.UpdateWindow) — drop one"
            )
        if keep_best not in ("", "valid_loss", "ks"):
            raise ValueError(
                f"unknown keep_best {keep_best!r} (valid_loss | ks)"
            )
        self.model_config = model_config
        self.num_features = num_features
        # retained so export_model can rebuild the serving graph with the
        # same column positions the training graph used
        self.feature_columns = (
            tuple(feature_columns) if feature_columns is not None else None
        )
        self.mesh = mesh
        self.worker_index = worker_index
        # cross-process SPMD (parallel.distributed.ProcessTopology): the
        # mesh spans every process's devices and each process feeds only its
        # local slice of the global batch — XLA all-reduces gradients across
        # processes, the clean SyncReplicasOptimizer equivalent
        # (ssgd_monitor.py:136-142)
        # the make_array_from_process_local_data path engages whenever a
        # topology is given alongside a mesh (even single-process: local
        # rows are then all rows) so the dryrun exercises exactly what
        # multi-process runs
        self._topology = topology
        self._cross_process = topology is not None and mesh is not None
        # ---- host-resident embedding spill (EmbeddingPlacement=host) ----
        # the capacity tier past N x HBM: table in host RAM, per-batch
        # hashed gather on the host, sparse Adagrad updates from the
        # step's embedding-slice gradient (models/host_embedding.py)
        p = model_config.params
        if p.embedding_placement not in ("device", "host"):
            raise ValueError(
                f"unknown EmbeddingPlacement {p.embedding_placement!r} "
                "(device | host)"
            )
        self._host_emb = None
        self._host_emb_pos: tuple[int, ...] = ()
        if (p.embedding_placement == "host" and p.embedding_columns
                and p.embedding_hash_size > 0):
            if self.scan_steps > 1 or self.accum_steps > 1:
                raise ValueError(
                    "EmbeddingPlacement=host runs the per-step path only: "
                    "the host applies a sparse table update after every "
                    "step, which a scanned/accumulated dispatch cannot "
                    "surface — drop scan-steps/accum-steps"
                )
            if topology is not None and getattr(
                    topology, "is_distributed", False):
                raise ValueError(
                    "EmbeddingPlacement=host is single-process for now: "
                    "each process would train a private table copy on its "
                    "own shard's gradients, silently diverging — use "
                    "device placement (table sharded over the mesh "
                    "'model' axis) for multi-process jobs"
                )
            if p.algorithm == "sagn":
                raise ValueError(
                    "EmbeddingPlacement=host does not compose with "
                    "Algorithm=sagn (local-SGD windows never surface "
                    "per-step embedding grads)"
                )
            if p.model_type == "sequence":
                raise ValueError(
                    "EmbeddingPlacement=host applies to tabular families "
                    "only")
            from shifu_tensorflow_tpu.models.factory import _column_positions
            from shifu_tensorflow_tpu.models.host_embedding import (
                HostEmbeddingTable,
            )

            pos = (
                _column_positions(p.embedding_columns, feature_columns)
                if feature_columns
                else tuple(range(len(p.embedding_columns)))
            )
            if pos:
                self._host_emb = HostEmbeddingTable(
                    p.embedding_hash_size, p.embedding_dim,
                    lr=p.learning_rate, seed=seed,
                )
                self._host_emb_pos = pos
                if p.l2_reg > 0:
                    import warnings

                    # dense L2 would touch EVERY table row per step,
                    # defeating the sparse-update design; device
                    # placement DOES regularize its table (it lives in
                    # params) — say so instead of silently diverging
                    warnings.warn(
                        "L2Reg applies to the dense net only under "
                        "EmbeddingPlacement=host: the host table "
                        "updates sparsely and is exempt (device "
                        "placement regularizes its table)"
                    )
        #: keep-best snapshot of the host table (parallel to best_params)
        self.best_host_table = None

        # shard embedding tables only when a >1 'model' axis exists
        shard_emb = mesh is not None and mesh.shape.get("model", 1) > 1
        self.model = build_model(
            model_config, feature_columns, dtype=dtype,
            shard_embeddings=shard_emb, mesh=mesh,
        )
        self.tx = make_optimizer(model_config.params)
        self.loss_name = loss
        self.seed = seed
        # a family with a loss of its own (a language model's per-token
        # cross-entropy): the per-step path differentiates it; the other
        # paths' steps are written around a (B, 1) prediction
        self._batch_loss = family_loss(self.model)
        #: the last epoch's step counters of such a family, ``{name:
        #: [value per step]}`` (host numpy), else ``{}``
        self.epoch_counters: dict = {}
        if self._batch_loss is not None and (
                self.scan_steps > 1 or self.accum_steps > 1
                or self._host_emb is not None or p.algorithm == "sagn"
                or (mesh is not None and mesh.size > 1)):
            raise ValueError(
                f"ModelType={p.model_type!r} trains on the per-step path "
                "of one device: drop scan-steps / accum-steps / "
                "Algorithm=sagn / EmbeddingPlacement=host, and give a "
                "mesh of one device (--mesh none)")

        # host-embedding runs widen the device model's input with the
        # gathered embeddings; num_features stays the RAW feature count
        # (the public/export contract)
        self._model_input_width = num_features + (
            len(self._host_emb_pos) * p.embedding_dim
            if self._host_emb is not None else 0
        )
        init = self.model.init
        if self._batch_loss is not None:
            # one program: run eagerly, a deep model's init compiles and
            # launches every op of a forward pass whose result it drops
            init = jax.jit(init)
        params = init(
            jax.random.key(seed),
            jnp.zeros((1, self._model_input_width), dtype)
        )["params"]

        self.state = TrainState.create(
            apply_fn=self.model.apply, params=params, tx=self.tx
        )
        # strong-typed step: create() seeds step=0 as a weak-typed Python
        # int, but every jitted step RETURNS a strong int32 state — left
        # alone, the second dispatch retraces (and on TPU recompiles) just
        # to promote the dtype
        self.state = self.state.replace(
            step=jnp.asarray(self.state.step, jnp.int32)
        )

        if mesh is not None:
            from shifu_tensorflow_tpu.parallel.mesh import data_axis_size
            from shifu_tensorflow_tpu.parallel.sharding import (
                DEFAULT_PARTITION_RULES,
                batch_sharding,
                shard_params,
            )

            # regex partition rules place the whole TrainState (optax
            # mirrors inherit their param's spec by path suffix); the
            # nn.with_partitioning annotations are the fallback for
            # leaves no rule names
            self._partition_rules = DEFAULT_PARTITION_RULES
            self.state = shard_params(
                self.state, mesh, rules=self._partition_rules
            )
            self._batch_sharding = batch_sharding(mesh)
            # stacked chunks (S, B, ...) shard the BATCH dim (1); the scan
            # dim stays replicated
            from jax.sharding import NamedSharding, PartitionSpec
            from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS

            self._stacked_sharding = NamedSharding(
                mesh, PartitionSpec(None, DATA_AXIS)
            )
            self._data_axis = data_axis_size(mesh)
        else:
            self._partition_rules = None
            self._batch_sharding = None
            self._stacked_sharding = None
            self._data_axis = 1
        # rows each *process* must supply per batch divide by its local
        # share of the data axis (single-process: the whole axis)
        self._local_data_divisor = (
            max(1, self._data_axis // topology.num_processes)
            if self._cross_process
            else self._data_axis
        )

        # the plain per-batch step: the per-step path's without the guard's
        # norm, SAGN's tail, and what the step-rate harnesses time
        self._train_step = make_train_step(
            self.model.apply, loss, model_config.params.l2_reg,
            batch_loss=self._batch_loss,
        )
        # training-health guard (shifu.tpu.health-*): divergence/hang
        # detection + the coordinator's rollback directives.  The guard
        # object exists whenever a HealthConfig is given (even with every
        # check disabled) so the skip-window directive and the nan-loss
        # injection seam stay active for the chaos drills' control arm.
        self.health_guard = (
            HealthGuard(health, worker_index=worker_index)
            if health is not None else None
        )
        self._path = self._choose_path(loss)
        if self.health_guard is not None:
            # the watchdog is ticked once per DISPATCH, which spans the
            # path's group of host batches (scan steps, microbatches, a
            # SAGN window)
            self.health_guard.scale_watchdog(
                self._path.group,
                f"{self._path.name}: one dispatch spans a group of batches",
            )
        self._eval_step = make_eval_step(self.model.apply, loss,
                                         self._batch_loss)
        # device-infeed lookahead (conf key shifu.tpu.prefetch-depth;
        # shifu.tpu.data-prefetch / the ingest autotuner may retarget it
        # between streaming epochs)
        self.prefetch_depth = max(1, int(prefetch_depth))
        # pipelined infeed: production + device placement of batch k+1 on
        # a put thread, overlapping batch k's dispatch (data/dataset.py
        # _PipelinedPrefetch).  Default on for the paths that allow it
        # (EpochPath.threaded) and the eval pass.
        self.infeed_pipelined = True
        # the epoch's ROOT stream (the ShardStream under the generator
        # chain), stashed by train_epoch/evaluate so _PipelinedPrefetch
        # can unwedge its put thread on close (data/dataset.py)
        self._infeed_root = None
        # optional ingest feedback loop (data/autotune.IngestAutotuner):
        # installed by the streaming CLI/worker paths; fit_stream feeds it
        # per-epoch stage stats and applies its prefetch decision
        self.ingest_autotuner = None
        # opt-in per-step timing (utils/profiling.StepTimer); None = free
        self.step_timer = None
        # observability span sink (obs/trace.py): picked up from the
        # process-wide install (obs.install_obs runs before trainer
        # construction in every CLI path) so the epoch loops report the
        # infeed/host/dispatch/block step breakdown without a new
        # make_trainer parameter; None = every instrumented site is one
        # is-None check
        self.tracer = obs_trace.active()
        # SLO watchdog (obs/slo.py): fed per epoch with the step-time and
        # infeed-wait-fraction signals the shifu.tpu.slo-* targets judge;
        # picked up at construction exactly like the tracer
        from shifu_tensorflow_tpu.obs import slo as _obs_slo

        self.slo = _obs_slo.active()
        # set by the fit loops when an EarlyStopper ends training early
        self.stop_reason: str | None = None
        # keep-best (conf key shifu.tpu.keep-best, validated at the top
        # of __init__): snapshot params to host whenever the chosen
        # validation metric improves; export then serves the BEST epoch,
        # not the last (with patience-based early stopping the last epoch
        # is by construction patience epochs past the best).
        self.keep_best = keep_best
        self.best_params = None
        self.best_epoch: int | None = None
        self.best_metric = float("inf") if keep_best == "valid_loss" else float("-inf")

    # ---- the epoch path ----
    def _choose_path(self, loss: str) -> EpochPath:
        """The epoch path of this trainer, from what ``__init__`` knows."""
        p = self.model_config.params
        apply_fn = self.model.apply
        if self._host_emb is not None:
            return EpochPath(
                "train.host_emb_step",
                make_host_emb_train_step(apply_fn, self.num_features, loss,
                                         p.l2_reg),
                self._put_host_emb, aux=("loss", "emb_grad"),
                threaded=False, depth=1, after_step=self._apply_emb_grad)
        if self.scan_steps > 1:
            # chunked-scan epochs (shifu.tpu.scan-steps): K batches stacked
            # per dispatch, K sequential optimizer updates in it.  Update
            # semantics are the per-step path's — same body, same order.
            # The losses are per batch but chunking lost their order: the
            # guard checks that every real batch produced a finite one.
            return EpochPath(
                "train.scan_epoch", make_scan_epoch(apply_fn, loss, p.l2_reg),
                _placing(self._put_stacked),
                units=partial(self._stacked_chunks, K=self.scan_steps),
                group=self.scan_steps, loss_mode="counted")
        if self.accum_steps > 1:
            # accumulated epochs (shifu.tpu.accum-steps): A microbatches
            # stacked per ONE optimizer update, so global_step advances
            # once a group; the epoch loss is the nanmean of per-UPDATE
            # losses, where a NaN may be a padding group: only the guard's
            # inf and epoch-mean checks apply.
            return EpochPath(
                "train.accum_step", make_accum_step(apply_fn, loss, p.l2_reg),
                _placing(self._put_stacked),
                units=partial(self._stacked_chunks, K=self.accum_steps),
                group=self.accum_steps, loss_mode="loose")
        # per-step: with the guard's finite check on, the step also returns
        # the on-device global grad norm (the other paths fall back to the
        # guard's loss-count checks)
        grad_norm = (self.health_guard is not None
                     and self.health_guard.cfg.check_finite)
        step = (
            make_train_step(apply_fn, loss, p.l2_reg, with_grad_norm=True,
                            batch_loss=self._batch_loss)
            if grad_norm else self._train_step
        )
        return self._per_step_path(step, grad_norm)

    def _per_step_path(self, step, grad_norm: bool = False) -> EpochPath:
        aux = ["loss"]
        if grad_norm:
            aux.append("grad_norm")
        if self._batch_loss is not None:
            aux.append("counters")
        return EpochPath("train.step", step, _placing(self._put),
                         aux=tuple(aux))

    # ---- device placement ----
    def _augment_host_emb(self, batch: Batch) -> tuple[Batch, np.ndarray]:
        """Host-side gather for EmbeddingPlacement=host: hash the
        designated columns, gather their table rows, and append the
        embeddings to the features — only the working set crosses the
        link.  Also returns the bucket ids, which pair a step's embedding
        gradient with its rows."""
        x = np.asarray(batch["x"], np.float32)
        emb, ids = self._host_emb.lookup(x[:, list(self._host_emb_pos)])
        return {**batch,
                "x": np.concatenate([x, emb.reshape(x.shape[0], -1)],
                                    axis=1)}, ids

    def _put(self, batch: Batch) -> Batch:
        if self._host_emb is not None:
            batch, _ = self._augment_host_emb(batch)
        return self._place(batch)

    def _place(self, batch: Batch) -> Batch:
        if self._batch_sharding is not None:
            batch = self._pad_for_mesh(batch)
        return self._to_device(batch, self._batch_sharding)

    def _to_device(self, tree, sharding):
        if self._cross_process:
            from shifu_tensorflow_tpu.parallel.distributed import (
                put_process_local,
            )

            return put_process_local(tree, sharding)
        if sharding is not None:
            return jax.device_put(tree, sharding)
        return jax.device_put(tree)

    def _put_host_emb(self, unit: Unit) -> Unit:
        """The host-embedding path's ``put``: the unit carries the ids of
        its gather to ``_apply_emb_grad``."""
        batch, ids = self._augment_host_emb(unit.data)
        return unit._replace(data=self._place(batch), note=ids)

    def _apply_emb_grad(self, aux: dict, unit: Unit) -> None:
        """The host-embedding path's ``after_step``: the step returned the
        gradient of its gathered-embedding slice; pair it with the ids of
        the unit's gather and apply the sparse Adagrad update before the
        NEXT batch is gathered.  The device_get per step serializes the
        pipeline on the gradient fetch — the price of a table the device
        cannot hold.

        STALENESS CONTRACT: ZERO.  ``prefetch_to_device`` is an
        unthreaded generator (data/dataset.py) — there is no producer
        thread — so at depth 1 the gather for batch N runs strictly
        AFTER step N-1's gradient fetch and table update complete in
        this same thread.  Every batch reads fully-updated table values;
        the price is that gather and step never overlap (no infeed
        pipelining on this path).  Prefetch depth is pinned to 1 here
        regardless of ``shifu.tpu.prefetch-depth``: a deeper (or ever
        threaded) lookahead would introduce staleness scaled by a knob
        documented as an infeed setting — any future move of the gather
        onto a real producer thread must bring a synchronization story
        for the numpy table it would then share with ``apply_grads``.
        Zero staleness is strictly tighter than the reference's
        fully-async PS reads (arbitrary staleness, ssgd_monitor's PS
        architecture); the device-placement path also has none (its
        gather is inside the differentiated step)."""
        ids = unit.note
        # the per-step gradient fetch is this path's real completion wait
        # (the table cannot update without it)
        with obs_trace.maybe_span(self.tracer, "step.block"):
            g = np.asarray(jax.device_get(aux["emb_grad"]))[: ids.shape[0]]
        self._host_emb.apply_grads(
            ids, g.reshape(ids.shape[0], len(self._host_emb_pos),
                           self._host_emb.dim))

    def _pad_for_mesh(self, batch: Batch) -> Batch:
        """Row count must divide this process's share of the data axis; pad
        with zero-weight rows (free under the nonzero-weight loss
        normalization).  Cross-process, padding only ever triggers if the
        caller broke the equal-local-batch contract (sync_plan) — identical
        local shapes are required, not merely aligned ones."""
        n = batch["x"].shape[0]
        rem = n % self._local_data_divisor
        if rem == 0:
            return batch
        pad = self._local_data_divisor - rem
        return {
            k: np.concatenate(
                [np.asarray(v), np.zeros((pad,) + v.shape[1:], v.dtype)], axis=0
            )
            for k, v in batch.items()
        }

    def _put_stacked(self, stacked: Batch) -> Batch:
        """Device-place one (S, B, ...) chunk; batch dim sharded."""
        return self._to_device(stacked, self._stacked_sharding)

    def align_batch_size(self, batch_size: int) -> int:
        """Round a requested (per-process) batch size up to a divisible one."""
        a = self._local_data_divisor
        return -(-batch_size // a) * a

    def warm_step(self, batch_size: int, x_dtype=None) -> list[str]:
        """Compile-warm the step functions a fit at ``batch_size`` would
        dispatch, WITHOUT touching training state — the hot-standby
        pre-build (coordinator/worker.py): a promoted standby's first
        real step then hits the executable cache instead of paying XLA
        mid-takeover.

        Uses the code's own padding invariant instead of AOT tricks: an
        all-zero-WEIGHT batch is a proven no-op on every step variant
        (the ``has_rows`` gate skips ``apply_gradients``, so params and
        optimizer moments pass through bit-identical — the same contract
        the fixed-step SPMD padding batches rely on), while the dispatch
        itself compiles and caches exactly like a real one.  The
        returned state is reassigned so donated buffers stay valid.

        Returns the names of the warmed callables.  Not supported under
        cross-process SPMD (the mesh spans processes that don't exist
        until the fleet forms) — returns [] there.
        """
        if self._cross_process:
            return []
        b = self.align_batch_size(batch_size)
        xd = np.dtype(x_dtype if x_dtype is not None else np.float32)

        def zeros() -> Batch:
            return {
                "x": np.zeros((b, self.num_features), xd),
                "y": np.zeros((b, 1), np.float32),
                "w": np.zeros((b, 1), np.float32),
            }

        warmed: list[str] = []
        for path in filter(None, (self._path, self._path.tail)):
            unit = next(iter(path.units(zeros() for _ in range(path.group))))
            self.state, _ = path.step(self.state, path.put(unit).data)
            warmed.append(path.name)
        # the eval/validation step shares the batch shape
        loss, _ = self._eval_step(self.state.params, self._put(zeros()))
        jax.block_until_ready(loss)
        jax.block_until_ready(self.state.step)
        warmed.append("train.eval_step")
        return warmed

    # ---- core loops ----
    def train_epoch(self, batches: Iterable[Batch]) -> tuple[float, int]:
        """Run one epoch; returns (mean loss over batches, batch count).

        The source is CLOSED on every exit — normal exhaustion, a
        health-guard trip, any exception — so a streaming source's
        producer threads (ShardStream close() contract) never outlive the
        epoch that abandoned them."""
        source = batches
        self._infeed_root = source
        try:
            return self._run_epoch(batches)
        finally:
            self._infeed_root = None
            close_stream(source)

    def _infeed(self, batches, put, tracer, *, threaded: bool = True,
                depth: int | None = None):
        """The device-placement stage of an epoch or eval pass: pipelined
        (put thread overlaps dispatch; step.infeed.wait/put split) by
        default, the inline generator otherwise (``threaded=False``: the
        path forbids the thread).  Callers close() the result."""
        depth = depth or self.prefetch_depth
        if self.infeed_pipelined and threaded:
            return prefetch_to_device(batches, put=put, depth=depth,
                                      pipelined=True, tracer=tracer,
                                      root=self._infeed_root)
        timed = (tracer.timed("step.infeed", put)
                 if tracer is not None else put)
        return prefetch_to_device(batches, put=timed, depth=depth)

    def _run_epoch(self, batches: Iterable[Batch]) -> tuple[float, int]:
        """The one epoch loop: ``self._path`` (:class:`EpochPath`) is what
        differs between the per-step, scanned, accumulated, host-embedding
        and SAGN epochs."""
        from shifu_tensorflow_tpu.utils import faults as _faults

        path = self._path
        if _faults.active() is not None:
            # straggler-drill seam (utils/faults.py `slow` kind): one
            # check per host batch under site train.step.w<index>, so a
            # plan term like "train.step.w1:slow@1.0" deterministically
            # lags exactly one rank.  Wrapped only while a plan is
            # active — the per-step cost without one stays zero.  Placed
            # BEFORE the tracer's wrap_iter below, so the injected sleep
            # lands inside the host/production phase and the
            # coordinator's dominant-phase attribution can name it.
            batches = _fault_lagged(batches, self.worker_index)
        guard = self.health_guard
        if guard is not None:
            # instrument the stream of HOST BATCHES, before the path groups
            # them: real-row bookkeeping, the rollback skip-window, and the
            # nan-loss injection seam apply to every path identically
            batches = guard.filter_batches(batches)
        tracer = self.tracer
        if tracer is not None:
            # host-batch production (parse / stack / filter).  Grouping
            # into units (scan/accum stacking) and device placement are
            # NOT in here; placement is "step.infeed*" at the put,
            # stacking lands in the budget's "other" slice.  SPAN NAME
            # depends on WHERE production runs: unthreaded (the path
            # forbids the put thread, or infeed_pipelined is off) it stalls
            # the consumer and is the disjoint "step.host" phase; under
            # pipelined infeed it runs on the put thread and OVERLAPS
            # dispatch, so it records as "step.host.produce" — reported
            # separately (host_produce_s, like infeed_put_s) and excluded
            # from the wall-clock budget, where counting it would
            # double-book the overlapped seconds (the consumer-visible
            # stall is step.infeed.wait alone).
            overlapped = self.infeed_pipelined and path.threaded
            batches = tracer.wrap_iter(
                "step.host.produce" if overlapped else "step.host",
                batches)
        with_norm = "grad_norm" in path.aux
        with_counters = "counters" in path.aux
        losses, gnorms, counters, weights = [], [], [], []

        def via(unit: Unit) -> EpochPath:
            return path.tail if unit.tail else path

        # the epoch's boundary on the consumer thread, beside the loop's
        # "epoch.turn": "epoch.fill" from the feed's build (the put
        # thread's start, the stream's first batch and its placement) to
        # the first unit in hand, so it contains the epoch's first
        # "step.infeed.wait" (unthreaded: the first "step.host" +
        # "step.infeed"); "epoch.drain" from the loop's exit, raised or
        # not, through the feed's close (the put thread's join) and
        # "step.block" to the epoch's mean.  Twice an epoch, not a step.
        feed = None
        with contextlib.ExitStack() as drain:
            try:
                with obs_trace.maybe_span(tracer, "epoch.fill"):
                    feed = self._infeed(
                        path.units(batches),
                        lambda unit: via(unit).put(unit),
                        tracer, threaded=path.threaded, depth=path.depth)
                    units = iter(feed)
                    first = next(units, None)
                for unit in (() if first is None
                             else itertools.chain((first,), units)):
                    with obs_trace.maybe_span(tracer, "step.dispatch"):
                        self.state, aux = via(unit).step(self.state,
                                                         unit.data)
                    if path.after_step is not None:
                        path.after_step(aux, unit)
                    losses.append(aux["loss"])
                    if with_norm:
                        gnorms.append(aux["grad_norm"])
                    if with_counters:
                        counters.append(aux["counters"])
                    weights.append(unit.batches)
                    if guard is not None:
                        guard.tick()
                    if self.step_timer is not None:
                        self.step_timer.step(aux["loss"], rows=unit.rows)
            finally:
                drain.enter_context(
                    obs_trace.maybe_span(tracer, "epoch.drain"))
                close_stream(feed)
            if not losses:
                return float("nan"), 0
            with obs_trace.maybe_span(tracer, "step.block"):
                # one loss a unit, or (scan) one a batch of it
                vals = np.asarray(jax.device_get(losses)).reshape(-1)
                gvals = (np.asarray(jax.device_get(gnorms))
                         if with_norm else None)
                if with_counters:
                    fetched = jax.device_get(counters)
                    self.epoch_counters = {
                        k: np.asarray([c[k] for c in fetched])
                        for k in fetched[0]}
            if guard is not None:
                guard.note_losses(vals, gvals, mode=path.loss_mode)
            # all-padding units report NaN by contract (apply_update);
            # exclude them from the epoch mean instead of biasing it
            real = ~np.isnan(vals)
            if not real.any():
                mean = float("nan")
            elif path.weighted:
                mean = float(np.average(
                    vals[real].astype(np.float64),
                    weights=np.asarray(weights, np.float64)[real]))
            else:
                mean = float(np.mean(vals[real]))
            return mean, sum(weights)

    def _stacked_chunks(self, batches: Iterable[Batch],
                        K: int) -> Iterator[Unit]:
        """Group K batches into stacked ``(K, B, ...)`` chunks, the units
        of the scan/accum paths: each carries its real (unpadded) row
        count and the real batches in it.

        The last chunk pads with zero-weight no-op batches (exact no-ops
        by the step bodies' has_rows/zero-count gates).  The stacked row
        count is FIXED from the first chunk (aligned max batch within
        it), so a constant-batch-size stream compiles exactly one shape
        and the short tail batch pads into it; a stream whose batch size
        later GROWS forces a one-time regrow, so distinct compiled shapes
        are bounded by growths, never by the number of distinct batch
        sizes.  Cross-process SPMD stays in lockstep because
        fixed_step_batches already guarantees identical per-process batch
        counts, hence identical chunk counts and padding.
        """
        fixed_rows: int | None = None

        def _pad_rows(b: Batch, rows: int) -> Batch:
            """Zero-weight-pad a batch up to ``rows`` — free under the
            nonzero-weight loss normalization, same as _pad_for_mesh."""
            n = b["x"].shape[0]
            if n == rows:
                return b
            return {
                k: np.concatenate(
                    [np.asarray(v),
                     np.zeros((rows - n,) + v.shape[1:],
                              np.asarray(v).dtype)]
                )
                for k, v in b.items()
            }

        def _emit(buf: list[Batch]) -> Batch:
            nonlocal fixed_rows
            # every batch padded to the fixed row count, itself aligned to
            # the mesh divisor — the stacked equivalent of the per-step
            # path's per-batch _pad_for_mesh (variable/indivisible batch
            # sizes must not become a crash the moment chunking is
            # enabled)
            rows = self.align_batch_size(
                max(b["x"].shape[0] for b in buf)
            )
            if fixed_rows is None or rows > fixed_rows:
                fixed_rows = rows
            rows = fixed_rows
            if len(buf) < K:
                pad = _zero_batch(rows, buf[0]["x"].shape[1],
                                  buf[0]["x"].dtype)
                buf = buf + [pad] * (K - len(buf))
            return {
                k: np.stack([np.asarray(_pad_rows(c, rows)[k]) for c in buf])
                for k in buf[0]
            }

        def unit(buf: list[Batch]) -> Unit:
            return Unit(_emit(buf), sum(c["x"].shape[0] for c in buf),
                        len(buf))

        buf: list[Batch] = []
        for b in batches:
            buf.append(b)
            if len(buf) == K:
                yield unit(buf)
                buf = []
        if buf:
            yield unit(buf)

    #: best-snapshot persistence filename inside the checkpoint directory
    _BEST_FILE = "keep-best.npz"
    #: host-embedding sidecar name pattern (checkpoint directory)
    _HOST_EMB_FILE = "host-emb-{epoch}.npz"

    def _maybe_save_with_sidecar(self, checkpointer, epoch: int) -> bool:
        """checkpointer.maybe_save plus, for EmbeddingPlacement=host, the
        table sidecar (table + Adagrad accumulator) published atomically
        beside the state checkpoint — the table IS model state, and a
        resume that silently re-initialized it would train a fresh table
        against converged dense weights."""
        saved = checkpointer.maybe_save(epoch, self.state)
        if not saved or self._host_emb is None:
            return saved
        import os as _os
        import re as _re

        directory = checkpointer.directory
        if "://" in directory:
            import warnings

            warnings.warn(
                "EmbeddingPlacement=host checkpoints its table sidecar to "
                "LOCAL directories only in this version; the table will "
                f"not persist under {directory}"
            )
            return saved
        self._host_emb.save(_os.path.join(
            directory, self._HOST_EMB_FILE.format(epoch=epoch)))
        # prune in lockstep with the checkpointer's own retention — a
        # sidecar pruned ahead of its state checkpoint would turn a
        # rollback into the fresh-table failure this method exists to
        # prevent
        keep = int(getattr(checkpointer, "max_to_keep", 3))
        pat = _re.compile(r"host-emb-(\d+)\.npz$")
        found = sorted(
            int(m.group(1))
            for name in _os.listdir(directory)
            if (m := pat.match(name))
        )
        for old in found[: -keep]:
            try:
                _os.remove(_os.path.join(
                    directory, self._HOST_EMB_FILE.format(epoch=old)))
            except OSError:
                pass
        return saved

    def _restore_host_emb(self, directory: str, latest_epoch: int) -> None:
        import os as _os

        path = _os.path.join(
            directory, self._HOST_EMB_FILE.format(epoch=latest_epoch))
        if _os.path.exists(path):
            self._host_emb.load(path)
        else:
            import warnings

            warnings.warn(
                f"no host-embedding sidecar for epoch {latest_epoch} in "
                f"{directory}: the table restarts from init while the "
                "dense net resumes — expect a KS dip until it re-trains"
            )

    # ---- health-guard hooks (shared by every fit loop) ----
    def _health_begin_epoch(self, epoch: int) -> None:
        if self.health_guard is not None:
            self.health_guard.begin_epoch(epoch)

    def _health_check_epoch(self, stats: EpochStats) -> None:
        """Raise :class:`TrainingUnhealthy` when the guard trips — called
        BEFORE keep-best snapshots, epoch reports, and the checkpoint
        save, so diverged parameters are never published anywhere."""
        g = self.health_guard
        if g is None:
            return
        reason = g.check_epoch(stats)
        if reason:
            self.stop_reason = reason
            raise TrainingUnhealthy(
                reason,
                epoch=stats.current_epoch,
                bad_steps=g.bad_steps(),
                diag=g.diagnostics(),
            )

    def _obs_epoch(self, stats: EpochStats) -> None:
        """Journal the epoch and its step-phase time budget (obs plane).

        Runs AFTER the health check, so a diverged epoch surfaces in the
        journal as the coordinator's health_trip/rollback events rather
        than a clean epoch record.  The step_breakdown event drains the
        tracer (take_summary), so spans recorded between epochs —
        checkpoint saves, barrier RPCs, retry sleeps — attribute to the
        NEXT epoch's breakdown; the budget math only ever compares a
        breakdown against its own epoch's phases, so the off-by-one on
        auxiliary spans is cosmetic and documented here once."""
        j = obs_journal.active()
        slo = self.slo
        t = self.tracer
        # the SLO watchdog runs journal-or-not: --obs alone configures
        # gauges + targets, and a target silently dead because a second
        # flag was missing is the same bug class the journal-implies-
        # enabled rule exists for
        if j is None and slo is None:
            return
        if j is not None:
            j.emit(
                "epoch",
                plane="train",
                worker=self.worker_index,
                epoch=stats.current_epoch,
                train_loss=stats.training_loss,
                valid_loss=stats.valid_loss,
                ks=stats.ks,
                auc=stats.auc,
                train_time_s=round(stats.training_time_s, 4),
                valid_time_s=round(stats.valid_time_s, 4),
                global_step=stats.global_step,
            )
        fields = None
        if t is not None:
            fields = obs_trace.budget_fields(t.take_summary())
            if j is not None:
                j.emit(
                    "step_breakdown",
                    plane="train",
                    worker=self.worker_index,
                    epoch=stats.current_epoch,
                    # (worker, epoch, global_step) coordinates: with the
                    # journal's job stamp, the triple locates this record
                    # in the fleet-wide causal story (`obs trace
                    # worker:epoch`)
                    global_step=stats.global_step,
                    **fields,
                )
            # fleet leg: attach the phase summary to the stats the epoch
            # callback reports, so the coordinator's FleetMonitor can
            # attribute this rank's skew to a phase without new traffic.
            # The barrier wait rides from the PREVIOUS epoch's
            # rpc.epoch_barrier span (this drain runs before on_epoch's
            # barrier — the same documented one-epoch lag every
            # auxiliary span has); the clock offset is the client's
            # current NTP-style estimate (obs/fleet.ClockSync).
            phases = {k: v for k, v in fields.items() if k != "spans"}
            barrier = (fields.get("spans") or {}).get("rpc.epoch_barrier")
            if barrier is not None:
                phases["barrier_s"] = barrier["total_s"]
            offset = _obs_fleet.clock_offset()
            if offset is not None:
                phases["offset_s"] = round(offset, 6)
            stats.phases = phases
            # per-epoch collective/transfer drain (ring rotations,
            # all-to-alls, shard_map calls, global device_puts): bytes
            # moved per kind since the last epoch, beside the comm.*
            # spans already in this breakdown
            comm = _obs_fleet.take_comm()
            if comm and j is not None:
                j.emit("comm", plane="train", worker=self.worker_index,
                       epoch=stats.current_epoch, kinds=comm)
        if slo is not None and fields is not None:
            # per-epoch SLO signals from the same drain: mean step wall
            # time and the infeed-wait share of the epoch — evaluated
            # immediately (the train plane's tick is the epoch; serve
            # runs a background tick instead)
            steps = int(fields.get("steps") or 0)
            wall = max(stats.training_time_s, 1e-9)
            if steps > 0:
                slo.observe("train_step_ms", wall / steps * 1000.0)
                slo.observe(
                    "train_infeed_frac",
                    min(1.0, float(fields.get("infeed_s", 0.0)) / wall),
                )
            slo.evaluate(epoch=stats.current_epoch)
        # device/compiler leg (PR 10), same per-epoch cadence: one
        # device-memory snapshot attributing the TrainState's trees
        # (params vs opt-state vs everything else), the compile flight
        # recorder's storm tick (a storm whose compiles stopped clears
        # here), and the on-demand profiler trigger poll — each an
        # is-None check when the leg is off
        from shifu_tensorflow_tpu.obs import cost as _obs_cost
        from shifu_tensorflow_tpu.obs import memory as _obs_memory
        from shifu_tensorflow_tpu.obs import profile as _obs_profile
        from shifu_tensorflow_tpu.obs import rollup as _obs_rollup

        mem = _obs_memory.active()
        if mem is not None:
            mem.snapshot(params=self.state.params,
                         opt_state=self.state.opt_state,
                         epoch=stats.current_epoch)
        rec = obs_compile.active()
        if rec is not None:
            rec.tick()
        # cost leg (obs/cost.py): attribute this epoch's device dispatch
        # seconds to (job, worker) from the SAME step-phase drain the
        # journal records — the train side of the fleet's cost ledger
        acct = _obs_cost.active()
        if acct is not None and fields is not None:
            acct.note_train_epoch(
                self.worker_index,
                dispatch_s=float(fields.get("dispatch_s", 0.0) or 0.0),
                steps=int(fields.get("steps", 0) or 0))
        # long-horizon leg: the train plane's regression-watchdog tick
        # (the epoch IS the train tick, like the storm detector's)
        _obs_rollup.tick()
        _obs_profile.poll()
        # data leg (obs/datastats.py): journal the cumulative train-side
        # feature sketch each epoch — the record `obs data` and the
        # fleet export path (baseline_from_journal) read, and the
        # in-bundle feature_stats.json baseline's provenance trail
        from shifu_tensorflow_tpu.obs import datastats as _obs_datastats

        sk = _obs_datastats.train_active()
        if sk is not None and j is not None:
            snap = sk.snapshot()
            if snap is not None:
                j.emit("data_stats", plane="train",
                       worker=self.worker_index,
                       epoch=stats.current_epoch, stats=snap)

    def _note_train_dataset(self, dataset) -> None:
        """Fold an in-memory dataset's training features into the
        process-wide train data sketch (obs/datastats.py) — the
        streaming paths feed it block-by-block at batch formation
        instead (data/pipeline.blocks_to_batches).  One vectorized fold
        per distinct array: epochs re-shuffle the same rows."""
        from shifu_tensorflow_tpu.obs import datastats as _obs_datastats

        sk = _obs_datastats.train_active()
        if sk is not None:
            try:
                sk.add_dataset(dataset.train.features)
            except Exception:  # observability must never fail the fit
                pass

    def _warn_if_validation_empty(self, stats: EpochStats,
                                  early_stop) -> None:
        """The preflights guard the configured validation RATE, but the
        REALIZED split can still be empty (tiny shard, unlucky content-
        hash salt): evaluate() then reports ks=0.0 / NaN loss every
        epoch, keep-best=ks crowns the first epoch, and early stopping
        never fires.  Say so once instead of silently doing the wrong
        thing for the whole budget."""
        if getattr(self, "_warned_empty_valid", False):
            return
        if not (self.keep_best or early_stop is not None):
            return
        if stats.ks == 0.0 and np.isnan(stats.valid_loss):
            import warnings

            self._warned_empty_valid = True
            warnings.warn(
                "validation produced no scored rows (ks=0, loss=NaN): "
                "keep-best/early-stop cannot act — check validSetRate "
                "and the split salt against the shard size"
            )

    def _maybe_snapshot_best(self, stats: EpochStats,
                             checkpointer=None) -> None:
        """Host-snapshot the params when the keep-best metric improves.
        Host memory only (tabular nets are MBs); no collectives, so under
        SPMD each process snapshots locally without synchronization — the
        chief's snapshot is the one that matters (it exports).  With a
        checkpointer present the snapshot also persists to the checkpoint
        directory, so a resumed run keeps competing against the TRUE best
        instead of restarting the race from scratch."""
        if not self.keep_best:
            return
        if stats.ks == 0.0 and np.isnan(stats.valid_loss):
            # no scored validation rows: ks=0 here is absence of a
            # measurement, not a measurement of 0 — crowning it would
            # export the first epoch as "best"
            return
        if self.keep_best == "valid_loss":
            m = stats.valid_loss
            improved = not np.isnan(m) and m < self.best_metric
        else:  # ks
            m = stats.ks
            improved = m > self.best_metric
        if improved:
            self.best_metric = float(m)
            self.best_epoch = stats.current_epoch
            self.best_params = jax.device_get(_unbox_params(self.state.params))
            if self._host_emb is not None:
                # the table is model state: a "best" without it would pair
                # the best dense net with the LAST epoch's embeddings
                self.best_host_table = self._host_emb.table.copy()
            if checkpointer is not None:
                self._persist_best(checkpointer.directory)

    def _persist_best(self, directory: str) -> None:
        """Atomic write of the best snapshot (tmp + rename, like the
        checkpointers); path->array keys so restore needs no treedef."""
        import json as _json
        import os as _os

        from shifu_tensorflow_tpu.export.saved_model import _flatten_params
        from shifu_tensorflow_tpu.utils import fs

        from shifu_tensorflow_tpu.train.checkpoint import _host_tag

        meta = _json.dumps({
            "epoch": self.best_epoch,
            "metric": self.best_metric,
            "keep_best": self.keep_best,
        })
        base = f"{directory.rstrip('/')}/{self._BEST_FILE}"
        # same .tmp.<host>.<pid> convention as the checkpointers, so the
        # stale-temp sweeper's host-aware pid-liveness rules apply to a
        # chief SIGKILLed mid-write here too
        tmp = f"{base}.tmp.{_host_tag()}.{_os.getpid()}"
        extra = {}
        if self.best_host_table is not None:
            # host-embedding best rides along (reserved __ prefix keys are
            # filtered out of the params unflatten on restore)
            extra["__host_table__"] = self.best_host_table
        with fs.filesystem_for(tmp).open_write(fs.strip_local(tmp)) as f:
            np.savez(f, __meta__=np.frombuffer(meta.encode(), np.uint8),
                     **extra, **_flatten_params(self.best_params))
        # verified commit, never blindly re-issued: a lost response after a
        # remote rename applied must read as success (fs.commit_rename)
        fs.commit_rename(tmp, base)

    def _restore_best(self, directory: str) -> None:
        """Load a persisted best snapshot (resume path).  Ignored when
        absent or recorded under a DIFFERENT metric — comparing a ks best
        against valid_loss improvements would be meaningless."""
        import io
        import json as _json

        from shifu_tensorflow_tpu.export.saved_model import _unflatten_params
        from shifu_tensorflow_tpu.utils import fs

        base = f"{directory.rstrip('/')}/{self._BEST_FILE}"
        try:
            with fs.filesystem_for(base).open_read(fs.strip_local(base)) as f:
                raw = f.read()
        except OSError:
            return  # no snapshot (the common case): silently none
        try:
            data = np.load(io.BytesIO(raw))
            meta = _json.loads(bytes(data["__meta__"]).decode())
            if meta.get("keep_best") != self.keep_best:
                return
            best_params = _unflatten_params(
                {k: data[k] for k in data.files
                 if not k.startswith("__")}
            )
            best_host_table = (
                data["__host_table__"] if "__host_table__" in data.files
                else None
            )
            best_epoch = int(meta["epoch"])
            best_metric = float(meta["metric"])
        except Exception as e:
            # an UNUSABLE snapshot (truncated zip, missing keys, bad
            # JSON — e.g. a non-atomic remote rename died mid-write) must
            # degrade to "no best yet", never brick every subsequent
            # resume and the fleet export
            import warnings

            warnings.warn(
                f"ignoring unreadable keep-best snapshot {base}: "
                f"{type(e).__name__}: {e}"
            )
            return
        self.best_params = best_params
        self.best_epoch = best_epoch
        self.best_metric = best_metric
        if best_host_table is not None:
            self.best_host_table = best_host_table

    def evaluate(self, batches: Iterable[Batch]) -> dict[str, float]:
        """Validation pass; closes the source on every exit (same stream
        teardown contract as train_epoch)."""
        source = batches
        self._infeed_root = source
        try:
            return self._evaluate_inner(batches)
        finally:
            self._infeed_root = None
            close_stream(source)

    def _no_validation(self) -> dict[str, float]:
        """What an epoch without a validation pass reports: KS 0 / AUC 0.5
        (no skill) for a ranked family, absent (NaN) for a family with a
        loss of its own, which has no score to rank."""
        if self._batch_loss is not None:
            return {"loss": float("nan"), "ks": float("nan"),
                    "auc": float("nan")}
        return {"loss": float("nan"), "ks": 0.0, "auc": 0.5}

    def _evaluate_inner(self, batches: Iterable[Batch]) -> dict[str, float]:
        losses, scores, labels, weights = [], [], [], []
        if self._cross_process:
            # labels/weights stay host-side (the device copies are global
            # row-sharded arrays, not locally fetchable); predictions come
            # back as this process's rows, so KS/AUC are per-worker over the
            # worker's own validation shard — parity with each reference
            # worker reporting valid metrics on its own data
            # (ssgd_monitor.py:281-293); the loss is the global scalar.
            from shifu_tensorflow_tpu.parallel.distributed import local_rows

            for host_batch in batches:
                dev = self._put(host_batch)
                loss, pred = self._eval_step(self.state.params, dev)
                if self.health_guard is not None:
                    self.health_guard.tick()
                losses.append(loss)
                # drop any locally-padded rows so rows align with the host
                # batch (padding sits at the tail)
                scores.append(local_rows(pred)[: host_batch["y"].shape[0]])
                labels.append(np.asarray(host_batch["y"]))
                weights.append(np.asarray(host_batch["w"]))
        else:
            feed = self._infeed(batches, self._put, None)
            try:
                for batch in feed:
                    loss, pred = self._eval_step(self.state.params, batch)
                    if self.health_guard is not None:
                        self.health_guard.tick()
                    losses.append(loss)
                    scores.append(np.asarray(pred))
                    labels.append(np.asarray(batch["y"]))
                    weights.append(np.asarray(batch["w"]))
            finally:
                close_stream(feed)
        if not losses:
            return self._no_validation()
        s = np.concatenate(scores)[:, 0]
        y = np.concatenate(labels)[:, 0]
        w = np.concatenate(weights)[:, 0]
        vals = np.asarray(jax.device_get(losses))
        real = vals[~np.isnan(vals)]
        # a family with its own loss has no (B, 1) score to rank: KS and
        # AUC are absent (NaN), not computed from something else
        ranked = self._batch_loss is None
        return {
            "loss": float(np.mean(real)) if real.size else float("nan"),
            "ks": M.ks_statistic(s, y, w) if ranked else float("nan"),
            "auc": M.auc(s, y, w) if ranked else float("nan"),
        }

    def _epoch_stats(self, epoch: int, train_loss: float, ev: dict,
                     train_time: float, valid_time: float) -> EpochStats:
        return EpochStats(
            worker_index=self.worker_index,
            current_epoch=epoch,
            training_loss=train_loss,
            valid_loss=ev["loss"],
            training_time_s=train_time,
            valid_time_s=valid_time,
            global_step=int(jax.device_get(self.state.step)),
            ks=ev["ks"],
            auc=ev["auc"],
        )

    def _close_epoch(self, stats: EpochStats, history: list, on_epoch,
                     checkpointer, early_stop) -> bool:
        """What every fit loop does with a finished epoch's stats: health
        check, journal, best snapshot, callback, checkpoint, early stop.
        True when early stopping says stop (``stop_reason`` holds why)."""
        self._health_check_epoch(stats)
        self._obs_epoch(stats)
        self._warn_if_validation_empty(stats, early_stop)
        self._maybe_snapshot_best(stats, checkpointer)
        history.append(stats)
        if on_epoch:
            on_epoch(stats)
        if checkpointer is not None:
            self._maybe_save_with_sidecar(checkpointer, stats.current_epoch)
        if early_stop is not None:
            self.stop_reason = early_stop.should_stop(stats)
            return bool(self.stop_reason)
        return False

    @_sketch_fit_scope
    def fit(
        self,
        dataset: InMemoryDataset,
        *,
        epochs: int | None = None,
        batch_size: int | None = None,
        on_epoch: MetricsCallback | None = None,
        checkpointer: "Any | None" = None,
        start_epoch: int = 0,
        early_stop: "EarlyStopper | None" = None,
    ) -> list[EpochStats]:
        """Epoch loop over an in-memory dataset (streaming fit lives in
        fit_stream).  ``start_epoch`` supports resume-with-correct-budget —
        restored jobs train only the remaining epochs (fixes the reference's
        acknowledged gap, backup.py:30)."""
        epochs = epochs or self.model_config.num_train_epochs
        batch_size = batch_size or self.model_config.batch_size
        history: list[EpochStats] = []
        self.stop_reason = None
        self._note_train_dataset(dataset)
        for epoch in range(start_epoch, epochs):
            self._health_begin_epoch(epoch)
            t0 = time.time()
            train_loss, _ = self.train_epoch(
                dataset.train_batches(batch_size, epoch=epoch)
            )
            train_time = time.time() - t0

            t1 = time.time()
            ev = self.evaluate(dataset.valid_batches(batch_size))
            valid_time = time.time() - t1

            with obs_trace.maybe_span(self.tracer, "epoch.turn"):
                stats = self._epoch_stats(epoch, train_loss, ev,
                                          train_time, valid_time)
                stop = self._close_epoch(stats, history, on_epoch,
                                         checkpointer, early_stop)
            if stop:
                break
        return history

    @_sketch_fit_scope
    def fit_device_resident(
        self,
        dataset: InMemoryDataset,
        *,
        epochs: int | None = None,
        batch_size: int | None = None,
        on_epoch: MetricsCallback | None = None,
        checkpointer: "Any | None" = None,
        start_epoch: int = 0,
        early_stop: "EarlyStopper | None" = None,
    ) -> list[EpochStats]:
        """All-in-HBM training: the reference's load-everything workload
        (ssgd_monitor.py:348-454) in its TPU-native form.

        The train/valid tensors transfer to device ONCE; every epoch is a
        single compiled program — on-device shuffle (jax.random.permutation
        gather) + lax.scan over the batched steps — so steady-state epochs
        involve zero host↔device batch traffic and one dispatch.  Per-epoch
        host work is only the scalar losses and the validation scores for
        KS/AUC.

        Single-controller only: multi-process SPMD feeds per-process shards
        through fit_stream; this path is for datasets that fit in HBM
        (demo/eval scale, the reference's own regime).
        """
        if self._batch_loss is not None:
            raise ValueError(
                "fit_device_resident scans the (B, 1)-prediction step: "
                f"ModelType={self.model_config.params.model_type!r} trains "
                "through fit / fit_stream")
        if self._cross_process:
            raise ValueError(
                "fit_device_resident is single-controller; multi-process "
                "SPMD jobs stream per-process shards (fit_stream)"
            )
        if self._host_emb is not None:
            raise ValueError(
                "EmbeddingPlacement=host contradicts --device-resident: "
                "the table exceeds device memory by assumption — use the "
                "streaming or in-memory fit paths"
            )
        if self.accum_steps > 1:
            # silently training per-B updates when the user configured
            # A-microbatch accumulation would change effective batch math
            raise ValueError(
                "fit_device_resident does not support "
                "shifu.tpu.accum-steps; raise the batch size instead "
                "(the dataset already fits in device memory)"
            )
        epochs = epochs or self.model_config.num_train_epochs
        B = self.align_batch_size(batch_size or self.model_config.batch_size)
        self.stop_reason = None
        self._note_train_dataset(dataset)
        if self.health_guard is not None:
            # one compiled dispatch IS the epoch here: there is no
            # per-step tick for the watchdog to measure against
            self.health_guard.disable_watchdog(
                "device-resident training runs one dispatch per epoch"
            )

        def _padded_device(block):
            n = len(block)
            if n == 0:
                return None, 0, None, None
            steps = -(-n // B)
            pad = steps * B - n
            x = np.asarray(block.features)
            y = np.asarray(block.targets)
            w = np.asarray(block.weights)
            if pad:
                x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
                y = np.concatenate([y, np.zeros((pad, 1), y.dtype)])
                w = np.concatenate([w, np.zeros((pad, 1), w.dtype)])
            data = {"x": x, "y": y, "w": w}
            dev = (
                jax.device_put(data, self._batch_sharding)
                if self._batch_sharding is not None
                else jax.device_put(data)
            )
            # host copies of labels/weights stay for KS/AUC (no fetch)
            return dev, steps, y, w

        train_dev, S, _, _ = _padded_device(dataset.train)
        valid_dev, Sv, valid_y, valid_w = _padded_device(dataset.valid)
        if train_dev is None:
            return []

        epoch_fn = self._make_device_epoch(S, B)
        eval_fn = self._make_device_eval(Sv, B) if valid_dev is not None else None

        history: list[EpochStats] = []
        base_key = jax.random.key(self.seed)
        for epoch in range(start_epoch, epochs):
            self._health_begin_epoch(epoch)
            t0 = time.time()
            # one compiled dispatch IS the epoch on this path: the step
            # budget degenerates to dispatch + block (no per-step
            # host/infeed phases exist to measure)
            with obs_trace.maybe_span(self.tracer, "step.dispatch"):
                self.state, aux = epoch_fn(
                    self.state, train_dev,
                    jax.random.fold_in(base_key, epoch)
                )
            # the tensors were placed once, before the first epoch: this
            # path has no feed to fill, so of the two boundary spans of
            # _run_epoch it opens "epoch.drain" alone
            with obs_trace.maybe_span(self.tracer, "epoch.drain"):
                with obs_trace.maybe_span(self.tracer, "step.block"):
                    vals = np.asarray(jax.device_get(aux["loss"]))
                real = vals[~np.isnan(vals)]
                train_loss = (float(np.mean(real)) if real.size
                              else float("nan"))
            train_time = time.time() - t0

            ev = self._no_validation()
            valid_time = 0.0
            if eval_fn is not None:
                t1 = time.time()
                vlosses, preds = eval_fn(self.state.params, valid_dev)
                vvals = np.asarray(jax.device_get(vlosses))
                vreal = vvals[~np.isnan(vvals)]
                # (Sv, B, C) -> rows x outputs; KS/AUC score column 0, the
                # same contract as evaluate() (multi-task C>1: head 0)
                p_host = np.asarray(jax.device_get(preds))
                scores = p_host.reshape(-1, p_host.shape[-1])[:, 0]
                mask = valid_w[:, 0] > 0
                ev = {
                    "loss": float(np.mean(vreal)) if vreal.size else float("nan"),
                    "ks": M.ks_statistic(scores[mask], valid_y[mask, 0],
                                         valid_w[mask, 0]),
                    "auc": M.auc(scores[mask], valid_y[mask, 0],
                                 valid_w[mask, 0]),
                }
                valid_time = time.time() - t1

            stats = self._epoch_stats(epoch, train_loss, ev,
                                      train_time, valid_time)
            # one-dispatch epochs have no per-step stream for the guard to
            # instrument; the epoch-level checks (mean-NaN, spike) and the
            # hang watchdog still apply
            if self.health_guard is not None:
                self.health_guard.tick()
                if not np.isfinite(train_loss):
                    self.health_guard._count_bad = (
                        "epoch mean loss non-finite"
                    )
            if self._close_epoch(stats, history, on_epoch, checkpointer,
                                 early_stop):
                break
        return history

    def _make_device_epoch(self, steps: int, batch_size: int):
        """One-dispatch epoch: on-device shuffle + scanned updates.  Memoized
        per (steps, batch) — a fresh jit closure per fit call would recompile
        the identical program every time."""
        cache = getattr(self, "_device_epoch_cache", None)
        if cache is None:
            cache = self._device_epoch_cache = {}
        key = (steps, batch_size)
        if key in cache:
            return cache[key]
        body = make_train_step_body(
            self.model.apply, self.loss_name, self.model_config.params.l2_reg
        )
        stacked_sh = self._stacked_sharding

        @partial(jax.jit, donate_argnums=(0,))
        def epoch_fn(state, data, key):
            n = data["x"].shape[0]
            perm = jax.random.permutation(key, n)
            stacked = {
                k: v[perm].reshape((steps, batch_size) + v.shape[1:])
                for k, v in data.items()
            }
            if stacked_sh is not None:
                stacked = jax.lax.with_sharding_constraint(
                    stacked, stacked_sh
                )
            return jax.lax.scan(body, state, stacked)

        cache[key] = obs_compile.observe(epoch_fn, "train.resident_epoch")
        return cache[key]

    def _make_device_eval(self, steps: int, batch_size: int):
        """Scanned validation pass: (losses, preds) in one dispatch.
        Memoized like _make_device_epoch."""
        cache = getattr(self, "_device_eval_cache", None)
        if cache is None:
            cache = self._device_eval_cache = {}
        key = (steps, batch_size)
        if key in cache:
            return cache[key]
        eval_body = make_eval_step_body(self.model.apply, self.loss_name)

        @jax.jit
        def eval_fn(params, data):
            stacked = {
                k: v.reshape((steps, batch_size) + v.shape[1:])
                for k, v in data.items()
            }

            def body(_, batch):
                return None, eval_body(params, batch)

            _, (losses, preds) = jax.lax.scan(body, None, stacked)
            return losses, preds

        cache[key] = obs_compile.observe(eval_fn, "train.resident_eval")
        return cache[key]

    @_sketch_fit_scope
    def fit_stream(
        self,
        make_train_stream: Callable[[int], Iterable[Batch]],
        make_valid_stream: Callable[[], Iterable[Batch]] | None = None,
        *,
        epochs: int | None = None,
        on_epoch: MetricsCallback | None = None,
        checkpointer: "Any | None" = None,
        start_epoch: int = 0,
        early_stop: "EarlyStopper | None" = None,
    ) -> list[EpochStats]:
        """Epoch loop over streaming shards (the 1B-row path):
        ``make_train_stream(epoch)`` returns a fresh batch iterator."""
        epochs = epochs or self.model_config.num_train_epochs
        history: list[EpochStats] = []
        self.stop_reason = None
        autotuner = self.ingest_autotuner
        for epoch in range(start_epoch, epochs):
            # "epoch.turn": what the loop does between one epoch's last
            # step and the next one's first (not a "step." name, so never
            # sampled; journaled under "spans")
            with obs_trace.maybe_span(self.tracer, "epoch.turn"):
                if autotuner is not None:
                    # apply the tuner's device-put depth for this epoch;
                    # the reader/decode widths land via the stream
                    # factory, which reads autotuner.settings() at build
                    # time
                    self.prefetch_depth = max(
                        1, autotuner.settings().prefetch)
                self._health_begin_epoch(epoch)
                t0 = time.time()  # train_time counts the stream's build
                stream = make_train_stream(epoch)
            train_loss, n = self.train_epoch(stream)
            train_time = time.time() - t0
            if autotuner is not None:
                # digest the epoch's stage stats (delivered through the
                # stream's stats_sink when train_epoch closed it) plus
                # THIS epoch's step spans.  With the obs journal (or the
                # SLO watchdog) active, _obs_epoch's take_summary()
                # drained the tracer at the end of the previous epoch,
                # so the non-destructive summary() covers exactly this
                # epoch (and the journal still gets it).  Without
                # either, nothing ever drains, so drain here — a
                # cumulative wait total divided by one epoch's wall
                # would ratchet the starvation signal toward 1.0 and the
                # tuner would widen forever on a healthy pipeline.
                summ = None
                if self.tracer is not None:
                    drained_by_obs = (obs_journal.active() is not None
                                      or self.slo is not None)
                    summ = (self.tracer.summary() if drained_by_obs
                            else self.tracer.take_summary())
                with obs_trace.maybe_span(self.tracer, "epoch.turn"):
                    autotuner.observe_epoch(summ)
            ev = self._no_validation()
            valid_time = 0.0
            if make_valid_stream is not None:
                t1 = time.time()
                ev = self.evaluate(make_valid_stream())
                valid_time = time.time() - t1
            with obs_trace.maybe_span(self.tracer, "epoch.turn"):
                stats = self._epoch_stats(epoch, train_loss, ev,
                                          train_time, valid_time)
                stop = self._close_epoch(stats, history, on_epoch,
                                         checkpointer, early_stop)
            if stop:
                break
        return history

    def predict(self, features: np.ndarray, batch_size: int = 4096) -> np.ndarray:
        """Batched scoring on device (serving-path parity with
        TensorflowModel.compute, TensorflowModel.java:53-94)."""
        out = []
        n = features.shape[0]
        for i in range(0, n, batch_size):
            x = jnp.asarray(features[i : i + batch_size], jnp.float32)
            out.append(np.asarray(self.model.apply({"params": self.state.params}, x)))
        return np.concatenate(out, axis=0) if out else np.empty((0, 1), np.float32)

    def restore(self, checkpointer: "Any") -> int:
        """Restore latest checkpoint; returns the next epoch to run.  With
        keep-best configured, the persisted best snapshot restores too —
        a resumed run must compete against the TRUE best, not restart the
        race (else export silently serves best-since-resume)."""
        restored, next_epoch = checkpointer.restore_latest(self.state)
        if restored is not None:
            self.state = restored
            if self._host_emb is not None and "://" not in checkpointer.directory:
                self._restore_host_emb(checkpointer.directory,
                                       next_epoch - 1)
        if self.keep_best:
            self._restore_best(checkpointer.directory)
        return next_epoch
