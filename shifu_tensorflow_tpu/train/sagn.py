"""SAGN — Synchronous Accumulated Gradients Normalization (local SGD).

Parity surface: the reference's SAGN variant (SAGN.py:110-176,
sagn_monitor.py:122-179) runs a communication window of ``update_window``
local optimizer steps on per-worker *local* variable copies, accumulates the
window's gradients, averages them (``tf.reduce_mean``, SAGN.py:137-142),
applies the averaged gradients to *global* PS-hosted twins through
SyncReplicasOptimizer (SAGN.py:158-167), then re-syncs global→local
(SAGN.py:169-176, helpers :427-505).

TPU-native re-design (no PS, no variable mirroring):

- one jitted step consumes a stacked **window** of K microbatches with
  leaves shaped ``(K, B, ...)``;
- ``shard_map`` over the mesh's ``data`` axis makes each shard a "worker":
  inside, a ``lax.scan`` runs K genuinely local optimizer steps (params
  drift per shard, zero cross-chip traffic) while summing the raw
  gradients;
- ONE ``psum`` round over ``data`` at window end is the entire
  communication — the reference's PS round-trip-per-window collapsed to a
  single ICI all-reduce;
- the global optimizer applies the averaged gradients to the (replicated)
  global params — SyncReplicasOptimizer's aggregation with none of its
  token-queue protocol.  The local drift is discarded exactly like the
  reference's ``assign_global_to_local`` re-sync.

Aggregation is count-weighted (per-microbatch nonzero-weight row counts)
rather than the reference's unweighted ``reduce_mean``: identical when all
microbatches are full, and exactly equal to the global weighted gradient
when zero-weight padding rows land unevenly across shards.

Local optimizer slots are re-initialized each window (the reference carried
per-worker Adam slots across windows; fresh slots per window is the
stateless-SPMD equivalent and keeps the step a pure function).
"""

from __future__ import annotations

from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.data.dataset import (
    Batch,
    close_stream,
    prefetch_to_device,
)
from shifu_tensorflow_tpu.obs import compile as obs_compile
from shifu_tensorflow_tpu.obs import trace as obs_trace
from shifu_tensorflow_tpu.ops.losses import get_loss, l2_penalty
from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS
from shifu_tensorflow_tpu.train.optimizers import make_base_optimizer
from shifu_tensorflow_tpu.train.trainer import Trainer, apply_if_rows

from shifu_tensorflow_tpu.parallel.shmap import shard_map


def make_sagn_step(
    apply_fn,
    local_tx: optax.GradientTransformation,
    *,
    loss_name: str = "mse",
    l2: float = 0.0,
    mesh: jax.sharding.Mesh | None = None,
):
    """Build the jitted SAGN window step.

    Takes ``(state, window_batch)`` where window_batch leaves are
    ``(K, B, ...)``; the window size K is whatever the stacked batch
    carries.  Returns ``(state, mean_window_loss)``.
    """
    loss_fn = get_loss(loss_name)

    def compute_loss(params, micro):
        # same compact-transport seam as the plain step: bf16-streamed
        # features widen to the params' precision on device
        from shifu_tensorflow_tpu.train.trainer import _widen_features

        pred = apply_fn({"params": params},
                        _widen_features(params, micro["x"]))
        with jax.named_scope("loss"):
            loss = loss_fn(pred, micro["y"], micro["w"])
            if l2:
                loss = loss + l2_penalty(params, l2)
        return loss

    def local_window(params, wb):
        """K local steps on drifting local params.  Returns count-weighted
        sums (Σ c_k·g_k, Σ c_k·loss_k, Σ c_k) where c_k is the microbatch's
        nonzero-weight row count: because each per-(micro)batch loss is
        normalized SUM_BY_NONZERO_WEIGHTS, re-weighting by count makes the
        cross-shard aggregate EXACTLY the global weighted gradient —
        zero-weight padding rows stay free even when they land unevenly on
        one shard."""
        opt_state = local_tx.init(params)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

        def body(carry, micro):
            p, os, gsum, lsum, csum = carry
            c = jnp.sum((micro["w"] != 0.0).astype(jnp.float32))
            loss, g = jax.value_and_grad(compute_loss)(p, micro)
            updates, os = local_tx.update(g, os, p)
            p = optax.apply_updates(p, updates)
            gsum = jax.tree_util.tree_map(lambda a, b: a + b * c, gsum, g)
            return (p, os, gsum, lsum + loss * c, csum + c), loss

        (_, _, gsum, lsum, csum), _ = jax.lax.scan(
            body, (params, opt_state, zeros, 0.0, 0.0), wb
        )
        return gsum, lsum, csum

    def _normalize(gsum, lsum, csum):
        denom = jnp.maximum(csum, 1.0)
        avg = jax.tree_util.tree_map(lambda g: g / denom, gsum)
        return avg, lsum / denom

    if mesh is None:
        def window_fn(params, wb):
            return _normalize(*local_window(params, wb))
    else:
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(None, DATA_AXIS)),
            out_specs=(P(), P()),
        )
        def window_fn(params, wb):
            gsum, lsum, csum = local_window(params, wb)
            gsum = jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, DATA_AXIS), gsum
            )
            return _normalize(
                gsum,
                jax.lax.psum(lsum, DATA_AXIS),
                jax.lax.psum(csum, DATA_AXIS),
            )

    @partial(jax.jit, donate_argnums=(0,))
    def sagn_step(state, window_batch):
        avg_grads, loss = window_fn(state.params, window_batch)
        # all-padding window: keep the state as it was (zero grads would
        # still move Adam-style momentum / increment step) and report NaN
        # so epoch means exclude it — same contract as make_train_step
        has_rows = jnp.sum(window_batch["w"] != 0.0) > 0
        with jax.named_scope("optimizer.update"):
            state = apply_if_rows(state, avg_grads, has_rows)
        return state, jnp.where(has_rows, loss, jnp.nan)

    return obs_compile.observe(sagn_step, "train.sagn_step")


class SAGNTrainer(Trainer):
    """Trainer running the SAGN communication-window algorithm.

    The epoch loop groups the batch stream into windows of
    ``update_window`` microbatches; a trailing partial window falls back to
    the parent's plain synchronous step (same gradients, window of 1), so no
    data is dropped and no alternate-K recompilation happens.
    """

    def __init__(
        self,
        model_config: ModelConfig,
        num_features: int,
        *,
        local_optimizer: str | None = None,
        **kw,
    ):
        # Gradient accumulation is REJECTED rather than ignored — and
        # BEFORE the expensive super().__init__ (model build, param init):
        # it would change what an "update window" means (accumulate-then-
        # update vs local-steps-then-average), and silently training
        # different semantics than configured is the round-1 class of bug.
        if int(kw.get("accum_steps", 1)) > 1:
            raise ValueError(
                "Algorithm=sagn does not compose with "
                "shifu.tpu.accum-steps: the SAGN window already defines "
                "its own accumulation semantics (UpdateWindow)"
            )
        p0 = model_config.params
        if p0.lr_schedule not in ("constant", "") or p0.warmup_steps > 0:
            # the schedule would apply only to the GLOBAL apply while the
            # window's local drift steps keep the flat LR — half-applied
            # semantics that match neither scheduled SSGD nor constant
            # SAGN; reject rather than train something nobody configured
            raise ValueError(
                "Algorithm=sagn does not support LearningRateSchedule/"
                "WarmupSteps: the window's local steps would keep the "
                "flat LearningRate while only the global apply followed "
                "the schedule"
            )
        # SAGN's window step already batches update_window microbatches
        # per dispatch — the scan_steps chunking would compose confusingly
        # with it for no additional amortization.  Forced to 1 BEFORE
        # super().__init__ so the parent never scales the hang-watchdog
        # timeout for a scan path that will not run.
        kw["scan_steps"] = 1
        super().__init__(model_config, num_features, **kw)
        self.scan_steps = 1
        self._scan_epoch = None
        p = model_config.params
        self.update_window = max(int(p.update_window), 1)
        if self.health_guard is not None:
            # one SAGN dispatch spans the whole communication window — the
            # per-step hang timeout must stretch with it (same contract as
            # the parent's scan/accum scaling)
            self.health_guard.scale_watchdog(
                self.update_window,
                "SAGN window: one dispatch spans update_window microbatches",
            )
        local_name = local_optimizer or p.optimizer
        local_tx = make_base_optimizer(local_name, p.learning_rate)
        if self.mesh is not None:
            import flax.linen as nn

            leaves = jax.tree_util.tree_leaves(
                self.state.params,
                is_leaf=lambda x: isinstance(x, nn.Partitioned),
            )
            if any(isinstance(l, nn.Partitioned) for l in leaves):
                raise ValueError(
                    "SAGNTrainer shard_map path requires replicated params; "
                    "model-parallel (Partitioned) tables are not supported — "
                    "use the plain Trainer for embedding-sharded models"
                )
        self._sagn_step = make_sagn_step(
            self.model.apply,
            local_tx,
            loss_name=self.loss_name,
            l2=p.l2_reg,
            mesh=self.mesh,
        )
        self._window_sharding = (
            NamedSharding(self.mesh, P(None, DATA_AXIS))
            if self.mesh is not None
            else None
        )

    def _put_window(self, micros: list[Batch]) -> Batch:
        stacked = {
            k: np.stack([np.asarray(m[k]) for m in micros], axis=0)
            for k in micros[0]
        }
        if self._cross_process:
            from shifu_tensorflow_tpu.parallel.distributed import (
                put_process_local,
            )

            return put_process_local(stacked, self._window_sharding)
        if self._window_sharding is not None:
            return jax.device_put(stacked, self._window_sharding)
        return jax.device_put(stacked)

    def fit_device_resident(self, *a, **kw):
        """The inherited device-resident epoch scans the PLAIN train-step
        body — running it here would silently replace SAGN's window-averaged
        update rule with per-batch SSGD.  Refuse instead."""
        raise NotImplementedError(
            "fit_device_resident trains with plain-SSGD semantics; the SAGN "
            "window algorithm uses fit/fit_stream"
        )

    def train_epoch(self, batches: Iterable[Batch]) -> tuple[float, int]:
        """SAGN window epoch; the source is closed on every exit (same
        stream-teardown contract as the parent's train_epoch)."""
        source = batches
        try:
            return self._train_epoch_sagn(batches)
        finally:
            close_stream(source)

    def _train_epoch_sagn(self, batches: Iterable[Batch]) -> tuple[float, int]:
        K = self.update_window
        losses: list = []
        weights: list[int] = []
        n_micro = 0
        tail: list[Batch] = []
        guard = self.health_guard
        if guard is not None:
            # same instrumentation seam as the parent's train_epoch:
            # real-row bookkeeping, rollback skip-window, nan injection
            batches = guard.filter_batches(batches)
        tracer = self.tracer
        if tracer is not None:
            # same step-phase seams as the parent (obs plane): raw batch
            # production is "step.host", window placement "step.infeed",
            # one dispatch per SAGN window
            batches = tracer.wrap_iter("step.host", batches)

        def windows():
            buf: list[Batch] = []
            for batch in batches:
                buf.append(self._pad_for_mesh(batch))
                if len(buf) == K:
                    yield buf
                    buf = []
            tail.extend(buf)

        # overlap host-side window stacking + transfer with device compute,
        # same double-buffering the plain trainer gets from prefetch_to_device
        put_window = (tracer.timed("step.infeed", self._put_window)
                      if tracer is not None else self._put_window)
        for wb in prefetch_to_device(windows(), put=put_window,
                                     depth=self.prefetch_depth):
            with obs_trace.maybe_span(tracer, "step.dispatch"):
                self.state, loss = self._sagn_step(self.state, wb)
            losses.append(loss)
            weights.append(K)
            n_micro += K
            if guard is not None:
                guard.tick()
        # trailing partial window: plain sync steps (window of 1); the
        # placement is timed as step.infeed like the main path, not
        # swallowed into the dispatch span
        put = (tracer.timed("step.infeed", self._put)
               if tracer is not None else self._put)
        for batch in tail:
            dev = put(batch)
            with obs_trace.maybe_span(tracer, "step.dispatch"):
                self.state, loss = self._train_step(self.state, dev)
            losses.append(loss)
            weights.append(1)
            n_micro += 1
            if guard is not None:
                guard.tick()
        if not losses:
            return float("nan"), 0
        # microbatch-weighted epoch mean: a K-micro window counts K times;
        # NaN losses mark all-padding windows (skipped by contract)
        with obs_trace.maybe_span(tracer, "step.block"):
            vals = np.asarray(jax.device_get(losses), np.float64)
        if guard is not None:
            # per-WINDOW losses: a NaN may be an all-padding window, so
            # only the inf and epoch-mean divergence checks apply
            guard.note_losses(vals, mode="loose")
        ws = np.asarray(weights, np.float64)
        mask = ~np.isnan(vals)
        return (
            float(np.average(vals[mask], weights=ws[mask]))
            if mask.any()
            else float("nan"),
            n_micro,
        )
