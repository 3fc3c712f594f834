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
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.data.dataset import Batch
from shifu_tensorflow_tpu.obs import compile as obs_compile
from shifu_tensorflow_tpu.ops.losses import get_loss, l2_penalty
from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS
from shifu_tensorflow_tpu.train.optimizers import make_base_optimizer
from shifu_tensorflow_tpu.train.trainer import (
    EpochPath,
    Trainer,
    Unit,
    _placing,
    _widen_features,
    apply_update,
)

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
    carries.  Returns ``(state, aux)`` with the window's mean loss in
    ``aux["loss"]`` (train/trainer.py ``apply_update``).
    """
    loss_fn = get_loss(loss_name)

    def compute_loss(params, micro):
        # same compact-transport seam as the plain step: bf16-streamed
        # features widen to the params' precision on device
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
        return apply_update(state, avg_grads, loss, window_batch["w"])

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
        # with it for no additional amortization
        kw["scan_steps"] = 1
        self.update_window = max(int(p0.update_window), 1)
        self._local_optimizer = local_optimizer or p0.optimizer
        super().__init__(model_config, num_features, **kw)

    def _choose_path(self, loss: str) -> EpochPath:
        """Windows of ``update_window`` microbatches, one dispatch each,
        unthreaded; a trailing partial window is plain synchronous steps
        (the parent's per-step path without the guard's norm).  The losses
        are per WINDOW — a NaN may be an all-padding window, so only the
        guard's inf and epoch-mean checks apply — and the epoch mean
        counts a K-micro window K times."""
        p = self.model_config.params
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
        step = make_sagn_step(
            self.model.apply,
            make_base_optimizer(self._local_optimizer, p.learning_rate),
            loss_name=loss,
            l2=p.l2_reg,
            mesh=self.mesh,
        )
        return EpochPath(
            "train.sagn_step", step, _placing(self._put_window),
            units=self._windows, group=self.update_window,
            loss_mode="loose", threaded=False, weighted=True,
            tail=self._per_step_path(self._train_step))

    def _windows(self, batches: Iterable[Batch]) -> Iterator[Unit]:
        K = self.update_window
        buf: list[Batch] = []
        for batch in batches:
            buf.append(batch)
            if len(buf) == K:
                yield Unit([self._pad_for_mesh(b) for b in buf],
                           sum(b["x"].shape[0] for b in buf), K)
                buf = []
        for batch in buf:
            yield Unit(batch, batch["x"].shape[0], tail=True)

    def _put_window(self, micros: list[Batch]) -> Batch:
        return self._put_stacked({
            k: np.stack([np.asarray(m[k]) for m in micros], axis=0)
            for k in micros[0]
        })

    def fit_device_resident(self, *a, **kw):
        """The inherited device-resident epoch scans the PLAIN train-step
        body — running it here would silently replace SAGN's window-averaged
        update rule with per-batch SSGD.  Refuse instead."""
        raise NotImplementedError(
            "fit_device_resident trains with plain-SSGD semantics; the SAGN "
            "window algorithm uses fit/fit_stream"
        )
