"""The one epoch loop over its six paths (train/trainer.py ``EpochPath``,
``Trainer._run_epoch``): per-step, per-step with the health guard's norm,
scan, accumulation, host-embedding and SAGN.  One parametrised test a seam
the loop owns, so a path that drifts from the others fails by name."""

import dataclasses
import inspect
import threading

import jax
import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.obs.trace import Tracer
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.trainer import HealthConfig
from shifu_tensorflow_tpu.utils import faults

N_FEATS = 6
ROWS = (16, 16, 12, 12, 8)  # five host batches (a SAGN window is of one size)

# path -> (train.params beside the net, make_trainer keywords, dispatches
# the five batches make, the compiled step's name)
PATHS = {
    "per_step": ({}, {"health": HealthConfig(check_finite=False)},
                 5, "train.step"),
    "per_step_health": ({}, {"health": HealthConfig()}, 5, "train.step"),
    "scan": ({}, {"scan_steps": 2, "health": HealthConfig()},
             3, "train.scan_epoch"),
    "accum": ({}, {"accum_steps": 2, "health": HealthConfig()},
              3, "train.accum_step"),
    "host_emb": ({"EmbeddingColumnNums": [2, 5], "EmbeddingHashSize": 64,
                  "EmbeddingDim": 4, "EmbeddingPlacement": "host"},
                 {"health": HealthConfig()}, 5, "train.host_emb_step"),
    # two windows of two, and a tail of one plain step
    "sagn": ({"Algorithm": "sagn", "UpdateWindow": 2},
             {"health": HealthConfig()}, 3, "train.sagn_step"),
}
AUX = {
    "per_step": {"loss"},
    "per_step_health": {"loss", "grad_norm"},
    "scan": {"loss"},
    "accum": {"loss"},
    "host_emb": {"loss", "emb_grad"},
    "sagn": {"loss"},
}
THREADED = {"per_step", "per_step_health", "scan", "accum"}

paths = pytest.mark.parametrize("path", list(PATHS))


def _trainer(path: str, **override):
    extra, kw, _, _ = PATHS[path]
    mc = ModelConfig.from_json({"train": {"numTrainEpochs": 1, "params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [8],
        "ActivationFunc": ["relu"], "LearningRate": 0.05,
        "Optimizer": "adam", **extra}}})
    return make_trainer(mc, N_FEATS, feature_columns=tuple(range(1, 7)),
                        seed=3, **{**kw, **override})


def _batches(seed: int = 0) -> list[dict]:
    rng = np.random.default_rng(seed)
    return [{"x": rng.normal(size=(n, N_FEATS)).astype(np.float32),
             "y": (rng.random((n, 1)) < 0.4).astype(np.float32),
             "w": np.ones((n, 1), np.float32)} for n in ROWS]


class _Source:
    """A batch source with the streams' ``close()``."""

    def __init__(self, batches):
        self.batches, self.closed = batches, False

    def __iter__(self):
        return iter(self.batches)

    def close(self):
        self.closed = True


@paths
def test_span_names(path):
    """Threaded paths record the pipelined infeed's pair and the
    overlapped production; unthreaded ones (host-embedding: its
    zero-staleness contract; SAGN) ``step.infeed`` and ``step.host``.
    One ``step.dispatch`` a unit, one ``epoch.fill`` and one
    ``epoch.drain`` an epoch, each from the loop's one site."""
    trainer = _trainer(path)
    trainer.tracer = Tracer()
    loss, n = trainer.train_epoch(_batches())
    assert n == 5 and np.isfinite(loss)
    spans = trainer.tracer.summary()
    infeed = ({"step.host.produce", "step.infeed.wait", "step.infeed.put"}
              if path in THREADED else {"step.host", "step.infeed"})
    assert set(spans) == infeed | {"step.dispatch", "step.block",
                                   "epoch.fill", "epoch.drain"}
    assert spans["step.dispatch"]["count"] == PATHS[path][2]
    # the epoch's two boundary spans, once on every path of the one loop
    assert spans["epoch.fill"]["count"] == spans["epoch.drain"]["count"] == 1
    # the epoch's value fetch, and host-embedding's gradient fetch a step
    assert spans["step.block"]["count"] == (6 if path == "host_emb" else 1)


@paths
def test_one_guard_tick_a_dispatch(path):
    trainer = _trainer(path)
    ticks = []
    trainer.health_guard.tick = lambda: ticks.append(1)
    trainer.health_guard.begin_epoch(0)
    trainer.train_epoch(_batches())
    assert len(ticks) == PATHS[path][2]


@paths
def test_hang_watchdog_stretches_by_the_paths_group(path):
    """One tick a dispatch, so the per-step timeout is scaled by the host
    batches a dispatch spans: the path's ``group``, whichever path."""
    trainer = _trainer(path, health=HealthConfig(hang_timeout_s=7.0))
    group = {"scan": 2, "accum": 2, "sagn": 2}.get(path, 1)
    try:
        assert trainer._path.group == group
        assert trainer.health_guard.watchdog.timeout_s == 7.0 * group
    finally:
        trainer.health_guard.close()


@paths
def test_step_timer_counts_the_real_rows(path):
    trainer = _trainer(path)
    seen = []

    class _Timer:
        def step(self, out, rows):
            seen.append(rows)

    trainer.step_timer = _Timer()
    trainer.train_epoch(_batches())
    assert len(seen) == PATHS[path][2]
    assert sum(seen) == sum(ROWS)


@paths
def test_source_and_feed_are_closed_when_the_step_raises(path):
    trainer = _trainer(path)
    calls, feeds = [], []
    real_step, real_infeed = trainer._path.step, trainer._infeed

    def step(state, data):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("boom")
        return real_step(state, data)

    def infeed(*a, **kw):
        feeds.append(real_infeed(*a, **kw))
        return feeds[-1]

    trainer._path = dataclasses.replace(trainer._path, step=step)
    trainer._infeed = infeed
    source = _Source(_batches())
    with pytest.raises(RuntimeError, match="boom"):
        trainer.train_epoch(source)
    assert source.closed and trainer._infeed_root is None
    (feed,) = feeds
    if path in THREADED:
        assert feed._closed and not feed._thread.is_alive()
    else:
        assert inspect.getgeneratorstate(feed) == inspect.GEN_CLOSED
    assert not [t for t in threading.enumerate()
                if t.name == "stpu-infeed-put" and t.is_alive()]


@paths
def test_slow_fault_lags_every_host_batch(path):
    """The straggler seam ``train.step.w<index>`` is consulted once a
    host batch on every path (SAGN lacked it)."""
    trainer = _trainer(path)
    plan = faults.FaultPlan.parse("train.step.w0:slow1@1.0")
    faults.set_plan(plan)
    try:
        trainer.train_epoch(_batches())
    finally:
        faults.set_plan(None)
    assert plan.fired() == {"train.step.w0:slow1": len(ROWS)}


@paths
def test_warm_step_keeps_every_leaf_and_names_the_paths_callable(path):
    trainer = _trainer(path)
    trainer.train_epoch(_batches())  # moments and counts nonzero
    before = [(p, np.array(leaf)) for p, leaf in
              jax.tree_util.tree_leaves_with_path(
                  jax.device_get(trainer.state))]
    warmed = trainer.warm_step(16)
    tail = ["train.step"] if path == "sagn" else []
    assert warmed == [PATHS[path][3], *tail, "train.eval_step"]
    assert trainer._path.name == PATHS[path][3]
    after = jax.tree_util.tree_leaves(jax.device_get(trainer.state))
    assert len(before) == len(after)
    for (p, a), b in zip(before, after):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), p


@paths
def test_step_returns_exactly_the_declared_auxiliary_keys(path):
    trainer = _trainer(path)
    chosen = trainer._path
    assert set(chosen.aux) == AUX[path]
    for p in filter(None, (chosen, chosen.tail)):
        unit = next(iter(p.units(iter(_batches()))))
        assert unit.batches == p.group
        trainer.state, aux = p.step(trainer.state, p.put(unit).data)
        assert isinstance(aux, dict) and set(aux) == set(p.aux)
        assert np.isfinite(np.asarray(aux["loss"])).all()
