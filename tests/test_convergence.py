"""Convergence gates at the BASELINE.md north star: KS >= 0.45.

SURVEY.md §7.2 item 3 requires convergence-parity validation, not
bit-parity: the clean psum equivalent of SyncReplicasOptimizer changes
effective batch/step math, so the proof is that every training path
reaches the quality bar on a learnable dataset.  Four gated paths:

    ssgd  x {single-process, 2-process SPMD}
    sagn  x {single-process, 2-process SPMD}

The dataset is synthetic logistic with a strong signal (scaled logits) so
the Bayes-optimal KS is comfortably above the gate; a regression that
breaks optimization math (loss weighting, gradient aggregation, SAGN
window averaging, SPMD batch assembly) lands well under it.
"""

from __future__ import annotations

import gzip
import os

import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.coordinator.coordinator import JobSpec, JobState
from shifu_tensorflow_tpu.coordinator.submitter import JobSubmitter
from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
from shifu_tensorflow_tpu.data.dataset import InMemoryDataset
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.data.splitter import split_training_data
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

KS_GATE = 0.45  # BASELINE.md north star
N_FEATURES = 10
EPOCHS = 6

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": REPO_ROOT,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


@pytest.fixture(scope="module")
def strong_dataset(tmp_path_factory):
    """Gzip PSV shards with a strongly learnable signal: logits scaled 3x
    so the Bayes-optimal KS is ~0.7 — far enough above the 0.45 gate that
    passing requires real optimization, not luck."""
    rng = np.random.default_rng(7)
    root = tmp_path_factory.mktemp("strongdata")
    w_true = rng.normal(size=N_FEATURES)
    w_true *= 3.0 / np.linalg.norm(w_true)
    paths = []
    for i in range(4):
        path = root / f"part-{i:05d}.gz"
        with gzip.open(path, "wt") as f:
            for _ in range(600):
                x = rng.normal(size=N_FEATURES)
                p = 1.0 / (1.0 + np.exp(-float(x @ w_true)))
                y = 1 if rng.random() < p else 0
                cols = [str(y)] + [f"{v:.5f}" for v in x] + ["1.0"]
                f.write("|".join(cols) + "\n")
        paths.append(str(path))
    return {"root": str(root), "paths": paths}


def _schema() -> RecordSchema:
    return RecordSchema(
        feature_columns=tuple(range(1, N_FEATURES + 1)),
        target_column=0,
        weight_column=N_FEATURES + 1,
    )


def _model_config(algorithm: str) -> ModelConfig:
    params = {
        "NumHiddenLayers": 2,
        "NumHiddenNodes": [16, 8],
        "ActivationFunc": ["relu", "tanh"],
        "LearningRate": 0.05,
        "Optimizer": "adam",
        "Algorithm": algorithm,
    }
    if algorithm == "sagn":
        # the reference's communication window (SAGN.py update_window=5);
        # window=1 degenerates to the plain step and would gate nothing
        # SAGN-specific
        params["UpdateWindow"] = 5
    return ModelConfig.from_json(
        {
            "train": {
                "numTrainEpochs": EPOCHS,
                "validSetRate": 0.2,
                "params": params,
            }
        }
    )


def _final_ks_from_checkpoint(ckpt_dir: str, mc: ModelConfig,
                              dataset: InMemoryDataset) -> float:
    """Restore the chief's final checkpoint into a fresh local trainer and
    score the union validation set — the quality the exported model would
    actually serve."""
    trainer = make_trainer(
        mc, N_FEATURES, feature_columns=_schema().feature_columns
    )
    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == EPOCHS - 1
    restored, _ = ckpt.restore_latest(trainer.state)
    trainer.state = restored
    ev = trainer.evaluate(dataset.valid_batches(64))
    return ev["ks"]


@pytest.mark.parametrize("algorithm", ["ssgd", "sagn"])
def test_single_process_reaches_ks_gate(strong_dataset, algorithm):
    mc = _model_config(algorithm)
    dataset = InMemoryDataset.load(
        strong_dataset["paths"], _schema(), mc.valid_set_rate, salt=0
    )
    trainer = make_trainer(
        mc, N_FEATURES, feature_columns=_schema().feature_columns
    )
    history = trainer.fit(dataset, batch_size=64)
    ks = history[-1].ks
    assert ks >= KS_GATE, (
        f"{algorithm} single-process KS {ks:.3f} < gate {KS_GATE}"
    )


def test_round4_training_features_reach_ks_gate(strong_dataset):
    """The round-4 training features composed — gradient accumulation,
    warmup+cosine LR schedule, keep-best, early-stop-at-target — must
    still clear the north-star gate (and the early stop must fire AT or
    above it, by definition of the criterion)."""
    params = {
        "NumHiddenLayers": 2,
        "NumHiddenNodes": [16, 8],
        "ActivationFunc": ["relu", "tanh"],
        "LearningRate": 0.1,
        "Optimizer": "adam",
        "LearningRateSchedule": "cosine",
        "WarmupSteps": 10,
        "DecaySteps": 200,
        "DecayRate": 0.1,
    }
    mc = ModelConfig.from_json(
        {"train": {"numTrainEpochs": 12, "validSetRate": 0.2,
                   "params": params}}
    )
    dataset = InMemoryDataset.load(
        strong_dataset["paths"], _schema(), mc.valid_set_rate, salt=0
    )
    from shifu_tensorflow_tpu.train.trainer import EarlyStopper

    trainer = make_trainer(
        mc, N_FEATURES, feature_columns=_schema().feature_columns,
        accum_steps=2, keep_best="ks",
    )
    history = trainer.fit(
        dataset, batch_size=64,
        early_stop=EarlyStopper(target_ks=KS_GATE),
    )
    assert trainer.stop_reason, "never reached the gate within the budget"
    assert history[-1].ks >= KS_GATE
    assert trainer.best_metric >= KS_GATE  # keep-best tracked the gate run


@pytest.mark.parametrize("algorithm", ["ssgd", "sagn"])
def test_two_process_spmd_reaches_ks_gate(strong_dataset, tmp_path,
                                          algorithm):
    mc = _model_config(algorithm)
    shards = split_training_data(strong_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    schema = _schema()

    def make_cfg(worker_id: str, addr) -> WorkerConfig:
        return WorkerConfig(
            worker_id=worker_id,
            coordinator_host=addr[0],
            coordinator_port=addr[1],
            model_config=mc,
            schema=schema,
            batch_size=64,
            checkpoint_dir=ckpt_dir,
            heartbeat_interval_s=0.2,
            seed=0,
            spmd=True,
        )

    spec = JobSpec(
        n_workers=2, shards=shards, spmd=True, epochs=EPOCHS,
        registration_timeout_s=120.0, epoch_barrier_timeout_s=120.0,
    )
    submitter = JobSubmitter(
        spec, make_cfg, launcher="process", worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
    )
    result = submitter.run(timeout_s=600.0)
    assert result.state == JobState.FINISHED, result.failure_reason

    dataset = InMemoryDataset.load(
        strong_dataset["paths"], schema, mc.valid_set_rate, salt=0
    )
    ks = _final_ks_from_checkpoint(ckpt_dir, mc, dataset)
    assert ks >= KS_GATE, (
        f"{algorithm} 2-process SPMD KS {ks:.3f} < gate {KS_GATE}"
    )
