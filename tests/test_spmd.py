"""Cross-process SPMD training: N worker processes, ONE model.

This is the reference's defining capability — SyncReplicasOptimizer
aggregating gradients across workers through the PS
(ssgd_monitor.py:136-142,234-257) — rebuilt as jax.distributed + XLA
all-reduce.  The tests here run real subprocesses over CPU loopback:

- params parity: 2 processes training one model must match (to float
  tolerance) a single process training on the union of their shards with
  the concatenated global batches;
- kill-based recovery: SIGKILL one process mid-job and watch the fleet
  restart from the shared checkpoint and finish — the test the reference
  only ever ran by hand (CommonUtils.java:265-273).
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.coordinator.coordinator import (
    Coordinator,
    JobSpec,
    JobState,
)
from shifu_tensorflow_tpu.coordinator.submitter import JobSubmitter
from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
from shifu_tensorflow_tpu.data.dataset import (
    InMemoryDataset,
    fixed_step_batches,
)
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.data.splitter import split_training_data
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: subprocess workers run on plain CPU (1 device each); 2 procs -> 2-device
#: global mesh over loopback
WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "PYTHONPATH": REPO_ROOT,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


def _spec(shards, n_workers, **kw) -> JobSpec:
    kw.setdefault("registration_timeout_s", 120.0)
    kw.setdefault("epoch_barrier_timeout_s", 120.0)
    return JobSpec(n_workers=n_workers, shards=shards, spmd=True, **kw)


def _model_config(epochs: int, **params_extra) -> ModelConfig:
    params = {
        "NumHiddenLayers": 1,
        "NumHiddenNodes": [8],
        "ActivationFunc": ["relu"],
        "LearningRate": 0.05,
        "Optimizer": "adam",
    }
    params.update(params_extra)
    return ModelConfig.from_json(
        {
            "train": {
                "numTrainEpochs": epochs,
                "validSetRate": 0.2,
                "params": params,
            }
        }
    )


def _schema(psv_dataset) -> RecordSchema:
    return RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )


# ---------------------------------------------------------------- unit level


def test_fixed_step_batches_pads_and_drops():
    def batches(sizes):
        for n in sizes:
            yield {
                "x": np.ones((n, 3), np.float32),
                "y": np.ones((n, 1), np.float32),
                "w": np.ones((n, 1), np.float32),
            }

    # short source: pads the partial batch and fabricates zero batches
    out = list(fixed_step_batches(batches([4, 2]), 4, 4, 3))
    assert len(out) == 4
    assert all(b["x"].shape == (4, 3) for b in out)
    assert float(out[1]["w"].sum()) == 2.0  # 2 real rows, 2 padded
    assert float(out[2]["w"].sum()) == 0.0  # fabricated
    assert float(out[3]["w"].sum()) == 0.0

    # long source: surplus dropped, reported
    dropped = []
    out = list(
        fixed_step_batches(
            batches([4, 4, 4]), 4, 2, 3, on_dropped=dropped.append
        )
    )
    assert len(out) == 2
    assert dropped == [4]


def test_npz_checkpointer_roundtrip(tmp_path):
    mc = _model_config(1)
    trainer = make_trainer(mc, 10, feature_columns=tuple(range(10)))
    ckpt = NpzCheckpointer(str(tmp_path), max_to_keep=2)
    assert ckpt.latest_epoch() is None
    ckpt.save(0, trainer.state)
    ckpt.save(1, trainer.state)
    ckpt.save(2, trainer.state)
    assert ckpt.latest_epoch() == 2
    # max_to_keep pruned the oldest
    assert not os.path.exists(os.path.join(str(tmp_path), "ckpt-0.npz"))

    other = make_trainer(mc, 10, feature_columns=tuple(range(10)), seed=7)
    restored, next_epoch = ckpt.restore_latest(other.state)
    assert next_epoch == 3
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(restored.params),
        jax.tree_util.tree_leaves(trainer.state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restore_epoch picks a specific (agreed) epoch
    state1, nxt = ckpt.restore_epoch(1, other.state)
    assert nxt == 2


def test_npz_checkpointer_async_roundtrip(tmp_path):
    """async_save moves writes off the epoch loop; restore paths must see
    in-flight saves (wait-before-read), eviction still applies, and a
    failed background write surfaces instead of vanishing."""
    mc = _model_config(1)
    trainer = make_trainer(mc, 10, feature_columns=tuple(range(10)))
    with NpzCheckpointer(str(tmp_path / "a"), max_to_keep=2,
                         async_save=True) as ckpt:
        ckpt.save(0, trainer.state)
        ckpt.save(1, trainer.state)
        ckpt.save(2, trainer.state)
        # restore_latest waits for the queue, then reads epoch 2
        other = make_trainer(mc, 10, feature_columns=tuple(range(10)), seed=7)
        restored, next_epoch = ckpt.restore_latest(other.state)
        assert next_epoch == 3
        assert ckpt._epochs() == [1, 2]  # eviction ran after publish
        import jax

        for a, b in zip(
            jax.tree_util.tree_leaves(restored.params),
            jax.tree_util.tree_leaves(trainer.state.params),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # write failure: checkpoint dir replaced by a plain file (covers "dir
    # vanished mid-run") -> surfaced on wait(), not lost (chmod tricks
    # don't work here: tests run as root, which ignores permission bits)
    import shutil

    bad = NpzCheckpointer(str(tmp_path / "b"), async_save=True)
    shutil.rmtree(str(tmp_path / "b"))
    (tmp_path / "b").write_text("not a directory")
    try:
        bad.save(0, trainer.state)
        with pytest.raises(OSError):
            bad.wait()
    finally:
        bad._pending = []
        bad.close()


def test_npz_checkpointer_sweeps_dead_writer_tmp(tmp_path):
    """SIGKILL'd writers leave ckpt-N.npz.tmp.<host>.<pid> debris;
    construction sweeps it once the pid is dead AND the file is past the
    in-flight grace — young files and live/own pids are kept.  Temps
    stamped with a FOREIGN hostname (shared NFS checkpoint dir: the
    writer's pid means nothing here) and legacy pid-only suffixes are
    never pid-checked: only the max-age ceiling collects them."""
    import time

    from shifu_tensorflow_tpu.train.checkpoint import _host_tag

    d = str(tmp_path)
    host = _host_tag()
    dead = os.path.join(d, f"ckpt-3.npz.tmp.{host}.999999")
    young = os.path.join(d, f"ckpt-4.npz.tmp.{host}.999998")
    mine = os.path.join(d, f"ckpt-5.npz.tmp.{host}.{os.getpid()}")
    foreign = os.path.join(d, "ckpt-6.npz.tmp.other-host.999999")
    foreign_old = os.path.join(d, "ckpt-7.npz.tmp.other-host.999998")
    legacy = os.path.join(d, "ckpt-8.npz.tmp.999997")
    for p in (dead, young, mine, foreign, foreign_old, legacy):
        open(p, "w").write("partial")
    old_t = time.time() - 600  # past the 120s grace, under the 1h max
    for p in (dead, mine, foreign, legacy):
        os.utime(p, (old_t, old_t))
    ancient = time.time() - 4000  # past the 1h debris ceiling
    os.utime(foreign_old, (ancient, ancient))
    NpzCheckpointer(d)
    assert not os.path.exists(dead)      # own host, dead pid, past grace
    assert os.path.exists(young)         # young: could be in flight
    assert os.path.exists(mine)          # own pid: kept
    assert os.path.exists(foreign)       # foreign host, inside ceiling
    assert not os.path.exists(foreign_old)  # foreign but ancient: debris
    assert os.path.exists(legacy)        # origin unknowable: ceiling only


def test_sync_plan_agrees_max_steps_min_epoch(tiny_shards):
    spec = _spec(tiny_shards, 2)
    coord = Coordinator(spec)
    coord.register("a", 0, host="127.0.0.1", jax_port=1234)
    coord.register("b", 1, host="127.0.0.1")

    results = {}

    def call(wid, plan):
        results[wid] = coord.sync_plan(wid, plan, timeout_s=10.0)

    t = threading.Thread(
        target=call,
        args=("a", {"train_steps": 5, "valid_steps": 1, "ckpt_epoch": 3}),
    )
    t.start()
    time.sleep(0.1)
    call("b", {"train_steps": 8, "valid_steps": 2, "ckpt_epoch": 2})
    t.join(timeout=5)
    for wid in ("a", "b"):
        assert results[wid]["ok"]
        assert results[wid]["train_steps"] == 8
        assert results[wid]["valid_steps"] == 2
        assert results[wid]["ckpt_epoch"] == 2
    coord.shutdown()


def test_await_start_carries_cluster_info(tiny_shards):
    spec = _spec(tiny_shards, 2)
    coord = Coordinator(spec)
    coord.register("a", 0, host="10.0.0.5", jax_port=4321)
    coord.register("b", 1, host="10.0.0.6", jax_port=9999)
    reply = coord.await_start(timeout_s=5.0)
    assert reply["ok"]
    cluster = reply["cluster"]
    assert cluster["chief_host"] == "10.0.0.5"
    assert cluster["jax_port"] == 4321  # the chief's port, not a peer's
    assert cluster["n_workers"] == 2
    coord.shutdown()


def test_fleet_restart_state_machine(tiny_shards):
    spec = _spec(tiny_shards, 2, spare_restarts=1)
    coord = Coordinator(spec)
    r0 = coord.register("a", 0)
    coord.register("b", 1)
    assert coord.state == JobState.TRAINING
    assert r0["generation"] == 0

    # any worker failing (chief included) bumps the generation
    coord.complete("a", 1)
    assert coord.generation == 1
    assert coord.state == JobState.REGISTERING
    assert coord._failed_restarts == 1

    # the peer's cascade exit must not consume budget
    coord.complete("b", 1)
    assert coord._failed_restarts == 1
    assert coord.state == JobState.REGISTERING

    # sticky re-registration into the new generation restarts training
    ra = coord.register("a", 0)
    assert ra["ok"] and ra["generation"] == 1
    coord.register("b", 1)
    assert coord.state == JobState.TRAINING

    # budget exhausted -> job fails
    coord.complete("b", 1)
    assert coord.state == JobState.FAILED
    coord.shutdown()


def test_submitter_rejects_spmd_threads(tiny_shards):
    spec = _spec(tiny_shards, 2)
    with pytest.raises(ValueError, match="process"):
        JobSubmitter(spec, lambda wid, addr: None, launcher="thread")


@pytest.fixture()
def tiny_shards(psv_dataset):
    return split_training_data(psv_dataset["root"], 2)


# --------------------------------------------------------- subprocess level


def _worker_cfg_factory(psv_dataset, mc, ckpt_dir, **extra):
    schema = _schema(psv_dataset)

    def make_cfg(worker_id: str, addr) -> WorkerConfig:
        return WorkerConfig(
            worker_id=worker_id,
            coordinator_host=addr[0],
            coordinator_port=addr[1],
            model_config=mc,
            schema=schema,
            batch_size=32,
            checkpoint_dir=ckpt_dir,
            heartbeat_interval_s=0.2,
            seed=0,
            spmd=True,
            **extra,
        )

    return make_cfg


def _emulate_single_process(psv_dataset, mc, shards, batch_size=32):
    """Single-device training on the union of shards with the exact global
    batches the SPMD fleet sees: per-shard fixed-step batches concatenated
    in worker order."""
    schema = _schema(psv_dataset)
    datasets = [
        InMemoryDataset.load(list(s.paths), schema, mc.valid_set_rate, salt=0)
        for s in shards
    ]
    steps = max(d.steps_per_epoch(batch_size) for d in datasets)
    valid_steps = max(d.valid_steps(batch_size) for d in datasets)
    nf = schema.num_features

    def make_train(epoch):
        its = [
            fixed_step_batches(
                d.train_batches(batch_size, epoch=epoch), batch_size, steps, nf
            )
            for d in datasets
        ]
        for parts in zip(*its):
            yield {
                k: np.concatenate([p[k] for p in parts]) for k in parts[0]
            }

    def make_valid():
        its = [
            fixed_step_batches(
                d.valid_batches(batch_size), batch_size, valid_steps, nf
            )
            for d in datasets
        ]
        for parts in zip(*its):
            yield {
                k: np.concatenate([p[k] for p in parts]) for k in parts[0]
            }

    trainer = make_trainer(
        mc, nf, feature_columns=schema.feature_columns, seed=0
    )
    trainer.fit_stream(
        make_train, make_valid, epochs=mc.num_train_epochs
    )
    return trainer


def test_spmd_two_processes_train_one_model(psv_dataset, tmp_path):
    """2 worker processes over jax.distributed == 1 process on the union of
    shards (same global batches), to float tolerance."""
    mc = _model_config(epochs=2)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    spec = _spec(shards, 2, epochs=2)
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.restarts_used == 0

    # reference: single-device run over the same global batch sequence
    ref = _emulate_single_process(psv_dataset, mc, shards)

    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == 1  # chief saved every epoch
    restored, _ = ckpt.restore_latest(ref.state)
    import jax

    ref_leaves = jax.tree_util.tree_leaves(ref.state.params)
    got_leaves = jax.tree_util.tree_leaves(restored.params)
    assert len(ref_leaves) == len(got_leaves)
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-5
        )


def test_spmd_sigkill_recovers_via_fleet_restart(psv_dataset, tmp_path):
    """SIGKILL one worker after its first epoch report: the coordinator
    expires it, bumps the generation, the submitter kills + relaunches the
    fleet, workers resume from the agreed checkpoint, and the job finishes
    within the restart budget."""
    mc = _model_config(epochs=3)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    spec = _spec(
        shards, 2, epochs=3,
        spare_restarts=1,
        heartbeat_interval_ms=200,
        max_missed_heartbeats=5,
    )
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        kill_injections={"worker-1": 0},
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.restarts_used == 1
    # the final model exists and covers the full epoch budget
    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == 2


def test_spmd_sigkill_keep_best_survives_fleet_restart(psv_dataset, tmp_path):
    """SIGKILL recovery with keep-best on: the chief's persisted best
    snapshot (keep-best.npz) must survive the fleet restart — the
    relaunched generation competes against the TRUE best, and the final
    snapshot's metric can never be worse than any pre-crash epoch's."""
    mc = _model_config(epochs=3)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    schema = _schema(psv_dataset)
    # DISCRIMINATOR: pre-seed the snapshot with an unbeatable metric.  If
    # the chief restores it at every (re)launch — including the relaunch
    # whose sync_plan agrees ckpt_epoch=-1 — no real epoch can improve on
    # it and the file survives both generations untouched.  If the
    # restore is broken, the race restarts and the first real epoch
    # OVERWRITES it with its own (lower) KS: the assertions below fail.
    os.makedirs(ckpt_dir, exist_ok=True)
    seed_trainer = make_trainer(mc, schema.num_features,
                                feature_columns=schema.feature_columns,
                                keep_best="ks")
    import jax

    seed_trainer.best_metric = 0.999
    seed_trainer.best_epoch = 0
    seed_trainer.best_params = jax.device_get(seed_trainer.state.params)
    seed_trainer._persist_best(ckpt_dir)
    seed_kernel = np.asarray(
        seed_trainer.best_params["shifu_output_0"]["kernel"]
    )

    spec = _spec(
        shards, 2, epochs=3,
        spare_restarts=1,
        heartbeat_interval_ms=200,
        max_missed_heartbeats=5,
    )
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir, keep_best="ks"),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        kill_injections={"worker-1": 0},
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.restarts_used == 1
    best_file = os.path.join(ckpt_dir, "keep-best.npz")
    import json as _json

    data = np.load(best_file)
    meta = _json.loads(bytes(data["__meta__"]).decode())
    assert meta["keep_best"] == "ks"
    assert meta["metric"] == 0.999, (
        "a real epoch overwrote the seeded best: the (re)launch restore "
        f"lost the race state ({meta})"
    )
    # and the snapshot the fleet export would restore is byte-identical
    # to the seeded one
    t = make_trainer(mc, schema.num_features,
                     feature_columns=schema.feature_columns,
                     keep_best="ks")
    t._restore_best(ckpt_dir)
    np.testing.assert_array_equal(
        np.asarray(t.best_params["shifu_output_0"]["kernel"]), seed_kernel
    )


def test_spmd_streaming_sigkill_during_cold_cache_build(psv_dataset, tmp_path):
    """SIGKILL a worker while the fleet is streaming its FIRST epoch — the
    cold pass that parses text shards and writes binary cache entries.
    Recovery must (a) not trip over half-written cache temp files (atomic
    commit: aborted entries are invisible), and (b) finish with the full
    epoch budget from the shared checkpoint."""
    mc = _model_config(epochs=3)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    cache_dir = str(tmp_path / "cache")
    spec = _spec(
        shards, 2, epochs=3,
        spare_restarts=1,
        heartbeat_interval_ms=200,
        max_missed_heartbeats=5,
    )
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(
            psv_dataset, mc, ckpt_dir,
            stream=True, cache_dir=cache_dir,
        ),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        kill_injections={"worker-1": 0},
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.restarts_used == 1
    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == 2
    # the relaunched fleet streams warm where entries committed; whatever
    # was mid-write at kill time must not have produced a visible entry
    # without its meta (lookup-able implies complete)
    import os

    names = os.listdir(cache_dir)
    keys_with_meta = {n[: -len(".meta.json")] for n in names
                      if n.endswith(".meta.json")}
    assert keys_with_meta, "warm epochs should have committed cache entries"
    for k in keys_with_meta:
        # a published meta implies its slabs exist (commit renames slabs
        # FIRST, meta last) — a kill can orphan slabs, never a meta
        assert any(n.startswith(f"{k}.x.") for n in names), k
        assert f"{k}.y.f32" in names and f"{k}.w.f32" in names, k


def test_spmd_trains_sequence_family(psv_dataset, tmp_path):
    """The sequence model family composes with cross-process SPMD: a
    2-process fleet trains ONE transformer over jax.distributed and
    checkpoints it (attention=auto resolves to full on the data-only
    mesh; seq-axis sharding is a single-controller mesh concern)."""
    mc = _model_config(
        1, LearningRate=0.01, ModelType="sequence",
        SeqLen=5, SeqDModel=16, SeqHeads=4, SeqBlocks=1,
    )
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "seq-ckpt")
    spec = _spec(shards, 2, epochs=1)
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == 0


def test_spmd_sigkill_recovery_with_async_checkpointing(psv_dataset, tmp_path):
    """Same SIGKILL drill with shifu.tpu.async-checkpoint on: background
    writes must leave either a complete published checkpoint or nothing —
    a crash mid-write must not corrupt what the restarted fleet restores."""
    mc = _model_config(epochs=3)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "ckpt")
    spec = _spec(
        shards, 2, epochs=3,
        spare_restarts=1,
        heartbeat_interval_ms=200,
        max_missed_heartbeats=5,
    )
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir,
                            async_checkpoint=True),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
        kill_injections={"worker-1": 0},
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.restarts_used == 1
    # atomic publish: only complete published checkpoints are ever visible
    # to restore (kill-mid-write debris, if any, is .tmp.* the reader
    # never parses; the age-gated sweep collects it later — see
    # test_npz_checkpointer_sweeps_dead_writer_tmp)
    ckpt = NpzCheckpointer(ckpt_dir)
    assert ckpt.latest_epoch() == 2


def test_spmd_scan_steps_matches_per_step_fleet(psv_dataset, tmp_path):
    """Cross-process chunked scan: a 2-process fleet with scan_steps=2
    (stacked (S, B_local, F) chunks through put_process_local) must match
    the single-process per-step emulation — the scan path's only
    semantic difference is dispatch granularity, even across processes."""
    mc = _model_config(epochs=2)
    shards = split_training_data(psv_dataset["root"], 2)
    ckpt_dir = str(tmp_path / "scan-ckpt")
    spec = _spec(shards, 2, epochs=2)
    submitter = JobSubmitter(
        spec,
        _worker_cfg_factory(psv_dataset, mc, ckpt_dir, scan_steps=2),
        launcher="process",
        worker_env=WORKER_ENV,
        log_dir=str(tmp_path / "logs"),
    )
    result = submitter.run(timeout_s=300.0)
    assert result.state == JobState.FINISHED, result.failure_reason

    ref = _emulate_single_process(psv_dataset, mc, shards)
    ckpt = NpzCheckpointer(ckpt_dir)
    restored, _ = ckpt.restore_latest(ref.state)
    import jax

    for r, g in zip(
        jax.tree_util.tree_leaves(ref.state.params),
        jax.tree_util.tree_leaves(restored.params),
    ):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-5
        )
