"""Elastic-fleet tests — hot-standby promotion, deterministic membership
re-split, and the SLO-driven serve autoscaler policy (ISSUE 15 /
ROADMAP item 3).

Coordinator-level units drive the promotion state machine directly (no
processes); the end-to-end leg runs a thread-launcher fleet with a real
standby worker through an injected failure; the autoscaler policy is
pure (injectable clock) and unit-tested for hysteresis, cooldown,
rebalance-before-scale ordering, and empty-window neutrality.  The
process-fleet kill drill is ``python scripts/bench_elastic.py``.
"""

import json
import threading
import time

import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.coordinator.coordinator import (
    Coordinator,
    JobSpec,
    JobState,
)
from shifu_tensorflow_tpu.coordinator.submitter import (
    JobSubmitter,
    make_job_spec,
)
from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.data.splitter import Shard, split_size_aware
from shifu_tensorflow_tpu.serve.autoscale import (
    AutoscaleConfig,
    AutoscalePolicy,
    JournalSignals,
    TickObservation,
)


def _spec(n=2, **kw):
    shards = [Shard(i, (f"/data/part-{i}",), 1) for i in range(n)]
    kw.setdefault("registration_timeout_s", 5.0)
    if kw.get("elastic"):
        # the coordinator validates the invariant elastic_spec_kwargs
        # enforces: elastic directives ride the per-epoch barrier
        kw.setdefault("sync_epochs", True)
    return JobSpec(n_workers=n, shards=shards, epochs=2, **kw)


# ---- standby registration + promotion (coordinator units) ----

def test_standby_registers_rankless_and_outside_quorum():
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    r = coord.register("sb0", role="standby")
    assert r["ok"] and r["role"] == "standby" and r["worker_index"] == -1
    # a standby never completes the start quorum
    assert coord.state == JobState.REGISTERING
    coord.register("w1", 1)
    assert coord.state == JobState.TRAINING
    st = coord.status()
    assert st["standbys"] == 1 and st["promotions"] == 0
    # re-registration is sticky, not a second pool slot
    coord.register("sb0", role="standby")
    assert coord.status()["standbys"] == 1


def test_promotion_takes_rank_shard_generation_without_budget():
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.complete("w1", 1)  # worker-1 dies
    st = coord.status()
    assert st["promotions"] == 1
    assert st["restarts_used"] == 0  # promotion is budget-free
    assert st["standbys"] == 0
    assert coord.active_worker_ids() == {0: "w0", 1: "sb0"}
    rec = coord.workers["sb0"]
    assert rec.worker_index == 1
    assert rec.shard_paths == ("/data/part-1",)  # sticky shard
    assert rec.role == "worker"
    # the dead identity is gone; the submitter must not relaunch it
    assert "w1" not in coord.workers
    assert coord.restartable_workers() == []
    # promotion history rides diagnostics, roles included
    d = coord.diagnostics()
    assert d["workers"]["sb0"]["role"] == "worker"
    p = d["promotions"][0]
    assert p["worker_index"] == 1 and p["standby_id"] == "sb0"
    assert p["old_id"] == "w1" and p["claim_latency_s"] is None


def test_standby_wait_longpoll_returns_promotion_and_claims():
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    # unpromoted poll times out promoted=False
    r = coord.standby_wait("sb0", timeout_s=0.05)
    assert r["ok"] and not r["promoted"]
    out = {}

    def wait():
        out["r"] = coord.standby_wait("sb0", timeout_s=10.0)

    t = threading.Thread(target=wait)
    t.start()
    time.sleep(0.1)
    coord.complete("w1", 1)
    t.join(timeout=5.0)
    r = out["r"]
    assert r["promoted"] and r["worker_index"] == 1
    assert r["shard"] == ["/data/part-1"]
    assert r["generation"] == 0 and "health" in r
    # the claim stamped the takeover latency into the history
    assert coord.diagnostics()["promotions"][0]["claim_latency_s"] is not None


def test_promotion_skips_expired_standby_and_picks_freshest():
    """Satellite fix: a standby the liveness monitor has written off must
    not be promoted while expired — the choice lands on the freshest
    heartbeat and the journal records who was skipped."""
    coord = Coordinator(_spec(2, standby_workers=2))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.register("sb1", role="standby")
    # sb0 expired; sb1 beats more recently than sb0 ever did
    with coord.liveness._lock:
        coord.liveness._expired.add("sb0")
        coord.liveness._last["sb1"] = coord.liveness._clock()
    coord.complete("w1", 1)
    assert coord.active_worker_ids()[1] == "sb1"
    assert coord.status()["standbys"] == 1  # sb0 still pooled
    # flap recovery: sb0 beats again -> eligible for the NEXT failure
    coord.liveness.beat("sb0")
    coord.complete("w0", 1)  # chief dies; sb0 promotes into rank 0
    assert coord.state == JobState.TRAINING
    assert coord.active_worker_ids()[0] == "sb0"


def test_all_standbys_expired_falls_back_to_restart_budget():
    coord = Coordinator(_spec(2, standby_workers=1, spare_restarts=2))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    with coord.liveness._lock:
        coord.liveness._expired.add("sb0")
    coord.complete("w1", 1)
    st = coord.status()
    # no promotion happened; the classic relaunch path charged budget
    assert st["promotions"] == 0
    assert st["restarts_used"] == 1
    assert [r.worker_id for r in coord.restartable_workers()] == ["w1"]


def test_chief_failure_without_standby_still_short_circuits():
    coord = Coordinator(_spec(2))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.complete("w0", 1)
    assert coord.state == JobState.FAILED
    assert "chief" in coord.failure_reason


def test_spmd_promotion_substitutes_uncharged_then_exhausts():
    """SPMD: first failure consumes the standby (uncharged generation
    bump, sticky rank/shard); second failure — pool empty — falls back
    to the charged PR-2 fleet restart."""
    coord = Coordinator(_spec(2, spmd=True, standby_workers=1,
                              spare_restarts=1))
    coord.register("w0", 0, host="127.0.0.1", jax_port=1)
    coord.register("w1", 1, host="127.0.0.1", jax_port=2)
    coord.register("sb0", role="standby")
    coord.complete("w1", 1)
    st = coord.status()
    assert st["generation"] == 1
    assert st["restarts_used"] == 0  # standby paid, not the budget
    assert st["promotions"] == 1
    assert coord.active_worker_ids() == {0: "w0", 1: "sb0"}
    # fleet re-registers into generation 1 (the submitter relaunched it
    # by the identity map), then the chief dies: charged restart
    coord.register("w0", 0, host="127.0.0.1", jax_port=1)
    coord.register("sb0", 1, host="127.0.0.1", jax_port=3)
    coord.complete("w0", 1)
    st = coord.status()
    assert st["generation"] == 2 and st["restarts_used"] == 1


def test_promotion_register_reply_is_sticky_for_promoted_standby():
    """A promoted standby re-registering (relaunch, SPMD generation
    bump) must route through the sticky worker path, not the standby
    pool."""
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.complete("w1", 1)
    r = coord.register("sb0")  # promoted: plain worker registration
    assert r["ok"] and r["worker_index"] == 1
    assert r["shard"] == ["/data/part-1"]
    # and registering it as a standby again is refused with a clear error
    r = coord.register("sb0", role="standby")
    assert not r["ok"] and "promoted" in r["error"]


def test_standby_exit_shrinks_pool_without_failing_any_rank():
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.complete("sb0", 1)  # standby crashes
    st = coord.status()
    assert st["standbys"] == 0
    assert st["restarts_used"] == 0
    assert coord.state == JobState.TRAINING


# ---- elastic membership re-split ----

def test_budget_exhaustion_shrinks_elastic_fleet_deterministically():
    coord = Coordinator(_spec(3, elastic=True, spare_restarts=0))
    for i in range(3):
        coord.register(f"w{i}", i)
    # budget = floor(0.1*3) + 0 = 0: the first failure exhausts it
    coord.complete("w2", 1)
    st = coord.status()
    assert coord.state == JobState.TRAINING
    assert st["active_workers"] == [0, 1]
    assert st["split_generation"] == 1
    # the re-split IS split_size_aware over the union of paths — a pure
    # function of paths x n_workers, so any observer can recompute it
    paths = sorted(f"/data/part-{i}" for i in range(3))
    expect = {s.worker_index: tuple(s.paths)
              for s in split_size_aware(paths, 2)}
    got = {r.worker_index: r.shard_paths for r in coord.workers.values()}
    assert got == {i: expect[k] for k, i in zip(sorted(expect), [0, 1])}
    # the epoch barrier completes on the survivor quorum and delivers
    # the new shard to a worker still echoing the old split generation
    coord.report_epoch(_stats(0, 0).__dict__)
    coord.report_epoch(_stats(1, 0).__dict__)
    resp = coord.epoch_barrier("w0", 0, timeout_s=1.0,
                               split_generation=0)
    assert resp["ok"]
    assert resp["resplit"]["split_generation"] == 1
    assert resp["resplit"]["shard"] == list(got[0])
    # once the worker echoes the new generation, no directive rides
    resp = coord.epoch_barrier("w0", 0, timeout_s=1.0,
                               split_generation=1)
    assert resp["ok"] and "resplit" not in resp


def test_budget_exhaustion_without_elastic_still_fails():
    coord = Coordinator(_spec(3, spare_restarts=0))
    for i in range(3):
        coord.register(f"w{i}", i)
    coord.complete("w2", 1)
    assert coord.state == JobState.FAILED
    assert "exhausted" in coord.failure_reason


def test_resize_shrink_releases_ranks_and_grow_adds_pending():
    coord = Coordinator(_spec(3, elastic=True))
    for i in range(3):
        coord.register(f"w{i}", i)
    # shrink 3 -> 2: rank 2 released cooperatively at its next barrier
    r = coord.resize(2)
    assert r["ok"] and r["ranks"] == [0, 1]
    resp = coord.epoch_barrier("w2", 0, timeout_s=1.0)
    assert resp.get("released")
    # the release is NOT consumed on delivery: a lost reply redelivers
    # at the retry (this op carries no dedup token)
    resp = coord.epoch_barrier("w2", 0, timeout_s=1.0)
    assert resp.get("released")
    # growing past the data-file count is a clean refusal
    r = coord.resize(4)
    assert not r["ok"] and "data file" in r["error"]
    # grow 2 -> 3: the refilled HOLE (rank 2, shrunk above) pends until
    # the submitter launches a worker for it
    r = coord.resize(3)
    assert r["ok"] and len(r["ranks"]) == 3
    new_idx = coord.pending_indices()[0]
    assert new_idx == 2  # holes refill first
    # a worker registering into the grown rank gets the shard the
    # RE-SPLIT computed for it (never a stale spec.shards entry, which
    # for ranks past the original width does not even exist)
    reg = coord.register("grown", new_idx)
    assert reg["ok"] and reg["worker_index"] == new_idx
    paths = sorted(f"/data/part-{i}" for i in range(3))
    expect = {i: tuple(s.paths)
              for i, s in enumerate(split_size_aware(paths, 3))}
    got = {r2.worker_index: r2.shard_paths
           for r2 in coord.workers.values()}
    assert got == {idx: expect[k]
                   for k, idx in zip(sorted(expect), sorted(got))}
    assert reg["shard"] == list(got[new_idx])
    # resize needs the elastic opt-in
    plain = Coordinator(_spec(2))
    plain.register("a", 0)
    assert not plain.resize(1)["ok"]


def test_regrown_rank_reusing_released_worker_id_is_not_released():
    """A rank shrunk away and grown back relaunches under its ORIGINAL
    worker id (the submitter derives ids from rank indices): the stale
    release directive must die at re-registration, or the new process is
    told 'released' at its first barrier, exits 0, and the rank wedges
    the surviving quorum forever."""
    coord = Coordinator(_spec(3, elastic=True))
    for i in range(3):
        coord.register(f"w{i}", i)
    coord.resize(2)
    assert coord.epoch_barrier("w2", 0, timeout_s=1.0).get("released")
    coord.resize(3)
    # the submitter refills rank 2 under the same id
    reg = coord.register("w2", coord.pending_indices()[0])
    assert reg["ok"] and reg["worker_index"] == 2
    for i in range(3):
        coord.report_epoch(_stats(i, 0).__dict__)
    resp = coord.epoch_barrier("w2", 0, timeout_s=1.0)
    assert resp["ok"] and not resp.get("released")


def test_promoted_over_flapper_is_released_at_next_barrier():
    """The 'dead' rank's old process may only be FLAPPED (GC pause,
    partition), not dead: if it wakes after the standby took over, its
    next epoch barrier must hand it the cooperative-exit directive —
    otherwise two live processes train the same rank's shard."""
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.complete("w1", 1)  # promotion consumes the standby
    assert coord.status()["promotions"] == 1
    resp = coord.epoch_barrier("w1", 0, timeout_s=1.0)
    assert resp.get("released")
    # NOT consumed on delivery: a lost reply must redeliver at retry
    resp = coord.epoch_barrier("w1", 0, timeout_s=1.0)
    assert resp.get("released")
    # the promoted standby itself keeps training under its own id
    assert "sb0" not in coord._released_ids


def test_shrunk_away_flapper_is_released_at_next_barrier():
    """Same flap hazard on the elastic-shrink path: a worker the
    re-split wrote off must exit at its next barrier instead of
    training rows the survivors now own."""
    coord = Coordinator(_spec(3, elastic=True, spare_restarts=0))
    for i in range(3):
        coord.register(f"w{i}", i)
    coord.complete("w2", 1)  # budget 0 + no standby -> shrink
    assert coord.status()["active_workers"] == [0, 1]
    resp = coord.epoch_barrier("w2", 0, timeout_s=1.0)
    assert resp.get("released")


def test_release_directive_rides_the_heartbeat_reply():
    """sync_epochs can be off outside the elastic path, so the barrier
    is not a guaranteed delivery channel: the heartbeat — which EVERY
    worker polls — must carry the release too, or a flapped-then-
    promoted-over worker trains its old shard in duplicate forever."""
    coord = Coordinator(_spec(2, standby_workers=1))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    coord.complete("w1", 1)  # standby promoted into rank 1
    assert coord.heartbeat("w1").get("released")
    assert not coord.heartbeat("w0").get("released")
    assert not coord.heartbeat("sb0").get("released")


def test_shrink_refused_without_data_paths_fails_instead_of_wedging():
    """Placeholder/in-memory shards have no data paths: split_size_aware
    over an empty union would raise AFTER the membership mutation inside
    the liveness callback, leaving the job half-shrunk (dead rank gone
    from workers but still in the barrier quorum).  The shrink must
    refuse up front and fall through to the normal failure policy."""
    spec = JobSpec(n_workers=2, shards=[None, None], epochs=2,
                   elastic=True, sync_epochs=True, spare_restarts=0,
                   registration_timeout_s=5.0)
    coord = Coordinator(spec)
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.complete("w1", 1)  # budget 0, no paths -> shrink refused
    assert coord.state == JobState.FAILED
    # no half-mutation: the failed job still accounts both ranks
    assert sorted(coord._active_indices) == [0, 1]


def test_resize_shrink_refused_without_data_paths_before_mutation():
    """resize() shrink must validate the path count BEFORE the drop loop
    mutates membership — split_size_aware raising mid-mutation would
    leave released workers still in the barrier quorum."""
    spec = JobSpec(n_workers=3, shards=[None, None, None], epochs=2,
                   elastic=True, sync_epochs=True,
                   registration_timeout_s=5.0)
    coord = Coordinator(spec)
    for i in range(3):
        coord.register(f"w{i}", i)
    r = coord.resize(2)
    assert not r["ok"] and "data file" in r["error"]
    # nothing was mutated by the refusal
    assert sorted(coord._active_indices) == [0, 1, 2]
    assert coord._released_ids == set()
    assert set(coord.workers) == {"w0", "w1", "w2"}


def test_policy_read_error_tick_is_fully_neutral():
    """An unreadable journal proves nothing: it must not reset the
    breach debounce, accrue recovery credit, or ever drive a decision —
    six blips in a row must not shrink a breached fleet."""
    clock = [100.0]
    p = AutoscalePolicy(AutoscaleConfig(workers_min=1, workers_max=3,
                                        ticks=2, recovery_ticks=2,
                                        cooldown_s=0.0),
                        clock=lambda: clock[0])
    breach = TickObservation(new_events=1, breached={"serve_p99_s"})
    assert p.observe(breach, 2) is None  # tick 1 of 2
    # a read-error tick holds the debounce still ...
    for _ in range(6):
        assert p.observe(TickObservation(read_error=True), 2) is None
    # ... so the next breached tick completes it
    d = p.observe(breach, 2)
    assert d is not None and d.action == "scale_up"
    # and read errors never accrue recovery credit toward scale_down
    p2 = AutoscalePolicy(AutoscaleConfig(workers_min=1, workers_max=3,
                                         ticks=2, recovery_ticks=2,
                                         cooldown_s=0.0),
                         clock=lambda: clock[0])
    p2.observe(TickObservation(new_events=1), 2)  # journal proven wired
    for _ in range(6):
        assert p2.observe(TickObservation(read_error=True), 2) is None
    assert p2._clean_ticks <= 1


def _stats(worker, epoch, loss=0.5):
    from shifu_tensorflow_tpu.train.trainer import EpochStats

    return EpochStats(
        worker_index=worker, current_epoch=epoch, training_loss=loss,
        valid_loss=loss, training_time_s=1.0, valid_time_s=0.1,
        global_step=epoch + 1,
    )


def test_coordinator_metrics_export_standby_and_budget_gauges():
    coord = Coordinator(_spec(2, standby_workers=1, spare_restarts=3))
    coord.register("w0", 0)
    coord.register("w1", 1)
    coord.register("sb0", role="standby")
    text = coord.metrics_text()
    assert "stpu_coord_standby_registered 1" in text
    assert "stpu_coord_standby_available 1" in text
    assert f"stpu_coord_restart_budget_remaining {coord.max_restarts}" \
        in text
    assert "stpu_coord_restart_budget_burn_window 0" in text
    coord.complete("w1", 1)  # promotion: still no budget burn
    text = coord.metrics_text()
    assert "stpu_coord_standby_promotions_total 1" in text
    assert f"stpu_coord_restart_budget_remaining {coord.max_restarts}" \
        in text
    coord.complete("sb0", 1)  # no standby left: budget burns
    text = coord.metrics_text()
    assert ("stpu_coord_restart_budget_remaining "
            f"{coord.max_restarts - 1}") in text
    assert "stpu_coord_restart_budget_burn_window 1" in text


# ---- worker-side resplit application ----

def test_shard_state_applies_resplit_and_release_raises():
    from shifu_tensorflow_tpu.coordinator.worker import (
        _epoch_callback,
        _Released,
        _ShardState,
    )

    shard_state = _ShardState(["/d/a"])

    class FakeHb:
        abort = threading.Event()
        restart = threading.Event()
        released = threading.Event()

    class FakeClient:
        def __init__(self):
            self.replies = []
            self.barrier_calls = []

        def report_epoch(self, stats):
            return {"ok": True}

        def epoch_barrier(self, wid, epoch, split_generation=None):
            self.barrier_calls.append(split_generation)
            return self.replies.pop(0)

    cfg = type("C", (), {"worker_id": "w1"})()
    client = FakeClient()
    cb = _epoch_callback(cfg, client, FakeHb(), sync_epochs=True,
                         fail_at_epoch=None, shard_state=shard_state)
    client.replies.append({"ok": True, "resplit": {
        "shard": ["/d/a", "/d/b"], "split_generation": 2,
        "n_workers": 2}})
    cb(_stats(1, 0))
    assert shard_state.paths == ["/d/a", "/d/b"]
    assert shard_state.split_generation == 2
    # the NEXT barrier echoes the applied generation
    client.replies.append({"ok": True})
    cb(_stats(1, 1))
    assert client.barrier_calls == [0, 2]
    # a released reply raises the cooperative-exit signal
    client.replies.append({"ok": True, "released": True})
    with pytest.raises(_Released):
        cb(_stats(1, 2))


# ---- end-to-end: thread fleet with a real standby takeover ----

def _worker_config_factory(psv_dataset, model_config, tmp_path):
    schema = RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )

    def make(worker_id, addr):
        return WorkerConfig(
            worker_id=worker_id,
            coordinator_host=addr[0],
            coordinator_port=addr[1],
            model_config=model_config,
            schema=schema,
            batch_size=100,
            checkpoint_dir=str(tmp_path / "job-ckpt"),
            heartbeat_interval_s=0.1,
        )

    return make


@pytest.fixture
def job_model_config():
    return ModelConfig.from_json(
        {"train": {"numTrainEpochs": 2, "validSetRate": 0.2,
                   "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.05,
                              "Optimizer": "adam"}}}
    )


def test_standby_takeover_end_to_end_thread_fleet(
    psv_dataset, tmp_path, job_model_config
):
    """A non-chief worker dies mid-job with ZERO restart budget; the
    hot standby — registered, pre-built, warm — takes the rank over and
    the job finishes without a single budgeted relaunch.  sync_epochs
    holds the chief at the barrier until the promoted rank catches up,
    so the takeover is provably on the critical path."""
    spec = make_job_spec(psv_dataset["root"], 2, epochs=2,
                         registration_timeout_s=10.0, spare_restarts=0,
                         sync_epochs=True, epoch_barrier_timeout_s=60.0,
                         standby_workers=1)
    # budget floor(0.1*2)+0 = 0: without the standby this kill is fatal
    sub = JobSubmitter(
        spec,
        _worker_config_factory(psv_dataset, job_model_config, tmp_path),
        fault_injections={"worker-1": 0},  # dies at epoch 0
    )
    result = sub.run(timeout_s=180.0)
    assert result.state == JobState.FINISHED, result.failure_reason
    assert result.promotions_used == 1
    assert result.restarts_used == 0
    # every epoch reached full (2-worker) quorum: the promoted rank
    # re-reported the epochs the dead rank owed the barrier
    assert [s.epoch for s in result.epoch_summaries] == [0, 1]
    assert all(s.n_workers == 2 for s in result.epoch_summaries)


def test_no_standby_same_fault_fails_the_job(
    psv_dataset, tmp_path, job_model_config
):
    """Control arm for the takeover test: identical fleet and fault,
    zero budget, no standby — the job dies.  Pinned so the e2e test
    above cannot silently pass for the wrong reason."""
    spec = make_job_spec(psv_dataset["root"], 2, epochs=2,
                         registration_timeout_s=10.0, spare_restarts=0,
                         sync_epochs=True, epoch_barrier_timeout_s=60.0)
    sub = JobSubmitter(
        spec,
        _worker_config_factory(psv_dataset, job_model_config, tmp_path),
        fault_injections={"worker-1": 0},
    )
    result = sub.run(timeout_s=180.0)
    assert result.state == JobState.FAILED
    assert "exhausted" in (result.failure_reason or "")


# ---- autoscaler policy (pure units) ----

def _cfg(**kw):
    kw.setdefault("workers_min", 1)
    kw.setdefault("workers_max", 3)
    kw.setdefault("ticks", 2)
    kw.setdefault("recovery_ticks", 3)
    kw.setdefault("cooldown_s", 10.0)
    return AutoscaleConfig(**kw)


def _obs(events=1, breached=(), sheds=None, tenants=0):
    return TickObservation(
        new_events=events, breached=set(breached),
        sheds_by_model=dict(sheds or {}), tenants_seen=tenants,
    )


def test_policy_hysteresis_then_scale_up_then_cooldown():
    clock = [0.0]
    p = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    breach = _obs(breached={"serve_p99_s"})
    assert p.observe(breach, 1) is None  # tick 1 < hysteresis 2
    d = p.observe(breach, 1)
    assert d is not None and d.action == "scale_up"
    assert "serve_p99_s" in d.reason
    clock[0] += 5.0  # inside cooldown
    assert p.observe(breach, 2) is None
    clock[0] += 10.0  # cooldown over; breach held through it
    assert p.observe(breach, 2).action == "scale_up"
    clock[0] += 20.0
    # at the ceiling the policy never acts, however long the breach
    assert all(p.observe(breach, 3) is None for _ in range(5))


def test_policy_recovery_shrinks_lazily_and_respects_floor():
    clock = [100.0]
    p = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    clean = _obs()
    assert p.observe(clean, 3) is None
    assert p.observe(clean, 3) is None
    d = p.observe(clean, 3)  # 3rd clean tick = recovery_ticks
    assert d is not None and d.action == "scale_down"
    clock[0] += 20.0
    # at the floor: no shrink no matter how clean
    assert all(p.observe(clean, 1) is None for _ in range(6))


def test_policy_empty_window_discipline():
    """Empty-window rules: (1) a latched breach whose writer went
    QUIET is a dead worker, never fresh overload evidence — no
    scale_up; (2) before the journal has produced any event at all the
    policy is inert — no blind shrink; (3) a quiet UN-breached fleet
    accrues recovery credit (traffic going away entirely IS recovery,
    the slo watchdog's drained-window rule)."""
    clock = [0.0]
    # (2) pristine policy, journal never speaks: inert forever
    p0 = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    for _ in range(20):
        assert p0.observe(TickObservation(), 3) is None
    # (1) breach latches, then the writer dies (no new events): the
    # stale breach must not scale anything up
    p1 = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    assert p1.observe(_obs(breached={"serve_p99_s"}), 1) is None
    dead = TickObservation(breached={"serve_p99_s"})
    for _ in range(10):
        assert p1.observe(dead, 1) is None
    # (3) recovered then quiet: empty un-breached ticks count toward
    # the shrink
    p2 = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    assert p2.observe(_obs(), 2) is None  # one real event proves wiring
    assert p2.observe(TickObservation(), 2) is None
    d = p2.observe(TickObservation(), 2)  # 3rd clean tick
    assert d is not None and d.action == "scale_down"


def test_policy_rebalances_dominant_tenant_before_scaling():
    clock = [0.0]
    p = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    hot = _obs(breached={"serve_p99_s:beta"},
               sheds={"alpha": 100, "beta": 2}, tenants=2)
    assert p.observe(hot, 1) is None
    d = p.observe(hot, 1)
    assert d.action == "rebalance" and d.model == "alpha"
    assert d.weight == pytest.approx(0.5)
    assert p.weight_overrides == {"alpha": 0.5}
    # breach persists: weight halves again after cooldown
    clock[0] += 20.0
    hot2 = _obs(breached={"serve_p99_s:beta"},
                sheds={"alpha": 220, "beta": 3}, tenants=2)
    assert p.observe(hot2, 1) is None
    d = p.observe(hot2, 1)
    assert d.action == "rebalance" and d.weight == pytest.approx(0.25)
    # floored: capacity is the remaining lever
    clock[0] += 20.0
    hot3 = _obs(breached={"serve_p99_s:beta"},
                sheds={"alpha": 340, "beta": 4}, tenants=2)
    assert p.observe(hot3, 1) is None
    d = p.observe(hot3, 1)
    assert d.action == "scale_up"


def test_policy_no_rebalance_without_dominance_or_single_tenant():
    clock = [0.0]
    p = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    # two tenants shedding evenly: capacity problem, not fairness
    even = _obs(breached={"serve_p99_s"},
                sheds={"alpha": 50, "beta": 50}, tenants=2)
    p.observe(even, 1)
    assert p.observe(even, 1).action == "scale_up"
    clock[0] += 100.0
    p2 = AutoscalePolicy(_cfg(), clock=lambda: clock[0])
    # single tenant: nothing to rebalance against
    solo = _obs(breached={"serve_p99_s"}, sheds={"alpha": 100}, tenants=1)
    p2.observe(solo, 1)
    assert p2.observe(solo, 1).action == "scale_up"


def test_journal_signals_parse_breach_state_and_sheds(tmp_path):
    """JournalSignals reads the same files `obs summary` does: breach
    state per (writer, signal) — one worker recovering must not mask
    another's open breach — and per-tenant sheds as summed per-writer
    maxima of the monotonic counter."""
    base = tmp_path / "serve.jsonl"

    def line(**kw):
        kw.setdefault("plane", "serve")
        return json.dumps(kw) + "\n"

    base.write_text(
        line(ts=1.0, event="slo_breach", signal="serve_p99_s", worker=0)
        + line(ts=2.0, event="shed", model="alpha", worker=0,
               shed_total=5)
        + line(ts=3.0, event="shed", model="alpha", worker=1,
               shed_total=7)
        + line(ts=4.0, event="shed", model="beta", worker=0,
               shed_total=1)
        + line(ts=5.0, event="serve_batch", model="beta", worker=0,
               rows=4)
    )
    sig = JournalSignals(str(base))
    obs = sig.poll()
    assert obs.breached == {"serve_p99_s"}
    assert obs.sheds_by_model == {"alpha": 12, "beta": 1}
    assert obs.tenants_seen == 2
    assert obs.new_events == 5
    # nothing new: empty tick
    obs = sig.poll()
    assert obs.new_events == 0
    # worker 0 recovers but worker 1 opens its own breach
    with open(base, "a") as f:
        f.write(line(ts=6.0, event="slo_recover", signal="serve_p99_s",
                     worker=0))
        f.write(line(ts=7.0, event="slo_breach",
                     signal="serve_shed_rate:alpha", worker=1))
    obs = sig.poll()
    assert obs.breached == {"serve_shed_rate:alpha"}
    assert obs.new_events == 2
    # a writer that dies or restarts cannot emit its own slo_recover —
    # its latched breach clears on serve_worker_exit/scale_down (the
    # supervisor's record of the death) or on a fresh serve_start (the
    # replacement's watchdog starts un-breached).  Without this, the
    # rebalance rolling restart latches a breach forever and drives
    # scale_ups to the ceiling.
    with open(base, "a") as f:
        f.write(line(ts=8.0, event="serve_worker_exit", index=1, rc=-15))
    assert sig.poll().breached == set()
    with open(base, "a") as f:
        f.write(line(ts=9.0, event="slo_breach", signal="serve_p99_s",
                     worker=0))
        f.write(line(ts=10.0, event="serve_start", worker=0, port=1))
    assert sig.poll().breached == set()


def test_journal_signals_survive_late_flush_and_worker_restart(tmp_path):
    """Two hardenings of the incremental fold: (1) a slow writer's
    events can reach disk AFTER a faster writer's later-ts events were
    already polled — the merged-order sort puts them BEFORE the seen
    tail, so a global list-index watermark would skip them silently;
    per-writer (ts, seq) marks must still fold them.  (2) a restarted
    serve worker's shed_total restarts near 0 — its dead process's
    high-water is retired on serve_start so fresh sheds are visible
    immediately (and totals stay monotonic) instead of masked until
    they beat the old maximum."""
    base = tmp_path / "serve.jsonl"
    base.write_text("")

    def line(**kw):
        kw.setdefault("plane", "serve")
        return json.dumps(kw) + "\n"

    # writer s1 flushes first, with LATER timestamps
    (tmp_path / "serve.jsonl.s1").write_text(
        line(ts=10.0, event="serve_batch", model="alpha", worker=1)
        + line(ts=11.0, event="serve_batch", model="beta", worker=1)
    )
    sig = JournalSignals(str(base))
    assert sig.poll().new_events == 2
    # writer s0's breach reaches disk late but carries an EARLIER ts:
    # it merges before the already-seen tail and must still be folded
    (tmp_path / "serve.jsonl.s0").write_text(
        line(ts=5.0, event="slo_breach", signal="serve_p99_s", worker=0)
    )
    obs = sig.poll()
    assert obs.new_events == 1
    assert obs.breached == {"serve_p99_s"}
    # worker 0 sheds heavily, restarts, then sheds a little: the fresh
    # process's counter must show through at once
    with open(tmp_path / "serve.jsonl.s0", "a") as f:
        f.write(line(ts=6.0, event="shed", model="alpha", worker=0,
                     shed_total=500))
    assert sig.poll().sheds_by_model == {"alpha": 500}
    with open(tmp_path / "serve.jsonl.s0", "a") as f:
        f.write(line(ts=7.0, event="serve_start", worker=0, port=1))
        f.write(line(ts=8.0, event="shed", model="alpha", worker=0,
                     shed_total=5))
    assert sig.poll().sheds_by_model == {"alpha": 505}


def test_read_keyed_events_after_watermarks_return_only_new(tmp_path):
    """The autoscaler's poll path: ``after=`` per-writer watermarks make
    the reader's RETURN incremental — an unchanged-and-fully-seen file
    is skipped outright, only the new tail is keyed/sorted, and a
    late-flushing writer's earlier-ts events still come back (the marks
    are per writer, not a global index)."""
    from shifu_tensorflow_tpu.obs.journal import read_keyed_events

    base = tmp_path / "j.jsonl"

    def line(seq, ts, **kw):
        kw.update(seq=seq, ts=ts)
        return json.dumps(kw) + "\n"

    base.write_text(line(0, 1.0, event="a") + line(1, 2.0, event="b"))
    (tmp_path / "j.jsonl.s0").write_text(line(0, 1.5, event="c"))
    cache, marks = {}, {}
    keyed = read_keyed_events(str(base), cache=cache, after=marks)
    assert [t[3]["event"] for t in keyed] == ["a", "c", "b"]
    for ts, writer, seq, _ in keyed:
        marks[writer] = max(marks.get(writer, (-1.0, -1)), (ts, seq))
    # everything at or below the marks: nothing returned
    assert read_keyed_events(str(base), cache=cache, after=marks) == []
    # only the tail returns; a second writer's late flush with EARLIER
    # timestamps is new to its own mark and still folds
    with open(base, "a") as f:
        f.write(line(2, 3.0, event="d"))
    (tmp_path / "j.jsonl.s1").write_text(line(0, 0.5, event="e"))
    keyed = read_keyed_events(str(base), cache=cache, after=marks)
    assert [t[3]["event"] for t in keyed] == ["e", "d"]
    # without after= the same cache still serves full merged history
    allk = read_keyed_events(str(base), cache=cache)
    assert [t[3]["event"] for t in allk] == ["e", "a", "c", "b", "d"]


# ---- obs CLI reconstruction ----

def test_obs_fleet_and_summary_render_elastic_story(tmp_path, capsys):
    """`obs fleet` renders standby promotions (rank, epoch, takeover
    latency) beside straggler excursions, and `obs summary` renders the
    autoscaler's decisions — from journal files alone."""
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = tmp_path / "fleet.jsonl"

    def line(**kw):
        return json.dumps(kw) + "\n"

    base.write_text(
        line(ts=10.0, event="standby_register", plane="coordinator",
             worker_id="standby-0", standbys=1)
        + line(ts=20.0, event="standby_promote", plane="coordinator",
               worker=1, worker_id="standby-0", old_worker_id="worker-1",
               why="missed heartbeats", epoch=3, hb_age_s=0.2,
               standbys_left=0, skipped_expired=[])
        + line(ts=20.5, event="standby_claim", plane="coordinator",
               worker=1, worker_id="standby-0", latency_s=0.42)
        + line(ts=30.0, event="resplit", plane="coordinator",
               split_generation=1, ranks=[0, 1], n_files=4,
               why="shrink after worker 2 failed")
        + line(ts=40.0, event="serve_fleet_start", plane="serve",
               workers=1, workers_max=3, autoscale=True, port=1)
        + line(ts=41.0, event="scale_up", plane="serve", index=1,
               to_workers=2, reason="serve_p99_s breached")
        + line(ts=50.0, event="rebalance", plane="serve", model="alpha",
               weight=0.5, reason="tenant alpha owns the overload")
        + line(ts=60.0, event="scale_down", plane="serve", index=1,
               to_workers=1, reason="recovered")
    )
    rc = obs_main(["fleet", "--journal", str(base)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "promotion: rank 1 <- standby-0" in out
    assert "takeover 0.42s" in out
    assert "@epoch 3" in out
    assert "resplit: generation 1" in out
    rc = obs_main(["summary", "--journal", str(base)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "autoscale: scale_up -> 2 workers" in out
    assert "autoscale: rebalance tenant alpha weight -> 0.5" in out
    assert "autoscale: scale_down -> 1 workers" in out
    # --json carries the structured decisions + promotions
    rc = obs_main(["summary", "--journal", str(base), "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert [d["action"] for d in doc["serve"]["autoscale"]] \
        == ["scale_up", "rebalance", "scale_down"]
    assert doc["fleet"]["promotions"][0]["latency_s"] == 0.42
