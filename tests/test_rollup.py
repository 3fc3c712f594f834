"""Long-horizon observability: rollup archive, cost attribution, and
cross-run regression detection (obs/rollup.py + obs/cost.py).

The load-bearing drills:

- **Rotation conservation**: with a journal small enough to rotate
  several times, the rollup-reconstructed totals must equal the live
  registry counters exactly — the sidecar is the survivor, the journal
  is not.
- **Restart idempotence**: a compactor that crashed mid-window loses at
  most that window; a restarted one can never double-count.
- **Shed exactness**: rate-limited `shed` events undercount by design;
  the report's totals must come from the monotonic counters.
- **Cost conservation**: per-tenant device-seconds must sum to within
  5% of the dispatch lane's measured busy wall.
"""

import json
import time

import numpy as np
import pytest

from shifu_tensorflow_tpu.obs import cost as cost_mod
from shifu_tensorflow_tpu.obs import journal as journal_mod
from shifu_tensorflow_tpu.obs import rollup as rollup_mod
from shifu_tensorflow_tpu.obs import slo as slo_mod
from shifu_tensorflow_tpu.obs import trace as trace_mod
from shifu_tensorflow_tpu.obs.__main__ import main as obs_main
from shifu_tensorflow_tpu.obs.journal import Journal, read_events
from shifu_tensorflow_tpu.obs.registry import MetricsRegistry
from shifu_tensorflow_tpu.obs.rollup import (
    RegressionWatchdog,
    RollupCompactor,
    merge_digest_snapshots,
    read_rollups,
    reconstruct,
    rollup_files,
    rollup_path,
)


@pytest.fixture(autouse=True)
def _clean_obs_hooks():
    yield
    from shifu_tensorflow_tpu.obs import compile as compile_mod
    from shifu_tensorflow_tpu.obs import datastats as datastats_mod
    from shifu_tensorflow_tpu.obs import fleet as fleet_mod
    from shifu_tensorflow_tpu.obs import memory as memory_mod

    trace_mod.uninstall()
    journal_mod.uninstall()
    slo_mod.uninstall()
    fleet_mod.uninstall()
    compile_mod.uninstall()
    memory_mod.uninstall()
    datastats_mod.uninstall()
    datastats_mod.uninstall_train()
    cost_mod.uninstall()
    rollup_mod.uninstall()
    rollup_mod.uninstall_regression()
    for name in ("test", "serve", "cost"):
        rollup_mod.unregister_source(name)


class _Clock:
    """Manually-advanced wall clock for the frozen-clock drills."""

    def __init__(self, t: float = 1_000_000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _serve_batch(ts: float, rows: int = 8, model: str | None = None,
                 bucket: int | None = None) -> dict:
    rec = {"ts": ts, "event": "serve_batch", "plane": "serve",
           "rows": rows, "requests": 2,
           "bucket": bucket if bucket is not None else rows,
           "dispatch_s": 0.004, "queue_delay_s": 0.001}
    if model:
        rec["model"] = model
    return rec


# ---- compactor folding ----

def test_compactor_folds_events_and_reconstructs(tmp_path):
    path = str(tmp_path / "j.jsonl.rollup.jsonl")
    comp = RollupCompactor(path, window_s=10.0, plane="serve",
                           worker=0, job="jobx", thread=False)
    t = 1000.0
    for i in range(30):
        comp.note_event(_serve_batch(t + i * 0.5, rows=8, model="alpha"))
    comp.note_event({"ts": t + 1, "event": "step_breakdown",
                     "plane": "train", "worker": 1, "steps": 64,
                     "dispatch_s": 0.5, "infeed_s": 0.1, "host_s": 0.2,
                     "block_s": 0.05})
    comp.note_event({"ts": t + 2, "event": "epoch", "plane": "train",
                     "worker": 1, "train_time_s": 1.25})
    comp.note_event({"ts": t + 3, "event": "device_mem",
                     "total_bytes": 1 << 20, "devmem_frac": 0.25})
    comp.note_event({"ts": t + 4, "event": "compile", "name": "x",
                     "compile_s": 0.8})
    comp.close()
    records = read_rollups(path)
    assert records, "no rollup records written"
    assert all(r["schema"] == rollup_mod.ROLLUP_SCHEMA for r in records)
    # 30 events at 0.5s spacing cross the 10s window boundary: >1 record
    assert len(records) >= 2
    doc = reconstruct(records)
    assert doc["events"]["serve_batch"] == 30
    assert doc["serve"]["alpha"]["rows"] == 240
    assert doc["serve"]["alpha"]["batches"] == 30
    assert doc["train"]["1"]["steps"] == 64
    assert doc["train"]["1"]["train_time_s"] == pytest.approx(1.25)
    assert doc["gauges"]["total_bytes"] == 1 << 20
    assert doc["compile"]["compiles"] == 1
    assert doc["jobs"] == ["jobx"]


def test_rotation_conservation_frozen_clock(tmp_path, monkeypatch):
    """The acceptance drill in miniature: a journal that rotated ≥2
    times has LOST events, but the rollup-reconstructed totals equal
    the live registry counters exactly, and the event folds equal what
    was emitted."""
    clk = _Clock()
    monkeypatch.setattr(rollup_mod, "_time", clk)
    monkeypatch.setattr(journal_mod.time, "time", clk)
    base = str(tmp_path / "journal.jsonl")
    jrn = Journal(base, max_bytes=4096, max_files=3, plane="serve")
    comp = RollupCompactor(rollup_path(base), window_s=10.0,
                           plane="serve", thread=False)
    jrn.set_tap(comp.note_event)
    jrn.on_close(comp.close)
    registry = MetricsRegistry()
    rollup_mod.register_source("test", registry.counters)

    emitted_rows = 0
    n_events = 400
    for i in range(n_events):
        rows = 4 + (i % 5)
        # padding (the x field) makes lines fat enough that 400 events
        # blow through the 4 KiB cap several times over
        jrn.emit("serve_batch", plane="serve", rows=rows, requests=1,
                 bucket=rows, dispatch_s=0.001, queue_delay_s=0.0,
                 x="p" * 64)
        registry.inc("requests_total")
        registry.inc("rows_total", rows)
        emitted_rows += rows
        clk.advance(0.25)
    jrn.close()

    # the journal really rotated and really lost history
    rotated = [p for p in journal_mod.journal_files(base)
               if p != base]
    assert len(rotated) >= 2, journal_mod.journal_files(base)
    surviving = [e for e in read_events(base)
                 if e["event"] == "serve_batch"]
    assert len(surviving) < n_events, \
        "journal never rotated anything away — the drill proves nothing"

    # ... but the rollup reconstruction is exact
    doc = reconstruct(read_rollups(base))
    assert doc["events"]["serve_batch"] == n_events
    assert doc["serve"]["default"]["rows"] == emitted_rows
    live = registry.counters()
    assert doc["counters"]["test"]["requests_total"] == live["requests_total"]
    assert doc["counters"]["test"]["rows_total"] == live["rows_total"]
    # windows actually downsampled: far fewer records than events
    assert doc["windows"] < n_events / 4


def test_compactor_restart_never_double_counts(tmp_path, monkeypatch):
    """Crash mid-window: the unflushed window is lost (undercount at
    most one window), never replayed (a restarted compactor appends,
    it does not re-read)."""
    clk = _Clock()
    monkeypatch.setattr(rollup_mod, "_time", clk)
    path = str(tmp_path / "j.jsonl.rollup.jsonl")

    reg_a = MetricsRegistry()
    rollup_mod.register_source("test", reg_a.counters)
    a = RollupCompactor(path, window_s=10.0, thread=False)
    for i in range(10):
        a.note_event(_serve_batch(clk.t, rows=8))
        reg_a.inc("rows_total", 8)
        clk.advance(0.5)
    a.flush(clk.t)
    flushed_counter = reg_a.counters()["rows_total"]
    # crash mid-window: more events + counter movement, NO flush/close
    for i in range(5):
        a.note_event(_serve_batch(clk.t, rows=8))
        reg_a.inc("rows_total", 8)
        clk.advance(0.5)
    del a  # the process died — nothing flushes

    # restart: a NEW process means fresh counters starting at zero
    reg_b = MetricsRegistry()
    rollup_mod.register_source("test", reg_b.counters)
    b = RollupCompactor(path, window_s=10.0, thread=False)
    for i in range(7):
        b.note_event(_serve_batch(clk.t, rows=8))
        reg_b.inc("rows_total", 8)
        clk.advance(0.5)
    b.close()

    doc = reconstruct(read_rollups(path))
    # 10 flushed + 7 after restart; the 5 crashed-window events are
    # lost, not doubled
    assert doc["events"]["serve_batch"] == 17
    assert doc["serve"]["default"]["rows"] == 17 * 8
    assert doc["counters"]["test"]["rows_total"] == (
        flushed_counter + reg_b.counters()["rows_total"])


def test_counter_reset_clamps_to_rate_semantics(tmp_path):
    """A source whose counter moves BACKWARD (replaced registry) is a
    reset: the delta is the new absolute value, never negative."""
    path = str(tmp_path / "j.rollup.jsonl")
    comp = RollupCompactor(path, window_s=10.0, thread=False)
    val = {"n": 100}
    rollup_mod.register_source("test", lambda: {"c": val["n"]})
    comp.note_event(_serve_batch(1000.0))
    comp.flush(1000.0)
    val["n"] = 30  # reset below the last poll
    comp.note_event(_serve_batch(1001.0))
    comp.flush(1001.0)
    comp.close()
    doc = reconstruct(read_rollups(path))
    assert doc["counters"]["test"]["c"] == 130  # 100 + 30, not 100 - 70


def test_shed_totals_come_from_counters_not_events(tmp_path):
    """Satellite drill: flood sheds past the journal's rate limit — the
    journal sees ONE shed event, the report total matches the monotonic
    counter exactly."""
    from shifu_tensorflow_tpu.serve.batcher import MicroBatcher, ShedLoad
    from shifu_tensorflow_tpu.serve.metrics import ServeMetrics

    base = str(tmp_path / "j.jsonl")
    jrn = journal_mod.install(Journal(base, plane="serve"))
    comp = RollupCompactor(rollup_path(base), window_s=10.0,
                           plane="serve", thread=False)
    jrn.set_tap(comp.note_event)
    jrn.on_close(comp.close)
    metrics = ServeMetrics()
    rollup_mod.register_source("serve", metrics.counters)

    import threading

    release = threading.Event()
    b = MicroBatcher(lambda x: (release.wait(10.0), x[:, :1])[1],
                     max_batch=8, max_delay_s=0.0, max_queue_rows=8,
                     metrics=metrics)
    rows = np.ones((8, 3), np.float32)
    # fillers: the pipeline absorbs ~3 batches (dispatch blocked in the
    # scorer), the 4th parks in the admission queue and pins it full —
    # every flood submit below then sheds.  Fillers retry their own
    # sheds: only a successfully parked submit pins the queue.
    def filler():
        while not release.is_set():
            try:
                b.submit(rows, timeout_s=30.0)
                return
            except ShedLoad:
                time.sleep(0.005)

    fillers = [threading.Thread(target=filler) for _ in range(4)]
    for t in fillers:
        t.start()
    # wait until the pipeline absorbed 3 batches AND one filler parked
    # in the admission queue (queued+inflight = 4 x 8 rows) — only then
    # does every flood submit shed deterministically
    deadline = time.monotonic() + 5.0
    while b.queued_rows() < 32 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert b.queued_rows() == 32, b.queued_rows()
    deadline = time.monotonic() + 10.0
    sheds = 0
    while sheds < 40 and time.monotonic() < deadline:
        try:
            b.submit(rows, timeout_s=0.01)
        except ShedLoad:
            sheds += 1
        except TimeoutError:
            pass  # absorbed before the fillers pinned the queue
    assert sheds >= 40, "flood never shed"
    # the journal's rate limiter would write one event per 5s window:
    # emit exactly one, the way ScoringServer.note_shed does
    jrn.emit("shed", plane="serve", rid="r1",
             shed_total=metrics.counters()["shed_total"])
    release.set()
    for t in fillers:
        t.join()
    b.close()
    jrn.close()
    journal_mod.uninstall()

    doc = reconstruct(read_rollups(base))
    live = metrics.counters()["shed_total"]
    assert live >= 40
    assert doc["events"].get("shed", 0) == 1  # rate-limited: undercounts
    assert doc["counters"]["serve"]["shed_total"] == live  # exact


# ---- excursion intervals ----

def test_excursion_intervals_fold_and_survive(tmp_path):
    path = str(tmp_path / "j.rollup.jsonl")
    comp = RollupCompactor(path, window_s=10.0, thread=False)
    comp.note_event({"ts": 1000.0, "event": "slo_breach",
                     "signal": "serve_p99_s", "value": 0.5})
    comp.note_event({"ts": 1025.0, "event": "slo_recover",
                     "signal": "serve_p99_s", "value": 0.01})
    comp.note_event({"ts": 1030.0, "event": "data_drift",
                     "model": "beta", "feature": 2})
    comp.close()
    doc = reconstruct(read_rollups(path))
    closed = [e for e in doc["excursions"] if e["end_ts"] is not None]
    assert len(closed) == 1
    assert closed[0]["kind"] == "slo" and closed[0]["name"] == "serve_p99_s"
    assert closed[0]["end_ts"] - closed[0]["start_ts"] == pytest.approx(25.0)
    assert [e["kind"] for e in doc["open_excursions"]] == ["drift"]
    assert doc["open_excursions"][0]["name"] == "beta/f2"


def test_open_excursions_matched_per_writer(tmp_path):
    """Worker A's recovery must not hide worker B's still-open
    excursion of the same signal: open/closed intervals match per
    writer, not fleet-wide."""
    base = str(tmp_path / "fleet.jsonl")
    a = RollupCompactor(rollup_path(base + ".s0"), window_s=10.0,
                        plane="serve", worker=0, thread=False)
    b = RollupCompactor(rollup_path(base + ".s1"), window_s=10.0,
                        plane="serve", worker=1, thread=False)
    a.note_event({"ts": 1000.0, "event": "slo_breach",
                  "signal": "serve_p99_s"})
    b.note_event({"ts": 1001.0, "event": "slo_breach",
                  "signal": "serve_p99_s"})
    a.note_event({"ts": 1030.0, "event": "slo_recover",
                  "signal": "serve_p99_s"})  # A recovers; B does not
    a.close()
    b.close()
    doc = reconstruct(read_rollups(base))
    closed = [e for e in doc["excursions"] if e["end_ts"] is not None]
    assert len(closed) == 1 and closed[0]["writer"] == "serve/w0"
    assert len(doc["open_excursions"]) == 1
    assert doc["open_excursions"][0]["writer"] == "serve/w1"


# ---- cost accountant ----

def test_cost_accountant_counters_and_render():
    acct = cost_mod.CostAccountant(plane="serve")
    acct.note_dispatch("alpha", dispatch_s=0.01, rows=10, bucket_rows=16,
                       nbytes=1200)
    acct.note_dispatch("alpha", dispatch_s=0.01, rows=6, bucket_rows=8,
                       nbytes=720)
    acct.note_dispatch("beta", dispatch_s=0.02, rows=4, bucket_rows=4,
                       nbytes=480)
    acct.note_busy(0.045)
    acct.note_train_epoch(1, dispatch_s=0.5, steps=64)
    c = acct.counters()
    assert c["device_seconds:alpha"] == pytest.approx(0.02)
    assert c["padded_row_seconds:alpha"] == pytest.approx(
        0.01 * 16 + 0.01 * 8)
    assert c["rows:alpha"] == 16
    assert c["bytes:beta"] == 480
    assert c["train_device_seconds:w1"] == pytest.approx(0.5)
    assert c["device_busy_seconds"] == pytest.approx(0.045)
    text = acct.render_prometheus()
    assert 'stpu_cost_device_seconds_total{model="alpha"} 0.02' in text
    assert 'stpu_cost_train_device_seconds_total{worker="1"} 0.5' in text
    assert "stpu_cost_device_busy_frac" in text
    util = acct.utilization()
    assert util is not None and 0.0 < util["busy_frac"] <= 1.0


def test_batcher_dispatch_feeds_cost_ledger():
    from shifu_tensorflow_tpu.serve.batcher import MicroBatcher

    acct = cost_mod.install(cost_mod.CostAccountant(plane="serve"))

    def score(x):
        # measurable dispatch time: sub-µs dispatches round to noise in
        # the 6-decimal counter export
        time.sleep(0.002)
        return x[:, :1]

    b = MicroBatcher(score, max_batch=16, max_delay_s=0.0,
                     model="alpha")
    rows = np.ones((6, 4), np.float32)
    for _ in range(5):
        b.submit(rows, timeout_s=5.0)
    b.close()
    c = acct.counters()
    assert c["rows:alpha"] == 30
    assert c["batches:alpha"] == 5
    assert c["device_seconds:alpha"] > 0
    # bucket ladder pads 6 -> 8: the DRR currency charges padded rows
    assert (c["padded_row_seconds:alpha"]
            >= c["device_seconds:alpha"] * 8 * 0.99)
    assert c["bytes:alpha"] == 30 * 4 * 4


def test_tenant_device_seconds_conserve_against_busy_wall():
    """Acceptance bound: per-tenant device-seconds sum to within 5% of
    the dispatch lane's measured busy wall when scoring dominates."""
    from shifu_tensorflow_tpu.serve.batcher import MicroBatcher
    from shifu_tensorflow_tpu.serve.tenancy.scheduler import DeviceScheduler

    acct = cost_mod.install(cost_mod.CostAccountant(plane="serve"))
    sched = DeviceScheduler()

    def slow_score(x):
        time.sleep(0.005)
        return x[:, :1]

    ba = MicroBatcher(slow_score, max_batch=8, max_delay_s=0.0,
                      scheduler=sched, model="alpha")
    bb = MicroBatcher(slow_score, max_batch=8, max_delay_s=0.0,
                      scheduler=sched, model="beta", weight=2.0)
    rows = np.ones((8, 3), np.float32)
    import threading

    def hammer(b, n):
        for _ in range(n):
            b.submit(rows, timeout_s=30.0)

    threads = [threading.Thread(target=hammer, args=(b, 20))
               for b in (ba, bb)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the scheduler's own ledger (read before close unregisters the
    # tenant queues)
    totals = sched.dispatch_totals()
    assert totals["alpha"]["device_s"] > 0
    assert totals["beta"]["device_s"] > 0
    ba.close()
    bb.close()
    state = acct.state()
    tenant_sum = sum(t["device_s"] for t in state["tenants"].values())
    busy = state["utilization"]["busy_s"]
    assert busy > 0
    assert tenant_sum <= busy * 1.0001
    assert tenant_sum >= busy * 0.95, (tenant_sum, busy)
    sched.close()


def test_trainer_epoch_attributes_device_seconds(tmp_path):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.data.dataset import (
        InMemoryDataset,
        ParsedBlock,
    )
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    from shifu_tensorflow_tpu.train import make_trainer

    acct = cost_mod.install(cost_mod.CostAccountant(plane="train"))
    tracer = trace_mod.install(trace_mod.Tracer(worker_index=0))
    # _obs_epoch runs only with a journal or watchdog installed
    journal_mod.install(Journal(str(tmp_path / "t.jsonl"),
                                plane="train"))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x[:, :1] > 0).astype(np.float32)
    block = ParsedBlock(features=x, targets=y,
                        weights=np.ones((64, 1), np.float32))
    dataset = InMemoryDataset(
        train=block, valid=ParsedBlock.empty(4),
        schema=RecordSchema(feature_columns=(1, 2, 3, 4),
                            target_column=0))
    mc = ModelConfig.from_json({"train": {"numTrainEpochs": 2, "params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    trainer = make_trainer(mc, 4, feature_columns=(1, 2, 3, 4))
    trainer.tracer = tracer
    trainer.fit(dataset, batch_size=16)
    c = acct.counters()
    assert c.get("train_device_seconds:w0", 0) > 0
    assert c.get("train_steps:w0", 0) >= 4


# ---- digests ----

def test_digest_snapshots_and_merge():
    wd = slo_mod.SloWatchdog(window_s=60.0, plane="serve")
    wd.track("serve_p99_s", stat="p99", target=0.0, unit="s")
    for i in range(200):
        wd.observe("serve_p99_s", 0.01 + (i % 10) * 0.001)
    snaps = wd.digest_snapshots()
    assert "serve_p99_s" in snaps
    s = snaps["serve_p99_s"]
    assert s["count"] == 200 and s["stat"] == "p99"
    merged = merge_digest_snapshots([s, s])
    assert merged["count"] == 400
    assert merged["mean"] == pytest.approx(s["mean"], rel=1e-6)
    assert merged["p99"] == pytest.approx(s["p99"], rel=1e-6)
    assert merged["stat"] == "p99"


def test_flush_records_digest_snapshots(tmp_path):
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=60.0,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    for _ in range(50):
        wd.observe("serve_p99_s", 0.02)
    path = str(tmp_path / "j.rollup.jsonl")
    comp = RollupCompactor(path, window_s=10.0, thread=False)
    comp.note_event(_serve_batch(1000.0))
    comp.close()
    doc = reconstruct(read_rollups(path))
    assert doc["digests"]["serve_p99_s"]["count"] == 50
    assert doc["digests"]["serve_p99_s"]["p99"] == pytest.approx(
        0.02, rel=0.05)


def test_digest_conservation_survives_expired_window(tmp_path):
    """Observations whose sliding SLO window expired BEFORE the flush
    still land in the sidecar (values unknown, count/sum exact) — the
    conservation property must not depend on flush timing."""
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=0.3, buckets=2,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    for _ in range(50):
        wd.observe("serve_p99_s", 0.02)
    time.sleep(0.4)  # the window drains; the lifetime totals do not
    assert wd.digest_snapshots() == {}
    path = str(tmp_path / "j.rollup.jsonl")
    comp = RollupCompactor(path, window_s=10.0, thread=False)
    comp.note_event(_serve_batch(1000.0))
    comp.close()
    doc = reconstruct(read_rollups(path))
    d = doc["digests"]["serve_p99_s"]
    assert d["count"] == 50
    assert d["mean"] == pytest.approx(0.02, rel=1e-6)


# ---- regression watchdog ----

def _baseline_doc(p99=0.01, count=1000):
    return {"digests": {"serve_p99_s": {
        "count": count, "sum": p99 * count * 0.9, "max": p99 * 2,
        "mean": p99 * 0.9, "p99": p99, "stat": "p99"}}}


def test_regression_watchdog_fires_names_metric_and_clears(tmp_path):
    base = str(tmp_path / "j.jsonl")
    journal_mod.install(Journal(base, plane="serve"))
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=0.5, buckets=2,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    rw = RegressionWatchdog(_baseline_doc(p99=0.01), threshold=1.5,
                            hysteresis=2, plane="serve")
    # slowdown: 5x the baseline p99, enough samples to clear the noise
    # discount
    for _ in range(100):
        wd.observe("serve_p99_s", 0.05)
    assert rw.evaluate() == []          # hysteresis tick 1
    events = rw.evaluate()              # tick 2: fires
    assert [e["event"] for e in events] == ["perf_regression"]
    ev = events[0]
    assert ev["metric"] == "serve_p99_s" and ev["stat"] == "p99"
    assert ev["ratio"] > 3.0 and ev["baseline"] == pytest.approx(0.01)
    # recovery: the slow window ages out, fast samples replace it
    time.sleep(0.6)
    for _ in range(100):
        wd.observe("serve_p99_s", 0.01)
    assert rw.evaluate() == []          # clean tick 1
    events = rw.evaluate()              # tick 2: clears
    assert [e["event"] for e in events] == ["perf_regression_clear"]
    assert events[0]["regression_s"] > 0
    journal_mod.active().close()
    evs = read_events(base)
    kinds = [e["event"] for e in evs]
    assert kinds.count("perf_regression") == 1
    assert kinds.count("perf_regression_clear") == 1


def test_regression_watchdog_control_arm_quiet():
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=60.0,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    rw = RegressionWatchdog(_baseline_doc(p99=0.01), threshold=1.5,
                            hysteresis=1, plane="serve")
    for _ in range(200):
        wd.observe("serve_p99_s", 0.0101)  # ~the baseline
    for _ in range(5):
        assert rw.evaluate() == []
    assert rw.state().get("serve_p99_s", {}).get("breached") is not True


def test_regression_watchdog_small_sample_discounted():
    """A handful of slow samples is not a regression: the k/√n discount
    (and the min-count floor) keeps tiny windows quiet."""
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=60.0,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    rw = RegressionWatchdog(_baseline_doc(p99=0.01), threshold=1.5,
                            hysteresis=1, plane="serve")
    for _ in range(5):
        wd.observe("serve_p99_s", 0.05)
    assert rw.evaluate() == []


def test_install_obs_wires_rollup_cost_and_regression(tmp_path):
    from shifu_tensorflow_tpu.obs import install_obs
    from shifu_tensorflow_tpu.obs.config import ObsConfig

    base = str(tmp_path / "wired.jsonl")
    # a pinned baseline sidecar with digests
    bl_path = str(tmp_path / "baseline.rollup.jsonl")
    with open(bl_path, "w") as f:
        f.write(json.dumps({
            "schema": rollup_mod.ROLLUP_SCHEMA, "t0": 0.0, "t1": 60.0,
            "digests": _baseline_doc()["digests"],
        }) + "\n")
    cfg = ObsConfig(enabled=True, journal_path=base,
                    rollup_window_s=5.0, baseline_path=bl_path,
                    slo_regression=2.0)
    tracer, jrn = install_obs(cfg, plane="serve")
    try:
        assert rollup_mod.active() is not None
        assert cost_mod.active() is not None
        assert rollup_mod.regression_active() is not None
        assert rollup_mod.regression_active().threshold == 2.0
        jrn.emit("serve_batch", plane="serve", rows=4, requests=1,
                 bucket=4, dispatch_s=0.001, queue_delay_s=0.0)
        jrn.close()  # close hook flushes the compactor
        doc = reconstruct(read_rollups(base))
        assert doc["events"]["serve_batch"] == 1
    finally:
        install_obs(ObsConfig(), plane="serve")
    assert rollup_mod.active() is None
    assert rollup_mod.regression_active() is None


# ---- CLI: report / diff ----

def _make_run(tmp_path, name: str, p99: float, rows_per_evt: int = 8,
              n: int = 40) -> str:
    """One synthetic run: a journal + compactor + slo digests + cost
    counters, flushed to its sidecar set."""
    base = str(tmp_path / f"{name}.jsonl")
    wd = slo_mod.install(slo_mod.SloWatchdog(window_s=600.0,
                                             plane="serve"))
    wd.track("serve_p99_s", stat="p99")
    acct = cost_mod.CostAccountant(plane="serve")
    rollup_mod.register_source("cost", acct.counters)
    reg = MetricsRegistry()
    rollup_mod.register_source("serve", reg.counters)
    comp = RollupCompactor(rollup_path(base), window_s=10.0,
                           plane="serve", worker=None, job=name,
                           thread=False)
    t = 1000.0
    for i in range(n):
        comp.note_event(_serve_batch(t + i * 0.5, rows=rows_per_evt,
                                     model="alpha"))
        wd.observe("serve_p99_s", p99)
        acct.note_dispatch("alpha", dispatch_s=p99, rows=rows_per_evt,
                           bucket_rows=rows_per_evt, nbytes=rows_per_evt * 12)
        acct.note_busy(p99 * 1.01)
        reg.inc("requests_total")
        reg.inc("rows_total", rows_per_evt)
    comp.close()
    slo_mod.uninstall()
    rollup_mod.unregister_source("cost")
    rollup_mod.unregister_source("serve")
    return base


def test_obs_report_renders_from_rollups_alone(tmp_path, capsys):
    base = _make_run(tmp_path, "runA", p99=0.01)
    # no journal file exists at all — the report reads sidecars only
    assert not (tmp_path / "runA.jsonl").exists()
    rc = obs_main(["report", "--journal", base])
    out = capsys.readouterr().out
    assert rc == 0
    assert "rollup report" in out
    assert "per-tenant cost" in out
    assert "alpha" in out
    assert "device lane" in out
    assert "totals (monotonic counters)" in out
    assert "requests 40" in out


def test_obs_report_json_schema_and_totals(tmp_path, capsys):
    base = _make_run(tmp_path, "runJ", p99=0.01)
    rc = obs_main(["report", "--journal", base, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["schema"] == "stpu.obs.report/1"
    assert doc["counters"]["serve"]["requests_total"] == 40
    assert doc["counters"]["serve"]["rows_total"] == 320
    assert doc["counters"]["cost"]["rows:alpha"] == 320
    assert doc["digests"]["serve_p99_s"]["count"] == 40


def test_obs_report_missing_rollups_rc1(tmp_path, capsys):
    rc = obs_main(["report", "--journal", str(tmp_path / "nope.jsonl")])
    assert rc == 1
    assert "no rollup records" in capsys.readouterr().err


def test_obs_diff_flags_regression(tmp_path, capsys):
    a = _make_run(tmp_path, "fast", p99=0.01, n=200)
    b = _make_run(tmp_path, "slow", p99=0.05, n=200)
    rc = obs_main(["diff", a, b, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["schema"] == "stpu.obs.diff/1"
    by_metric = {r["metric"]: r for r in doc["metrics"]}
    assert by_metric["serve_p99_s.p99"]["verdict"] == "REGRESSED"
    assert "serve_p99_s.p99" in doc["regressions"]
    assert by_metric["device_s_per_krow"]["verdict"] == "REGRESSED"
    # human renderer names the regression too
    rc = obs_main(["diff", a, b])
    out = capsys.readouterr().out
    assert rc == 0 and "REGRESSED" in out


def test_obs_diff_same_run_is_quiet(tmp_path, capsys):
    a = _make_run(tmp_path, "same1", p99=0.01, n=200)
    b = _make_run(tmp_path, "same2", p99=0.01, n=200)
    rc = obs_main(["diff", a, b, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["regressions"] == []


def test_obs_summary_json_schema_pinned(tmp_path, capsys):
    base = str(tmp_path / "s.jsonl")
    jrn = Journal(base, plane="train")
    jrn.emit("worker_start", plane="train", worker=0)
    jrn.close()
    rc = obs_main(["summary", "--journal", base, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["schema"] == "stpu.obs.summary/1"


# ---- bench history ----

def _bench_entry(name, ts, value):
    return {"ts": ts, "name": name, "rc": 0,
            "artifact": f"BENCH_{name.upper()}.json",
            "host": {"hostname": "h", "cpus": 2},
            "metrics": {"value": value, "threshold_pct": 2.0}}


def test_obs_diff_bench_renders_last_two_entries(tmp_path, capsys):
    hist = str(tmp_path / "BENCH_HISTORY.jsonl")
    with open(hist, "w") as f:
        for e in (_bench_entry("obs", 1.0, 1.2),
                  _bench_entry("serve", 2.0, 9.0),
                  _bench_entry("obs", 3.0, 1.5)):
            f.write(json.dumps(e) + "\n")
    rc = obs_main(["diff", "--bench", "--history", hist, "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["schema"] == "stpu.obs.diff/1"
    assert doc["mode"] == "bench" and doc["name"] == "obs"
    row = {r["metric"]: r for r in doc["metrics"]}["value"]
    assert row["a"] == 1.2 and row["b"] == 1.5
    assert row["delta_pct"] == pytest.approx(25.0)
    # human render
    rc = obs_main(["diff", "--bench", "--history", hist])
    out = capsys.readouterr().out
    assert rc == 0 and "bench diff — obs" in out


def test_obs_diff_bench_needs_two_entries(tmp_path, capsys):
    hist = str(tmp_path / "h.jsonl")
    with open(hist, "w") as f:
        f.write(json.dumps(_bench_entry("obs", 1.0, 1.2)) + "\n")
    rc = obs_main(["diff", "--bench", "--history", hist])
    assert rc == 1
    assert "two" in capsys.readouterr().err


def test_bench_history_append_helper(tmp_path, monkeypatch):
    """bench.py's history hook: one JSONL line with host fingerprint,
    scalar metrics from the result, and the caller-supplied ts."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr(
        bench.os.path, "abspath",
        lambda p: str(tmp_path / "bench.py") if p.endswith("bench.py")
        else os.path.abspath(p))
    monkeypatch.setenv("BENCH_TS", "2026-08-04T00:00:00")
    bench._append_bench_history(
        "x", rc=0, result={"value": 3.5, "acceptance_ok": True,
                           "unit": "x", "nested": {"a": 1}})
    lines = (tmp_path / "BENCH_HISTORY.jsonl").read_text().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["name"] == "x" and rec["ts"] == "2026-08-04T00:00:00"
    assert rec["metrics"] == {"value": 3.5}  # scalars only, bools out
    assert rec["host"]["cpus"] == os.cpu_count()


# ---- sidecar discovery ----

def test_rollup_files_discovers_fleet_siblings(tmp_path):
    base = str(tmp_path / "fleet.jsonl")
    for suffix in ("", ".w0", ".w1", ".s0"):
        comp = RollupCompactor(rollup_path(base + suffix),
                               window_s=10.0, thread=False)
        comp.note_event(_serve_batch(1000.0))
        comp.close()
    files = rollup_files(base)
    assert len(files) == 4
    doc = reconstruct(read_rollups(base))
    assert doc["events"]["serve_batch"] == 4
    # journal readers must NOT pick sidecars up as journal files
    assert not journal_mod.journal_files(base)
