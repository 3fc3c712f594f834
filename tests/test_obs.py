"""Observability plane: registry thread-safety, journal rotation and
corrupt-tail recovery, span tracing, trainer/coordinator integration,
and the obs CLI.

Every test that installs a process-global tracer/journal uninstalls it
(the obs hooks are module state the rest of the suite must not see).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from shifu_tensorflow_tpu.obs import journal as journal_mod
from shifu_tensorflow_tpu.obs import trace as trace_mod
from shifu_tensorflow_tpu.obs.config import ObsConfig
from shifu_tensorflow_tpu.obs.journal import (
    Journal,
    journal_files,
    read_events,
)
from shifu_tensorflow_tpu.obs.registry import LatencyHistogram, MetricsRegistry
from shifu_tensorflow_tpu.obs.trace import Tracer, budget_fields


@pytest.fixture(autouse=True)
def _clean_obs_hooks():
    yield
    trace_mod.uninstall()
    journal_mod.uninstall()
    from shifu_tensorflow_tpu.obs import fleet as fleet_mod
    from shifu_tensorflow_tpu.obs import slo as slo_mod

    slo_mod.uninstall()
    fleet_mod.uninstall()


# ---- registry ----

def test_registry_prereg_counters_render_at_zero():
    r = MetricsRegistry()
    r.counter("requests_total")
    text = r.render_prometheus("t_")
    assert "# TYPE t_requests_total counter" in text
    assert "t_requests_total 0" in text


def test_registry_thread_safety_under_concurrent_writers():
    """8 writer threads hammering one registry: counter totals must be
    exact (no lost increments), histogram count must equal records."""
    r = MetricsRegistry()
    hist = r.histogram("lat")
    N, T = 2000, 8

    def writer(i):
        for k in range(N):
            r.inc("ops_total")
            r.set_gauge("last_writer", i)
            hist.record(0.001 * (k % 7))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.counters()["ops_total"] == N * T
    assert hist.snapshot()["count"] == N * T
    # render must not crash mid-write either (smoke: it parses as text)
    assert "ops_total" in r.render_prometheus("x_")


def test_serve_metrics_format_unchanged_over_registry():
    """The /metrics body through the shared registry must keep the exact
    serve exposition format (the CI smoke greps these lines verbatim)."""
    from shifu_tensorflow_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.inc("requests_total")
    m.inc("rows_total", 2)
    m.request_latency.record(0.004)
    text = m.render_prometheus(
        queue_rows=3, model_epoch=7, model_digest="abc123", model_verified=True
    )
    lines = text.splitlines()
    assert "stpu_serve_requests_total 1" in lines
    assert "stpu_serve_rows_total 2" in lines
    assert "# TYPE stpu_serve_queue_rows gauge" in lines
    assert "stpu_serve_queue_rows 3" in lines
    assert 'stpu_serve_model_info{digest="abc123"} 1' in lines
    assert any(
        l.startswith('stpu_serve_request_latency_seconds{quantile="0.99"}')
        for l in lines
    )
    assert any(l.startswith("stpu_serve_request_latency_seconds_count 1")
               for l in lines)
    # the full counter set renders even before any event (dashboards)
    assert "stpu_serve_shed_total 0" in lines


def test_registry_renders_cumulative_bucket_lines():
    """Satellite: real `_bucket{le=...}` cumulative lines beside the
    quantile gauges, so external Prometheus can histogram_quantile()
    instead of trusting our ladder-bound estimates."""
    r = MetricsRegistry(bounds=(0.01, 0.1, 1.0))
    h = r.histogram("lat")
    for v in (0.005, 0.005, 0.05, 0.5, 5.0):
        h.record(v)
    lines = r.render_prometheus("t_").splitlines()
    assert 't_lat_bucket{le="0.01"} 2' in lines
    assert 't_lat_bucket{le="0.1"} 3' in lines
    assert 't_lat_bucket{le="1.0"} 4' in lines
    assert 't_lat_bucket{le="+Inf"} 5' in lines  # +Inf == _count
    assert "t_lat_count 5" in lines
    # the existing quantile gauges stay (dashboards grep them)
    assert any(l.startswith('t_lat{quantile="0.99"}') for l in lines)


def test_serve_scrape_carries_bucket_lines():
    """Serve /metrics parity after the bucket satellite: cumulative
    buckets for both latency histograms, +Inf equal to the count."""
    from shifu_tensorflow_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.request_latency.record(0.004)
    m.request_latency.record(0.2)
    text = m.render_prometheus(queue_rows=0, model_epoch=0,
                               model_digest="d", model_verified=True)
    lines = text.splitlines()
    assert ('stpu_serve_request_latency_seconds_bucket{le="+Inf"} 2'
            in lines)
    assert ('stpu_serve_batch_latency_seconds_bucket{le="+Inf"} 0'
            in lines)
    # cumulative: every bucket count is <= the next one
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines
              if l.startswith("stpu_serve_request_latency_seconds_bucket")]
    assert counts == sorted(counts) and counts[-1] == 2


def test_latency_histogram_lives_only_in_the_registry():
    """serve/metrics re-exports the obs registry type (no third copy),
    and the coordinator/metrics_board deprecation shim is GONE — the
    PR-4 migration window closed, obs.registry is the one address."""
    from shifu_tensorflow_tpu.coordinator import metrics_board
    from shifu_tensorflow_tpu.serve import metrics as serve_metrics

    assert serve_metrics.LatencyHistogram is LatencyHistogram
    assert not hasattr(metrics_board, "LatencyHistogram")


def test_coordinator_metrics_render_through_registry():
    from types import SimpleNamespace

    from shifu_tensorflow_tpu.coordinator.coordinator import (
        Coordinator,
        JobSpec,
    )

    spec = JobSpec(n_workers=1, shards=[SimpleNamespace(paths=("s0",))])
    coord = Coordinator(spec)
    try:
        assert coord.register("w0", 0)["ok"]
        text = coord.metrics_text()
    finally:
        coord.shutdown()
    assert "stpu_coord_registrations_total 1" in text
    assert "stpu_coord_workers_registered 1" in text
    assert 'stpu_coord_state_info{state="training"} 1' in text
    # the dispatch surface exposes it too (the serve-/metrics analogue)
    resp = coord.dispatch({"op": "metrics"})
    assert resp["ok"] and "stpu_coord_registrations_total" in resp["text"]


# ---- journal ----

def test_journal_emit_read_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path, plane="train", worker=3) as j:
        j.emit("epoch", epoch=0, loss=0.5)
        j.emit("epoch", epoch=1, loss=0.25, worker=9)  # explicit wins
    events = read_events(path)
    assert [e["event"] for e in events] == ["epoch", "epoch"]
    assert events[0]["plane"] == "train" and events[0]["worker"] == 3
    assert events[1]["worker"] == 9
    assert events[0]["ts"] <= events[1]["ts"]


def test_journal_rotation_bounds_footprint(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path, max_bytes=4096, max_files=3) as j:
        for i in range(2000):
            j.emit("tick", i=i, pad="x" * 40)
    files = journal_files(path)
    assert 1 < len(files) <= 3
    for f in files:
        # one event of slack past the cap, never unbounded growth
        assert os.path.getsize(f) <= 4096 + 200
    events = read_events(path)
    assert events, "rotation must not lose the active file"
    # the newest event always survives rotation
    assert events[-1]["i"] == 1999


def test_journal_corrupt_tail_and_middle_recovery(tmp_path):
    """A writer killed mid-write tears the final line; at-rest corruption
    can garble a middle line.  Readers skip both, keep every intact
    event, and never raise."""
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        for i in range(5):
            j.emit("tick", i=i)
    raw = open(path, "rb").read().splitlines(keepends=True)
    raw[2] = b"\x00\xff garbage not json \xfe\n"  # corrupted middle
    raw.append(b'{"ts": 1.0, "event": "torn", "i"')  # torn tail, no \n
    open(path, "wb").write(b"".join(raw))
    events = read_events(path)
    assert [e["i"] for e in events] == [0, 1, 3, 4]


def test_journal_merges_worker_siblings_and_rotations(tmp_path):
    base = str(tmp_path / "job.jsonl")
    with Journal(base, plane="coordinator") as j:
        j.emit("register", worker=0)
    for w in (0, 1):
        with Journal(f"{base}.w{w}", max_bytes=4096, max_files=2,
                     plane="train", worker=w) as jw:
            for i in range(200):
                jw.emit("epoch", epoch=i, pad="y" * 30)
    files = journal_files(base)
    assert any(f.endswith(".w0") for f in files)
    assert any(".w0.1" in f for f in files), "rotations must be discovered"
    # an unrelated sibling must NOT be swept in
    open(str(tmp_path / "job.jsonl.bak"), "w").write('{"event": "no"}\n')
    assert not any(f.endswith(".bak") for f in journal_files(base))
    events = read_events(base)
    assert {e["event"] for e in events} == {"register", "epoch"}
    assert events == sorted(events, key=lambda e: e["ts"])


def test_journal_discovers_serve_worker_siblings(tmp_path):
    """--serve-workers scoring processes write <base>.s<i> siblings; the
    reader merges them beside train (.w<i>) siblings and rotations, and
    install_obs routes a serve-plane worker to the .s path."""
    base = str(tmp_path / "job.jsonl")
    with Journal(base, plane="serve") as j:
        j.emit("serve_fleet_start", workers=2)
    for s in (0, 1):
        with Journal(f"{base}.s{s}", plane="serve", worker=s) as js:
            js.emit("serve_start", port=1234)
    files = journal_files(base)
    assert any(f.endswith(".s0") for f in files)
    assert any(f.endswith(".s1") for f in files)
    events = read_events(base)
    assert [e["event"] for e in events] == [
        "serve_fleet_start", "serve_start", "serve_start"]
    assert {e.get("worker") for e in events
            if e["event"] == "serve_start"} == {0, 1}

    from shifu_tensorflow_tpu.obs import install_obs

    cfg = ObsConfig(enabled=True, journal_path=base)
    _, j = install_obs(cfg, worker_index=3, plane="serve")
    assert j.path.endswith(".s3")
    journal_mod.uninstall()


def test_journal_seq_is_per_writer_monotonic(tmp_path):
    """Every record carries a monotonic per-writer seq, surviving
    rotation — `obs trace` renders merge order as causality, so
    same-microsecond events must keep emission order."""
    path = str(tmp_path / "j.jsonl")
    with Journal(path, max_bytes=4096, max_files=8) as j:
        for i in range(300):
            j.emit("tick", i=i, pad="x" * 40)
    events = read_events(path)
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    assert [e["i"] for e in events] == sorted(e["i"] for e in events)


def test_read_events_merges_same_timestamp_by_seq(tmp_path,
                                                  monkeypatch):
    """The satellite's pinned contract: with every event stamped the
    SAME ts (a frozen clock — the worst case a fast writer can produce),
    the merged read still returns one writer's events in seq order
    across a rotation boundary."""
    import shifu_tensorflow_tpu.obs.journal as jm

    monkeypatch.setattr(jm.time, "time", lambda: 1234.5)
    path = str(tmp_path / "j.jsonl")
    with Journal(path, max_bytes=4096, max_files=4) as j:
        for i in range(200):
            j.emit("tick", i=i, pad="y" * 40)
    files = journal_files(path)
    assert len(files) > 1, "the drill needs a rotation to mean anything"
    events = read_events(path)
    assert all(e["ts"] == 1234.5 for e in events)
    ids = [e["i"] for e in events]
    assert ids == sorted(ids), "same-ts events must merge in seq order"


def test_read_events_same_ts_across_writers_stable(tmp_path, monkeypatch):
    """Equal timestamps across writers keep the deterministic base →
    .w<k> → .s<k> writer order, each writer internally seq-ordered."""
    import shifu_tensorflow_tpu.obs.journal as jm

    monkeypatch.setattr(jm.time, "time", lambda: 99.0)
    base = str(tmp_path / "job.jsonl")
    with Journal(base + ".s0", plane="serve", worker=0) as js:
        js.emit("s-first")
        js.emit("s-second")
    with Journal(base + ".w1", plane="train", worker=1) as jw:
        jw.emit("w-first")
    with Journal(base, plane="coordinator") as j:
        j.emit("base-first")
    names = [e["event"] for e in read_events(base)]
    assert names == ["base-first", "w-first", "s-first", "s-second"]


def test_journal_job_stamp_and_install_wiring(tmp_path):
    """The fleet-wide job correlation id stamps every event the writer
    emits; install_obs threads it through."""
    from shifu_tensorflow_tpu.obs import install_obs

    base = str(tmp_path / "j.jsonl")
    with Journal(base, plane="train", job="abc123") as j:
        j.emit("epoch", epoch=0)
    assert read_events(base)[0]["job"] == "abc123"
    cfg = ObsConfig(enabled=True, journal_path=base)
    _, jrn = install_obs(cfg, worker_index=1, plane="train", job="abc123")
    assert jrn.job == "abc123"
    journal_mod.emit("worker_start")
    journal_mod.uninstall()
    ev = [e for e in read_events(base) if e["event"] == "worker_start"][0]
    assert ev["job"] == "abc123" and ev["worker"] == 1


def test_read_events_cache_reuses_unchanged_files(tmp_path, monkeypatch):
    """The `obs top` refresh contract: with a caller-held cache, files
    whose (size, mtime) are unchanged are NOT re-parsed — only growth
    is paid for."""
    import shifu_tensorflow_tpu.obs.journal as jm

    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        for i in range(5):
            j.emit("tick", i=i)
    cache: dict = {}
    first = read_events(path, cache=cache)
    assert [e["i"] for e in first] == [0, 1, 2, 3, 4]
    # unchanged file: the parse layer must not even be consulted
    real_iter = jm.iter_events
    monkeypatch.setattr(jm, "iter_events",
                        lambda p: (_ for _ in ()).throw(AssertionError(
                            f"re-parsed unchanged {p}")))
    assert [e["i"] for e in read_events(path, cache=cache)] == [0, 1, 2, 3, 4]
    monkeypatch.setattr(jm, "iter_events", real_iter)
    # growth invalidates the cached entry and the new event appears
    with Journal(path) as j:
        j.emit("tick", i=5)
    assert [e["i"] for e in read_events(path, cache=cache)][-1] == 5


def test_read_events_cache_invalidates_across_rotation(tmp_path):
    """Satellite (PR 10): a journal rolling path→.1 while a poller holds
    a parse cache must never serve stale lines — even on a coarse-mtime
    filesystem where the NEW active file can land with the same (size,
    mtime) the cached one had.  The cache signature includes st_ino,
    which travels WITH the content across the rotation rename."""
    path = str(tmp_path / "j.jsonl")

    def write_lines(p, ts0, tags):
        # hand-rolled fixed-width lines (a Journal's float ts wobbles
        # by a byte run to run): equal line lengths -> EQUAL file sizes
        with open(p, "w") as f:
            for k, tag in enumerate(tags):
                f.write('{"ts":%.6f,"seq":%d,"event":"tick",'
                        '"tag":"%s"}\n' % (ts0 + k, k, tag))

    write_lines(path, 100.0, ["old0", "old1", "old2"])
    cache: dict = {}
    assert [e["tag"] for e in read_events(path, cache=cache)] \
        == ["old0", "old1", "old2"]
    st_old = os.stat(path)
    # the rotation: path -> path.1 (content + inode + mtime travel),
    # a fresh active file appears with same-length lines
    os.replace(path, path + ".1")
    write_lines(path, 200.0, ["new0", "new1", "new2"])
    # force the coarse-mtime collision: same size, same mtime_ns
    assert os.stat(path).st_size == st_old.st_size
    os.utime(path, ns=(st_old.st_atime_ns, st_old.st_mtime_ns))
    got = [e["tag"] for e in read_events(path, cache=cache)]
    # every event exactly once, rotation first: stale cache would have
    # yielded old0..old2 TWICE (and lost new0..new2 entirely)
    assert got == ["old0", "old1", "old2", "new0", "new1", "new2"], got


def test_obs_cli_trace_json(tmp_path, capsys):
    """Satellite: `obs trace --json` — one raw event object per line,
    CLI parity with summary/tail."""
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["trace", "rid-scored-1", "--journal", base,
                     "--json"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines, "trace --json printed nothing"
    evs = [json.loads(l) for l in lines]
    assert all(
        e.get("rid") == "rid-scored-1"
        or "rid-scored-1" in (e.get("rids") or [])
        for e in evs
    )


def test_obs_cli_tail_follow_streams_new_events(tmp_path):
    """Satellite: `obs tail --follow` — a live poller prints events as
    they land, re-reading only the growing file (parse cache)."""
    path = str(tmp_path / "j.jsonl")
    with Journal(path) as j:
        j.emit("worker_start", i=0)
    p = subprocess.Popen(
        [sys.executable, "-m", "shifu_tensorflow_tpu.obs", "tail",
         "--journal", path, "--follow", "--interval", "0.2", "--json"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    def readline_deadline(timeout_s=30.0):
        # bare readline() would hang the whole suite on a follow-mode
        # regression; a reader thread turns "no output" into a red test
        import queue

        q: queue.Queue = queue.Queue()
        threading.Thread(target=lambda: q.put(p.stdout.readline()),
                         daemon=True).start()
        try:
            return q.get(timeout=timeout_s)
        except queue.Empty:
            raise AssertionError(
                "follower printed nothing within the deadline")

    try:
        first = json.loads(readline_deadline())
        assert first["event"] == "worker_start"
        # an event appended AFTER the follower started must stream out
        with Journal(path) as j:
            j.emit("late_event", i=1)
        late = json.loads(readline_deadline())
        assert late["event"] == "late_event"
    finally:
        p.kill()
        p.wait(timeout=10)


def test_journal_install_emit_is_noop_without_install():
    journal_mod.uninstall()
    journal_mod.emit("nobody-listening", x=1)  # must not raise


def test_journal_write_failure_degrades_not_raises(tmp_path):
    j = Journal(str(tmp_path / "j.jsonl"))
    j.emit("ok")
    # simulate the disk going away mid-job: further emits drop, not raise
    os.close(j._file)
    j._file = -1
    j.emit("dropped")
    assert j.dropped == 1
    j._file = None  # avoid double-close on cleanup
    j.close()


# ---- tracer ----

def test_tracer_spans_and_budget_fields():
    t = Tracer(worker_index=2)
    with t.span("step.dispatch"):
        pass
    with t.span("step.dispatch"):
        pass
    t.add("step.infeed", 0.25)
    t.add("checkpoint.save", 1.5)
    fields = budget_fields(t.take_summary())
    assert fields["steps"] == 2
    assert fields["infeed_s"] == 0.25
    assert fields["host_s"] == 0.0
    assert fields["spans"]["checkpoint.save"]["count"] == 1
    # take_summary drained the tracer
    assert t.summary() == {}


def test_tracer_sampling_measures_every_nth():
    # sampling applies to the hot-path step.* phases only
    t = Tracer(sample_every=4)
    f = t.timed("step.host", lambda: None)
    for _ in range(8):
        f()
    s = t.summary()["step.host"]
    assert s["count"] == 2 and s["sampled_every"] == 4


def test_maybe_span_is_noop_without_tracer():
    with trace_mod.maybe_span(None, "x"):
        pass
    trace_mod.record("x", 1.0)  # no tracer installed: no-op


def test_retry_sleep_records_span():
    from shifu_tensorflow_tpu.utils import retry as retry_util

    t = trace_mod.install(Tracer())
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("nope")
        return "ok"

    pol = retry_util.RetryPolicy(max_attempts=5, base_delay_s=0.001,
                                 max_delay_s=0.002, seed=7)
    assert retry_util.call(flaky, policy=pol, site="test.seam") == "ok"
    spans = t.summary()
    assert spans["retry.sleep"]["count"] == 2


def test_checkpoint_save_restore_spans_and_events(tmp_path):
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    t = trace_mod.install(Tracer())
    j = journal_mod.install(Journal(str(tmp_path / "j.jsonl")))
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(0, 1))
    with NpzCheckpointer(str(tmp_path / "ckpt")) as ckpt:
        ckpt.save(0, trainer.state)
        restored, nxt = ckpt.restore_latest(trainer.state)
    assert nxt == 1
    spans = t.summary()
    assert spans["checkpoint.save"]["count"] == 1
    assert spans["checkpoint.restore"]["count"] == 1
    events = [e["event"] for e in read_events(str(tmp_path / "j.jsonl"))]
    assert "checkpoint_saved" in events and "checkpoint_restored" in events


# ---- trainer integration ----

def _tiny_dataset(tmp_path):
    from shifu_tensorflow_tpu.data.dataset import InMemoryDataset
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    rng = np.random.default_rng(0)
    path = tmp_path / "data.psv"
    with open(path, "w") as f:
        for _ in range(120):
            x = rng.normal(size=2)
            y = int(x[0] + 0.5 * x[1] > 0)
            f.write(f"{y}|{x[0]:.4f}|{x[1]:.4f}\n")
    schema = RecordSchema(feature_columns=(1, 2), target_column=0)
    return InMemoryDataset.load([str(path)], schema, valid_rate=0.2), schema


@pytest.mark.parametrize("scan_steps", [1, 4])
def test_trainer_journals_epoch_and_step_breakdown(tmp_path, scan_steps):
    """The acceptance loop in miniature: a traced fit emits one epoch +
    one step_breakdown event per epoch, and the breakdown's phases are
    populated (dispatch counted per device call)."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    trace_mod.install(Tracer())
    journal_mod.install(Journal(str(tmp_path / "j.jsonl"), plane="train"))
    dataset, schema = _tiny_dataset(tmp_path)
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(1, 2),
                           scan_steps=scan_steps)
    assert trainer.tracer is trace_mod.active()
    trainer.fit(dataset, epochs=2, batch_size=32)
    events = read_events(str(tmp_path / "j.jsonl"))
    epochs = [e for e in events if e["event"] == "epoch"]
    breakdowns = [e for e in events if e["event"] == "step_breakdown"]
    assert len(epochs) == 2 and len(breakdowns) == 2
    for b in breakdowns:
        assert b["steps"] > 0
        assert b["dispatch_s"] > 0.0
        assert b["infeed_s"] > 0.0
        # pipelined infeed (default): host production ran on the put
        # thread — reported as overlapped host_produce_s, with the
        # disjoint host_s phase ~0 by construction
        assert b.get("host_produce_s", 0.0) > 0.0
        assert b["host_s"] == 0.0
    assert epochs[0]["global_step"] > 0


def test_trainer_untraced_emits_nothing_and_has_no_tracer(tmp_path):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    dataset, _ = _tiny_dataset(tmp_path)
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(1, 2))
    assert trainer.tracer is None
    trainer.fit(dataset, epochs=1, batch_size=32)  # must not journal/crash


# ---- the spans on the profiler's clock ----

def _captured_events(dump_dir) -> list[list]:
    """``[name, start_ns, dur_ns]`` of the program's spans on a capture's
    host planes, by start."""
    from shifu_tensorflow_tpu.obs import profile as profile_mod

    path = profile_mod.find_xplane(str(dump_dir))
    assert path, f"no capture under {dump_dir}"
    return sorted(profile_mod.load_capture(
        path, profile_mod.STEP_PROGRAM)["host"], key=lambda e: e[1])


def _captured_spans(dump_dir) -> list[str]:
    """Names of the program's spans on a capture's host planes."""
    return [name for name, _, _ in _captured_events(dump_dir)]


def _drive_span(t):
    for _ in range(8):
        with t.span("step.host"):
            pass


def _drive_timed(t):
    f = t.timed("step.host", lambda: None)
    for _ in range(8):
        f()


def _drive_wrap_iter(t):
    # next() is called 8 times: 7 items and the StopIteration
    assert list(t.wrap_iter("step.host", range(7))) == list(range(7))


@pytest.mark.parametrize("drive", [_drive_span, _drive_timed,
                                   _drive_wrap_iter])
def test_measured_events_are_profiler_annotations(tmp_path, drive):
    """A plain Tracer (no harness subclass): what it measures is in the
    capture, what sampling skipped is not."""
    import jax

    t = Tracer(sample_every=4)
    with jax.profiler.trace(str(tmp_path)):
        drive(t)
    assert t.summary()["step.host"]["count"] == 2
    assert _captured_spans(tmp_path).count("step.host") == 2


def test_nested_spans_are_both_annotated(tmp_path):
    import jax

    t = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with t.span("checkpoint.save"):
            with t.span("retry.sleep"):
                pass
    names = _captured_spans(tmp_path)
    assert names.count("checkpoint.save") == 1
    assert names.count("retry.sleep") == 1


def test_tracer_sums_without_jax_in_the_process(monkeypatch):
    """The tracer looks JAX up in sys.modules and never imports it: a
    process without JAX (coordinator, obs CLI, load client) sums spans
    and annotates nothing; once JAX is there the next event finds it."""
    jax_mod = sys.modules.get("jax")
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    t = Tracer()
    with t.span("rpc.heartbeat"):
        pass
    assert list(t.wrap_iter("step.infeed.wait", [1, 2])) == [1, 2]
    assert t.timed("step.dispatch", lambda: 7)() == 7
    assert t._trace_annotation is None
    assert "jax" not in sys.modules
    assert t.summary()["rpc.heartbeat"]["count"] == 1
    if jax_mod is not None:
        monkeypatch.setitem(sys.modules, "jax", jax_mod)
        with t.span("rpc.heartbeat"):
            pass
        assert t._trace_annotation is jax_mod.profiler.TraceAnnotation
        assert t.summary()["rpc.heartbeat"]["count"] == 2


def test_obs_trace_and_cli_import_without_jax():
    code = ("import sys; import shifu_tensorflow_tpu.obs.trace, "
            "shifu_tensorflow_tpu.obs.profile, "
            "shifu_tensorflow_tpu.obs.__main__; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=os.path.dirname(os.path.dirname(__file__)))


@pytest.mark.parametrize("stream", [False, True])
def test_epoch_turn_is_spanned_journaled_and_annotated(tmp_path, stream):
    """What the epoch loop does outside train_epoch is `epoch.turn`: not
    a `step.` name, so it lands under the breakdown's `spans`; with the
    step's own spans it is in a capture taken around the fit."""
    import jax

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    t = trace_mod.install(Tracer())
    journal_mod.install(Journal(str(tmp_path / "j.jsonl"), plane="train"))
    dataset, _ = _tiny_dataset(tmp_path)
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(1, 2))
    with jax.profiler.trace(str(tmp_path / "dump")):
        if stream:
            trainer.fit_stream(
                lambda epoch: dataset.train_batches(32, epoch=epoch),
                epochs=2)
        else:
            trainer.fit(dataset, epochs=2, batch_size=32)
    names = _captured_spans(tmp_path / "dump")
    # fit: once after each epoch; fit_stream: before and after each
    assert names.count("epoch.turn") == (4 if stream else 2)
    assert {"step.dispatch", "step.infeed.wait", "step.infeed.put",
            "step.block"} <= set(names)
    # and nothing of the runtime's own (the CPU backend's op events are
    # dotted lower-case names too: dot_general.19)
    assert all(n.startswith(("step.", "epoch.", "checkpoint."))
               for n in names), sorted(set(names))
    breakdowns = [e for e in read_events(str(tmp_path / "j.jsonl"))
                  if e["event"] == "step_breakdown"]
    assert len(breakdowns) == 2
    # the turn after an epoch closes after that epoch's drain: it is in
    # the next breakdown (the lag every auxiliary span has)
    assert breakdowns[1]["spans"]["epoch.turn"]["count"] >= 1
    assert "epoch.turn" in t.summary()


# ---- the epoch's boundary: epoch.fill and epoch.drain ----

def _tiny_trainer(**kw):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    return make_trainer(mc, 2, feature_columns=(1, 2), **kw)


def _holds(outer, inner) -> bool:
    return (outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def _fit(trainer, dataset, how, epochs=2):
    if how == "fit":
        trainer.fit(dataset, epochs=epochs, batch_size=32)
    else:
        if how == "fit_stream_unthreaded":
            trainer.infeed_pipelined = False
        trainer.fit_stream(
            lambda epoch: dataset.train_batches(32, epoch=epoch),
            epochs=epochs)


@pytest.mark.parametrize("how", ["fit", "fit_stream",
                                 "fit_stream_unthreaded"])
def test_epoch_fill_and_drain_once_an_epoch_in_sums_journal_and_capture(
        tmp_path, how):
    """The two spans that, with `epoch.turn`, cover the consumer thread
    from one epoch's last dispatch to the next one's first: in the
    tracer's sums, under the breakdown's `spans` (not `step.` names) and
    on the profiler's clock, where `epoch.fill` holds the epoch's first
    wait for a batch and `epoch.drain` the value fetch."""
    import jax

    t = trace_mod.install(Tracer())
    journal_mod.install(Journal(str(tmp_path / "j.jsonl"), plane="train"))
    dataset, _ = _tiny_dataset(tmp_path)
    trainer = _tiny_trainer()
    with jax.profiler.trace(str(tmp_path / "dump")):
        _fit(trainer, dataset, how)
    breakdowns = [e for e in read_events(str(tmp_path / "j.jsonl"))
                  if e["event"] == "step_breakdown"]
    assert len(breakdowns) == 2
    for b in breakdowns:
        assert b["spans"]["epoch.fill"]["count"] == 1
        assert b["spans"]["epoch.drain"]["count"] == 1
        assert b["spans"]["epoch.drain"]["total_s"] >= b["block_s"]
    events = _captured_events(tmp_path / "dump")
    fills, drains, blocks = ([e for e in events if e[0] == n] for n in (
        "epoch.fill", "epoch.drain", "step.block"))
    assert len(fills) == len(drains) == len(blocks) == 2
    first = ("step.infeed" if how == "fit_stream_unthreaded"
             else "step.infeed.wait")
    for fill, drain, block in zip(fills, drains, blocks):
        inside = [e for e in events if e[0] == first and _holds(fill, e)]
        # the fill ends with the first unit in hand: it holds the first
        # wait (unthreaded: the placements that fill the look-ahead) and
        # no later one, and every dispatch comes after it
        assert inside and inside[0] is next(
            e for e in events if e[0] == first and e[1] >= fill[1])
        assert all(e[1] >= fill[1] + fill[2] or e[1] < fill[1]
                   for e in events if e[0] == "step.dispatch")
        assert _holds(drain, block)
        assert fill[1] + fill[2] <= drain[1]
    if how != "fit_stream_unthreaded":
        # only the first wait of an epoch is the fill's
        waits = [e for e in events if e[0] == "step.infeed.wait"]
        assert sum(any(_holds(f, w) for f in fills) for w in waits) == 2
        assert len(waits) > 2
    # the journal drained both epochs' sums: what is left is the last turn
    assert not {"epoch.fill", "epoch.drain"} & set(t.summary())


def test_epoch_drain_in_the_device_resident_fit(tmp_path):
    """One dispatch an epoch over tensors placed once: no feed to fill;
    the fetch and the mean are the drain."""
    t = trace_mod.install(Tracer())
    dataset, _ = _tiny_dataset(tmp_path)
    _tiny_trainer().fit_device_resident(dataset, epochs=3, batch_size=32)
    summary = t.summary()
    assert summary["epoch.drain"]["count"] == 3
    assert summary["step.block"]["count"] == 3
    assert summary["epoch.drain"]["total_s"] >= summary["step.block"][
        "total_s"]
    assert "epoch.fill" not in summary


@pytest.mark.parametrize("empty", [False, True])
def test_an_epoch_that_raises_or_is_empty_closes_both_spans(tmp_path,
                                                            empty):
    """A stream that raises mid-loop: `epoch.fill` closed when the first
    unit came, `epoch.drain` opens on the way out, joins the put thread
    inside it and closes with the exception in flight.  An empty stream:
    both close, and nothing is fetched."""
    import jax

    t = trace_mod.install(Tracer())
    dataset, _ = _tiny_dataset(tmp_path)
    trainer = _tiny_trainer()

    def batches():
        if empty:
            return
        for k, b in enumerate(dataset.train_batches(32, epoch=0)):
            if k == 2:
                raise RuntimeError("the stream broke")
            yield b

    with jax.profiler.trace(str(tmp_path / "dump")):
        if empty:
            loss, n = trainer.train_epoch(batches())
            assert n == 0 and np.isnan(loss)
        else:
            with pytest.raises(RuntimeError, match="the stream broke"):
                trainer.train_epoch(batches())
    summary = t.summary()
    assert summary["epoch.fill"]["count"] == 1
    assert summary["epoch.drain"]["count"] == 1
    assert "step.block" not in summary  # nothing fetched on either way out
    assert not [th for th in threading.enumerate()
                if th.name == "stpu-infeed-put"]
    events = _captured_events(tmp_path / "dump")
    fill, drain = (next(e for e in events if e[0] == n)
                   for n in ("epoch.fill", "epoch.drain"))
    assert fill[1] + fill[2] <= drain[1]
    # the put thread's last event ends before the drain does: it was
    # joined inside the span
    puts = [e for e in events if e[0] in ("step.infeed.put",
                                          "step.host.produce")]
    assert all(e[1] + e[2] <= drain[1] + drain[2] for e in puts)
    assert len([e for e in events if e[0] == "step.dispatch"]) == (
        0 if empty else 2)


def test_without_a_tracer_the_epoch_loop_opens_no_span(tmp_path,
                                                       monkeypatch):
    """No tracer installed: each site is `maybe_span`'s `is None` check
    and the shared null context, on every path of the loop."""
    opened = []
    real = trace_mod.maybe_span

    def spy(tracer, name):
        cm = real(tracer, name)
        opened.append((name, cm))
        return cm

    monkeypatch.setattr(trace_mod, "maybe_span", spy)
    dataset, _ = _tiny_dataset(tmp_path)
    trainer = _tiny_trainer()
    assert trainer.tracer is None
    trainer.fit(dataset, epochs=2, batch_size=32)
    names = [n for n, _ in opened]
    assert names.count("epoch.fill") == names.count("epoch.drain") == 2
    assert all(cm is trace_mod._NULL_CM for _, cm in opened)


def test_the_harness_tracer_names_both_and_annotates_each_once(tmp_path):
    """`benchmark/tracing.py`'s subclass, as it stands: the trainer's new
    spans go through `Tracer.span`, so it names them and puts each event
    in the capture once (not once by the base class and once by it)."""
    import jax

    from benchmark.tracing import AnnotatingTracer

    t = trace_mod.install(AnnotatingTracer())
    dataset, _ = _tiny_dataset(tmp_path)
    trainer = _tiny_trainer()
    with jax.profiler.trace(str(tmp_path / "dump")):
        _fit(trainer, dataset, "fit_stream")
    assert {"epoch.fill", "epoch.drain", "epoch.turn"} <= t.names
    names = [e[0] for e in _captured_events(tmp_path / "dump")]
    for name in ("epoch.fill", "epoch.drain"):
        assert names.count(name) == 2
        assert t.cumulative()[name]["count"] == 2
    assert names.count("step.block") == 2


# ---- CLI ----

def _seed_cli_journal(tmp_path) -> str:
    base = str(tmp_path / "job.jsonl")
    with Journal(base, plane="coordinator") as j:
        j.emit("register", worker=0, worker_id="w-0", generation=0)
        j.emit("rollback", worker=0, epoch=1, rollbacks=1, lr_scale=0.5)
    with Journal(f"{base}.w0", plane="train", worker=0) as jw:
        jw.emit("epoch", epoch=0, train_loss=0.4, train_time_s=2.0)
        jw.emit("step_breakdown", epoch=0, steps=10, infeed_s=0.2,
                host_s=0.3, dispatch_s=1.2, block_s=0.1,
                spans={"rpc.epoch": {"count": 1, "total_s": 0.05}})
    return base


def test_obs_cli_summary_renders_budget_and_timeline(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_cli_journal(tmp_path)
    assert obs_main(["summary", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "per-step time budget" in out
    assert "fleet timeline" in out
    assert "register" in out and "rollback" in out
    # the budget row: 1.2s dispatch of a 2.0s epoch wall = 60%
    assert "60.0" in out
    assert "rpc.epoch 1x 0.050s" in out


def test_obs_cli_summary_renders_serve_plane(tmp_path, capsys):
    """The serve plane renders per-worker from journal events alongside
    the train/fleet views: request volume + rate, shed pressure, reload
    outcomes, and the --serve-workers split."""
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_cli_journal(tmp_path)  # train events: plane must coexist
    with Journal(base + ".sup", plane="serve") as sup:
        pass  # (unmatched name: must NOT be swept in)
    with Journal(base, plane="serve") as j:
        j.emit("serve_fleet_start", port=9100, workers=2)
        j.emit("serve_worker_restart", index=1, restarts=1)
    for s, reqs in ((0, 120), (1, 80)):
        with Journal(f"{base}.s{s}", plane="serve", worker=s) as js:
            js.emit("serve_start", port=9100)
            js.emit("reload", epoch=1, digest="abc", verified=True)
            if s == 1:
                js.emit("reload_refused", why="weights.npz: sha256 differs")
                js.emit("shed", queue_rows=64, shed_total=17)
            js.emit("serve_stop", requests_total=reqs, shed_total=17 * s)
    assert obs_main(["summary", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "serve plane" in out
    assert "fleet: 2 workers, 1 restart(s)" in out
    lines = [ln for ln in out.splitlines() if ln.strip().startswith(("0 ", "1 "))]
    serve_rows = {ln.split()[0]: ln.split() for ln in lines}
    assert serve_rows["0"][1] == "120"
    assert serve_rows["1"][1] == "80"
    assert serve_rows["1"][3] == "17"   # shed column
    assert serve_rows["1"][5] == "1"    # refused column
    # the train budget and timeline still render beside it
    assert "per-step time budget" in out and "fleet timeline" in out


def test_obs_cli_tail_shows_last_events(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_cli_journal(tmp_path)
    assert obs_main(["tail", "--journal", base, "-n", "2"]) == 0
    out = capsys.readouterr().out
    assert len(out.strip().splitlines()) == 2


def test_obs_cli_missing_journal_fails_cleanly(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    assert obs_main(["summary", "--journal",
                     str(tmp_path / "nope.jsonl")]) == 1
    assert "no journal events" in capsys.readouterr().err


def _seed_trace_journal(tmp_path) -> str:
    """A journal with one scored request (rid riding a serve_batch), one
    shed rid, and slo transitions — the trace/top fixtures."""
    base = str(tmp_path / "job.jsonl")
    with Journal(base, plane="coordinator", job="j1") as j:
        j.emit("register", worker=0, worker_id="w-0")
        j.emit("epoch_summary", epoch=1, n_workers=1, ks=0.31)
    with Journal(f"{base}.w0", plane="train", worker=0, job="j1") as jw:
        jw.emit("epoch", epoch=1, train_loss=0.4, train_time_s=1.0,
                global_step=20)
        jw.emit("step_breakdown", epoch=1, steps=10, infeed_s=0.1,
                host_s=0.1, dispatch_s=0.7, block_s=0.1, global_step=20)
    with Journal(f"{base}.s0", plane="serve", worker=0, job="j1") as js:
        js.emit("serve_start", port=9100)
        js.emit("serve_batch", rids=["rid-scored-1", "rid-peer"],
                requests=2, rows=3, bucket=4, queue_delay_s=0.004,
                dispatch_s=0.002)
        js.emit("shed", rid="rid-shed-1", queue_rows=64, shed_total=9)
        js.emit("slo_breach", signal="serve_shed_rate", value=0.4,
                target=0.2, window_s=5.0,
                window={"count": 50, "p99": 0.4})
        js.emit("slo_recover", signal="serve_shed_rate", value=0.0,
                target=0.2, breach_s=3.5)
        js.emit("serve_stop", requests_total=40, shed_total=9)
    return base


def test_obs_cli_summary_and_tail_json(tmp_path, capsys):
    """Satellite: machine-readable output — the autoscaling supervisor
    must not screen-scrape the human renderer."""
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["summary", "--journal", base, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["jobs"] == ["j1"]
    assert doc["counts"]["serve_batch"] == 1
    assert doc["budget"]["0"]["steps"] == 10
    assert doc["budget"]["0"]["pct"]["dispatch"] == 70.0
    assert doc["serve"]["workers"]["0"]["requests"] == 40
    slo = doc["slo"]["serve_shed_rate"]
    assert slo["breaches"] == 1 and slo["breached"] is False
    assert obs_main(["tail", "--journal", base, "-n", "3", "--json"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3
    assert all(json.loads(l)["event"] for l in lines)


def test_obs_cli_summary_renders_slo_section(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["summary", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "slo" in out and "serve_shed_rate" in out
    # recovered by the journal's last transition: renders ok, not BREACHED
    assert "BREACHED" not in out


def test_obs_cli_trace_resolves_rid(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["trace", "rid-scored-1", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "serve_batch" in out and "rid-scored-1" in out
    assert "coalesced into a 3-row dispatch" in out
    # a shed request's id resolves to its shed event
    assert obs_main(["trace", "rid-shed-1", "--journal", base]) == 0
    assert "shed" in capsys.readouterr().out
    # an unknown rid is a clean failure, not a stack trace
    assert obs_main(["trace", "rid-nope", "--journal", base]) == 1
    assert "no events for rid" in capsys.readouterr().err


def test_obs_cli_trace_colon_rid_falls_back(tmp_path, capsys):
    """The serve sanitizer strips ':' from new rids, but a hand-written
    or legacy journal may carry one — a worker:epoch-shaped query that
    matches nothing falls back to a rid match."""
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = str(tmp_path / "j.jsonl")
    with Journal(base, plane="serve", worker=0) as j:
        j.emit("serve_batch", rids=["12:3"], requests=1, rows=1, bucket=8)
    assert obs_main(["trace", "12:3", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "rid 12:3" in out and "serve_batch" in out


def test_obs_cli_trace_worker_epoch(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["trace", "0:1", "--journal", base]) == 0
    out = capsys.readouterr().out
    # the worker's epoch + breakdown AND the coordinator's quorum record
    # merge into one causal story
    assert "step_breakdown" in out and "epoch_summary" in out
    assert "global_step=20" in out


def test_obs_cli_top_once_renders_all_sections(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main as obs_main

    base = _seed_trace_journal(tmp_path)
    assert obs_main(["top", "--journal", base, "--once"]) == 0
    out = capsys.readouterr().out
    assert "obs top" in out and "job j1" in out
    assert "slo" in out and "serve_shed_rate" in out
    assert "train" in out and "serve" in out
    assert "recent events" in out
    # dead-fleet contract: an unreachable metrics URL must not break it
    assert obs_main(["top", "--journal", base, "--once",
                     "--metrics-url", "http://127.0.0.1:9/metrics"]) == 0
    assert "scraped 0/1" in capsys.readouterr().out


# ---- ObsConfig ----

def test_obs_config_json_bridge_roundtrip():
    cfg = ObsConfig(enabled=True, journal_path="/tmp/j.jsonl",
                    journal_max_bytes=1 << 20, journal_max_files=2,
                    trace_sample=3, hist_buckets=(0.001, 0.01, 0.1))
    assert ObsConfig.from_json(json.loads(json.dumps(cfg.to_json()))) == cfg


def test_obs_config_rejects_misconfiguration():
    with pytest.raises(ValueError, match="obs-trace-sample"):
        ObsConfig(trace_sample=0)
    with pytest.raises(ValueError, match="obs-journal-max-files"):
        ObsConfig(journal_max_files=0)
    with pytest.raises(ValueError, match="obs-hist-buckets"):
        ObsConfig(hist_buckets=(0.1, 0.01))
    with pytest.raises(ValueError, match="obs-journal-max-bytes"):
        ObsConfig(journal_max_bytes=100)


def test_install_obs_wires_worker_sibling_paths(tmp_path):
    from shifu_tensorflow_tpu.obs import install_obs

    cfg = ObsConfig(enabled=True, journal_path=str(tmp_path / "j.jsonl"))
    tracer, j = install_obs(cfg, worker_index=2, plane="train")
    assert tracer is trace_mod.active() and tracer.worker_index == 2
    assert j.path.endswith(".w2") and j.worker == 2
    journal_mod.emit("hello")
    journal_mod.uninstall()
    assert read_events(str(tmp_path / "j.jsonl"))[0]["worker"] == 2
    # disabled config installs nothing
    assert install_obs(ObsConfig()) == (None, None)


# ---- review-fix regressions ----

def test_budget_fields_scales_sampled_step_phases():
    """trace-sample=N measures 1/N of step events; the journal must carry
    unbiased ABSOLUTE estimates or the CLI budget overstates step_ms by N."""
    t = Tracer(sample_every=4)
    f = t.timed("step.infeed", lambda: None)
    for _ in range(8):
        f()
        with t.span("step.dispatch"):
            pass
    t.add("retry.sleep", 0.5)  # aux spans are never sampled
    fields = budget_fields(t.take_summary())
    assert fields["steps"] == 8  # 2 measured x 4
    assert fields["trace_sample"] == 4
    assert fields["spans"]["retry.sleep"]["count"] == 1


def test_aux_spans_are_never_sampled():
    t = Tracer(sample_every=10)
    for _ in range(3):
        with t.span("checkpoint.save"):
            pass
    assert t.summary()["checkpoint.save"]["count"] == 3


def test_journal_survives_persistent_rotation_failure(tmp_path):
    """Rotation failing forever (dir lost write permission) must degrade
    to append-past-the-cap, not recurse to a crash."""
    path = str(tmp_path / "j.jsonl")
    j = Journal(path, max_bytes=4096, max_files=3)
    j._rotate = lambda: None  # every rotation attempt silently fails
    for i in range(500):
        j.emit("tick", i=i, pad="x" * 40)
    j.close()
    events = read_events(path)
    assert events[-1]["i"] == 499  # nothing lost, nothing raised
    assert os.path.getsize(path) > 4096  # bound degraded, job alive


def test_hist_buckets_reach_scrape_registries(tmp_path):
    """shifu.tpu.obs-hist-buckets must actually drive the histograms the
    scrape surfaces build (it was once resolved-but-dead)."""
    from shifu_tensorflow_tpu.obs import install_obs
    from shifu_tensorflow_tpu.obs import registry as registry_mod
    from shifu_tensorflow_tpu.serve.metrics import ServeMetrics

    try:
        install_obs(ObsConfig(enabled=True, hist_buckets=(0.5, 1.0)))
        m = ServeMetrics()
        m.request_latency.record(0.7)
        assert m.request_latency.percentile(99) == 1.0  # custom ladder
        snap = m.request_latency.snapshot()
        assert set(snap["buckets"]) == {"0.5", "1.0", "+Inf"}
    finally:
        registry_mod.set_default_bounds(None)


def test_run_worker_does_not_clobber_shared_process_obs(tmp_path):
    """Thread-launcher seam: a worker sharing the submitter's process must
    NOT replace the installed journal/tracer (coordinator events would be
    misattributed and the journal fd leaked) — it gets a private tracer
    and emits into the shared journal with explicit plane/worker."""
    from shifu_tensorflow_tpu.obs import journal as jm
    from shifu_tensorflow_tpu.obs import trace as tm

    base = str(tmp_path / "job.jsonl")
    shared_j = jm.install(Journal(base, plane="coordinator"))
    shared_t = tm.install(Tracer(worker_index=0))
    # simulate the run_worker install-guard branch
    from shifu_tensorflow_tpu.obs.config import ObsConfig as OC

    cfg = OC(enabled=True, journal_path=base)
    assert jm.active() is shared_j and tm.active() is shared_t
    # the guard condition run_worker checks:
    assert not (jm.active() is None and tm.active() is None)
    jm.emit("epoch", plane="train", worker=1)
    jm.uninstall()
    tm.uninstall()
    ev = read_events(base)[0]
    assert ev["plane"] == "train" and ev["worker"] == 1


# ---- fleet leg: clock sync, journal offsets, comm spans, CLI ----

def test_clock_sync_symmetric_exchange_recovers_offset():
    from shifu_tensorflow_tpu.obs.fleet import ClockSync

    cs = ClockSync()
    # frozen clocks: server 5s AHEAD, 10ms symmetric network legs, 2s of
    # server processing (a barrier hold) — processing must cancel exactly
    assert cs.offset() is None
    cs.update(t0=100.0, t1=105.010, t2=107.010, t3=102.020)
    assert cs.offset() == pytest.approx(5.0, abs=1e-9)
    assert cs.delay() == pytest.approx(0.020, abs=1e-9)


def test_clock_sync_asymmetric_latency_error_bounded_by_half_delay():
    from shifu_tensorflow_tpu.obs.fleet import ClockSync

    cs = ClockSync()
    # request leg 10ms, reply leg 50ms: the symmetric assumption is off
    # by (50-10)/2 = 20ms — exactly the NTP bound delay/2 = 30ms
    cs.update(t0=100.0, t1=105.010, t2=105.010, t3=100.060)
    err = abs(cs.offset() - 5.0)
    assert err <= cs.delay() / 2 + 1e-12
    assert err == pytest.approx(0.020, abs=1e-9)
    # a later LOW-delay exchange wins over the congested one
    cs.update(t0=200.0, t1=205.001, t2=205.001, t3=200.002)
    assert cs.offset() == pytest.approx(5.0, abs=1e-3)
    assert cs.delay() == pytest.approx(0.002, abs=1e-9)


def test_clock_sync_rejects_garbage_and_resets():
    from shifu_tensorflow_tpu.obs.fleet import ClockSync

    cs = ClockSync()
    assert cs.update(1.0, None, 2.0, 3.0) is None
    assert cs.update(10.0, 5.0, 4.0, 11.0) is None  # t2 < t1
    assert cs.offset() is None
    cs.update(100.0, 105.0, 105.0, 100.1)
    assert cs.offset() is not None
    # worker restart semantics: a fresh estimator has no carry-over
    cs.reset()
    assert cs.offset() is None and cs.delay() is None


def test_client_clock_resets_with_the_client():
    """A relaunched worker builds a fresh CoordinatorClient; its clock
    estimate must not survive the process whose clock it described."""
    from shifu_tensorflow_tpu.coordinator.coordinator import (
        CoordinatorClient,
    )

    c1 = CoordinatorClient("127.0.0.1", 1)
    c1.clock.update(100.0, 105.0, 105.0, 100.1)
    assert c1.clock_offset() is not None
    c2 = CoordinatorClient("127.0.0.1", 1)
    assert c2.clock_offset() is None


def test_journal_stamps_offset_once_known(tmp_path):
    base = str(tmp_path / "off.jsonl")
    j = Journal(base, plane="train", worker=1)
    j.emit("before")
    j.set_offset(0.125)
    j.emit("after")
    j.set_offset(None)
    j.emit("cleared")
    j.close()
    evs = read_events(base)
    assert "offset" not in evs[0]
    assert evs[1]["offset"] == pytest.approx(0.125)
    assert "offset" not in evs[2]


def test_note_offset_reaches_active_journal(tmp_path):
    from shifu_tensorflow_tpu.obs import fleet as fleet_mod

    base = str(tmp_path / "noted.jsonl")
    journal_mod.install(Journal(base, plane="train", worker=0))
    fleet_mod.note_offset(0.25)
    assert fleet_mod.clock_offset() == pytest.approx(0.25)
    journal_mod.emit("ev", plane="train")
    journal_mod.uninstall()
    assert read_events(base)[0]["offset"] == pytest.approx(0.25)


def test_comm_region_records_span_bytes_and_epoch_drain():
    from shifu_tensorflow_tpu.obs import fleet as fleet_mod

    t = trace_mod.install(Tracer(worker_index=0))
    fleet_mod.take_comm()  # drain residue other tests' collectives left
    with fleet_mod.comm_region("ring_attention", nbytes=1024):
        pass
    with fleet_mod.comm_region("ring_attention", nbytes=1024):
        pass
    summ = t.summary()
    assert summ["comm.ring_attention"]["count"] == 2
    drained = fleet_mod.take_comm()
    assert drained["ring_attention"] == {"calls": 2, "bytes": 2048}
    # the per-epoch drain resets; the scrape-surface totals do not
    # (process-lifetime counters — assert presence, not a value other
    # tests' collectives would shift)
    assert fleet_mod.take_comm() == {}
    assert 'fleet_comm_bytes_total{kind="ring_attention"}' in \
        fleet_mod.comm_text()


def test_shard_map_calls_run_under_comm_region():
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.parallel.shmap import shard_map
    from jax.sharding import PartitionSpec as P

    t = trace_mod.install(Tracer(worker_index=0))
    mesh = make_mesh("data:-1")

    def double(x):
        return x * 2

    fn = shard_map(double, mesh, in_specs=(P("data"),), out_specs=P("data"))
    out = fn(jnp.ones((8, 2)))
    assert out.shape == (8, 2)
    assert "comm.shmap.double" in t.summary()
    # call sites that run their own comm region can opt out
    bare = shard_map(double, mesh, in_specs=(P("data"),),
                     out_specs=P("data"), comm_label=None)
    t.take_summary()
    bare(jnp.ones((8, 2)))
    assert "comm.shmap.double" not in t.summary()


def _write_fleet_journal(tmp_path):
    base = str(tmp_path / "fleet.jsonl")
    j = Journal(base, plane="coordinator")
    j.emit("register", worker=0)
    j.emit("straggler_detect", worker=1, epoch=2, skew=2.5,
           phase="infeed", step_s=0.9, fleet_step_s=0.36, threshold=1.5)
    j.emit("fleet_skew", epoch=2, n_workers=2, max_skew=2.5, straggler=1,
           ranks={"0": {"step_s": 0.36, "skew": 0.4, "phase": "dispatch",
                        "straggler": False, "epoch": 2,
                        "offset_s": 0.0001},
                  "1": {"step_s": 0.9, "skew": 2.5, "phase": "infeed",
                        "straggler": True, "epoch": 2, "barrier_s": 0.01,
                        "offset_s": -0.002}})
    j.emit("comm", plane="train", worker=1, epoch=2,
           kinds={"ring_attention": {"calls": 4, "bytes": 4096}})
    j.emit("straggler_clear", worker=1, epoch=7, skew=1.1,
           straggler_s=12.5, since_epoch=2)
    j.close()
    return base


def test_obs_cli_fleet_renders_table_and_excursions(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main

    base = _write_fleet_journal(tmp_path)
    assert main(["fleet", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "fleet skew" in out
    assert "STRAGGLER" in out or "straggler: worker 1" in out
    assert "infeed" in out
    assert "ring_attention" in out
    # machine-readable: excursion carries detect AND clear coordinates
    assert main(["fleet", "--journal", base, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    exc = doc["excursions"][0]
    assert exc["worker"] == 1 and exc["phase"] == "infeed"
    assert exc["clear_epoch"] == 7 and exc["straggler_s"] == 12.5
    assert doc["ranks"]["1"]["skew"] == 2.5
    assert doc["comm"]["ring_attention"]["bytes"] == 4096


def test_obs_cli_fleet_clean_miss(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main

    base = str(tmp_path / "empty.jsonl")
    j = Journal(base, plane="train")
    j.emit("worker_start", worker=0)
    j.close()
    assert main(["fleet", "--journal", base]) == 1
    assert "no fleet events" in capsys.readouterr().out


def test_obs_cli_top_renders_fleet_panel(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main

    base = _write_fleet_journal(tmp_path)
    assert main(["top", "--once", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "fleet" in out
    assert "STRAGGLER" in out


def test_obs_cli_summary_renders_fleet_section(tmp_path, capsys):
    from shifu_tensorflow_tpu.obs.__main__ import main

    base = _write_fleet_journal(tmp_path)
    assert main(["summary", "--journal", base]) == 0
    assert "fleet skew" in capsys.readouterr().out
    assert main(["summary", "--journal", base, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["fleet"]["ranks"]["1"]["straggler"] is True


def test_obs_cli_trace_renders_offset_aligned(tmp_path, capsys):
    """Two writers whose wall clocks disagree by 10s: the raw merge
    interleaves wrong, the offset-aligned trace restores causality —
    and --json preserves the raw clocks untouched."""
    import time as _time

    from shifu_tensorflow_tpu.obs.__main__ import main

    base = str(tmp_path / "aligned.jsonl")
    now = _time.time()
    coord = Journal(base, plane="coordinator")
    # worker 1's clock runs 10s BEHIND the coordinator: offset=+10
    w1 = Journal(base + ".w1", plane="train", worker=1)
    w1.set_offset(10.0)
    # hand-build timestamps: the coordinator publishes the epoch at
    # now+1; the worker's step_breakdown happened at now+0.5 REAL time
    # but its skewed clock wrote now-9.5
    coord._file = None  # force open at emit
    import json as _json
    import os as _os

    def raw(journal_path, rec):
        with open(journal_path, "a") as f:
            f.write(_json.dumps(rec) + "\n")

    raw(base, {"ts": now + 1.0, "seq": 0, "event": "epoch_summary",
               "plane": "coordinator", "epoch": 3})
    raw(base + ".w1", {"ts": now - 9.5, "seq": 0, "event":
                       "step_breakdown", "plane": "train", "worker": 1,
                       "epoch": 3, "offset": 10.0, "steps": 4})
    coord.close()
    w1.close()
    assert main(["trace", "1:3", "--journal", base]) == 0
    out = capsys.readouterr().out
    assert "offset-aligned" in out
    # aligned: the worker event (+0.5) renders BEFORE the coordinator's
    # (+1.0) despite its raw ts sorting 10.5s earlier
    lines = [ln for ln in out.splitlines() if "+" in ln]
    bd = next(i for i, ln in enumerate(lines) if "step_breakdown" in ln)
    es = next(i for i, ln in enumerate(lines) if "epoch_summary" in ln)
    assert bd < es
    assert main(["trace", "1:3", "--journal", base, "--json"]) == 0
    docs = [json.loads(ln) for ln in
            capsys.readouterr().out.splitlines()]
    w1_ev = next(d for d in docs if d["event"] == "step_breakdown")
    assert w1_ev["ts"] == pytest.approx(now - 9.5)  # raw clock preserved
    assert w1_ev["offset"] == 10.0
