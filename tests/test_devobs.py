"""Device & compiler observability (PR 10): the compile flight recorder
(obs/compile.py), the device-memory accountant (obs/memory.py), the
on-demand profiler window (obs/profile.py), and their CLI renders.

Every test that installs a process-global recorder/accountant/journal
uninstalls it — the hooks are shared state by design.
"""

from __future__ import annotations

import io
import json
import os
import time
from contextlib import redirect_stderr, redirect_stdout

import numpy as np
import pytest

from shifu_tensorflow_tpu.obs import compile as compile_mod
from shifu_tensorflow_tpu.obs import journal as journal_mod
from shifu_tensorflow_tpu.obs import memory as memory_mod
from shifu_tensorflow_tpu.obs import profile as profile_mod
from shifu_tensorflow_tpu.obs import slo as slo_mod
from shifu_tensorflow_tpu.obs.journal import Journal, read_events


@pytest.fixture(autouse=True)
def _clean_hooks():
    yield
    compile_mod.uninstall()
    memory_mod.uninstall()
    journal_mod.uninstall()
    slo_mod.uninstall()
    profile_mod.unconfigure()


def _journal(tmp_path, plane="train"):
    path = str(tmp_path / "journal.jsonl")
    journal_mod.install(Journal(path, plane=plane))
    return path


def _recorder(plane="train", **kw) -> compile_mod.CompileRecorder:
    return compile_mod.install(
        compile_mod.CompileRecorder(plane=plane, **kw))


# ---- compile flight recorder ----

def test_observed_jit_journals_one_compile_event_per_signature(tmp_path):
    """Each NEW abstract signature journals exactly one `compile` event
    carrying the signature, timing, and the backend's cost/memory
    analysis; cache hits journal nothing."""
    import jax
    import jax.numpy as jnp

    path = _journal(tmp_path)
    _recorder()
    f = compile_mod.observe(jax.jit(lambda x: (x * 2).sum()),
                            "unit.fn")
    f(jnp.ones((8, 4)))
    f(jnp.ones((8, 4)))   # dispatch-cache hit: no event
    f(jnp.ones((16, 4)))  # new shape: one more event
    journal_mod.uninstall()
    evs = [e for e in read_events(path) if e["event"] == "compile"]
    assert len(evs) == 2
    sigs = {e["signature"] for e in evs}
    assert sigs == {"float32[8,4]", "float32[16,4]"}
    for e in evs:
        assert e["name"] == "unit.fn"
        assert e["compile_s"] > 0
        assert e["wall_s"] >= e["compile_s"] * 0.1  # same order, sane
        assert e["backend"] == "cpu"
        # CPU provides both analyses (memory_analysis code bytes may be
        # 0 on CPU, but the argument/output fields are real)
        assert e["flops"] > 0
        assert e["arg_bytes"] > 0
        assert "temp_bytes" in e


def test_observed_jit_with_recorder_off_is_transparent():
    import jax
    import jax.numpy as jnp

    calls = []

    def raw(x):
        calls.append(1)
        return x + 1

    f = compile_mod.observe(jax.jit(raw), "unit.fn")
    out = f(jnp.ones(3))
    assert np.allclose(np.asarray(out), 2.0)
    # attribute proxying: jit introspection still works through the wrap
    assert f._cache_size() == 1
    assert f.__wrapped__ is not None


def test_analysis_off_still_journals_timing(tmp_path):
    import jax
    import jax.numpy as jnp

    path = _journal(tmp_path)
    _recorder(analysis="off")
    f = compile_mod.observe(jax.jit(lambda x: x * 3), "unit.fn")
    f(jnp.ones((4,)))
    journal_mod.uninstall()
    (ev,) = [e for e in read_events(path) if e["event"] == "compile"]
    assert ev["compile_s"] > 0
    assert "flops" not in ev and "arg_bytes" not in ev


def test_executable_registry_and_gauges(tmp_path):
    _journal(tmp_path)
    rec = _recorder()
    rec.record(name="a", signature="s1", compile_s=0.5)
    rec.record(name="a", signature="s1", compile_s=0.25)  # re-compile
    rec.record(name="a", signature="s2", compile_s=0.5, code_bytes=1024)
    rec.record(name="b", signature="s1", compile_s=1.0, code_bytes=2048)
    s = rec.state()
    assert s["live_executables"] == 3  # (a,s1), (a,s2), (b,s1)
    assert s["compile_seconds_total"] == pytest.approx(2.25)
    assert s["executable_bytes"] == 1024 + 2048
    text = rec.render_prometheus()
    assert "stpu_compile_live_executables 3" in text
    assert "stpu_compile_executable_bytes 3072" in text
    assert "stpu_compile_storm_active 0" in text


def test_compile_events_feed_slo_compile_s_signal(tmp_path):
    from shifu_tensorflow_tpu.obs.config import ObsConfig

    _journal(tmp_path)
    wd = slo_mod.install(slo_mod.from_config(
        ObsConfig(enabled=True, slo_compile_s=1.0, slo_hysteresis=1),
        plane="train"))
    rec = _recorder()
    rec.record(name="a", signature="s", compile_s=2.0)
    events = wd.evaluate()
    assert any(e["event"] == "slo_breach" and e["signal"] == "compile_s"
               for e in events)


def test_recompile_storm_opens_names_culprit_and_clears(tmp_path):
    path = _journal(tmp_path)
    rec = _recorder(storm_window_s=60.0, storm_threshold=4)
    t0 = 1000.0
    # a churning callable + one innocent bystander
    rec.record(name="innocent", signature="x", compile_s=0.01, now=t0)
    for i in range(4):
        rec.record(name="eval.native_score",
                   signature=f"float32[{i + 3},6]",
                   compile_s=0.01, now=t0 + 1 + i)
    assert rec.state()["storm_active"] is True
    assert rec.state()["storms_total"] == 1
    # compiles stop; the tick (epoch / slo-loop seam) clears the storm
    rec.tick(now=t0 + 300)
    assert rec.state()["storm_active"] is False
    journal_mod.uninstall()
    evs = read_events(path)
    storm = next(e for e in evs if e["event"] == "recompile_storm")
    clear = next(e for e in evs if e["event"] == "recompile_storm_clear")
    # the storm names the CHURNING signature, not the bystander
    assert storm["culprit"] == "eval.native_score"
    assert storm["signature"].startswith("float32[")
    assert storm["compiles_in_window"] >= 4
    # the clear still names the storm's culprit (the window is empty by
    # then — "who churned" must not degrade to '?')
    assert clear["culprit"] == "eval.native_score"
    assert clear["storm_s"] > 0


def test_warm_compiles_never_count_toward_a_storm(tmp_path):
    _journal(tmp_path)
    rec = _recorder(storm_window_s=60.0, storm_threshold=3)
    t0 = 2000.0
    with compile_mod.warm_section():
        for i in range(10):
            rec.record(name="eval.native_score", signature=f"w{i}",
                       compile_s=0.01, kind="warm", now=t0 + i)
    assert rec.state()["storm_active"] is False
    # explicit kind="warm" (no section) is excluded too
    for i in range(10):
        rec.record(name="eval.native_score", signature=f"v{i}",
                   compile_s=0.01, kind="warm", now=t0 + 20 + i)
    assert rec.state()["storm_active"] is False


def test_eval_model_warm_journals_warm_compiles(tmp_path):
    """The serve warm ladder journals kind="warm" compile events with
    bucket + model attribution, and the pinned trace-count contract
    survives the observe() wrap."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.eval_model import EvalModel
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    t = Trainer(mc, 5)
    bundle = str(tmp_path / "m")
    export_native_bundle(bundle, t.state.params, mc, 5)

    path = _journal(tmp_path, plane="serve")
    _recorder(plane="serve")
    m = EvalModel(bundle, backend="native")
    assert m.warm((8, 16)) == 2
    assert m.warm((8, 16)) == 0  # already compiled: no new traces
    m.compute_batch(np.zeros((3, 5), np.float32))  # pads into bucket 8
    journal_mod.uninstall()
    evs = [e for e in read_events(path) if e["event"] == "compile"]
    assert len(evs) == 2  # the two warm buckets; the padded call hit
    assert {e["bucket"] for e in evs} == {8, 16}
    assert all(e["kind"] == "warm" for e in evs)
    assert all(e["model"] == "m" for e in evs)
    m.release()


def test_ladder_disabled_knob_reproduces_raw_shape_churn(tmp_path):
    """STPU_NO_BUCKET (the storm drill's lever) makes bucket_size the
    identity: distinct batch lengths each compile their own program and
    the storm detector names the scorer."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export import bucketing
    from shifu_tensorflow_tpu.export.eval_model import EvalModel
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    t = Trainer(mc, 5)
    bundle = str(tmp_path / "m")
    export_native_bundle(bundle, t.state.params, mc, 5)

    path = _journal(tmp_path, plane="serve")
    rec = _recorder(plane="serve", storm_window_s=60.0, storm_threshold=4)
    m = EvalModel(bundle, backend="native")
    bucketing.set_ladder_disabled(True)
    try:
        for n in (1, 2, 3, 4, 5):
            m.compute_batch(np.zeros((n, 5), np.float32))
    finally:
        bucketing.set_ladder_disabled(False)
    assert m.native_trace_count == 5  # the unpadded-shape bug, on purpose
    assert rec.state()["storm_active"] is True
    # ladder back on: the same request mix collapses to one bucket
    before = m.native_trace_count
    for n in (1, 2, 3):
        m.compute_batch(np.zeros((n, 5), np.float32))
    assert m.native_trace_count == before + 1  # bucket 8, once
    journal_mod.uninstall()
    storm = next(e for e in read_events(path)
                 if e["event"] == "recompile_storm")
    assert storm["culprit"] == "eval.native_score"
    m.release()


def test_attribute_region_records_eager_pallas_compiles(tmp_path,
                                                        pallas_interpret):
    """The attribute() seam catches compiles with no jitted callable to
    lower: an eager Pallas flash attention journals under the pallas
    name (timing only — no signature/analysis, by contract)."""
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    path = _journal(tmp_path)
    _recorder()
    qkv = jnp.ones((1, 16, 1, 8), jnp.float32)
    np.asarray(flash_attention(qkv, qkv, qkv))
    journal_mod.uninstall()
    evs = [e for e in read_events(path) if e["event"] == "compile"]
    pallas = [e for e in evs if e["name"] == "pallas.flash_attention"]
    assert pallas, [e["name"] for e in evs]
    assert pallas[0]["compile_s"] > 0


def test_trainer_epoch_paths_journal_compile_events(tmp_path):
    """The per-step and scanned epoch paths both journal their step
    compiles under the train.* names (the seam the ROADMAP SPMD work
    will lean on)."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    path = _journal(tmp_path)
    _recorder()
    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    rng = np.random.default_rng(0)

    def batches(n_batches, rows):
        for _ in range(n_batches):
            yield {"x": rng.normal(size=(rows, 6)).astype(np.float32),
                   "y": rng.integers(0, 2, (rows, 1)).astype(np.float32),
                   "w": np.ones((rows, 1), np.float32)}

    t = make_trainer(mc, 6)
    t.train_epoch(batches(2, 16))
    t2 = make_trainer(mc, 6, scan_steps=2)
    t2.train_epoch(batches(2, 16))
    journal_mod.uninstall()
    names = {e["name"] for e in read_events(path)
             if e["event"] == "compile"}
    assert "train.step" in names
    assert "train.scan_epoch" in names


# ---- device-memory accountant ----

def test_memory_snapshot_buckets_and_high_water(tmp_path):
    import jax.numpy as jnp

    path = _journal(tmp_path)
    rec = _recorder()
    rec.record(name="a", signature="s", compile_s=0.1, code_bytes=4096)
    mem = memory_mod.install(memory_mod.MemoryAccountant(plane="train"))
    params = {"w": jnp.ones((32, 32)), "b": jnp.ones((32,))}
    opt = {"m": jnp.ones((32, 32))}
    snap = mem.snapshot(params=params, opt_state=opt, epoch=3)
    assert snap["params_bytes"] == 4 * (32 * 32 + 32)
    assert snap["opt_bytes"] == 4 * 32 * 32
    assert snap["exec_bytes"] == 4096  # from the compile registry
    assert snap["total_bytes"] >= snap["params_bytes"] + snap["opt_bytes"]
    assert snap["other_bytes"] == (snap["total_bytes"]
                                   - snap["params_bytes"]
                                   - snap["opt_bytes"])
    assert snap["hwm_bytes"] == snap["total_bytes"]
    # high water sticks when arrays are freed
    del params, opt
    snap2 = mem.snapshot(epoch=4)
    assert snap2["hwm_bytes"] >= snap2["total_bytes"]
    journal_mod.uninstall()
    evs = [e for e in read_events(path) if e["event"] == "device_mem"]
    assert len(evs) == 2
    assert evs[0]["epoch"] == 3 and evs[0]["params_bytes"] > 0
    text = mem.render_prometheus()
    assert "stpu_devmem_total_bytes" in text
    assert "stpu_devmem_hwm_bytes" in text


def test_memory_snapshot_counts_reserved_bytes_as_held(tmp_path, monkeypatch):
    """A loaded step's temporaries are `bytes_reserved`, not in use: the
    held figure, the high water and the fraction of the limit hold them
    (the flagship step: 3.31 GB in use, 9.13 GB reserved, PERF.md §4)."""
    import jax

    class Dev:
        def memory_stats(self):
            return {"bytes_in_use": 3_000, "bytes_reserved": 9_000,
                    "peak_bytes_in_use": 3_300, "bytes_limit": 16_000}

    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(), Dev()])
    path = _journal(tmp_path)
    mem = memory_mod.install(memory_mod.MemoryAccountant(plane="train"))
    snap = mem.snapshot(epoch=0)
    assert snap["bytes_in_use"] == 6_000 and snap["bytes_reserved"] == 18_000
    assert snap["bytes_held"] == 24_000
    assert snap["hwm_bytes"] == 24_000
    assert snap["devmem_frac"] == 0.75
    journal_mod.uninstall()
    text = mem.render_prometheus()
    assert "stpu_devmem_backend_bytes_held 24000" in text
    assert "stpu_devmem_backend_bytes_in_use 6000" in text
    from shifu_tensorflow_tpu.obs.__main__ import _mem_data

    worker = _mem_data(read_events(path))["workers"]["train"]
    assert worker["hwm_bytes"] == 24_000
    assert worker["last"]["bytes_held"] == 24_000


def test_memory_snapshot_per_model_merge_and_drop(tmp_path):
    _journal(tmp_path)
    mem = memory_mod.install(memory_mod.MemoryAccountant(plane="serve"))
    mem.snapshot(models={"alpha": 1000, "beta": 2000})
    # a single-model reload snapshot must not wipe the sibling
    mem.snapshot(models={"alpha": 1500})
    assert mem.model_bytes() == {"alpha": 1500, "beta": 2000}
    text = mem.render_prometheus()
    assert 'stpu_devmem_model_bytes_alpha{model="alpha"} 1500' in text
    assert 'stpu_devmem_model_bytes_beta{model="beta"} 2000' in text
    mem.drop_model("beta")
    assert "beta" not in mem.model_bytes()
    assert "beta" not in mem.render_prometheus()


def test_tenancy_admission_journals_device_mem(tmp_path):
    """Admission/eviction are the serve plane's snapshot cadence: the
    journaled device_mem names each admitted model's device bytes and
    the model_admit event carries them."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle
    from shifu_tensorflow_tpu.serve.config import ServeConfig
    from shifu_tensorflow_tpu.serve.tenancy.store import MultiModelStore
    from shifu_tensorflow_tpu.train.trainer import Trainer

    models_dir = tmp_path / "models"
    models_dir.mkdir()
    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    t = Trainer(mc, 5)
    export_native_bundle(str(models_dir / "alpha"), t.state.params, mc, 5)

    path = _journal(tmp_path, plane="serve")
    memory_mod.install(memory_mod.MemoryAccountant(plane="serve"))
    cfg = ServeConfig(models_dir=str(models_dir), max_batch=8,
                      max_queue_rows=16)
    store = MultiModelStore(cfg, warm=False)
    try:
        tenant = store.acquire("alpha")
        assert tenant.store.current().model.device_bytes() > 0
    finally:
        store.close()
    journal_mod.uninstall()
    evs = read_events(path)
    admit = next(e for e in evs if e["event"] == "model_admit")
    assert admit["device_bytes"] > 0
    mems = [e for e in evs if e["event"] == "device_mem"]
    assert any((e.get("models") or {}).get("alpha", 0) > 0 for e in mems)


# ---- profiler capture window ----

def test_profile_request_trigger_roundtrip(tmp_path):
    base = str(tmp_path / "j.jsonl")
    trig = profile_mod.request(base, str(tmp_path / "dump"), seconds=1.5,
                               worker=1)
    assert os.path.exists(trig)
    body = json.load(open(trig))
    assert body["seconds"] == 1.5 and body["worker"] == 1
    # a poller with the WRONG worker index leaves the trigger in place
    profile_mod.configure(base, plane="train", worker=0)
    assert profile_mod.poll() is False
    assert os.path.exists(trig)
    # the addressed worker consumes it and journals the capture
    journal_mod.install(Journal(base, plane="train", worker=1))
    profile_mod.configure(base, plane="train", worker=1)
    assert profile_mod.poll() is True
    assert not os.path.exists(trig)
    deadline = time.monotonic() + 20.0
    done = None
    while time.monotonic() < deadline:
        evs = [e for e in read_events(base)
               if e.get("event") == "profile_capture"]
        done = next((e for e in evs if e.get("status") in
                     ("done", "failed")), None)
        if done is not None:
            break
        time.sleep(0.1)
    journal_mod.uninstall()
    assert done is not None, "capture thread never finished"
    # on this backend the capture should succeed and leave a dump dir
    assert done["status"] == "done", done
    assert os.path.isdir(done["dir"])


def test_profile_poll_without_configure_is_noop():
    assert profile_mod.poll() is False


# ---- CLI ----

def _run_cli(argv) -> tuple[int, str]:
    from shifu_tensorflow_tpu.obs.__main__ import main

    out = io.StringIO()
    with redirect_stdout(out), redirect_stderr(out):
        rc = main(argv)
    return rc, out.getvalue()


def _drill_journal(tmp_path) -> str:
    """A dead fleet's journal with compiles, a storm, and memory events
    — everything the jax-free CLI renders from files alone."""
    path = str(tmp_path / "dead.jsonl")
    journal_mod.install(Journal(path, plane="serve", worker=0))
    rec = _recorder(plane="serve", storm_window_s=60.0, storm_threshold=4)
    mem = memory_mod.install(memory_mod.MemoryAccountant(plane="serve",
                                                         worker=0))
    t0 = 100.0
    rec.record(name="eval.native_score", signature="float32[8,6]",
               compile_s=0.02, bucket=8, kind="warm", now=t0)
    for i in range(5):
        rec.record(name="eval.native_score",
                   signature=f"float32[{i + 1},6]",
                   compile_s=0.02, bucket=i + 1, now=t0 + i)
    rec.tick(now=t0 + 300)  # clears the storm
    mem._model_bytes = {"alpha": 4096}
    journal_mod.emit("device_mem", plane="serve", worker=0,
                     total_bytes=8192, params_bytes=0, opt_bytes=0,
                     infeed_bytes=0, exec_bytes=0, other_bytes=8192,
                     arrays=3, hwm_bytes=8192,
                     models={"alpha": 4096})
    journal_mod.uninstall()
    compile_mod.uninstall()
    memory_mod.uninstall()
    return path


def test_cli_compile_renders_history_and_storm(tmp_path):
    path = _drill_journal(tmp_path)
    rc, out = _run_cli(["compile", "--journal", path])
    assert rc == 0
    assert "compile flight recorder" in out
    assert "eval.native_score" in out
    assert "recompile storms" in out
    assert "churning: eval.native_score" in out
    # the storm cleared — the excursion shows a bounded span, and the
    # journal alone reconstructs which signature churned
    assert "STILL ACTIVE" not in out
    rc, out = _run_cli(["compile", "--journal", path, "--json"])
    assert rc == 0
    doc = json.loads(out)
    assert doc["callables"]["eval.native_score"]["compiles"] == 6
    assert doc["callables"]["eval.native_score"]["warm"] == 1
    (storm,) = doc["storms"]
    assert storm["culprit"] == "eval.native_score"
    assert storm["cleared_ts"] is not None


def test_cli_mem_renders_buckets_and_models(tmp_path):
    path = _drill_journal(tmp_path)
    rc, out = _run_cli(["mem", "--journal", path])
    assert rc == 0
    assert "device memory accountant" in out
    assert "serve/w0" in out
    assert "alpha" in out
    rc, out = _run_cli(["mem", "--journal", path, "--json"])
    doc = json.loads(out)
    assert doc["models"]["alpha"] == 4096
    assert doc["workers"]["serve/w0"]["hwm_bytes"] == 8192


def test_cli_profile_lists_and_requests(tmp_path):
    path = str(tmp_path / "j.jsonl")
    with Journal(path, plane="train") as j:
        journal_mod.install(j)
        journal_mod.emit("profile_capture", plane="train", status="done",
                         dir="/tmp/dump", wall_s=5.0)
        journal_mod.uninstall()
    rc, out = _run_cli(["profile", "--journal", path])
    assert rc == 0 and "profile_capture" in out
    rc, out = _run_cli(["profile", "--journal", path, "--request",
                        "--dir", str(tmp_path / "dump")])
    assert rc == 0
    assert os.path.exists(profile_mod.trigger_path(path))
    # --request without --dir fails loudly
    rc, _ = _run_cli(["profile", "--journal", path, "--request"])
    assert rc == 2


def test_exec_bytes_absent_when_analysis_is_not_full(tmp_path):
    """Under analysis=cost/off no memory_analysis ever runs: executable
    bytes must be ABSENT from the scrape and the device_mem event, not a
    measured zero (the accountant's absent-never-zero discipline)."""
    _journal(tmp_path)
    rec = _recorder(analysis="cost")
    rec.record(name="a", signature="s", compile_s=0.1)
    assert "stpu_compile_executable_bytes" not in rec.render_prometheus()
    mem = memory_mod.install(memory_mod.MemoryAccountant(plane="serve"))
    snap = mem.snapshot()
    assert "exec_bytes" not in snap
    assert "stpu_devmem_exec_bytes" not in mem.render_prometheus()


def test_cli_mem_prunes_evicted_models(tmp_path):
    """An evicted tenant's device bytes leave the `obs mem` table (the
    live /metrics drops the gauge via drop_model; the dead-fleet CLI
    must agree, or it inverts the leak diagnosis)."""
    path = str(tmp_path / "j.jsonl")
    with Journal(path, plane="serve") as j:
        journal_mod.install(j)
        journal_mod.emit("device_mem", plane="serve", total_bytes=100,
                         models={"alpha": 60, "beta": 40}, hwm_bytes=100)
        journal_mod.emit("model_evict", plane="serve", model="alpha",
                         reason="budget", freed_bytes=60)
        journal_mod.emit("device_mem", plane="serve", total_bytes=40,
                         models={"beta": 40}, hwm_bytes=100)
        journal_mod.uninstall()
    rc, out = _run_cli(["mem", "--journal", path, "--json"])
    assert rc == 0
    doc = json.loads(out)
    assert doc["models"] == {"beta": 40}, doc["models"]


def test_cli_compile_clean_miss(tmp_path):
    rc, _ = _run_cli(["compile", "--journal",
                      str(tmp_path / "nothing.jsonl")])
    assert rc == 1


# ---- scrape surfaces ----

def test_serve_metrics_carry_device_leg_and_build_info(tmp_path):
    """/metrics (single-model path) appends stpu_compile_*,
    stpu_devmem_*, and the stpu_build_info identity gauge."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle
    from shifu_tensorflow_tpu.serve.config import ServeConfig
    from shifu_tensorflow_tpu.serve.server import ScoringServer
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.05}}})
    t = Trainer(mc, 5)
    bundle = str(tmp_path / "m")
    export_native_bundle(bundle, t.state.params, mc, 5)

    _journal(tmp_path, plane="serve")
    _recorder(plane="serve")
    memory_mod.install(memory_mod.MemoryAccountant(plane="serve"))
    with ScoringServer(ServeConfig(model_dir=bundle, port=0),
                       warm=False) as srv:
        srv.start()
        text = srv.metrics_text()
    assert "stpu_compile_live_executables" in text
    assert "stpu_devmem_total_bytes" in text
    assert "stpu_build_info{" in text
    assert 'backend="cpu"' in text  # jax initialized in this process
    import jax

    assert f'jax="{jax.__version__}"' in text


def test_build_info_without_device_leg_still_renders(tmp_path):
    """stpu_build_info rides every scrape even with no recorder (the
    satellite's contract: every /metrics surface identifies the build)."""
    from shifu_tensorflow_tpu.obs.registry import build_info_text

    text = build_info_text()
    assert "stpu_build_info{" in text
    assert 'version="' in text


def test_coordinator_metrics_carry_build_info():
    from shifu_tensorflow_tpu.coordinator.coordinator import (
        Coordinator,
        JobSpec,
    )

    coord = Coordinator(JobSpec(n_workers=1, shards=[None]))
    text = coord.metrics_text()
    assert "stpu_coord_" in text
    assert "stpu_build_info{" in text
