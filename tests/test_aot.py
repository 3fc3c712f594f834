"""AOT executable shipping (export/aot.py): compile once at export,
serve everywhere.

The drills the acceptance criteria pin: an AOT bundle admits by
DESERIALIZE (zero new traces, ``kind=aot_load`` compile events with
``compile_s`` ~ 0) and scores bit-identically to the live-compile path;
a bit-flipped serialized executable refuses cleanly PER BUCKET (falls
back, journals ``kind=aot_fallback``) without refusing the bundle; a
bundle exported under a faked compile environment falls back everywhere
and still serves bit-identical scores; legacy no-AOT bundles admit
byte-identically to before; and the manifest chain covers the shipped
executables like any artifact.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.export import aot as aot_mod
from shifu_tensorflow_tpu.export.eval_model import EvalModel
from shifu_tensorflow_tpu.export.saved_model import (
    NATIVE_MANIFEST,
    export_model,
    export_native_bundle,
)
from shifu_tensorflow_tpu.obs import compile as compile_mod
from shifu_tensorflow_tpu.obs import journal as journal_mod
from shifu_tensorflow_tpu.obs import slo as slo_mod
from shifu_tensorflow_tpu.obs.journal import Journal, read_events
from shifu_tensorflow_tpu.serve.model_store import (
    ArtifactCorrupt,
    ModelStore,
    _verify_manifest,
)
from shifu_tensorflow_tpu.train.trainer import Trainer

N_FEATURES = 6
BUCKETS = (8, 16)


@pytest.fixture(autouse=True)
def _clean_hooks():
    yield
    compile_mod.uninstall()
    journal_mod.uninstall()
    slo_mod.uninstall()


def _model_config():
    return ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.05}}}
    )


def _export(tmp_dir: str, seed: int = 0, aot=BUCKETS) -> str:
    export_model(tmp_dir, Trainer(_model_config(), N_FEATURES, seed=seed),
                 aot_buckets=aot)
    return tmp_dir


def _rows(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).random((n, N_FEATURES)).astype(
        np.float32)


def _journal(tmp_path, plane="serve"):
    path = str(tmp_path / "journal.jsonl")
    journal_mod.install(Journal(path, plane=plane))
    return path


def _recorder(**kw):
    return compile_mod.install(
        compile_mod.CompileRecorder(plane="serve", **kw))


def _compile_events(path):
    journal_mod.uninstall()
    return [e for e in read_events(path) if e["event"] == "compile"]


# --------------------------------------------------------- bundle layout


def test_export_aot_bundle_layout_and_manifest(tmp_path):
    """The aot/ files land committed AND digested into the export
    manifest — the PR-3 verify chain covers the executables exactly
    like the weights."""
    d = _export(str(tmp_path / "m"))
    meta_path = os.path.join(d, aot_mod.AOT_META)
    assert os.path.exists(meta_path)
    for b in BUCKETS:
        assert os.path.exists(os.path.join(d, aot_mod.bucket_file(b)))
    meta = json.loads(open(meta_path).read())
    assert set(meta["buckets"]) == {str(b) for b in BUCKETS}
    fp = meta["fingerprint"]
    assert fp == aot_mod.compile_env_fingerprint()
    # the weights-generation stamp matches the manifest's bundle digest
    manifest = _verify_manifest(d)  # raises on any digest mismatch
    assert meta["weights_sha256"] == manifest["sha256"]
    covered = set(manifest["files"])
    assert aot_mod.AOT_META in covered
    assert {aot_mod.bucket_file(b) for b in BUCKETS} <= covered


def test_export_without_aot_prunes_stale_executables(tmp_path):
    """A re-export WITHOUT AOT removes the previous generation's aot/
    dir: executables compiled for other weights must not linger beside
    a manifest that no longer vouches for them."""
    d = str(tmp_path / "m")
    _export(d, seed=0)
    assert os.path.exists(os.path.join(d, aot_mod.AOT_DIR))
    _export(d, seed=1, aot=None)
    assert not os.path.exists(os.path.join(d, aot_mod.AOT_DIR))


def test_stale_aot_generation_refuses_and_falls_back(tmp_path):
    """An aot/ dir restored beside RE-EXPORTED weights (a copy/rsync
    accident) refuses wholesale via the stamped weights digest — and
    the model still serves through the live-compile fallback."""
    d = str(tmp_path / "m")
    _export(d, seed=0)
    saved = str(tmp_path / "stale_aot")
    shutil.copytree(os.path.join(d, aot_mod.AOT_DIR), saved)
    _export(d, seed=1, aot=None)  # new weights, no aot
    shutil.copytree(saved, os.path.join(d, aot_mod.AOT_DIR))
    m = EvalModel(d)
    st = m.aot_stats
    assert st["shipped"] is True
    assert "weights generation" in (st["unusable"] or "")
    # serves anyway, bit-identical to a clean live-compile model
    clean = EvalModel(_export(str(tmp_path / "clean"), seed=1, aot=None))
    rows = _rows(5)
    np.testing.assert_array_equal(m.compute_batch(rows),
                                  clean.compute_batch(rows))
    m.release()
    clean.release()


# ------------------------------------------------- admission deserialize


def test_aot_admission_deserializes_bit_identical(tmp_path):
    """The headline: warming an AOT bundle causes ZERO new traces (the
    executables deserialize), journals one ``kind=aot_load`` compile
    event per bucket with ``compile_s`` == 0, and scores bit-identical
    to the live-compiled path."""
    aot_dir = _export(str(tmp_path / "aot"))
    plain_dir = _export(str(tmp_path / "plain"), aot=None)
    path = _journal(tmp_path)
    _recorder()
    m = EvalModel(aot_dir)
    assert m.warm(BUCKETS) == 0  # no traces: admission is a deserialize
    assert m.native_trace_count == 0
    assert m.aot_stats == {"shipped": True, "loads": 2, "fallbacks": 0,
                           "unusable": None}
    plain = EvalModel(plain_dir)
    plain.warm(BUCKETS)
    rows = _rows(5)
    np.testing.assert_array_equal(m.compute_batch(rows),
                                  plain.compute_batch(rows))
    rows = _rows(12, seed=1)  # bucket 16
    np.testing.assert_array_equal(m.compute_batch(rows),
                                  plain.compute_batch(rows))
    assert m.native_trace_count == 0  # requests ride the AOT executables
    evs = _compile_events(path)
    aot_evs = [e for e in evs if e.get("kind") == "aot_load"]
    assert {e["bucket"] for e in aot_evs} == set(BUCKETS)
    for e in aot_evs:
        assert e["compile_s"] == 0.0
        assert e["wall_s"] > 0  # the deserialize cost, visible
        assert e["model"] == "aot"
    # the plain bundle's warms journaled kind=warm, untouched by AOT
    assert {e.get("kind") for e in evs if e.get("model") == "plain"} \
        == {"warm"}
    m.release()
    plain.release()


def test_unshipped_bucket_rides_the_plain_live_path(tmp_path):
    """A bucket the bundle never promised (beyond --export-aot-rows)
    live-compiles WITHOUT an aot_fallback marker — fallback means
    'promised and failed', not 'never promised'."""
    d = _export(str(tmp_path / "m"))  # ships 8, 16 only
    path = _journal(tmp_path)
    _recorder()
    m = EvalModel(d)
    m.compute_batch(_rows(20))  # bucket 32: not shipped
    evs = _compile_events(path)
    (ev,) = [e for e in evs if e.get("bucket") == 32]
    assert ev.get("kind") is None
    assert m.native_trace_count == 1
    m.release()


def test_bitflip_refuses_per_bucket_and_falls_back(tmp_path):
    """A corrupted serialized executable refuses ONLY its bucket: the
    meta's CRC catches it before the pickle layer, the bucket journals
    ``kind=aot_fallback`` (with the reason), the OTHER bucket still
    deserializes, and scores stay bit-identical."""
    d = _export(str(tmp_path / "m"))
    victim = os.path.join(d, aot_mod.bucket_file(8))
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    path = _journal(tmp_path)
    _recorder()
    m = EvalModel(d)
    assert m.warm(BUCKETS) == 1  # bucket 8 live-compiled, 16 deserialized
    st = m.aot_stats
    assert st["loads"] == 1 and st["fallbacks"] == 1
    plain = EvalModel(_export(str(tmp_path / "plain"), aot=None))
    rows = _rows(5)
    np.testing.assert_array_equal(m.compute_batch(rows),
                                  plain.compute_batch(rows))
    evs = _compile_events(path)
    fb = [e for e in evs if e.get("kind") == "aot_fallback"]
    loads = [e for e in evs if e.get("kind") == "aot_load"]
    assert [e["bucket"] for e in fb] == [8]
    assert "CRC32" in fb[0]["aot_error"]
    assert fb[0]["compile_s"] > 0  # a real compile, honestly priced
    assert [e["bucket"] for e in loads] == [16]
    m.release()
    plain.release()


def test_fingerprint_mismatch_falls_back_everywhere(tmp_path):
    """A bundle exported under a different compile environment (faked
    fingerprint) falls back on EVERY bucket — journaled aot_fallback
    naming the mismatch — and still serves bit-identical scores."""
    d = str(tmp_path / "m")
    fake = dict(aot_mod.compile_env_fingerprint(), jax="9.9.9")
    real_fp = aot_mod.compile_env_fingerprint
    aot_mod.compile_env_fingerprint = lambda **kw: fake
    try:
        _export(d)
    finally:
        aot_mod.compile_env_fingerprint = real_fp
    path = _journal(tmp_path)
    _recorder()
    m = EvalModel(d)
    assert m.warm(BUCKETS) == 2  # everything live-compiled
    st = m.aot_stats
    assert st["loads"] == 0 and st["fallbacks"] == 2
    assert "jax" in st["unusable"]
    plain = EvalModel(_export(str(tmp_path / "plain"), aot=None))
    rows = _rows(9, seed=2)
    np.testing.assert_array_equal(m.compute_batch(rows),
                                  plain.compute_batch(rows))
    evs = _compile_events(path)
    fb = [e for e in evs if e.get("kind") == "aot_fallback"]
    assert {e["bucket"] for e in fb} == set(BUCKETS)
    assert all("jax" in e["aot_error"] for e in fb)
    assert not [e for e in evs if e.get("kind") == "aot_load"]
    m.release()
    plain.release()


# -------------------------------------------------- serve admission path


def test_model_store_admission_deserializes(tmp_path):
    """ModelStore's verify→warm admission rides AOT end to end: the
    manifest chain verifies the shipped executables, the warm ladder
    deserializes them (zero traces), and the hot-reload swap journals
    the aot split."""
    d = _export(str(tmp_path / "m"))
    path = _journal(tmp_path)
    _recorder()
    store = ModelStore(d, poll_interval_s=0, warm_buckets=BUCKETS)
    loaded = store.current()
    assert loaded.verified is True
    assert loaded.model.native_trace_count == 0
    assert loaded.model.aot_stats["loads"] == len(BUCKETS)
    # hot reload re-admits through the same ladder
    os.utime(os.path.join(d, NATIVE_MANIFEST))
    reloaded = store.reload_now()
    assert reloaded.model.native_trace_count == 0
    journal_mod.uninstall()
    evs = read_events(path)
    reload_ev = next(e for e in evs if e["event"] == "reload")
    assert reload_ev["aot_loads"] == len(BUCKETS)
    assert reload_ev["aot_fallbacks"] == 0
    store.close()


def test_manifest_chain_refuses_corrupt_aot_artifact(tmp_path):
    """At the serve admission boundary a flipped executable is caught
    by the MANIFEST (before EvalModel ever constructs): the bundle
    refuses exactly like corrupt weights — AOT artifacts are bundle
    artifacts, not a side channel."""
    d = _export(str(tmp_path / "m"))
    victim = os.path.join(d, aot_mod.bucket_file(16))
    blob = bytearray(open(victim, "rb").read())
    blob[10] ^= 0x01
    open(victim, "wb").write(bytes(blob))
    with pytest.raises(ArtifactCorrupt, match="bucket_16"):
        ModelStore(d, poll_interval_s=0, warm_buckets=BUCKETS)


def test_legacy_bundle_admits_byte_identically(tmp_path):
    """No aot/ dir → the pre-AOT behavior exactly: warms live-compile
    with kind=warm, no aot fields on the reload event, no aot gauges
    movement."""
    d = _export(str(tmp_path / "m"), aot=None)
    path = _journal(tmp_path)
    rec = _recorder()
    store = ModelStore(d, poll_interval_s=0, warm_buckets=BUCKETS)
    assert store.current().model.aot_stats["shipped"] is False
    os.utime(os.path.join(d, NATIVE_MANIFEST))
    store.reload_now()
    journal_mod.uninstall()
    evs = read_events(path)
    reload_ev = next(e for e in evs if e["event"] == "reload")
    assert "aot_loads" not in reload_ev and "aot_fallbacks" not in reload_ev
    warm_evs = [e for e in evs if e["event"] == "compile"]
    assert warm_evs and all(e["kind"] == "warm" for e in warm_evs)
    assert rec.state()["aot_loads_total"] == 0
    store.close()


# --------------------------------------------- recorder/storm/CLI/rollup


def test_aot_kinds_never_count_toward_a_storm():
    """A 10-tenant fleet restart deserializing (or even fallback-
    compiling) its ladders must keep the storm detector quiet — while
    the same volume of UNMARKED compiles still storms (control arm)."""
    rec = _recorder(storm_window_s=60.0, storm_threshold=4)
    t0 = 1000.0
    for i in range(10):
        rec.record(name="eval.native_score", signature=f"a{i}",
                   compile_s=0.0, kind="aot_load", now=t0 + i)
    for i in range(10):
        rec.record(name="eval.native_score", signature=f"f{i}",
                   compile_s=0.01, kind="aot_fallback", now=t0 + i)
    assert rec.state()["storm_active"] is False
    assert rec.state()["aot_loads_total"] == 10
    # aot loads are not compilations
    assert rec.state()["compiles_total"] == 10  # the fallbacks only
    text = rec.render_prometheus()
    assert "stpu_compile_aot_loads_total 10" in text
    # control: the same volume unmarked storms immediately
    for i in range(5):
        rec.record(name="eval.native_score", signature=f"u{i}",
                   compile_s=0.01, now=t0 + 20 + i)
    assert rec.state()["storm_active"] is True


def test_kind_section_overrides_and_carries_fields(tmp_path):
    """kind_section (the generalized warm_section) stamps kind + extra
    fields onto compiles inside its extent; innermost wins."""
    path = _journal(tmp_path)
    _recorder()
    import jax
    import jax.numpy as jnp

    f = compile_mod.observe(jax.jit(lambda x: x * 2), "unit.fn")
    with compile_mod.warm_section():
        with compile_mod.kind_section("aot_fallback", aot_error="why"):
            f(jnp.ones((3,)))
        f(jnp.ones((5,)))
    evs = _compile_events(path)
    by_sig = {e["signature"]: e for e in evs}
    assert by_sig["float32[3]"]["kind"] == "aot_fallback"
    assert by_sig["float32[3]"]["aot_error"] == "why"
    assert by_sig["float32[5]"]["kind"] == "warm"


def test_obs_compile_cli_distinguishes_aot_kinds(tmp_path, capsys):
    """`obs compile` renders what admission actually did: loads vs
    fallbacks vs live compiles, from the dead fleet's journal alone."""
    from shifu_tensorflow_tpu.obs.__main__ import _compile_data, main

    path = _journal(tmp_path)
    rec = _recorder()
    rec.record(name="eval.native_score", signature="s8", compile_s=0.0,
               wall_s=0.002, bucket=8, kind="aot_load")
    rec.record(name="eval.native_score", signature="s16", compile_s=0.03,
               bucket=16, kind="aot_fallback", aot_error="CRC32 mismatch")
    rec.record(name="eval.native_score", signature="s32", compile_s=0.02,
               bucket=32, kind="warm")
    journal_mod.uninstall()
    data = _compile_data(read_events(path))
    a = data["callables"]["eval.native_score"]
    assert a["aot_loads"] == 1
    assert a["aot_fallbacks"] == 1
    assert a["warm"] == 1
    assert a["compiles"] == 2  # the aot_load is a LOAD, not a compile
    rc = main(["compile", "--journal", path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "1 AOT executable load(s)" in out
    assert "aot" in out and "fb" in out


def test_rollup_folds_aot_kinds(tmp_path):
    """The PR-13 rollup sidecar distinguishes aot loads from compiles:
    a window full of aot_load events folds zero into the compile-cost
    bucket and counts the loads on their own key."""
    from shifu_tensorflow_tpu.obs import rollup as rollup_mod

    comp = rollup_mod.RollupCompactor(
        str(tmp_path / "r.rollup.jsonl"), window_s=60.0, thread=False)
    for i in range(3):
        comp.note_event({"event": "compile", "ts": 100.0 + i,
                         "kind": "aot_load", "compile_s": 0.0})
    comp.note_event({"event": "compile", "ts": 103.0,
                     "kind": "aot_fallback", "compile_s": 0.5})
    comp.note_event({"event": "compile", "ts": 104.0, "compile_s": 0.25})
    comp.close()
    recs = [json.loads(l) for l in
            open(str(tmp_path / "r.rollup.jsonl"))]
    c = recs[0]["compile"]
    assert c["aot_loads"] == 3
    assert c["aot_fallbacks"] == 1
    assert c["compiles"] == 2
    assert c["compile_s"] == pytest.approx(0.75)


# -------------------------------------------- persistent cache satellite


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_enable_compilation_cache")


@pytest.fixture
def restore_cache_config():
    """apply_persistent_cache writes jax's process-wide config: put it
    back, and drop the live cache object too — it initialized against
    the test's directory and would otherwise serve cache HITS to later
    tests whose compile-event assertions expect real backend compiles."""
    import jax
    from jax.experimental.compilation_cache import (
        compilation_cache as _cc,
    )

    before = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    _cc.reset_cache()


def test_persistent_compile_cache_populates_and_applies(
        tmp_path, monkeypatch, restore_cache_config):
    """apply_persistent_cache points jax's on-disk cache at the dir (the
    AOT fallback ladder's middle tier): compiles land entries there."""
    import jax
    import jax.numpy as jnp

    cache = tmp_path / "xla-cache"
    # set-then-delete so monkeypatch restores the variable the helper
    # is about to fill
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "x")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # the conftest keeps the cache off for every other test
    jax.config.update("jax_enable_compilation_cache", True)
    assert compile_mod.apply_persistent_cache(str(cache)) == str(cache)
    f = jax.jit(lambda x: jnp.tanh(x) * 3 + 1)
    np.asarray(f(jnp.ones((7,))))
    assert any(cache.iterdir())


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placed_from_outside(tmp_path, monkeypatch,
                                           restore_cache_config, from_env):
    """JAX_COMPILATION_CACHE_DIR set -> that directory, the key ignored;
    unset (and no key) -> <checkout>/.jax_cache.  Never a moving path."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "outside"))
        want = str(tmp_path / "outside")
        got = compile_mod.apply_persistent_cache(str(tmp_path / "key"))
    else:
        # set-then-delete so monkeypatch restores the variable the
        # helper is about to fill
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "x")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(repo, ".jax_cache")
        got = compile_mod.apply_persistent_cache()
    assert got == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_dir_rides_obs_config(tmp_path):
    """shifu.tpu.compile-cache-dir resolves ObsConfig-style (conf key,
    CLI flag wins) and survives the JSON bridge to subprocess
    workers."""
    from shifu_tensorflow_tpu.config.conf import Conf
    from shifu_tensorflow_tpu.obs.config import ObsConfig, resolve_obs_config

    class _A:
        pass

    conf = Conf()
    conf.update({"shifu.tpu.compile-cache-dir": "/cache/from-conf"},
                source="<test>")
    cfg = resolve_obs_config(_A(), conf)
    assert cfg.compile_cache_dir == "/cache/from-conf"
    a = _A()
    a.compile_cache_dir = "/cache/from-cli"
    assert resolve_obs_config(a, conf).compile_cache_dir \
        == "/cache/from-cli"
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # default: off
    assert resolve_obs_config(_A(), Conf()).compile_cache_dir == ""


def test_resolve_aot_buckets_cli_and_conf(tmp_path):
    """--export-aot / shifu.tpu.export-aot decide; --export-aot-rows
    sizes the ladder (default = the serve warm set)."""
    from shifu_tensorflow_tpu.config import keys as K
    from shifu_tensorflow_tpu.config.conf import Conf
    from shifu_tensorflow_tpu.export.bucketing import ladder

    class _A:
        export_aot = None
        export_aot_rows = None

    assert aot_mod.resolve_aot_buckets(_A(), Conf()) is None
    a = _A()
    a.export_aot = True
    assert aot_mod.resolve_aot_buckets(a, Conf()) \
        == ladder(K.DEFAULT_SERVE_QUEUE_ROWS)
    a.export_aot_rows = 64
    assert aot_mod.resolve_aot_buckets(a, Conf()) == ladder(64)
    conf = Conf()
    conf.update({K.EXPORT_AOT: "true", K.EXPORT_AOT_ROWS: "32"},
                source="<test>")
    assert aot_mod.resolve_aot_buckets(_A(), conf) == ladder(32)
    # CLI false... (store_true can only enable; conf-off + no flag = off)
    conf2 = Conf()
    conf2.update({K.EXPORT_AOT: "false"}, source="<test>")
    assert aot_mod.resolve_aot_buckets(_A(), conf2) is None
