"""Every conf key must change behavior — no dead keys.

Round-2 verdict found keys with accessors nothing called
(shifu.worker.instances.backup, heartbeat tunables, shifu.tpu.dtype,
shifu.tpu.prefetch-depth).  These tests pin each key to the object it now
configures, through the same CLI resolution paths run_single/run_multi use.
"""

import jax.numpy as jnp

from shifu_tensorflow_tpu.config import keys as K
from shifu_tensorflow_tpu.config.conf import Conf
from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.__main__ import (
    build_parser,
    job_spec_kwargs,
    trainer_extras,
)


def _args(extra=()):
    return build_parser().parse_args(
        ["--training-data-path", "/tmp/x", "--feature-columns", "1,2",
         *extra]
    )


def _conf(values: dict) -> Conf:
    conf = Conf()
    conf.update(values, source="<test>")
    return conf


def test_backup_instances_key_drives_spare_restarts():
    kw = job_spec_kwargs(_conf({K.backup_instances_key("worker"): 3}))
    assert kw["spare_restarts"] == 3
    assert job_spec_kwargs(_conf({}))["spare_restarts"] == 0


def test_heartbeat_keys_drive_job_spec():
    kw = job_spec_kwargs(_conf({
        K.TASK_HEARTBEAT_INTERVAL_MS: 250,
        K.TASK_MAX_MISSED_HEARTBEATS: 7,
    }))
    assert kw["heartbeat_interval_ms"] == 250
    assert kw["max_missed_heartbeats"] == 7
    base = job_spec_kwargs(_conf({}))
    assert base["heartbeat_interval_ms"] == K.DEFAULT_TASK_HEARTBEAT_INTERVAL_MS
    assert base["max_missed_heartbeats"] == K.DEFAULT_TASK_MAX_MISSED_HEARTBEATS


def test_sync_epochs_key_drives_job_spec():
    assert job_spec_kwargs(_conf({K.SYNC_EPOCHS: "true"}))["sync_epochs"] is True
    assert job_spec_kwargs(_conf({}))["sync_epochs"] is False


def test_dtype_conf_key_reaches_trainer():
    extras = trainer_extras(_args(), _conf({K.DTYPE: "bfloat16"}))
    assert extras["dtype"] is jnp.bfloat16
    # CLI flag wins over conf
    extras = trainer_extras(_args(["--dtype", "float32"]),
                            _conf({K.DTYPE: "bfloat16"}))
    assert extras["dtype"] is jnp.float32
    # and the dtype actually lands in the model parameters
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(0, 1),
                           dtype=jnp.bfloat16)
    pred = trainer.model.apply(
        {"params": trainer.state.params}, jnp.zeros((1, 2), jnp.bfloat16)
    )
    assert pred.dtype == jnp.bfloat16


def test_prefetch_depth_key_reaches_trainer():
    extras = trainer_extras(_args(), _conf({K.PREFETCH_DEPTH: 5}))
    assert extras["prefetch_depth"] == 5
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(0, 1), prefetch_depth=5)
    assert trainer.prefetch_depth == 5


def test_prefetch_depth_changes_infeed_lookahead():
    """The depth value must actually govern the prefetch window: with
    depth=d, d batches are transferred before the first is consumed."""
    from shifu_tensorflow_tpu.data.dataset import prefetch_to_device

    for depth in (1, 3):
        put_order = []

        def put(b, _log=put_order):
            _log.append(b)
            return b

        it = prefetch_to_device(iter(range(10)), put=put, depth=depth)
        first = next(it)
        assert first == 0
        assert len(put_order) == depth  # exactly the window, no more


def test_ps_keys_are_gone():
    assert not hasattr(K, "PS_JOB_NAME")
    assert not hasattr(K, "PS_FAULT_TOLERANCE_THRESHOLD")
    # legacy configs carrying shifu.ps.* still parse
    conf = _conf({"shifu.ps.instances": 2})
    assert conf.get_int("shifu.ps.instances", 0) == 2


def test_cache_max_bytes_prunes_oldest(tmp_path):
    import gzip
    import os
    import time as _time

    import numpy as np

    from shifu_tensorflow_tpu.data import cache as shard_cache
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    schema = RecordSchema(feature_columns=(1, 2), target_column=0)
    cache_dir = str(tmp_path / "cache")
    rng = np.random.default_rng(0)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"s{i}.gz")
        with gzip.open(p, "wt") as f:
            for _ in range(500):
                x = rng.normal(size=2)
                f.write(f"1|{x[0]:.5f}|{x[1]:.5f}\n")
        paths.append(p)
    for p in paths:  # build one entry per shard, oldest first
        for _ in ShardStream([p], schema, 128, cache_dir=cache_dir):
            pass
        _time.sleep(0.02)
    assert len([f for f in os.listdir(cache_dir)
                if f.endswith(".meta.json")]) == 3
    total = shard_cache.cache_size_bytes(cache_dir)
    removed = shard_cache.prune_cache(cache_dir, total // 2)
    assert removed >= 1
    assert shard_cache.cache_size_bytes(cache_dir) <= total // 2
    # the NEWEST entry survives and still serves warm reads
    survivors = [f for f in os.listdir(cache_dir)
                 if f.endswith(".meta.json")]
    assert survivors
    newest = shard_cache.lookup(cache_dir, paths[-1], schema, 0)
    assert newest is not None and newest.n_rows == 500
    # unbounded budget is a no-op
    assert shard_cache.prune_cache(cache_dir, 10**12) == 0


def test_cache_max_bytes_key_reaches_prune(tmp_path, capsys):
    from shifu_tensorflow_tpu.train.__main__ import prune_cache_if_configured

    from shifu_tensorflow_tpu.data.cache import CACHE_VERSION

    conf = _conf({K.CACHE_DIR: str(tmp_path), K.CACHE_MAX_BYTES: 1})
    (tmp_path / "aaaa.meta.json").write_text(
        '{"version": %d, "n_rows": 0}' % CACHE_VERSION
    )
    (tmp_path / "aaaa.x.f32").write_bytes(b"\0" * 4096)
    (tmp_path / "aaaa.y.f32").write_bytes(b"")
    (tmp_path / "aaaa.w.f32").write_bytes(b"")
    prune_cache_if_configured(conf)
    assert not (tmp_path / "aaaa.meta.json").exists()
    assert "evicted" in capsys.readouterr().out


def test_prune_sweeps_stale_tmp_and_orphan_slabs(tmp_path):
    import os
    import time as _time

    from shifu_tensorflow_tpu.data import cache as shard_cache

    old = _time.time() - 7200
    # stale tmp from a SIGKILLed writer + slab orphaned before meta publish
    for name in ("k1.x.f32.tmp.123.456.0", "k2.x.f32", "k2.y.f32"):
        p = tmp_path / name
        p.write_bytes(b"\0" * 128)
        os.utime(p, (old, old))
    # fresh tmp (in-flight writer) must survive
    fresh = tmp_path / "k3.x.f32.tmp.789.1.2"
    fresh.write_bytes(b"\0" * 128)
    shard_cache.prune_cache(str(tmp_path), max_bytes=10**9)
    left = sorted(os.listdir(tmp_path))
    assert left == ["k3.x.f32.tmp.789.1.2"], left


def test_cache_max_bytes_accepts_memory_strings(tmp_path, capsys):
    from shifu_tensorflow_tpu.train.__main__ import prune_cache_if_configured

    # "2g" must parse, not crash a finished run
    conf = _conf({K.CACHE_DIR: str(tmp_path), K.CACHE_MAX_BYTES: "2g"})
    prune_cache_if_configured(conf)  # no entries: no-op, no raise
    # garbage values are reported, never raised
    conf = _conf({K.CACHE_DIR: str(tmp_path), K.CACHE_MAX_BYTES: "lots"})
    prune_cache_if_configured(conf)
    assert "ignoring" in capsys.readouterr().err


def test_prune_drops_superseded_version_entries(tmp_path):
    import json
    import os

    from shifu_tensorflow_tpu.data import cache as shard_cache

    # a v1-era entry: unreadable by lookup, must not sit on disk forever
    (tmp_path / "old.meta.json").write_text(
        json.dumps({"version": 1, "n_rows": 5, "n_features": 2})
    )
    (tmp_path / "old.x.f32").write_bytes(b"\0" * 40)
    shard_cache.prune_cache(str(tmp_path), max_bytes=10**9)
    assert not (tmp_path / "old.meta.json").exists()
    assert not (tmp_path / "old.x.f32").exists()


def test_scan_steps_key_reaches_trainer():
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer

    extras = trainer_extras(_args(), _conf({K.SCAN_STEPS: 8}))
    assert extras["scan_steps"] == 8
    # CLI flag wins over conf
    extras = trainer_extras(_args(["--scan-steps", "2"]),
                            _conf({K.SCAN_STEPS: 8}))
    assert extras["scan_steps"] == 2
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(0, 1), scan_steps=8)
    assert trainer.scan_steps == 8
    assert trainer._path.name == "train.scan_epoch"
    # default stays on the per-step path
    trainer = make_trainer(mc, 2, feature_columns=(0, 1))
    assert trainer.scan_steps == 1 and trainer._path.name == "train.step"


def test_accum_steps_key_reaches_trainer():
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.__main__ import worker_runtime_kwargs

    extras = trainer_extras(_args(), _conf({K.ACCUM_STEPS: 4}))
    assert extras["accum_steps"] == 4
    # CLI flag wins over conf
    extras = trainer_extras(_args(["--accum-steps", "2"]),
                            _conf({K.ACCUM_STEPS: 4}))
    assert extras["accum_steps"] == 2
    # multi-worker path resolves the same key
    kw = worker_runtime_kwargs(_args(), _conf({K.ACCUM_STEPS: 4}))
    assert kw["accum_steps"] == 4
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    trainer = make_trainer(mc, 2, feature_columns=(0, 1), accum_steps=4)
    assert trainer.accum_steps == 4
    assert trainer._path.name == "train.accum_step"
    # default stays on the per-step path
    trainer = make_trainer(mc, 2, feature_columns=(0, 1))
    assert trainer.accum_steps == 1 and trainer._path.name == "train.step"


def test_keep_best_key_reaches_trainer():
    import pytest

    from shifu_tensorflow_tpu.train.__main__ import resolve_keep_best

    # the conf-key path has no argparse choices guard: a typo must be one
    # clean pre-launch error, not an N-worker Trainer crash cascade
    with pytest.raises(SystemExit, match="keep-best"):
        resolve_keep_best(_args(), _conf({K.KEEP_BEST: "auc"}))
    assert resolve_keep_best(_args(), _conf({})) == ""
    assert resolve_keep_best(_args(), _conf({K.KEEP_BEST: "ks"})) == "ks"
    # CLI flag wins over conf
    assert resolve_keep_best(
        _args(["--keep-best", "valid_loss"]), _conf({K.KEEP_BEST: "ks"})
    ) == "valid_loss"
    extras = trainer_extras(_args(), _conf({K.KEEP_BEST: "ks"}))
    assert extras["keep_best"] == "ks"


def test_early_stop_keys_reach_fit_loop():
    from shifu_tensorflow_tpu.train.__main__ import resolve_early_stop

    assert resolve_early_stop(_args(), _conf({})) is None
    es = resolve_early_stop(_args(), _conf({K.EARLY_STOP_KS: 0.45}))
    assert es is not None and es.target_ks == 0.45
    es = resolve_early_stop(_args(), _conf({K.EARLY_STOP_PATIENCE: 3}))
    assert es is not None and es.patience == 3
    # CLI flags win over conf
    es = resolve_early_stop(_args(["--early-stop-ks", "0.3"]),
                            _conf({K.EARLY_STOP_KS: 0.45}))
    assert es.target_ks == 0.3


def test_async_checkpoint_key_reaches_worker_config():
    """shifu.tpu.async-checkpoint drives WorkerConfig.async_checkpoint via
    the run_multi field resolution (worker_runtime_kwargs) and lands in
    NpzCheckpointer's async machinery."""
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
    from shifu_tensorflow_tpu.train.__main__ import worker_runtime_kwargs
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    kw = worker_runtime_kwargs(_args(), _conf({K.ASYNC_CHECKPOINT: "true"}))
    assert kw["async_checkpoint"] is True
    kw = worker_runtime_kwargs(_args(), _conf({}))
    assert kw["async_checkpoint"] is False

    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with NpzCheckpointer(d, async_save=True) as ck:
            assert ck._executor is not None
        with NpzCheckpointer(d) as ck:
            assert ck._executor is None


def test_stream_feature_dtype_key_reaches_worker_config():
    """shifu.tpu.stream-feature-dtype drives WorkerConfig through
    worker_runtime_kwargs and resolves through the hashing-aware gate."""
    from shifu_tensorflow_tpu.train.__main__ import worker_runtime_kwargs

    kw = worker_runtime_kwargs(_args(), _conf({}))
    assert kw["stream_feature_dtype"] == "auto"
    kw = worker_runtime_kwargs(
        _args(), _conf({K.STREAM_FEATURE_DTYPE: "float32"}))
    assert kw["stream_feature_dtype"] == "float32"


def test_stream_feature_dtype_survives_worker_json_bridge():
    """The field must survive to_json/from_json — subprocess workers get
    their config over this bridge, so an omitted field silently reverts
    an operator's explicit opt-out to the bf16 default."""
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.1}}})
    schema = RecordSchema(feature_columns=(1, 2), target_column=0)
    cfg = WorkerConfig(
        worker_id="w", coordinator_host="h", coordinator_port=1,
        model_config=mc, schema=schema, stream_feature_dtype="float32",
    )
    rt = WorkerConfig.from_json(cfg.to_json())
    assert rt.stream_feature_dtype == "float32"


def test_serve_keys_round_trip_xml_to_dataclass(tmp_path):
    """Every shifu.tpu.serve-* key must survive the full resolution
    chain: Hadoop-XML resource → layered Conf merge → CLI override →
    ServeConfig dataclass (the serving WorkerConfig analogue) → JSON
    bridge — same contract the PR-2 health keys are held to."""
    from shifu_tensorflow_tpu.serve.config import ServeConfig
    from shifu_tensorflow_tpu.serve.__main__ import build_parser as serve_parser
    from shifu_tensorflow_tpu.serve import resolve_serve_config

    xml = tmp_path / "serve.xml"
    values = {
        K.SERVE_HOST: "0.0.0.0",
        K.SERVE_PORT: "9100",
        K.SERVE_BACKEND: "cpp",
        K.SERVE_MAX_BATCH: "128",
        K.SERVE_MAX_DELAY_MS: "7.5",
        K.SERVE_QUEUE_ROWS: "2048",
        K.SERVE_RETRY_AFTER_S: "3",
        K.SERVE_RELOAD_POLL_MS: "500",
        K.SERVE_WORKERS: "4",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    args = serve_parser().parse_args(["--model-dir", "/m"])
    cfg = resolve_serve_config(args, conf)
    assert cfg.host == "0.0.0.0" and cfg.port == 9100
    assert cfg.backend == "cpp"
    assert cfg.max_batch == 128 and cfg.max_delay_ms == 7.5
    assert cfg.max_queue_rows == 2048
    assert cfg.retry_after_s == 3 and cfg.reload_poll_ms == 500
    assert cfg.workers == 4
    # CLI flags win over the XML layer
    args = serve_parser().parse_args(
        ["--model-dir", "/m", "--port", "9200", "--backend", "native",
         "--max-batch", "64", "--max-delay-ms", "2", "--queue-rows",
         "512", "--retry-after", "9", "--reload-poll-ms", "0",
         "--serve-workers", "2"]
    )
    cfg = resolve_serve_config(args, conf)
    assert (cfg.port, cfg.backend, cfg.max_batch, cfg.max_delay_ms,
            cfg.max_queue_rows, cfg.retry_after_s, cfg.reload_poll_ms,
            cfg.workers) \
        == (9200, "native", 64, 2.0, 512, 9, 0, 2)
    # and the WorkerConfig-style JSON bridge round-trips every field
    assert ServeConfig.from_json(cfg.to_json()) == cfg
    # defaults with neither layer set
    d = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), Conf()
    )
    assert d.port == K.DEFAULT_SERVE_PORT
    assert d.max_batch == K.DEFAULT_SERVE_MAX_BATCH
    assert d.backend == K.DEFAULT_SERVE_BACKEND
    assert d.workers == K.DEFAULT_SERVE_WORKERS


def test_serve_config_rejects_misconfiguration():
    """Typos/incoherent values are one clean pre-launch error (the conf
    path has no argparse choices guard), not a crash inside the server."""
    import pytest

    from shifu_tensorflow_tpu.serve.config import ServeConfig

    with pytest.raises(ValueError, match="serve-backend"):
        ServeConfig(model_dir="/m", backend="tensorrt")
    with pytest.raises(ValueError, match="serve-queue-rows"):
        ServeConfig(model_dir="/m", max_batch=256, max_queue_rows=100)
    with pytest.raises(ValueError, match="serve-max-batch"):
        ServeConfig(model_dir="/m", max_batch=0)
    with pytest.raises(ValueError, match="serve-workers"):
        ServeConfig(model_dir="/m", workers=0)
    # tenancy: exactly one of model_dir/models_dir, positive weights
    with pytest.raises(ValueError, match="exactly one"):
        ServeConfig(model_dir="/m", models_dir="/ms")
    with pytest.raises(ValueError, match="exactly one"):
        ServeConfig()
    with pytest.raises(ValueError, match="tenant-weight"):
        ServeConfig(models_dir="/ms", tenant_weights=(("a", 0.0),))
    with pytest.raises(ValueError, match="model-budget"):
        ServeConfig(models_dir="/ms", model_budget_mb=-1)
    # wire protocol: -1 (ephemeral) is the only negative frame port, and
    # the frame bound must fit the admission bound (a frame the queue
    # can never admit would always be refused AFTER its bytes shipped)
    with pytest.raises(ValueError, match="serve-frame-port"):
        ServeConfig(model_dir="/m", frame_port=-2)
    with pytest.raises(ValueError, match="serve-frame-max-rows"):
        ServeConfig(model_dir="/m", frame_max_rows=-4)
    with pytest.raises(ValueError, match="serve-frame-max-rows"):
        ServeConfig(model_dir="/m", max_queue_rows=512,
                    frame_max_rows=1024)


def test_serve_wire_keys_round_trip(tmp_path):
    """The wire-protocol / shared-lane keys (shifu.tpu.serve-frame-port
    / serve-frame-max-rows / serve-shared-lane) resolve XML → CLI-wins →
    ServeConfig → JSON bridge like every other serve key."""
    from shifu_tensorflow_tpu.serve import resolve_serve_config
    from shifu_tensorflow_tpu.serve.__main__ import (
        build_parser as serve_parser,
    )
    from shifu_tensorflow_tpu.serve.config import ServeConfig

    xml = tmp_path / "wire.xml"
    xml.write_text(
        "<configuration>"
        f"<property><name>{K.SERVE_FRAME_PORT}</name>"
        "<value>9300</value></property>"
        f"<property><name>{K.SERVE_FRAME_MAX_ROWS}</name>"
        "<value>2048</value></property>"
        f"<property><name>{K.SERVE_SHARED_LANE}</name>"
        "<value>true</value></property>"
        "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), conf)
    assert cfg.frame_port == 9300
    assert cfg.frame_max_rows == 2048
    assert cfg.shared_lane is True
    # CLI wins over the XML layer
    cfg = resolve_serve_config(
        serve_parser().parse_args(
            ["--model-dir", "/m", "--frame-port", "-1",
             "--frame-max-rows", "512"]), conf)
    assert cfg.frame_port == -1 and cfg.frame_max_rows == 512
    assert cfg.shared_lane is True  # XML still supplies the lane flag
    cfg = resolve_serve_config(
        serve_parser().parse_args(
            ["--model-dir", "/m", "--shared-lane"]), Conf())
    assert cfg.shared_lane is True
    # JSON bridge round-trips the new fields
    assert ServeConfig.from_json(cfg.to_json()) == cfg
    # defaults: frame listener off, lane off, frame bound tracking the
    # admission bound (the 0 sentinel resolves in __post_init__)
    d = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), Conf())
    assert d.frame_port == K.DEFAULT_SERVE_FRAME_PORT == 0
    assert d.frame_max_rows == d.max_queue_rows
    assert d.shared_lane is K.DEFAULT_SERVE_SHARED_LANE is False
    small = ServeConfig(model_dir="/m", max_queue_rows=512, max_batch=8)
    assert small.frame_max_rows == 512


def test_serve_tenancy_keys_round_trip(tmp_path):
    """The multi-tenant keys (shifu.tpu.serve-models-dir /
    serve-model-budget-mb / serve-model-admit-wait /
    serve-tenant-weight-<model>) resolve XML → CLI-wins → ServeConfig →
    JSON bridge, with per-model weight merge (CLI overrides the conf
    key for the SAME model only)."""
    from shifu_tensorflow_tpu.serve import resolve_serve_config
    from shifu_tensorflow_tpu.serve.__main__ import (
        build_parser as serve_parser,
    )
    from shifu_tensorflow_tpu.serve.config import ServeConfig

    xml = tmp_path / "tenancy.xml"
    values = {
        K.SERVE_MODELS_DIR: "/models",
        K.SERVE_MODEL_BUDGET_MB: "512.5",
        K.SERVE_MODEL_ADMIT_WAIT_S: "12",
        K.SERVE_TENANT_WEIGHT_PREFIX + "alpha": "2.0",
        K.SERVE_TENANT_WEIGHT_PREFIX + "beta": "0.5",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_serve_config(serve_parser().parse_args([]), conf)
    assert cfg.models_dir == "/models" and cfg.model_dir is None
    assert cfg.model_budget_mb == 512.5
    assert cfg.model_admit_wait_s == 12.0
    assert cfg.weight_for("alpha") == 2.0
    assert cfg.weight_for("beta") == 0.5
    assert cfg.weight_for("other") == K.DEFAULT_SERVE_TENANT_WEIGHT
    # CLI wins: models-dir, budget, and the alpha weight (beta's conf
    # weight survives the merge)
    args = serve_parser().parse_args(
        ["--models-dir", "/other", "--model-budget-mb", "64",
         "--model-admit-wait", "5", "--tenant-weight", "alpha=4",
         "--tenant-weight", "gamma=3"]
    )
    cfg = resolve_serve_config(args, conf)
    assert cfg.models_dir == "/other"
    assert cfg.model_budget_mb == 64.0
    assert cfg.model_admit_wait_s == 5.0
    assert (cfg.weight_for("alpha"), cfg.weight_for("beta"),
            cfg.weight_for("gamma")) == (4.0, 0.5, 3.0)
    # JSON bridge round-trips the weight pairs back to hashable form
    assert ServeConfig.from_json(cfg.to_json()) == cfg
    # defaults: no tenancy keys → single-model mode requirements hold
    d = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), Conf()
    )
    assert d.models_dir is None and d.tenant_weights == ()
    assert d.model_budget_mb == K.DEFAULT_SERVE_MODEL_BUDGET_MB
    # CLI --model-dir beats a fleet-wide conf serve-models-dir key: an
    # explicit single-model flag must not be vetoed into a hard error
    # by shared XML (CLI wins over the conf layer)
    s = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), conf
    )
    assert s.model_dir == "/m" and s.models_dir is None


def test_health_keys_drive_worker_and_spec_fields():
    import pytest

    from shifu_tensorflow_tpu.train.__main__ import (
        resolve_health,
        worker_runtime_kwargs,
    )

    conf = _conf({
        K.HEALTH_CHECK_FINITE: "false",
        K.HEALTH_SPIKE_FACTOR: "3.5",
        K.HEALTH_SPIKE_MIN_EPOCHS: "4",
        K.HEALTH_HANG_TIMEOUT_MS: "1500",
        K.HEALTH_LR_BACKOFF: "0.25",
        K.HEALTH_MAX_ROLLBACKS: "7",
        K.HEALTH_SKIP_WINDOW: "3",
    })
    kw = worker_runtime_kwargs(_args(), conf)
    assert kw["health_check_finite"] is False
    assert kw["health_spike_factor"] == pytest.approx(3.5)
    assert kw["health_spike_min_epochs"] == 4
    assert kw["health_hang_timeout_s"] == pytest.approx(1.5)
    spec_kw = job_spec_kwargs(conf)
    assert spec_kw["health_lr_backoff"] == pytest.approx(0.25)
    assert spec_kw["health_max_rollbacks"] == 7
    assert spec_kw["health_skip_window"] == 3
    # single-process path: same keys feed the Trainer's HealthConfig
    hc = resolve_health(conf)
    assert hc.check_finite is False
    assert hc.spike_factor == pytest.approx(3.5)
    assert hc.hang_timeout_s == pytest.approx(1.5)
    # defaults: guard on, spike/hang off
    d = resolve_health(_conf({}))
    assert d.check_finite is True and d.spike_factor == 0.0
    assert d.hang_timeout_s == 0.0


def test_obs_keys_round_trip_xml_to_dataclass(tmp_path):
    """Every shifu.tpu.obs-* key must survive the full resolution chain:
    Hadoop-XML resource → layered Conf merge → CLI override → ObsConfig →
    JSON bridge (the WorkerConfig transport) — the same contract the
    serve and health keys are held to."""
    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "obs.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.OBS_JOURNAL: "/tmp/job.jsonl",
        K.OBS_JOURNAL_MAX_BYTES: "2m",
        K.OBS_JOURNAL_MAX_FILES: "6",
        K.OBS_TRACE_SAMPLE: "5",
        K.OBS_HIST_BUCKETS: "0.001,0.01,0.1,1.0",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.enabled is True
    assert cfg.journal_path == "/tmp/job.jsonl"
    assert cfg.journal_max_bytes == 2 << 20
    assert cfg.journal_max_files == 6
    assert cfg.trace_sample == 5
    assert cfg.hist_buckets == (0.001, 0.01, 0.1, 1.0)
    # JSON bridge round-trips (subprocess workers receive this dict)
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # CLI flags win over the XML layer
    cfg = resolve_obs(
        _args(["--obs-journal", "/tmp/other.jsonl"]), conf
    )
    assert cfg.journal_path == "/tmp/other.jsonl"


def test_obs_defaults_are_off_and_cli_flags_imply_enabled():
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    cfg = resolve_obs(_args(), _conf({}))
    assert cfg.enabled is False and cfg.journal_path == ""
    # --obs enables tracing without a journal
    assert resolve_obs(_args(["--obs"]), _conf({})).enabled is True
    # --obs-journal implies enabled (a requested journal that silently
    # recorded nothing would be the worst observability bug)
    cfg = resolve_obs(_args(["--obs-journal", "/tmp/x.jsonl"]), _conf({}))
    assert cfg.enabled is True and cfg.journal_path == "/tmp/x.jsonl"
    # a conf journal path alone also enables
    assert resolve_obs(_args(),
                       _conf({K.OBS_JOURNAL: "/tmp/y.jsonl"})).enabled


def test_slo_keys_round_trip_xml_to_dataclass(tmp_path):
    """shifu.tpu.slo-* keys ride the SAME ObsConfig (and therefore the
    same WorkerConfig JSON bridge) as the obs keys: Hadoop-XML resource →
    layered Conf → ObsConfig → JSON round trip."""
    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "slo.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.SLO_WINDOW_S: "30",
        K.SLO_SERVE_P99_MS: "250",
        K.SLO_SERVE_SHED_RATE: "0.2",
        K.SLO_STEP_TIME_MS: "50",
        K.SLO_INFEED_FRAC: "0.3",
        K.SLO_HYSTERESIS: "3",
        K.SLO_ANOMALY_SIGMA: "4.5",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.slo_window_s == 30.0
    assert cfg.slo_serve_p99_ms == 250.0
    assert cfg.slo_serve_shed_rate == 0.2
    assert cfg.slo_step_time_ms == 50.0
    assert cfg.slo_infeed_frac == 0.3
    assert cfg.slo_hysteresis == 3
    assert cfg.slo_anomaly_sigma == 4.5
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # defaults: window 60s, hysteresis 2, sigma 6, every target off
    d = resolve_obs(_args(), _conf({}))
    assert d.slo_window_s == 60.0 and d.slo_hysteresis == 2
    assert d.slo_anomaly_sigma == 6.0
    assert d.slo_serve_p99_ms == d.slo_serve_shed_rate == 0.0
    assert d.slo_step_time_ms == d.slo_infeed_frac == 0.0
    assert d.slo_compile_s == d.slo_devmem_frac == 0.0


def test_device_obs_keys_round_trip_xml_to_dataclass(tmp_path):
    """The PR-10 device/compiler keys ride the same ObsConfig chain:
    compile-analysis depth, storm threshold, and the two new watchdog
    targets — XML → Conf → ObsConfig → JSON bridge."""
    import pytest

    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "devobs.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.OBS_COMPILE_ANALYSIS: "cost",
        K.OBS_COMPILE_STORM: "12",
        K.SLO_COMPILE_S: "2.5",
        K.SLO_DEVMEM_FRAC: "0.9",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.compile_analysis == "cost"
    assert cfg.compile_storm == 12
    assert cfg.slo_compile_s == 2.5
    assert cfg.slo_devmem_frac == 0.9
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # defaults: auto analysis (full on train, cost on serve — resolved
    # per plane by install_obs), storm threshold 8, targets off
    d = resolve_obs(_args(), _conf({}))
    assert d.compile_analysis == "auto" and d.compile_storm == 8
    # misconfiguration fails loudly
    with pytest.raises(ValueError, match="obs-compile-analysis"):
        ObsConfig(compile_analysis="verbose")
    with pytest.raises(ValueError, match="obs-compile-storm"):
        ObsConfig(compile_storm=1)
    with pytest.raises(ValueError, match="slo-devmem-frac"):
        ObsConfig(slo_devmem_frac=1.5)


def test_fleet_obs_keys_round_trip_xml_to_dataclass(tmp_path):
    """The PR-11 fleet keys ride the same ObsConfig chain: the
    straggler-skew watchdog target and the detect/clear threshold —
    XML → Conf → ObsConfig → JSON bridge."""
    import pytest

    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "fleetobs.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.SLO_STRAGGLER_SKEW: "2.5",
        K.FLEET_SKEW_THRESHOLD: "1.8",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.slo_straggler_skew == 2.5
    assert cfg.fleet_skew_threshold == 1.8
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # the target reaches the watchdog signal on train/coordinator planes
    from shifu_tensorflow_tpu.obs import slo as slo_mod

    wd = slo_mod.from_config(cfg, plane="coordinator")
    assert wd.state()["fleet_skew"]["target"] == 2.5
    # defaults: no watchdog target, detection threshold 1.5
    d = resolve_obs(_args(), _conf({}))
    assert d.slo_straggler_skew == 0.0
    assert d.fleet_skew_threshold == 1.5
    # misconfiguration fails loudly: skew is a RATIO, 1 means balanced
    with pytest.raises(ValueError, match="slo-straggler-skew"):
        ObsConfig(slo_straggler_skew=0.8)
    with pytest.raises(ValueError, match="fleet-skew-threshold"):
        ObsConfig(fleet_skew_threshold=1.0)


def test_data_obs_keys_round_trip_xml_to_dataclass(tmp_path):
    """The PR-12 data keys ride the same ObsConfig chain: the
    drift-score watchdog target and the per-feature detect/clear
    threshold — XML → Conf → ObsConfig → JSON bridge."""
    import pytest

    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "dataobs.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.SLO_DATA_DRIFT: "2.0",
        K.DATA_DRIFT_THRESHOLD: "0.5",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.slo_data_drift == 2.0
    assert cfg.data_drift_threshold == 0.5
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # the target reaches the watchdog signal (every plane)
    from shifu_tensorflow_tpu.obs import slo as slo_mod

    wd = slo_mod.from_config(cfg, plane="serve")
    assert wd.state()["data_drift_score"]["target"] == 2.0
    assert wd.state()["data_drift_score"]["stat"] == "max"
    # install_obs builds the monitor from these knobs
    from shifu_tensorflow_tpu.obs import datastats as ds_mod
    from shifu_tensorflow_tpu.obs import install_obs

    try:
        install_obs(cfg, plane="serve")
        mon = ds_mod.active()
        assert mon is not None and mon.threshold == 0.5
        assert ds_mod.train_active() is not None
    finally:
        install_obs(ObsConfig(enabled=False), plane="serve")
    # defaults: no watchdog target, detection threshold 1.0
    d = resolve_obs(_args(), _conf({}))
    assert d.slo_data_drift == 0.0
    assert d.data_drift_threshold == 1.0
    # misconfiguration fails loudly
    with pytest.raises(ValueError, match="slo-data-drift"):
        ObsConfig(slo_data_drift=-1.0)
    with pytest.raises(ValueError, match="data-drift-threshold"):
        ObsConfig(data_drift_threshold=0.0)


def test_rollup_keys_round_trip_xml_to_dataclass(tmp_path):
    """The PR-13 long-horizon keys ride the same ObsConfig chain: the
    rollup compactor knobs, the pinned baseline, and the regression
    watchdog target — XML → Conf → ObsConfig → JSON bridge."""
    import pytest

    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "rollup.xml"
    values = {
        K.OBS_ENABLED: "true",
        K.OBS_ROLLUP: "false",
        K.OBS_ROLLUP_WINDOW_S: "30",
        K.OBS_BASELINE: "/tmp/base.rollup.jsonl",
        K.SLO_REGRESSION: "1.5",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_obs(_args(), conf)
    assert cfg.rollup is False
    assert cfg.rollup_window_s == 30.0
    assert cfg.baseline_path == "/tmp/base.rollup.jsonl"
    assert cfg.slo_regression == 1.5
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    # rollup=false: install_obs must NOT start a compactor even with a
    # journal configured
    from shifu_tensorflow_tpu.obs import install_obs
    from shifu_tensorflow_tpu.obs import rollup as rollup_mod

    off = ObsConfig(enabled=True,
                    journal_path=str(tmp_path / "j.jsonl"),
                    rollup=False)
    try:
        install_obs(off, plane="train")
        assert rollup_mod.active() is None
        on = ObsConfig(enabled=True,
                       journal_path=str(tmp_path / "j2.jsonl"))
        install_obs(on, plane="train")
        assert rollup_mod.active() is not None
    finally:
        install_obs(ObsConfig(enabled=False), plane="train")
        from shifu_tensorflow_tpu.obs import journal as journal_mod
        from shifu_tensorflow_tpu.obs import trace as trace_mod

        journal_mod.uninstall()
        trace_mod.uninstall()
    # defaults: rollup on (with a journal), no baseline, watchdog off
    d = resolve_obs(_args(), _conf({}))
    assert d.rollup is True
    assert d.rollup_window_s == 60.0
    assert d.baseline_path == ""
    assert d.slo_regression == 0.0
    # misconfiguration fails loudly
    with pytest.raises(ValueError, match="obs-rollup-window"):
        ObsConfig(rollup_window_s=0.0)
    with pytest.raises(ValueError, match="slo-regression"):
        ObsConfig(slo_regression=-1.0)
    with pytest.raises(ValueError, match="slo-regression"):
        ObsConfig(slo_regression=0.8)


def test_obs_keys_reach_worker_config_bridge():
    """run_multi ships the resolved ObsConfig to subprocess workers via
    WorkerConfig.obs (JSON bridge) — and omits it entirely when obs is
    off, so the off path stays a None check."""
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import worker_runtime_kwargs

    kw = worker_runtime_kwargs(
        _args(), _conf({K.OBS_JOURNAL: "/tmp/fleet.jsonl"})
    )
    assert kw["obs"]["journal_path"] == "/tmp/fleet.jsonl"
    assert ObsConfig.from_json(kw["obs"]).enabled is True
    assert worker_runtime_kwargs(_args(), _conf({}))["obs"] is None
    # and the field survives the WorkerConfig JSON transport
    import dataclasses
    fields = {f.name for f in dataclasses.fields(WorkerConfig)}
    assert "obs" in fields


def test_data_keys_round_trip_xml_to_worker_config(tmp_path):
    """shifu.tpu.data-* keys: Hadoop-XML resource → layered Conf → CLI
    override → resolve_ingest → WorkerConfig JSON round-trip — the same
    contract the obs/serve/health keys are held to."""
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
    from shifu_tensorflow_tpu.train.__main__ import (
        resolve_ingest,
        worker_runtime_kwargs,
    )

    xml = tmp_path / "data.xml"
    values = {
        K.DATA_READERS: "3",
        K.DATA_DECODE_WORKERS: "2",
        K.DATA_PREFETCH: "5",
        K.DATA_AUTOTUNE: "false",
        K.DATA_SHUFFLE_ROWS: "4096",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    ing = resolve_ingest(_args(), conf)
    assert ing == {"readers": 3, "decode_workers": 2, "prefetch": 5,
                   "autotune": False, "shuffle_rows": 4096}
    # CLI flags win over the XML layer
    ing = resolve_ingest(
        _args(["--readers", "7", "--data-autotune"]), conf)
    assert ing["readers"] == 7 and ing["autotune"] is True
    # worker bridge carries every field, and the WorkerConfig JSON
    # transport round-trips them to subprocess workers
    kw = worker_runtime_kwargs(_args(), conf)
    assert kw["n_readers"] == 3  # one resolver feeds run_multi's bridge
    assert kw["decode_workers"] == 2
    assert kw["data_prefetch"] == 5
    assert kw["data_autotune"] is False
    assert kw["data_shuffle_rows"] == 4096
    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [4],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.1}}}
    )
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    cfg = WorkerConfig(
        worker_id="w0", coordinator_host="127.0.0.1", coordinator_port=1,
        model_config=mc,
        schema=RecordSchema(feature_columns=(1,), target_column=0),
        n_readers=3, decode_workers=2, data_prefetch=5,
        data_autotune=False, data_shuffle_rows=4096,
    )
    back = WorkerConfig.from_json(cfg.to_json())
    assert (back.n_readers, back.decode_workers, back.data_prefetch,
            back.data_autotune, back.data_shuffle_rows) == (3, 2, 5,
                                                            False, 4096)


def test_data_keys_defaults_autotune_on_and_auto_widths():
    """Defaults: every width 0 (= auto), autotune ON, shuffle off —
    and resolve_ingest_knobs turns explicit values into PINNED
    dimensions the tuner must not touch."""
    from shifu_tensorflow_tpu.data.autotune import resolve_ingest_knobs
    from shifu_tensorflow_tpu.train.__main__ import resolve_ingest

    ing = resolve_ingest(_args(), _conf({}))
    assert ing == {"readers": 0, "decode_workers": 0, "prefetch": 0,
                   "autotune": True, "shuffle_rows": 0}
    knobs, tuner = resolve_ingest_knobs(
        ing["readers"], ing["decode_workers"], ing["prefetch"],
        autotune=ing["autotune"], fallback_prefetch=2, cpu_count=4)
    assert tuner is not None and tuner.pinned == frozenset()
    assert knobs.readers >= 1 and knobs.prefetch == 2
    # an explicit knob wins AND disables autotuning for that dimension
    ing = resolve_ingest(_args(["--decode-workers", "3"]), _conf({}))
    knobs, tuner = resolve_ingest_knobs(
        ing["readers"], ing["decode_workers"], ing["prefetch"],
        autotune=ing["autotune"], fallback_prefetch=2, cpu_count=4)
    assert knobs.decode_workers == 3
    assert "decode_workers" in tuner.pinned
    # --no-data-autotune freezes everything (no tuner object at all)
    ing = resolve_ingest(_args(["--no-data-autotune"]), _conf({}))
    assert ing["autotune"] is False


def test_aot_keys_round_trip_xml_cli_and_json_bridge(tmp_path):
    """The AOT shipping keys (PR 14): shifu.tpu.export-aot /
    export-aot-rows resolve the export ladder (CLI wins), and
    shifu.tpu.compile-cache-dir rides ObsConfig through the same
    XML → Conf → CLI → JSON-bridge chain as every obs key."""
    from shifu_tensorflow_tpu.export.aot import resolve_aot_buckets
    from shifu_tensorflow_tpu.export.bucketing import ladder
    from shifu_tensorflow_tpu.obs.config import ObsConfig
    from shifu_tensorflow_tpu.train.__main__ import resolve_obs

    xml = tmp_path / "aot.xml"
    values = {
        K.EXPORT_AOT: "true",
        K.EXPORT_AOT_ROWS: "128",
        K.COMPILE_CACHE_DIR: "/cache/xla",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    assert resolve_aot_buckets(_args(), conf) == ladder(128)
    # CLI wins over the conf ladder size; the flag alone enables
    assert resolve_aot_buckets(
        _args(["--export-aot-rows", "64"]), conf) == ladder(64)
    assert resolve_aot_buckets(_args(["--export-aot"]), _conf({})) \
        == ladder(K.DEFAULT_SERVE_QUEUE_ROWS)
    # defaults: AOT export off, cache off
    assert resolve_aot_buckets(_args(), _conf({})) is None
    cfg = resolve_obs(_args(), conf)
    assert cfg.compile_cache_dir == "/cache/xla"
    assert ObsConfig.from_json(cfg.to_json()) == cfg
    cfg = resolve_obs(_args(["--compile-cache-dir", "/cache/cli"]), conf)
    assert cfg.compile_cache_dir == "/cache/cli"
    assert resolve_obs(_args(), _conf({})).compile_cache_dir == ""


def test_elastic_keys_round_trip_xml_cli_and_spec(tmp_path):
    """shifu.tpu.standby-workers / shifu.tpu.elastic: XML → Conf → CLI
    override → JobSpec kwargs (the elastic-fleet switchboard)."""
    from shifu_tensorflow_tpu.train.__main__ import elastic_spec_kwargs

    xml = tmp_path / "elastic.xml"
    xml.write_text(
        "<configuration>"
        f"<property><name>{K.STANDBY_WORKERS}</name><value>2</value>"
        "</property>"
        f"<property><name>{K.ELASTIC}</name><value>true</value>"
        "</property>"
        "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    kw = elastic_spec_kwargs(_args(), conf)
    # elastic forces sync_epochs: the shrink/release/re-split directives
    # are delivered through the per-epoch barrier
    assert kw == {"standby_workers": 2, "elastic": True,
                  "sync_epochs": True}
    # CLI wins over the XML layer
    kw = elastic_spec_kwargs(
        _args(["--standby-workers", "1", "--no-elastic"]), conf)
    assert kw == {"standby_workers": 1, "elastic": False}
    # defaults: no standbys, elastic off (budget exhaustion still fails)
    kw = elastic_spec_kwargs(_args(), _conf({}))
    assert kw == {"standby_workers": K.DEFAULT_STANDBY_WORKERS,
                  "elastic": K.DEFAULT_ELASTIC}
    # the JobSpec accepts them and the worker JSON bridge carries role
    from shifu_tensorflow_tpu.coordinator.coordinator import JobSpec
    from shifu_tensorflow_tpu.coordinator.worker import WorkerConfig
    from shifu_tensorflow_tpu.data.reader import RecordSchema
    from shifu_tensorflow_tpu.data.splitter import Shard

    spec = JobSpec(n_workers=1, shards=[Shard(0, ("/d/p0",), 1)],
                   standby_workers=2, elastic=True)
    assert spec.standby_workers == 2 and spec.elastic is True
    wc = WorkerConfig(
        worker_id="sb-0", coordinator_host="127.0.0.1",
        coordinator_port=1,
        model_config=ModelConfig.from_json({}),
        schema=RecordSchema(feature_columns=(1,), target_column=0),
        role="standby",
    )
    assert WorkerConfig.from_json(wc.to_json()).role == "standby"


def test_autoscale_keys_round_trip_xml_to_serve_config(tmp_path):
    """shifu.tpu.serve-workers-max / serve-autoscale-* /
    serve-supervisor-port: XML → Conf → CLI override → ServeConfig →
    JSON bridge."""
    from shifu_tensorflow_tpu.serve import resolve_serve_config
    from shifu_tensorflow_tpu.serve.__main__ import (
        build_parser as serve_parser,
    )
    from shifu_tensorflow_tpu.serve.config import ServeConfig

    xml = tmp_path / "autoscale.xml"
    values = {
        K.SERVE_WORKERS: "2",
        K.SERVE_WORKERS_MAX: "6",
        K.SERVE_AUTOSCALE_COOLDOWN_S: "45",
        K.SERVE_AUTOSCALE_TICKS: "3",
        K.SERVE_AUTOSCALE_RECOVERY_TICKS: "9",
        K.SERVE_AUTOSCALE_POLL_S: "2.5",
        K.SERVE_SUPERVISOR_PORT: "9301",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), conf)
    assert (cfg.workers, cfg.workers_max) == (2, 6)
    assert cfg.autoscale_cooldown_s == 45.0
    assert cfg.autoscale_ticks == 3
    assert cfg.autoscale_recovery_ticks == 9
    assert cfg.autoscale_poll_s == 2.5
    assert cfg.supervisor_port == 9301
    # CLI wins
    cfg = resolve_serve_config(serve_parser().parse_args(
        ["--model-dir", "/m", "--serve-workers-max", "4",
         "--autoscale-cooldown", "5", "--autoscale-poll", "1",
         "--supervisor-port", "0"]), conf)
    assert cfg.workers_max == 4 and cfg.autoscale_cooldown_s == 5.0
    assert cfg.autoscale_poll_s == 1.0 and cfg.supervisor_port == 0
    # JSON bridge round-trips the new fields
    assert ServeConfig.from_json(cfg.to_json()) == cfg
    # defaults: autoscale off
    d = resolve_serve_config(
        serve_parser().parse_args(["--model-dir", "/m"]), Conf())
    assert d.workers_max == K.DEFAULT_SERVE_WORKERS_MAX == 0
    # validation: a ceiling below the floor is a config error
    import pytest

    with pytest.raises(ValueError, match="serve-workers-max"):
        ServeConfig(model_dir="/m", workers=4, workers_max=2)


def test_lifecycle_keys_round_trip_xml_to_dataclass(tmp_path):
    """Every shifu.tpu.lifecycle-* key must survive the full resolution
    chain: Hadoop-XML resource → layered Conf merge → CLI override →
    LifecycleConfig dataclass → JSON bridge — the serve-key contract,
    applied to the controller surface."""
    import pytest

    from shifu_tensorflow_tpu.lifecycle.__main__ import (
        build_parser as lifecycle_parser,
    )
    from shifu_tensorflow_tpu.lifecycle.config import (
        LifecycleConfig,
        resolve_lifecycle_config,
    )

    xml = tmp_path / "lifecycle.xml"
    values = {
        K.LIFECYCLE_MODEL: "beta",
        K.SERVE_MODELS_DIR: "/srv/models",
        K.OBS_JOURNAL: "/var/log/stpu/j",
        K.TRAINING_DATA_PATH: "/data/train",
        K.LIFECYCLE_POLL_S: "0.5",
        K.LIFECYCLE_TRIGGER_HYSTERESIS: "5",
        K.LIFECYCLE_COOLDOWN_S: "120.5",
        K.LIFECYCLE_SHADOW_MIN_ROWS: "64",
        K.LIFECYCLE_DIVERGENCE_THRESHOLD: "0.8",
        K.LIFECYCLE_RAMP_STEPS: "0.1,0.4,0.8",
        K.LIFECYCLE_RAMP_INTERVAL_S: "12.5",
        K.LIFECYCLE_ROLLBACK_HYSTERESIS: "4",
        K.LIFECYCLE_RETRAIN_TIMEOUT_S: "900",
    }
    xml.write_text(
        "<configuration>" + "".join(
            f"<property><name>{k}</name><value>{v}</value></property>"
            for k, v in values.items()
        ) + "</configuration>"
    )
    conf = Conf()
    conf.add_resource(str(xml))
    cfg = resolve_lifecycle_config(
        lifecycle_parser().parse_args(["run"]), conf)
    assert cfg.model == "beta"
    assert cfg.models_dir == "/srv/models"
    assert cfg.journal_base == "/var/log/stpu/j"
    assert cfg.train_data_path == "/data/train"
    assert cfg.poll_s == 0.5
    assert cfg.trigger_hysteresis == 5
    assert cfg.cooldown_s == 120.5
    assert cfg.shadow_min_rows == 64
    assert cfg.divergence_threshold == 0.8
    assert cfg.ramp_steps == (0.1, 0.4, 0.8)
    assert cfg.ramp_interval_s == 12.5
    assert cfg.rollback_hysteresis == 4
    assert cfg.retrain_timeout_s == 900.0
    # CLI flags win over the XML layer
    cfg = resolve_lifecycle_config(lifecycle_parser().parse_args(
        ["run", "--model", "gamma", "--models-dir", "/m2",
         "--journal", "/j2", "--train-data", "/d2",
         "--train-arg=--epochs", "--train-arg=3",
         "--poll", "2", "--trigger-hysteresis", "2",
         "--cooldown", "60", "--shadow-min-rows", "32",
         "--divergence-threshold", "1.5", "--ramp-steps", "0.5",
         "--ramp-interval", "5", "--rollback-hysteresis", "1",
         "--retrain-timeout", "30"]), conf)
    assert (cfg.model, cfg.models_dir, cfg.journal_base,
            cfg.train_data_path) == ("gamma", "/m2", "/j2", "/d2")
    assert cfg.train_args == ("--epochs", "3")
    assert (cfg.poll_s, cfg.trigger_hysteresis, cfg.cooldown_s,
            cfg.shadow_min_rows, cfg.divergence_threshold,
            cfg.ramp_steps, cfg.ramp_interval_s,
            cfg.rollback_hysteresis, cfg.retrain_timeout_s) \
        == (2.0, 2, 60.0, 32, 1.5, (0.5,), 5.0, 1, 30.0)
    # the JSON bridge round-trips every field (drill harnesses ship the
    # config to the controller subprocess whole)
    assert LifecycleConfig.from_json(cfg.to_json()) == cfg
    # defaults with only the required identity keys set
    d = resolve_lifecycle_config(lifecycle_parser().parse_args(
        ["run", "--model", "beta", "--models-dir", "/m",
         "--journal", "/j"]), Conf())
    assert d.poll_s == K.DEFAULT_LIFECYCLE_POLL_S
    assert d.trigger_hysteresis == K.DEFAULT_LIFECYCLE_TRIGGER_HYSTERESIS
    assert d.cooldown_s == K.DEFAULT_LIFECYCLE_COOLDOWN_S
    assert d.shadow_min_rows == K.DEFAULT_LIFECYCLE_SHADOW_MIN_ROWS
    assert (d.divergence_threshold
            == K.DEFAULT_LIFECYCLE_DIVERGENCE_THRESHOLD)
    assert d.ramp_steps == tuple(
        float(s) for s in K.DEFAULT_LIFECYCLE_RAMP_STEPS.split(","))
    assert d.ramp_interval_s == K.DEFAULT_LIFECYCLE_RAMP_INTERVAL_S
    assert (d.rollback_hysteresis
            == K.DEFAULT_LIFECYCLE_ROLLBACK_HYSTERESIS)
    assert d.retrain_timeout_s == K.DEFAULT_LIFECYCLE_RETRAIN_TIMEOUT_S
    # misconfiguration is one clean pre-launch ValueError naming the key
    with pytest.raises(ValueError, match="lifecycle-ramp-steps"):
        resolve_lifecycle_config(lifecycle_parser().parse_args(
            ["run", "--model", "beta", "--models-dir", "/m",
             "--journal", "/j", "--ramp-steps", "0.5,0.25"]), Conf())
    with pytest.raises(ValueError, match="lifecycle-trigger-hysteresis"):
        resolve_lifecycle_config(lifecycle_parser().parse_args(
            ["run", "--model", "beta", "--models-dir", "/m",
             "--journal", "/j", "--trigger-hysteresis", "0"]), Conf())
