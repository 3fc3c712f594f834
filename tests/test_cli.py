"""Training CLI (`python -m shifu_tensorflow_tpu.train`) — the client
surface that replaces the reference's TensorflowClient arg/conf handling
(TensorflowClient.java:211-290)."""

import json

import pytest

from shifu_tensorflow_tpu.config import keys as K
from shifu_tensorflow_tpu.train.__main__ import (
    build_parser,
    load_conf,
    main,
    resolve_schema,
)


def _write_model_config(tmp_path, model_config_json, epochs=2):
    mc = dict(model_config_json)
    mc["train"] = dict(mc["train"], numTrainEpochs=epochs)
    p = tmp_path / "ModelConfig.json"
    p.write_text(json.dumps(mc))
    return str(p)


def _write_column_config(tmp_path, n_feats, weight_col):
    cols = [{"columnNum": 0, "columnName": "tgt", "columnFlag": "Target"}]
    for i in range(1, n_feats + 1):
        cols.append(
            {
                "columnNum": i,
                "columnName": f"f{i}",
                "finalSelect": True,
                "columnStats": {"mean": 0.0, "stdDev": 1.0},
            }
        )
    cols.append(
        {"columnNum": weight_col, "columnName": "wgt", "columnFlag": "Weight"}
    )
    p = tmp_path / "ColumnConfig.json"
    p.write_text(json.dumps(cols))
    return str(p)


def test_conf_precedence_cli_over_globalconfig(tmp_path):
    gc = tmp_path / "global.json"
    gc.write_text(json.dumps({K.EPOCHS: 7, K.BATCH_SIZE: 64}))
    args = build_parser().parse_args(
        ["--training-data-path", "/data", "--globalconfig", str(gc),
         "--epochs", "3"]
    )
    conf = load_conf(args)
    assert conf.get_int(K.EPOCHS) == 3  # CLI wins
    assert conf.get_int(K.BATCH_SIZE) == 64  # file layer survives


def test_resolve_schema_from_column_config(tmp_path, model_config_json):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig

    cc = _write_column_config(tmp_path, 4, weight_col=5)
    args = build_parser().parse_args(
        ["--training-data-path", "/d", "--column-config", cc, "--zscale"]
    )
    schema, _ = resolve_schema(args, ModelConfig.from_json(model_config_json))
    assert schema.feature_columns == (1, 2, 3, 4)
    assert schema.target_column == 0
    assert schema.weight_column == 5
    assert len(schema.means) == 4


def test_resolve_schema_flags_override(model_config_json):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig

    args = build_parser().parse_args(
        ["--training-data-path", "/d", "--feature-columns", "2,3",
         "--target-column", "1", "--weight-column", "4"]
    )
    schema, _ = resolve_schema(args, ModelConfig.from_json(model_config_json))
    assert schema.feature_columns == (2, 3)
    assert schema.target_column == 1
    assert schema.weight_column == 4


def test_main_requires_data_path(capsys):
    assert main(["--feature-columns", "1"]) == 2


def test_globalconfig_can_provide_artifact_paths(
    tmp_path, capsys, psv_dataset, model_config_json
):
    """Artifact paths from a --globalconfig file must be honored, same as
    epochs/batch-size (the documented three-layer precedence)."""
    export_dir = tmp_path / "gc-export"
    gc = tmp_path / "global.json"
    gc.write_text(json.dumps({
        K.FINAL_MODEL_PATH: str(export_dir),
        K.TMP_MODEL_PATH: str(tmp_path / "gc-ckpt"),
        K.EPOCHS: 1,
    }))
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", _write_model_config(tmp_path, model_config_json, 2),
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--globalconfig", str(gc),
    ]
    assert main(argv) == 0
    assert (export_dir / "shifu_tpu_model.json").exists()
    assert (tmp_path / "gc-ckpt").exists()


@pytest.mark.parametrize("stream", [False, True])
def test_cli_single_worker_end_to_end(
    tmp_path, capsys, psv_dataset, model_config_json, stream
):
    mc = _write_model_config(tmp_path, model_config_json, epochs=2)
    export_dir = tmp_path / "export"
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", mc,
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--batch-size", "100",
        "--export-dir", str(export_dir),
        "--seed", "3",
    ]
    if stream:
        argv.append("--stream")
    assert main(argv) == 0
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["state"] == "finished"
    assert tail["epochs_run"] == 2
    assert (export_dir / "shifu_tpu_model.json").exists()
    assert (export_dir / "GenericModelConfig.json").exists()


def test_cli_multi_worker_end_to_end(
    tmp_path, capsys, psv_dataset, model_config_json
):
    mc = _write_model_config(tmp_path, model_config_json, epochs=2)
    export_dir = tmp_path / "export-multi"
    ckpt_dir = tmp_path / "ckpt-multi"
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", mc,
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--workers", "2",
        "--checkpoint-dir", str(ckpt_dir),
        "--export-dir", str(export_dir),
    ]
    assert main(argv) == 0
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["state"] == "finished"
    assert tail["epochs_run"] == 2
    assert (export_dir / "shifu_tpu_weights.npz").exists()


def test_cli_multi_worker_keep_best_exports_chief_snapshot(
    tmp_path, capsys, psv_dataset, model_config_json
):
    """Fleet keep-best: the chief persists its best snapshot beside the
    shared checkpoints and the export serves exactly those parameters."""
    import numpy as np

    mcj = dict(model_config_json)
    mcj["train"] = dict(mcj["train"])
    mcj["train"]["params"] = dict(mcj["train"]["params"], Optimizer="adam")
    mc = _write_model_config(tmp_path, mcj, epochs=3)
    export_dir = tmp_path / "export-best"
    ckpt_dir = tmp_path / "ckpt-best"
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", mc,
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--workers", "2",
        "--keep-best", "ks",
        "--checkpoint-dir", str(ckpt_dir),
        "--export-dir", str(export_dir),
    ]
    assert main(argv) == 0
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["state"] == "finished"
    best_file = ckpt_dir / "keep-best.npz"
    assert best_file.exists(), "chief never persisted its best snapshot"
    best = np.load(best_file)
    exported = np.load(export_dir / "shifu_tpu_weights.npz")
    # identical param trees: the export IS the best snapshot
    keys = [k for k in best.files if k != "__meta__"]
    assert sorted(keys) == sorted(exported.files)
    for k in keys:
        np.testing.assert_array_equal(best[k], exported[k])


def test_cli_resume_from_checkpoint(
    tmp_path, capsys, psv_dataset, model_config_json
):
    """Interrupted job resumes with the correct remaining epoch budget (the
    reference's acknowledged gap, backup.py:30)."""
    ckpt = tmp_path / "ckpt"
    base = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", _write_model_config(tmp_path, model_config_json, 1),
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--checkpoint-dir", str(ckpt),
    ]
    assert main(base) == 0  # trains epoch 0, checkpoints
    capsys.readouterr()
    # second run with a 3-epoch budget resumes at epoch 1
    base[3] = _write_model_config(tmp_path, model_config_json, 3)
    assert main(base) == 0
    out = capsys.readouterr().out
    assert "resuming at epoch 1" in out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["epochs_run"] == 2  # only the remaining budget


class TestDataCli:
    def test_build_status_prune_roundtrip(self, psv_dataset, tmp_path, capsys):
        import json

        from shifu_tensorflow_tpu.data.__main__ import main as data_main
        from shifu_tensorflow_tpu.data.dataset import ShardStream
        from shifu_tensorflow_tpu.data.reader import RecordSchema

        cache_dir = str(tmp_path / "cache")
        cols = ",".join(str(c) for c in psv_dataset["feature_cols"])
        rc = data_main([
            "build", "--training-data-path", psv_dataset["root"],
            "--cache-dir", cache_dir, "--feature-columns", cols,
            "--target-column", str(psv_dataset["target_col"]),
            "--weight-column", str(psv_dataset["weight_col"]),
        ])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()
        summary = json.loads(out[-1])
        assert summary["rows"] == psv_dataset["n_rows"]

        # a training stream over the SAME schema/salt hits the prebuilt
        # entries — including a valid split (hashes were stored)
        schema = RecordSchema(
            feature_columns=tuple(psv_dataset["feature_cols"]),
            target_column=psv_dataset["target_col"],
            weight_column=psv_dataset["weight_col"],
        )
        ref = [b["x"].copy() for b in ShardStream(
            psv_dataset["paths"], schema, 128, valid_rate=0.2)]
        warm = [b["x"].copy() for b in ShardStream(
            psv_dataset["paths"], schema, 128, valid_rate=0.2,
            cache_dir=cache_dir)]
        assert len(ref) == len(warm)
        import numpy as np

        for r, w in zip(ref, warm):
            np.testing.assert_array_equal(r, w)

        rc = data_main(["status", "--cache-dir", cache_dir])
        assert rc == 0
        status = json.loads(capsys.readouterr().out.strip())
        assert status["entries"] == len(psv_dataset["paths"])
        assert status["bytes"] > 0

        rc = data_main(["prune", "--cache-dir", cache_dir,
                        "--max-bytes", "1"])
        assert rc == 0
        removed = json.loads(capsys.readouterr().out.strip())
        assert removed["removed"] == len(psv_dataset["paths"])

    def test_build_fails_nonzero_when_nothing_caches(self, psv_dataset,
                                                     tmp_path, capsys,
                                                     monkeypatch):
        import json

        from shifu_tensorflow_tpu.data import cache as shard_cache
        from shifu_tensorflow_tpu.data.__main__ import main as data_main

        monkeypatch.setattr(shard_cache, "cache_key",
                            lambda *a, **k: None)
        cols = ",".join(str(c) for c in psv_dataset["feature_cols"])
        rc = data_main([
            "build", "--training-data-path", psv_dataset["root"],
            "--cache-dir", str(tmp_path / "c"), "--feature-columns", cols,
        ])
        assert rc == 1
        summary = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert summary["cached_files"] == 0

    def test_build_with_column_config_zscale_matches_training_keys(
            self, tmp_path, capsys):
        import gzip
        import json

        import numpy as np

        from shifu_tensorflow_tpu.config.model_config import ColumnConfig
        from shifu_tensorflow_tpu.data.__main__ import main as data_main
        from shifu_tensorflow_tpu.data.dataset import ShardStream

        rng = np.random.default_rng(0)
        p = tmp_path / "s.gz"
        with gzip.open(p, "wt") as f:
            for _ in range(300):
                x = rng.normal(size=2)
                f.write(f"1|{x[0]:.5f}|{x[1]:.5f}|1.0\n")
        cc_path = tmp_path / "ColumnConfig.json"
        cc_path.write_text(json.dumps([
            {"columnNum": 0, "columnName": "t", "finalSelect": False},
            {"columnNum": 1, "columnName": "a", "finalSelect": True,
             "columnStats": {"mean": 0.1, "stdDev": 1.2}},
            {"columnNum": 2, "columnName": "b", "finalSelect": True,
             "columnStats": {"mean": -0.3, "stdDev": 0.8}},
            {"columnNum": 3, "columnName": "w", "finalSelect": False},
        ]))
        cache_dir = str(tmp_path / "cache")
        rc = data_main([
            "build", "--training-data-path", str(p),
            "--cache-dir", cache_dir, "--column-config", str(cc_path),
            "--zscale", "--target-column", "0", "--weight-column", "3",
            "--salt", "7",
        ])
        assert rc == 0
        capsys.readouterr()
        # the training-side schema (same stats, same salt) must HIT
        cc = ColumnConfig.load(str(cc_path))
        from shifu_tensorflow_tpu.data.reader import RecordSchema

        features = tuple(cc.selected_column_nums)
        means, stds = cc.zscale_stats(features)
        schema = RecordSchema(feature_columns=features, target_column=0,
                              weight_column=3).with_zscale(means, stds)
        from shifu_tensorflow_tpu.data import cache as shard_cache

        assert shard_cache.lookup(cache_dir, str(p), schema, 7) is not None
        warm = [b["x"].copy() for b in ShardStream(
            [str(p)], schema, 64, valid_rate=0.2, salt=7,
            cache_dir=cache_dir)]
        assert warm


def test_stream_and_device_resident_conflict(tmp_path):
    """Explicitly requested but silently dropped modes are bugs: the pair
    is rejected up front."""
    import pytest

    from shifu_tensorflow_tpu.train.__main__ import main

    with pytest.raises(SystemExit, match="conflict"):
        main([
            "--training-data-path", str(tmp_path),
            "--feature-columns", "1,2", "--stream", "--device-resident",
        ])


def test_device_resident_rejected_for_multi_worker_and_sagn(tmp_path):
    import gzip

    import pytest

    from shifu_tensorflow_tpu.train.__main__ import main

    with gzip.open(tmp_path / "part-0.gz", "wt") as f:
        for i in range(50):
            f.write(f"{i % 2}|0.5|1.5|1.0\n")
    base = [
        "--training-data-path", str(tmp_path),
        "--feature-columns", "1,2", "--device-resident",
    ]
    with pytest.raises(SystemExit, match="single-process"):
        main(base + ["--workers", "2"])

    import json
    mc = tmp_path / "mc.json"
    mc.write_text(json.dumps({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.1,
        "Algorithm": "sagn"}}}))
    with pytest.raises(SystemExit, match="sagn"):
        main(base + ["--model-config", str(mc)])


def test_multi_worker_preflight_rejects_bad_accum_configs(tmp_path):
    """Invalid scan/accum combinations must be ONE clean error before
    launch — not an N-worker crash cascade after cluster bring-up."""
    import gzip
    import json

    import pytest

    from shifu_tensorflow_tpu.train.__main__ import main

    with gzip.open(tmp_path / "part-0.gz", "wt") as f:
        for i in range(50):
            f.write(f"{i % 2}|0.5|1.5|1.0\n")
    base = [
        "--training-data-path", str(tmp_path),
        "--feature-columns", "1,2", "--workers", "2",
    ]
    with pytest.raises(SystemExit, match="mutually exclusive"):
        main(base + ["--scan-steps", "4", "--accum-steps", "4"])

    mc = tmp_path / "mc.json"
    mc.write_text(json.dumps({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [4],
        "ActivationFunc": ["relu"], "LearningRate": 0.1,
        "Algorithm": "sagn"}}}))
    with pytest.raises(SystemExit, match="sagn"):
        main(base + ["--model-config", str(mc), "--accum-steps", "4"])

def test_cli_multi_worker_fleet_early_stop(
    tmp_path, capsys, psv_dataset, model_config_json
):
    """Fleet-coordinated early stopping: the coordinator evaluates quorum
    epoch aggregates and every worker stops after the SAME epoch, well
    short of the budget."""
    # adam, not the fixture's default adadelta: per-shard KS must actually
    # clear the target within the budget for the stop to have a trigger
    mcj = dict(model_config_json)
    mcj["train"] = dict(mcj["train"])
    mcj["train"]["params"] = dict(mcj["train"]["params"], Optimizer="adam")
    mc = _write_model_config(tmp_path, mcj, epochs=30)
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", mc,
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--workers", "2",
        "--early-stop-ks", "0.2",
    ]
    assert main(argv) == 0
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["state"] == "finished"
    assert tail["epochs_run"] < 30, tail


def test_single_process_preflight_rejects_unfireable_configs(tmp_path):
    """Configs that could only fail late (after dataset load) or silently
    (early stop that can never fire) must be one clean error up front."""
    import gzip

    import pytest

    from shifu_tensorflow_tpu.train.__main__ import main

    with gzip.open(tmp_path / "part-0.gz", "wt") as f:
        for i in range(50):
            f.write(f"{i % 2}|0.5|1.5|1.0\n")
    base = ["--training-data-path", str(tmp_path), "--feature-columns", "1,2"]
    with pytest.raises(SystemExit, match="accum"):
        main(base + ["--device-resident", "--accum-steps", "2"])
    with pytest.raises(SystemExit, match="validation"):
        main(base + ["--early-stop-ks", "0.45", "--valid-rate", "0"])
    # fleet keep-best needs the shared checkpoint dir the chief persists
    # the snapshot into — without it the key would be a silent no-op
    with pytest.raises(SystemExit, match="checkpoint-dir"):
        main(base + ["--workers", "2", "--keep-best", "ks"])
    # and, like early stop, it needs validation data to rank epochs
    with pytest.raises(SystemExit, match="validation"):
        main(base + ["--workers", "2", "--keep-best", "ks",
                     "--valid-rate", "0"])


@pytest.mark.parametrize("pin,probe_says,refused", [
    ("cpu", None, False),          # the pin answers: nothing is probed
    ("", "cpu 1", False),          # a CPU-only host may run N processes
    ("tpu,cpu", "tpu 1", True),    # the chip machine's own setting
    ("", "tpu 4", True),
])
def test_local_worker_processes_refused_where_they_would_share_a_chip(
        monkeypatch, pin, probe_says, refused):
    """``--workers N --launcher process`` / ``--serve-workers N`` start N
    JAX processes on this host.  A chip belongs to one process, so on a
    host with an accelerator the entry point exits, naming the cause,
    before any child starts; the platform is asked of a short-lived
    probe process so the caller never opens the chip itself."""
    import subprocess

    from shifu_tensorflow_tpu.utils import jaxenv

    probes = []

    def fake_run(argv, **kw):
        probes.append(argv)
        return subprocess.CompletedProcess(argv, 0, stdout=probe_says + "\n",
                                           stderr="")

    monkeypatch.setenv("JAX_PLATFORMS", pin)
    monkeypatch.setattr(subprocess, "run", fake_run)
    if refused:
        with pytest.raises(SystemExit, match="belongs to the one process"):
            jaxenv.refuse_processes_sharing_a_chip(2, "--workers")
    else:
        jaxenv.refuse_processes_sharing_a_chip(2, "--workers")
    assert len(probes) == (0 if probe_says is None else 1)
    # one process is always fine, and asks nothing
    jaxenv.refuse_processes_sharing_a_chip(1, "--workers")
    assert len(probes) == (0 if probe_says is None else 1)
