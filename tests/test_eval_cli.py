"""Scoring CLI (`python -m shifu_tensorflow_tpu.export`) and model-family
coverage through the training CLI — the end-to-end surface a reference user
would exercise."""

import json

import numpy as np
import pytest

from shifu_tensorflow_tpu.export.__main__ import main as eval_main
from shifu_tensorflow_tpu.train.__main__ import main as train_main


def _write_model_config(tmp_path, model_config_json, **params):
    mc = dict(model_config_json)
    mc["train"] = dict(mc["train"], numTrainEpochs=2)
    mc["train"]["params"] = dict(mc["train"]["params"], **params)
    p = tmp_path / "ModelConfig.json"
    p.write_text(json.dumps(mc))
    return str(p)


def _train(tmp_path, psv_dataset, mc_path, export_name="export", extra=()):
    export_dir = tmp_path / export_name
    argv = [
        "--training-data-path", psv_dataset["root"],
        "--model-config", mc_path,
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--export-dir", str(export_dir),
        *extra,
    ]
    assert train_main(argv) == 0
    return export_dir


def test_score_cli_with_metrics(tmp_path, capsys, psv_dataset,
                                model_config_json):
    export_dir = _train(
        tmp_path, psv_dataset,
        _write_model_config(tmp_path, model_config_json),
    )
    capsys.readouterr()
    scores_file = tmp_path / "scores.txt"
    rc = eval_main([
        "--model-dir", str(export_dir),
        "--data-path", psv_dataset["root"],
        "--feature-columns", ",".join(map(str, psv_dataset["feature_cols"])),
        "--target-column", str(psv_dataset["target_col"]),
        "--weight-column", str(psv_dataset["weight_col"]),
        "--output", str(scores_file),
    ])
    assert rc == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["rows"] == psv_dataset["n_rows"]
    assert 0.0 <= summary["ks"] <= 1.0 and 0.0 <= summary["auc"] <= 1.0
    vals = np.loadtxt(scores_file)
    assert vals.shape[0] == psv_dataset["n_rows"]
    assert (vals >= 0).all() and (vals <= 1).all()


def test_score_cli_cpp_backend_matches_native(tmp_path, capsys, psv_dataset,
                                              model_config_json):
    from shifu_tensorflow_tpu.export import native_scorer

    if not native_scorer.available():
        pytest.skip("native scorer library unavailable")
    export_dir = _train(
        tmp_path, psv_dataset,
        _write_model_config(tmp_path, model_config_json), "exp-cpp",
    )
    capsys.readouterr()
    outs = {}
    for backend in ("native", "cpp"):
        f = tmp_path / f"scores-{backend}.txt"
        assert eval_main([
            "--model-dir", str(export_dir),
            "--data-path", psv_dataset["root"],
            "--feature-columns",
            ",".join(map(str, psv_dataset["feature_cols"])),
            "--backend", backend,
            "--output", str(f),
        ]) == 0
        outs[backend] = np.loadtxt(f)
    np.testing.assert_allclose(outs["cpp"], outs["native"],
                               rtol=2e-5, atol=2e-6)


def test_score_cli_feature_count_mismatch(tmp_path, capsys, psv_dataset,
                                          model_config_json):
    export_dir = _train(
        tmp_path, psv_dataset,
        _write_model_config(tmp_path, model_config_json), "exp-mm",
    )
    rc = eval_main([
        "--model-dir", str(export_dir),
        "--data-path", psv_dataset["root"],
        "--feature-columns", "1,2",
    ])
    assert rc == 2


def test_multi_worker_embedding_checkpoint_matches_export(
    tmp_path, capsys, psv_dataset, model_config_json
):
    """Workers and the chief-export trainer must build the same param tree:
    feature_columns resolve wide/embedding positions, so a worker trained
    without them would checkpoint a structurally different model than the
    export path restores."""
    mc = _write_model_config(
        tmp_path, model_config_json,
        EmbeddingColumnNums=[psv_dataset["feature_cols"][1]],
        EmbeddingHashSize=32, EmbeddingDim=4,
    )
    export_dir = _train(
        tmp_path, psv_dataset, mc, "exp-mw-emb",
        extra=["--workers", "2",
               "--checkpoint-dir", str(tmp_path / "mw-emb-ckpt")],
    )
    tail = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert tail["state"] == "finished"
    assert (export_dir / "shifu_tpu_weights.npz").exists()
    # the exported weights include the embedding table
    weights = np.load(export_dir / "shifu_tpu_weights.npz")
    assert any("hashed_columns" in k for k in weights.files)


@pytest.mark.parametrize(
    "params",
    [
        {"ModelType": "wide_deep", "WideColumnNums": [1, 2],
         "CrossHashSize": 64},
        {"ModelType": "multi_task", "NumTasks": 3},
        {"Algorithm": "sagn", "UpdateWindow": 3},
    ],
    ids=["wide_deep", "multi_task", "sagn"],
)
def test_train_cli_model_families(tmp_path, capsys, psv_dataset,
                                  model_config_json, params):
    """Every model family / algorithm trains and exports through the same
    CLI the plain DNN uses."""
    mc = _write_model_config(tmp_path, model_config_json, **params)
    export_dir = _train(tmp_path, psv_dataset, mc,
                        f"exp-{params.get('ModelType', 'sagn')}")
    out = capsys.readouterr().out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["state"] == "finished" and tail["epochs_run"] == 2
    assert (export_dir / "shifu_tpu_weights.npz").exists()
