"""The settings the ``train_stream`` plane resolves are the ``--stream``
CLI's own, and the configuration files record them: the benchmark cannot
drift from the path users run."""

import dataclasses
import json
import os

import pytest

from benchmark.planes import train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass
class FakeCtx:
    config: dict
    traffic: dict
    work_dir: str
    seed: int = 7
    trace: bool = False


def load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    STREAM_CELLS = [w for w in json.load(_f)["workloads"]
                    if load("workloads", w["traffic"])["plane"]
                    == "train_stream"]


@pytest.mark.parametrize("cell", STREAM_CELLS, ids=lambda w: w["name"])
def test_plane_resolves_what_the_file_records(tmp_path, cell):
    cfg, tr = load("configs", cell["config"]), load("workloads",
                                                    cell["traffic"])
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps(cfg["model_config"]))
    ctx = FakeCtx(cfg, tr, str(tmp_path))
    argv = train_stream.cli_argv(ctx, str(tmp_path), str(mc))
    args, conf, model_config, schema = train_stream.resolve(argv)
    got = train_stream.resolved_settings(args, conf, model_config)
    assert got == cfg["settings"]
    d = cfg["data"]
    assert schema.num_features == d["numeric"] + d["categorical"]
    assert args.stream and args.checkpoint_dir is None
    # MiniBatchs is a chip's rows: the global batch over the data axis
    data_axis = dict(a.split(":") for a in (cfg["mesh"] or "data:1").split(
        ","))["data"]
    assert model_config.batch_size * int(data_axis) == tr["batch"]


def test_cli_defaults_are_what_the_files_record(tmp_path):
    """A bare ``--stream`` command line (no flag of the benchmark's own
    beyond data, schema and batch) resolves the same path settings."""
    from shifu_tensorflow_tpu.train import __main__ as cli

    cfg = load("configs", "wdl_criteo")
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps(cfg["model_config"]))
    bare = cli.build_parser().parse_args(
        ["--training-data-path", str(tmp_path), "--model-config", str(mc),
         "--feature-columns", ",".join(map(str, range(1, 40))), "--stream"])
    conf = cli.load_conf(bare)
    from shifu_tensorflow_tpu.config.model_config import ModelConfig

    got = train_stream.resolved_settings(bare, conf, ModelConfig.load(str(mc)))
    assert got == cfg["settings"]


def test_traced_run_differs_only_by_obs(tmp_path):
    cfg, tr = load("configs", "wdl_criteo"), load("workloads",
                                                  "stream_1m_b16k")
    plain = train_stream.cli_argv(FakeCtx(cfg, tr, str(tmp_path)), "d", "m")
    traced = train_stream.cli_argv(
        FakeCtx(cfg, tr, str(tmp_path), trace=True), "d", "m")
    assert traced == plain + ["--obs"]
