"""The looped decoder's cell (``ouro_2p6b_pp8_stream_s4k``): its entries
against the contract, its file against the catalog's keys and the cut, its
arithmetic, its plane's settings against the file, ``shapes_looped_lm.py``
against hand-worked numbers and the initialised tree, its readers on
readings with and without their phases, a tiny copy of the cell and of its
control end to end on the CPU through ``run.py``, and the parent's plane
ending in ``prepare``.

Where an entry stands is asked relative to its own neighbours, never to
the list's end, so that the next cell breaks none of these."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_looped_lm
from benchmark.planes import (
    train_lm_stream,
    train_lm_stream_ref,
    train_looped_lm_stream,
    train_stream,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ouro_2p6b_pp8_stream_s4k"
CONFIG = "ouro_2p6b_pp8"
TRAFFIC = "lm_stream_s4096_b1"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
LOOPED_METRICS = (
    "looped_lm_step_mfu", "loop_attn_core_ms", "loop_attn_core_roofline",
    "loop_dense_mlp_ms", "exit_heads_ms", "loop_exit_ms", "exit_steps_mean")
#: the catalog's ``config`` for the model (model-configs guide,
#: ``architectures.jsonl``), by key
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
CUT = {"num_hidden_layers": 6, "layer_types": ["full_attention"] * 6}
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 1, "hidden_size": 64, "num_hidden_layers": 2,
    "layer_types": ["full_attention"] * 2, "intermediate_size": 96,
    "hidden_act": "silu", "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 100, "rope_scaling": None, "sliding_window": None,
    "use_sliding_window": False, "tie_word_embeddings": False,
    "total_ut_steps": 3, "early_exit_threshold": 1,
    "exit_entropy_weight": 0.1, "initializer_range": 0.15,
    "vocab_size": 256}


def load(kind, name):
    with open(os.path.join(ROOT, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


@dataclasses.dataclass
class FakeCtx:
    config: dict
    traffic: dict
    work_dir: str
    seed: int = 7
    trace: bool = False


def _stand_together(names, group):
    """The entries of ``group`` stand in ``names`` one after the other, in
    ``group``'s order; where the first of them stands."""
    at = names.index(group[0])
    assert names[at:at + len(group)] == list(group)
    return at


def test_the_cell_meets_what_every_cell_meets():
    from test_bench_contract import check_cell

    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    check_cell(BENCH, ROOT, cell)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, TRAFFIC, CONFIG)
    assert "24 block applications" in cell["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == SOURCE == load("configs", CONFIG)["source"]
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert sum(w["config"] == CONFIG for w in cells) == 1  # no second cell
    # after the short-convolution cell, its neighbour when it came
    _stand_together([w["name"] for w in cells],
                    ["lfm2_24b_ep8_stream_s8k", CELL])
    _stand_together([c["name"] for c in BENCH["configs"]],
                    ["lfm2_24b_ep8", CONFIG])


def test_the_file_holds_the_catalog_keys_and_states_the_cut():
    """Every published key under its own name; what differs is listed in
    ``reduced``, with the published value and the deployment beside it; no
    width among them."""
    cfg = load("configs", CONFIG)
    assert sorted(CUT) == sorted(cfg["reduced"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    p = cfg["model_config"]["train"]["params"]
    for key, value in PUBLISHED.items():
        if key not in ("max_position_embeddings", "max_window_layers",
                       "model_type"):
            assert p[key] == CUT.get(key, value), key
    assert "post_norms" not in p and "mlp_layer_types" not in p
    assert "rope_parameters" not in p
    assert (p["exit_entropy_weight"], p["LearningRate"], p["MiniBatchs"]) == (
        0.1, 1e-05, 1)
    assert "8 stages x 6" in cfg["deployment"]
    assert "509,661,185 parameters x 16 B" in cfg["deployment"]
    assert "8.15 GB" in cfg["deployment"]
    assert {"sandwich_norms", "loop", "exit_gate", "exit_loss", "rotary",
            "hidden_act", "optimizer", "dtype", "initializer_range", "rows",
            "recomputation", "attention_tile"} <= set(cfg["assumed"])
    assert cfg["data"] == {"tokens_per_row": 4096, "id_below": 49152}
    assert cfg["check"]["reference"] == "looped_lm"


def test_the_configurations_arithmetic():
    """51,388,416 a block, 509,661,185 parameters, 8.15 GB at 16 B each,
    part by part as the file's ``deployment`` and ISSUE 42 write them."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    assert shapes_looped_lm.attention_matrices(p) == 4 * 2048 ** 2
    assert 4 * 2048 ** 2 == 16_777_216
    assert shapes_looped_lm.mlp_matrices(p) == 3 * 2048 * 5632 == 34_603_008
    assert shapes_looped_lm.block_parameters(p) == 51_388_416
    assert 16_777_216 + 34_603_008 + 8_192 == 51_388_416
    total = shapes_looped_lm.parameter_count(p)
    assert total == 6 * 51_388_416 + 2 * 49152 * 2048 + 2048 + 2049 == (
        509_661_185)
    assert round(total * 16 / 1e9, 2) == 8.15
    assert round(total * 12 / 1e9, 2) == 6.12
    # the published 48 blocks by the same count: the row's "2.6B"
    whole = dict(p, layer_types=PUBLISHED["layer_types"])
    assert shapes_looped_lm.parameter_count(whole) == 2_667_974_657
    assert (shapes_looped_lm.passes(p), shapes_looped_lm.blocks(p),
            shapes_looped_lm.applications(p)) == (4, 6, 24)


def test_the_counts_are_the_hand_worked_numbers():
    """About 45 TFLOP a step of one row of 4,096, as ISSUE 42 counts it:
    67% the 24 block applications' products, 11% their cores, 22% the four
    head passes."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    tokens = seq = 4096
    core = shapes_looped_lm.core_flops(p, tokens, seq)
    assert core == 3 * (4096 * 4097 // 2) * 16 * 2 * 256
    total = shapes_looped_lm.train_step_flops(p, tokens, seq)
    blocks = 6 * tokens * 24 * 51_380_224
    heads = 6 * tokens * 4 * 49152 * 2048
    gates = 6 * tokens * 3 * 2048
    assert total == blocks + heads + gates + 24 * core
    assert total == pytest.approx(45.18e12, rel=1e-3)
    assert blocks / total == pytest.approx(0.67, abs=0.01)
    assert 24 * core / total == pytest.approx(0.11, abs=0.01)
    assert heads / total == pytest.approx(0.22, abs=0.01)
    # the core of one application: 1.031 ms for the products against
    # 0.403 GB / 819 GB/s = 0.492 ms for the bytes: the products bind
    from benchmark import peaks, shapes_lm

    v5e = peaks.lookup("TPU v5 lite")
    assert shapes_looped_lm.core_bytes(p, tokens) == 4 * 4096 * 128 * 192
    assert shapes_lm.least_seconds(
        core, shapes_looped_lm.core_bytes(p, tokens), v5e) == pytest.approx(
            core / v5e["flops_per_s"]) == pytest.approx(1.0465e-3, rel=1e-3)


def test_plane_resolves_what_the_file_records(tmp_path):
    cfg = load("configs", CONFIG)
    tr = load("workloads", TRAFFIC)
    assert tr["plane"] == "train_looped_lm_stream"
    assert train_looped_lm_stream.run is train_lm_stream_ref.run
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps(cfg["model_config"]))
    argv = train_lm_stream.cli_argv(FakeCtx(cfg, tr, str(tmp_path)),
                                    str(tmp_path), str(mc))
    args, conf, model_config, schema = train_stream.resolve(argv)
    assert train_lm_stream.resolved_settings(
        args, conf, model_config) == cfg["settings"]
    assert schema.num_features == cfg["data"]["tokens_per_row"] == 4096
    assert args.stream and args.checkpoint_dir is None
    assert model_config.batch_size == tr["batch"] == 1
    c = model_config.params.hybrid_lm
    assert c.hybrid_override_pattern == "*D" * 6
    assert (c.total_ut_steps, c.post_norms, c.exit_entropy_weight,
            c.head_dim, c.num_key_value_heads, c.tie_word_embeddings) == (
        4, True, 0.1, 128, 16, False)
    assert c.rope_for("*").rope_theta == 1e6
    assert train_lm_stream_ref.reference_of(cfg).__name__ == (
        "benchmark.reference.looped_lm")
    control = load("workloads", TRAFFIC + "_bf16")
    assert {k: v for k, v in control.items() if k not in ("dtype", "what")
            } == {k: v for k, v in tr.items() if k != "what"}
    assert control["dtype"] == "bfloat16"
    assert not any(w["traffic"] == TRAFFIC + "_bf16"
                   for w in BENCH["workloads"])


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "looped_lm.py")) as f:
        source = f.read()
    assert "shifu_tensorflow_tpu" not in source.split('"""', 2)[2]
    for line in source.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in (
                "__future__", "math", "jax"), line


@pytest.mark.parametrize("blocks,steps", [(2, 3), (6, 4), (1, 2)],
                         ids=["tiny", "the-cut-in-small", "one-block"])
def test_parameter_count_equals_the_initialised_tree(blocks, steps):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    p = dict(TINY_PARAMS, num_hidden_layers=blocks,
             layer_types=["full_attention"] * blocks, total_ut_steps=steps)
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert tree["exit_gate"]["kernel"].shape == (64, 1)
    assert shapes_looped_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, config=CONFIG):
    from benchmark import peaks

    cfg = load("configs", config)
    tr = load("workloads", {CONFIG: TRAFFIC,
                            "lfm2_24b_ep8": "lm_stream_s8192_b2",
                            "glm47_flash_ep8": "lm_stream_s8192_b1",
                            "laguna_xs2_ep8": "lm_stream_s8192_b1",
                            "mellum2_ep4": "lm_stream_s8192_b2",
                            "nemotron3_nano_ep16": "lm_stream_s4096_b2",
                            "wdl_criteo": "stream_1m_b16k"}[config])
    spans = {"step.dispatch": {"count": 4, "total_s": 1.6, "mean_s": 0.4}}
    if phases is not None:
        spans["@phases_ms"] = phases
    if counters is not None:
        spans["@counters"] = counters
    return {"trace": {"devices": {}, "host": []}, "window_ns": None,
            "window_s": 1.0, "spans": spans, "cell": {"chips": 1},
            "config": cfg, "traffic": tr, "device": {},
            "peaks": peaks.lookup("TPU v5 lite"), "step_pattern": None}


PHASES = {"attn.core.fwd": 30.0, "attn.core.bwd": 70.0,
          "mlp.dense.fwd": 60.0, "mlp.dense.bwd": 120.0,
          "lm.head.fwd": 40.0, "lm.head.bwd": 80.0,
          "loop.exit.fwd": 0.5, "loop.exit.bwd": 1.0,
          "loop.norm.fwd": 3.0, "loop.norm.bwd": 6.0}
COUNTERS = {"exit_steps_mean": [2.125, 2.5], "exit_entropy": [1.2, 1.1],
            "loop_last_loss": [10.9, 10.8], "moe_held_pairs": [0.0, 0.0],
            "moe_held_max": [0.0, 0.0]}


@pytest.mark.parametrize("name", LOOPED_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scope or the counter, a capture
    off the TPU, another family's cell, and the other decoders' cells, which
    have an ``attn.core``, an ``mlp.dense``, an ``lm.head`` and counters of
    their own: nothing, and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0})) is None
    for other in ("wdl_criteo", "nemotron3_nano_ep16", "mellum2_ep4",
                  "laguna_xs2_ep8", "glm47_flash_ep8", "lfm2_24b_ep8"):
        assert reader.read(_reading(PHASES, COUNTERS, config=other)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    r = _reading(PHASES, COUNTERS)
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in LOOPED_METRICS}
    assert read["loop_attn_core_ms"] == 100.0
    assert read["loop_dense_mlp_ms"] == 180.0
    assert read["exit_heads_ms"] == 120.0 and read["loop_exit_ms"] == 1.5
    assert read["exit_steps_mean"] == pytest.approx(2.3125)
    # 24 cores at 1.0465 ms each over 100 ms
    assert read["loop_attn_core_roofline"] == pytest.approx(
        100 * 24 * 1.0465 / 100, rel=1e-3)
    assert read["looped_lm_step_mfu"] is None  # no step program here
    assert all(v is None or 0 < v < 100 for n, v in read.items()
               if n.endswith(("_roofline", "_mfu")))


def test_step_mfu_reads_the_step_programs_time():
    """45.18 TFLOP / 197 TFLOP/s = 229.3 ms at peak: an 800 ms step is
    28.66%."""
    from benchmark import xplane
    from benchmark.metrics import looped_lm_step_mfu

    r = _reading(PHASES, COUNTERS)
    r["step_pattern"] = "jit_train_step"
    r["window_ns"] = (0, 3 * 10**9)
    r["trace"] = {"devices": {0: {xplane.MODULE_LINE: [
        ("jit_train_step(1)", i * 9 * 10**8, 8 * 10**8) for i in range(3)]}},
        "host": []}
    assert looped_lm_step_mfu.read(r) == pytest.approx(
        100 * 45.18e12 / 197e12 / 0.8, rel=1e-3)
    r["config"] = load("configs", "lfm2_24b_ep8")
    assert looped_lm_step_mfu.read(r) is None


def test_every_metric_of_the_cell_is_an_entry_of_its_own():
    """No accepted entry's ``workloads`` list was touched: each reading of
    the new cell is a new entry with a new reader, and the seven stand
    together right after the short-convolution cell's thirteen."""
    from benchmark import run
    from test_bench_conv_lm import CONV_METRICS

    for name in LOOPED_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
        assert m["source"] == ("program_counter" if name == "exit_steps_mean"
                               else "device_trace")
        assert (m["unit"] == "%") == name.endswith(("_roofline", "_mfu"))
        reader = importlib.import_module("benchmark.metrics." + name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == set(LOOPED_METRICS) | {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert not any(CELL in m.get("workloads", ())
                   for m in BENCH["per_layer"]
                   if m["name"] not in LOOPED_METRICS)
    names = [m["name"] for m in BENCH["per_layer"]]
    at = _stand_together(names, LOOPED_METRICS)
    assert names[at - len(CONV_METRICS):at] == list(CONV_METRICS)


@pytest.fixture(scope="module")
def tiny_looped_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, two blocks run three
    times, vocabulary 256, rows of 32, one row a step."""
    root = tmp_path_factory.mktemp("tiny_looped_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", CONFIG)
    config["name"] = "tiny_looped"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 32, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_looped.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_looped_lm_stream", "rows": 8, "shards": 2,
           "batch": 1, "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", TRAFFIC + "_bf16")
    for name, traffic in (("tiny_looped_mix", mix),
                          ("tiny_looped_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_looped", "source": "tests",
                         "why": "tiny", "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_looped.json"}]
    bench["workloads"] = [{"name": "tiny_looped_cell", "config": "tiny_looped",
                           "traffic": "tiny_looped_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_looped_bf16_cell",
                           "config": "tiny_looped",
                           "traffic": "tiny_looped_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_looped_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_looped_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "4200000019", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _note(lines):
    return next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_looped_root):
    proc, lines = _run(tiny_looped_root, "--rehearse")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 8  # whole epochs of 8 steps
    assert set(last["metrics"]) == {"train_rows_per_s", "setup_s"}
    note = _note(lines)
    assert note["compiles_in_window"] == 0
    check = note["info"]["check"]
    assert check["loss_rel_err"] < 1e-5 and check["update_rel_err"] < 0.05
    assert check["pooled_update_rel_err"] < 0.02
    assert check["grad_norm_rel_err"] < 1e-3
    assert check["pooled_grad_rel_err"] < 1e-3
    assert len(check["sys_losses"]) == 2
    for leaf in ("layers_0/mixer/q_proj/kernel", "layers_0/post_norm/scale",
                 "layers_3/mixer/down/kernel", "exit_gate/kernel",
                 "exit_gate/bias", "lm_head/kernel", "embed/embedding",
                 "final_norm/scale"):
        assert any(n.endswith(leaf) for n in check["leaf_update_rel_err"])
    counters = note["info"]["counters_last_epoch"]
    assert {"exit_steps_mean", "exit_entropy", "loop_last_loss"} <= set(
        counters)
    assert all(1.0 <= s <= 3.0 for s in counters["exit_steps_mean"])
    assert note["info"]["batch"] == 1


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_looped_root):
    """The control traffic is the cell's with the step taken at ``--dtype
    bfloat16``, the nearest precision below the stated one; the comparison
    has to refuse it (on the chip under the shipped limits, PERF.md
    section 2; here under the tiny copy's)."""
    proc, lines = _run(tiny_looped_root, "--rehearse",
                       cell="tiny_looped_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = _note(lines)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    assert not note["info"]["check"]["ok"]


def test_a_traced_rehearsal_reads_the_exit_counter_and_no_device_phase(
        tiny_looped_root):
    proc, lines = _run(tiny_looped_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the shares read nothing and the line leaves them out; the spans and
    # the step's counters are there
    assert {"dispatch_ms", "infeed_put_ms",
            "exit_steps_mean"} <= set(last["metrics"])
    assert 1.0 <= last["metrics"]["exit_steps_mean"]["value"] <= 3.0
    assert set(LOOPED_METRICS) & set(last["metrics"]) == {"exit_steps_mean"}


@dataclasses.dataclass(frozen=True)
class _ParentsConfig:
    """The parent's reader in small: no loop field, and a ``from_json``
    that drops what it does not know."""

    hidden_size: int = 0

    @classmethod
    def from_json(cls, params):
        return cls(int(params["hidden_size"]))


def test_the_parents_plane_ends_in_prepare(tmp_path, monkeypatch, capsys):
    """The parent of this PR on the new cell: its ``HybridLMConfig`` has no
    ``total_ut_steps`` and would read the configuration as one pass of
    blocks, so exit 3 from ``prepare``, before JAX is touched and before
    anything is written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", CONFIG)
    monkeypatch.setattr(model_config, "HybridLMConfig", _ParentsConfig)
    with pytest.raises(SystemExit) as e:
        train_looped_lm_stream.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    err = capsys.readouterr().err
    assert "no result" in err and "total_ut_steps" in err


def test_a_reader_that_loses_the_loop_ends_in_prepare(tmp_path, monkeypatch,
                                                      capsys):
    """A reader with the field that reads another number of passes than the
    configuration states is held to it: exit 3, nothing written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", CONFIG)
    real = model_config.HybridLMConfig.from_json

    def once(params):
        return dataclasses.replace(real(params), total_ut_steps=1)

    monkeypatch.setattr(model_config.HybridLMConfig, "from_json",
                        staticmethod(once))
    with pytest.raises(SystemExit) as e:
        train_looped_lm_stream.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    assert "total_ut_steps=1" in capsys.readouterr().err
