"""The language-model cell: its entries against the contract, its plane's
settings against the file, ``shapes_lm.py`` against the initialised tree,
its readers on readings with and without their phases, and a tiny copy of
the cell end to end on the CPU through ``run.py``."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_lm
from benchmark.planes import train_lm_stream, train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron3_nano_ep16_stream_s4k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
LM_METRICS = ("lm_step_mfu", "ssm_scan_ms", "ssm_scan_roofline",
              "moe_experts_ms", "moe_experts_roofline", "moe_route_ms",
              "attn_core_ms", "lm_head_ms", "moe_held_max_over_mean")
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*E", "layer_norm_epsilon": 1e-5,
    "vocab_size": 256, "mamba_num_heads": 4, "mamba_head_dim": 16,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "n_routed_experts": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16}


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


def test_the_cell_meets_what_every_cell_meets():
    from test_bench_contract import check_cell

    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    check_cell(BENCH, ROOT, cell)
    assert cell["chips"] == 1 and cell["traffic"] == "lm_stream_s4096_b2"
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size"]
    assert "huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B" in entry[
        "source"]


def test_the_file_holds_the_catalog_keys_and_states_the_cut():
    """Every published number under its own key; what differs is listed
    in ``reduced``, with the published value and the deployment beside
    it; no width among them."""
    cfg = load("configs", "nemotron3_nano_ep16")
    published = {"hidden_size": 2688, "mamba_num_heads": 64,
                 "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
                 "conv_kernel": 4, "chunk_size": 128,
                 "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "num_experts_per_tok": 6, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "head_dim": 128,
                 "routed_scaling_factor": 2.5, "expand": 2}
    for key, value in published.items():
        assert cfg[key] == value and key not in cfg["reduced"], key
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (
        9, "MEMEM*EME", 8, 16384)
    assert cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["hybrid_override_pattern"].startswith("MEMEM*EME")
    p = cfg["model_config"]["train"]["params"]
    # the program's keys: the router keeps its published width and is told
    # what is held; of how many shards is the file's to state, in words
    assert (p["n_routed_experts"], p["experts_held"], p["vocab_size"]) == (
        128, [0, 8], 16384)
    assert "16 ways" in cfg["deployment"] and "8 ways" in cfg["deployment"]
    for key, value in published.items():
        assert p.get(key, value) == value, key
    assert "16" in cfg["deployment"] and "1/16" in cfg["load"]
    assert "rotary" in cfg["assumed"]


def test_plane_resolves_what_the_file_records(tmp_path):
    cfg, tr = load("configs", "nemotron3_nano_ep16"), load(
        "workloads", "lm_stream_s4096_b2")
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps(cfg["model_config"]))
    argv = train_lm_stream.cli_argv(FakeCtx(cfg, tr, str(tmp_path)),
                                    str(tmp_path), str(mc))
    args, conf, model_config, schema = train_stream.resolve(argv)
    assert train_lm_stream.resolved_settings(
        args, conf, model_config) == cfg["settings"]
    assert schema.num_features == cfg["data"]["tokens_per_row"] == 4096
    assert args.stream and args.checkpoint_dir is None
    assert model_config.batch_size == tr["batch"] == 2
    assert tr["rows"] % tr["batch"] == 0 and tr["rows"] // tr["batch"] == 16
    traced = train_lm_stream.cli_argv(
        FakeCtx(cfg, tr, str(tmp_path), trace=True), str(tmp_path), str(mc))
    assert traced == argv + ["--obs"]


def test_parameter_count_is_the_shipped_configurations():
    p = load("configs", "nemotron3_nano_ep16")["model_config"]["train"][
        "params"]
    assert shapes_lm.parameter_count(p) == 666_963_456
    # 2.04 GFLOP a token forward + backward, 16.7 TFLOP a step
    flops = shapes_lm.train_step_flops(p, 8192, 4096)
    assert 16.5e12 < flops < 17.0e12


@pytest.mark.parametrize("pattern", ["ME*E", "M", "E*", "MEMEM*EME"])
def test_parameter_count_equals_the_initialised_tree(pattern):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    p = dict(TINY_PARAMS, hybrid_override_pattern=pattern,
             num_hidden_layers=len(pattern))
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert shapes_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, lm=True):
    from benchmark import peaks

    if lm:
        cfg, tr = load("configs", "nemotron3_nano_ep16"), load(
            "workloads", "lm_stream_s4096_b2")
    else:
        cfg, tr = load("configs", "wdl_criteo"), load("workloads",
                                                      "stream_1m_b16k")
    spans = {"step.dispatch": {"count": 4, "total_s": 1.6, "mean_s": 0.4}}
    if phases is not None:
        spans["@phases_ms"] = phases
    if counters is not None:
        spans["@counters"] = counters
    return {"trace": {"devices": {}, "host": []}, "window_ns": None,
            "window_s": 1.0, "spans": spans, "cell": {"chips": 1},
            "config": cfg, "traffic": tr, "device": {},
            "peaks": peaks.lookup("TPU v5 lite"), "step_pattern": None}


@pytest.mark.parametrize("name", LM_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scopes, a capture off the TPU,
    another family's cell: no phase, no counter — nothing, and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(lm=False)) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0},
                                lm=False)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    phases = {"ssm.scan.fwd": 20.0, "ssm.scan.bwd": 30.0,
              "moe.experts.fwd": 10.0, "moe.experts.bwd": 15.0,
              "moe.route.fwd": 3.0, "attn.core.fwd": 8.0,
              "attn.core.bwd": 16.0, "lm.head.bwd": 9.0}
    r = _reading(phases, {"moe_held_pairs": [12288.0, 12288.0],
                          "moe_held_max": [480.0, 384.0]})
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in LM_METRICS}
    assert read["ssm_scan_ms"] == 50.0 and read["moe_experts_ms"] == 25.0
    assert read["moe_route_ms"] == 3.0 and read["attn_core_ms"] == 24.0
    assert read["lm_head_ms"] == 9.0
    # 4 layers' least time over the measured: bytes bind both kernels
    assert read["ssm_scan_roofline"] == pytest.approx(100 * 4.2916 / 50, 1e-3)
    assert read["moe_experts_roofline"] == pytest.approx(100 * 5.3235 / 25,
                                                         1e-3)
    # 12,288 pairs over 4 layers x 8 experts = 384 a slot
    assert read["moe_held_max_over_mean"] == pytest.approx((1.25 + 1.0) / 2)
    assert read["lm_step_mfu"] is None  # no step program in this trace
    # the experts' share counts the pairs the counter saw, not the uniform
    # share: at 5,250 a layer the products bind, 6.3818 ms over 4 layers
    drifted = _reading(phases, {"moe_held_pairs": [20000.0, 22000.0],
                                "moe_held_max": [1900.0, 1500.0]})
    from benchmark.metrics import moe_experts_roofline

    assert moe_experts_roofline.read(drifted) == pytest.approx(
        100 * 6.3818 / 25, 1e-3)
    assert moe_experts_roofline.read(_reading(phases)) is None


def test_train_step_roofline_names_the_cells_it_can_read():
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "train_step_roofline")
    assert entry["workloads"] == ["wdl_criteo_stream", "wdl_criteo_x4_stream"]
    for name in LM_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"


@pytest.fixture(scope="module")
def tiny_lm_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, four layers
    ``ME*E``, 8 experts of which 4 held, vocabulary 256, rows of 64 ids."""
    root = tmp_path_factory.mktemp("tiny_lm_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", "nemotron3_nano_ep16")
    config["name"] = "tiny_lm"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 64, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_lm.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_lm_stream", "rows": 16, "shards": 2, "batch": 2,
           "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", "lm_stream_s4096_b2_bf16")
    for name, traffic in (("tiny_lm_mix", mix),
                          ("tiny_lm_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_lm", "source": "tests", "why": "tiny",
                         "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_lm.json"}]
    bench["workloads"] = [{"name": "tiny_lm_cell", "config": "tiny_lm",
                           "traffic": "tiny_lm_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_lm_bf16_cell", "config": "tiny_lm",
                           "traffic": "tiny_lm_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_lm_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_lm_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2400000011", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_lm_root):
    proc, lines = _run(tiny_lm_root, "--rehearse")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 8  # whole epochs of 8 steps
    assert set(last["metrics"]) == {"train_rows_per_s", "setup_s"}
    note = next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)
    assert note["compiles_in_window"] == 0
    check = note["info"]["check"]
    assert check["loss_rel_err"] < 1e-5 and check["update_rel_err"] < 0.05
    assert check["pooled_update_rel_err"] < 0.02
    assert check["grad_norm_rel_err"] < 1e-3
    assert check["pooled_grad_rel_err"] < 1e-3
    assert len(check["sys_losses"]) == 2
    assert note["info"]["settings"]["stream_feature_dtype"] == "float32"
    assert note["info"]["counters_last_epoch"]["moe_held_pairs"]


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_lm_root):
    """``lm_stream_s4096_b2_bf16`` is the cell's traffic with the step
    taken at ``--dtype bfloat16``, the nearest precision below the stated
    one; the comparison has to refuse it (on the chip under the shipped
    limits, PERF.md section 2; here under the tiny copy's)."""
    cell, control = (load("workloads", n) for n in (
        "lm_stream_s4096_b2", "lm_stream_s4096_b2_bf16"))
    assert control.pop("dtype") == "bfloat16"
    assert {**control, "what": ""} == {**cell, "what": ""}
    assert not any(w["traffic"] == "lm_stream_s4096_b2_bf16"
                   for w in BENCH["workloads"])
    proc, lines = _run(tiny_lm_root, "--rehearse", cell="tiny_lm_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    check = note["info"]["check"]
    assert check["pooled_grad_rel_err"] > 0.01 and not check["ok"]


def test_a_traced_rehearsal_reads_the_counter_and_no_device_phase(
        tiny_lm_root):
    proc, lines = _run(tiny_lm_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the share of the peak read nothing; spans and the counter are there
    assert "moe_held_max_over_mean" in last["metrics"]
    assert {"dispatch_ms", "infeed_put_ms"} <= set(last["metrics"])
    assert not {"ssm_scan_ms", "lm_step_mfu"} & set(last["metrics"])
    assert last["metrics"]["moe_held_max_over_mean"]["value"] >= 1.0


def test_a_program_without_the_family_fails_at_once(tiny_lm_root, tmp_path,
                                                    monkeypatch):
    """The parent of this PR on the new cell: exit 3 from ``prepare``,
    before JAX is touched."""
    from shifu_tensorflow_tpu.config import model_config

    monkeypatch.delattr(model_config, "HybridLMConfig")
    with pytest.raises(SystemExit) as e:
        train_lm_stream.prepare(FakeCtx({}, {}, str(tmp_path)))
    assert e.value.code == 3
