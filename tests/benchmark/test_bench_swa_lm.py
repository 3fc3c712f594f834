"""The sliding-window + gated-expert cell: its entries against the
contract, its file against the catalog's keys and the cut, its plane's
settings against the file, ``shapes_swa_lm.py`` against the initialised
tree, its readers on readings with and without their phases, and a tiny
copy of the cell end to end on the CPU through ``run.py``."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_swa_lm
from benchmark.planes import train_lm_stream, train_lm_stream_ref, train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2_ep4_stream_s8k"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
TRAFFIC = next(w["traffic"] for w in BENCH["workloads"] if w["name"] == CELL)
SWA_METRICS = ("swa_lm_step_mfu", "attn_window_ms", "attn_window_roofline",
               "attn_full_ms", "attn_rope_ms", "gated_experts_ms",
               "gated_experts_roofline", "softmax_route_ms")
#: the catalog's ``config`` for the model (model-configs guide,
#: ``architectures.jsonl``), by key; the lists are its 28 blocks
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28}
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 2,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse", "sparse"], "sliding_window": 16,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4,
                           "original_max_position_embeddings": 32}},
    "rms_norm_eps": 1e-6, "vocab_size": 256, "num_experts": 8,
    "experts_held": [0, 4], "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 0,
    "hidden_act": "silu", "scoring_func": "softmax", "norm_topk_prob": True,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16}


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
    assert cell["chips"] == 1
    assert cell["traffic"] in ("lm_stream_s8192_b2", "lm_stream_s8192_b1")
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/JetBrains/"
                               "Mellum2-12B-A2.5B-Instruct/blob/main/"
                               "config.json")
    cells = BENCH["workloads"]
    assert len(cells) == 4 and sum(w["chips"] == 4 for w in cells) == 1


def test_the_file_holds_the_catalog_keys_and_states_the_cut():
    """Every published key under its own name; what differs is listed in
    ``reduced``, with the published value and the deployment beside it; no
    width among them."""
    cfg = load("configs", "mellum2_ep4")
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 24576,
           "layer_types": PUBLISHED["layer_types"][:4],
           "mlp_layer_types": ["sparse"] * 4}
    assert sorted(cut) == sorted(cfg["reduced"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
        assert cfg["published"].get(key, value) == value, key
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])
    p = cfg["model_config"]["train"]["params"]
    # the program's keys: the router keeps its published width and is told
    # what is held; of how many shards is the file's to state, in words
    assert (p["num_experts"], p["experts_held"], p["vocab_size"]) == (
        64, [0, 16], 24576)
    for key, value in PUBLISHED.items():
        assert p.get(key, value) == cut.get(key, value) or key in (
            "num_experts",), key
    assert (p["hidden_act"], p["scoring_func"], p["n_shared_experts"]) == (
        "silu", "softmax", 0)
    assert "4 ways" in cfg["deployment"]
    assert "595,153,152 parameters x 16 B" in cfg["deployment"]
    assert "9.52 GB" in cfg["deployment"]
    assert "a quarter" in cfg["load"] or "1/4" in cfg["load"]
    assert {"rotary_pairing", "qk_norm_and_bias", "router", "mtp_head",
            "optimizer", "dtype", "initializer_range", "rows",
            "recomputation", "expert_tile"} <= set(cfg["assumed"])
    assert cfg["data"] == {"tokens_per_row": 8192, "id_below": 24576}
    assert cfg["check"]["reference"] == "swa_moe_lm"


def test_plane_resolves_what_the_file_records(tmp_path):
    cfg = load("configs", "mellum2_ep4")
    tr = load("workloads", TRAFFIC)
    assert tr["plane"] == "train_lm_stream_ref"
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps(cfg["model_config"]))
    argv = train_lm_stream.cli_argv(FakeCtx(cfg, tr, str(tmp_path)),
                                    str(tmp_path), str(mc))
    args, conf, model_config, schema = train_stream.resolve(argv)
    assert train_lm_stream.resolved_settings(
        args, conf, model_config) == cfg["settings"]
    assert schema.num_features == cfg["data"]["tokens_per_row"] == 8192
    assert args.stream and args.checkpoint_dir is None
    assert model_config.batch_size == tr["batch"]
    assert model_config.params.hybrid_lm.hybrid_override_pattern == "WEWEWE*E"
    assert tr["rows"] % tr["batch"] == 0 and tr["rows"] == 32
    assert train_lm_stream_ref.reference_of(cfg).__name__ == (
        "benchmark.reference.swa_moe_lm")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "swa_moe_lm.py")) as f:
        source = f.read()
    assert "shifu_tensorflow_tpu" not in source.split('"""', 2)[2]
    for line in source.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in (
                "__future__", "math", "jax"), line


def test_parameter_count_is_the_shipped_configurations():
    p = load("configs", "mellum2_ep4")["model_config"]["train"]["params"]
    assert shapes_swa_lm.parameter_count(p) == 595_153_152
    assert shapes_swa_lm.attention_parameters(p) == 21_233_664
    assert shapes_swa_lm.expert_parameters(p) == 6_193_152
    # 24.46 TFLOP a step of 2 rows of 8,192: the full core 3.30, a window
    # core 0.77 (960 keys a query on average against 4,096.5), a layer's
    # held experts 1.22 at uniform routing
    tokens, seq = 16384, 8192
    assert shapes_swa_lm.train_step_flops(p, tokens, seq) == pytest.approx(
        24.46e12, rel=1e-3)
    full = shapes_swa_lm.attention_flops(p, tokens, seq, "full_attention")
    band = shapes_swa_lm.attention_flops(p, tokens, seq, "sliding_attention")
    assert full == pytest.approx(3.299e12, rel=1e-3)
    assert band == pytest.approx(0.773e12, rel=1e-3)
    assert shapes_swa_lm.keys_seen(8192, 1024) / 8192 == pytest.approx(
        960.06, abs=0.01)
    assert shapes_swa_lm.keys_seen(8192, 8192) == shapes_swa_lm.keys_seen(
        8192, None) == 8192 * 8193 // 2
    assert shapes_swa_lm.held_pairs(p, tokens) == 32768
    assert shapes_swa_lm.gated_experts_flops(p, 32768) == pytest.approx(
        1.2176e12, rel=1e-3)


@pytest.mark.parametrize("kinds", ["WF", "W", "WWWF"])
def test_parameter_count_equals_the_initialised_tree(kinds):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    names = {"W": "sliding_attention", "F": "full_attention"}
    p = dict(TINY_PARAMS, layer_types=[names[k] for k in kinds],
             mlp_layer_types=["sparse"] * len(kinds),
             num_hidden_layers=len(kinds))
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert shapes_swa_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, config="mellum2_ep4"):
    from benchmark import peaks

    cfg = load("configs", config)
    tr = load("workloads", {"mellum2_ep4": TRAFFIC,
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


PHASES = {"attn.window.fwd": 12.0, "attn.window.bwd": 28.0,
          "attn.core.fwd": 30.0, "attn.core.bwd": 70.0,
          "attn.rope.fwd": 2.0, "attn.rope.bwd": 3.0,
          "moe.experts.fwd": 20.0, "moe.experts.bwd": 40.0,
          "moe.route.fwd": 6.0, "moe.route.bwd": 1.0}


@pytest.mark.parametrize("name", SWA_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scopes, a capture off the TPU,
    another family's cell, and the other decoder's cell, which has an
    ``attn.core`` and a ``moe.route`` of its own: nothing, and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0})) is None
    for other in ("wdl_criteo", "nemotron3_nano_ep16"):
        assert reader.read(_reading(PHASES, {"moe_held_pairs": [9e4]},
                                    config=other)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    r = _reading(PHASES, {"moe_held_pairs": [131072.0, 131072.0],
                          "moe_held_max": [2300.0, 2100.0]})
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in SWA_METRICS}
    assert read["attn_window_ms"] == 40.0 and read["attn_full_ms"] == 100.0
    assert read["attn_rope_ms"] == 5.0 and read["gated_experts_ms"] == 60.0
    assert read["softmax_route_ms"] == 7.0
    # three window layers' least time, 3 x 0.773 TFLOP / 197 TFLOP/s =
    # 11.77 ms (the products bind; the bytes would take 3 x 1.97 ms)
    assert read["attn_window_roofline"] == pytest.approx(
        100 * 11.774 / 40, rel=1e-3)
    # 131,072 pairs over 4 layers = the uniform 32,768: 4 x 1.2176 TFLOP
    assert read["gated_experts_roofline"] == pytest.approx(
        100 * 24.723 / 60, rel=1e-3)
    assert read["swa_lm_step_mfu"] is None  # no step program in this trace
    assert all(v is None or v < 100 for n, v in read.items()
               if n.endswith(("_roofline", "_mfu")))
    # no pair on a held expert: the weights' bytes still bind, 3 x 396 MB
    idle = _reading(PHASES, {"moe_held_pairs": [0.0], "moe_held_max": [0.0]})
    from benchmark.metrics import gated_experts_roofline

    assert gated_experts_roofline.read(idle) == pytest.approx(
        100 * 4 * 1.4517 / 60, rel=1e-3)
    assert gated_experts_roofline.read(_reading(PHASES)) is None


def test_every_metric_of_the_cell_is_an_entry_of_its_own():
    """No accepted entry's ``workloads`` list was touched: each reading of
    the new cell is a new entry with a new reader."""
    from benchmark import run

    for name in SWA_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
        assert m["source"] == "device_trace"
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == set(SWA_METRICS) | {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert not any(CELL in m.get("workloads", ())
                   for m in BENCH["per_layer"]
                   if m["name"] not in SWA_METRICS)


@pytest.fixture(scope="module")
def tiny_swa_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, a window and a
    full block, 8 experts of which 4 held, vocabulary 256, rows of 64."""
    root = tmp_path_factory.mktemp("tiny_swa_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", "mellum2_ep4")
    config["name"] = "tiny_swa"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 64, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_swa.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_lm_stream_ref", "rows": 16, "shards": 2,
           "batch": 2, "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", TRAFFIC + "_bf16")
    for name, traffic in (("tiny_swa_mix", mix),
                          ("tiny_swa_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_swa", "source": "tests", "why": "tiny",
                         "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_swa.json"}]
    bench["workloads"] = [{"name": "tiny_swa_cell", "config": "tiny_swa",
                           "traffic": "tiny_swa_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_swa_bf16_cell", "config": "tiny_swa",
                           "traffic": "tiny_swa_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_swa_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_swa_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000011", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _note(lines):
    return next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_swa_root):
    proc, lines = _run(tiny_swa_root, "--rehearse")
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
    assert any(n.endswith("experts/gate")
               for n in check["leaf_update_rel_err"])
    assert note["info"]["settings"]["stream_feature_dtype"] == "float32"
    assert note["info"]["counters_last_epoch"]["moe_held_pairs"]


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_swa_root):
    """The control traffic is the cell's with the step taken at ``--dtype
    bfloat16``, the nearest precision below the stated one; the comparison
    has to refuse it (on the chip under the shipped limits, PERF.md
    section 2; here under the tiny copy's)."""
    name = TRAFFIC
    cell, control = (load("workloads", n) for n in (name, name + "_bf16"))
    assert control.pop("dtype") == "bfloat16"
    assert {**control, "what": ""} == {**cell, "what": ""}
    assert not any(w["traffic"] == name + "_bf16"
                   for w in BENCH["workloads"])
    proc, lines = _run(tiny_swa_root, "--rehearse",
                       cell="tiny_swa_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = _note(lines)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    assert not note["info"]["check"]["ok"]


def test_a_traced_rehearsal_reads_spans_and_no_device_phase(tiny_swa_root):
    proc, lines = _run(tiny_swa_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the shares read nothing and the line leaves them out; spans are there
    assert {"dispatch_ms", "infeed_put_ms"} <= set(last["metrics"])
    assert not set(SWA_METRICS) & set(last["metrics"])


@pytest.mark.parametrize("lacks", ["the family", "the keys"])
def test_a_program_without_the_keys_ends_in_prepare(tmp_path, monkeypatch,
                                                    lacks, capsys):
    """The parent of this PR on the new cell: exit 3 from ``prepare``,
    before JAX is touched and before anything is written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", "mellum2_ep4")
    if lacks == "the family":
        monkeypatch.delattr(model_config, "HybridLMConfig")
    else:  # the parent's reader: the pattern string is a required key
        def parents(params):
            missing = [k for k in ("hidden_size", "hybrid_override_pattern",
                                   "vocab_size") if k not in params]
            if missing:
                raise ValueError(
                    f"ModelType=hybrid_lm needs train.params {missing}")

        monkeypatch.setattr(model_config.HybridLMConfig, "from_json",
                            staticmethod(parents))
    with pytest.raises(SystemExit) as e:
        train_lm_stream_ref.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    assert "no result" in capsys.readouterr().err
