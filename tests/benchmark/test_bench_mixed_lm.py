"""The mixed-head decoder's cell (``laguna_xs2_ep8_stream_s8k``): its
entries against the contract, its file against the catalog's keys and the
cut, its arithmetic, its plane's settings against the file,
``shapes_mixed_lm.py`` against hand-worked numbers and the initialised
tree, its readers on readings with and without their phases, and a tiny
copy of the cell end to end on the CPU through ``run.py``."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_mixed_lm
from benchmark.planes import train_lm_stream, train_lm_stream_ref, train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna_xs2_ep8_stream_s8k"
CONFIG = "laguna_xs2_ep8"
TRAFFIC = "lm_stream_s8192_b1"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
MIXED_METRICS = (
    "mixed_lm_step_mfu", "attn_window64_ms", "attn_window64_roofline",
    "attn_full48_ms", "partial_rope_ms", "route256_ms", "small_experts_ms",
    "small_experts_roofline", "shared_expert_ms", "dense_mlp_ms",
    "route256_held_max_over_mean")
FULL, SLIDING = "full_attention", "sliding_attention"
PERIOD = [FULL, SLIDING, SLIDING, SLIDING]
#: the catalog's ``config`` for the model (model-configs guide,
#: ``architectures.jsonl``), by key; the lists are its 40 blocks
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": PERIOD * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 1, "hidden_size": 64, "num_hidden_layers": 2,
    "layer_types": [FULL, SLIDING], "mlp_layer_types": ["dense", "sparse"],
    "num_attention_heads": 4, "num_attention_heads_per_layer": [4, 6],
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
               "original_max_position_embeddings": 32,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 100,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 32},
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "intermediate_size": 96, "num_experts": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "n_shared_experts": 1,
    "moe_routed_scaling_factor": 2.5, "gating": True, "hidden_act": "silu",
    "scoring_func": "sigmoid", "norm_topk_prob": True}


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
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, TRAFFIC, CONFIG)
    # the load is an eighth of the deployment's, and the why says so
    assert "256 pairs (deployed 2,048)" in cell["why"]
    assert "attention over its share" in cell["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/poolside/Laguna-XS.2/"
                               "blob/main/config.json")
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) == 1 <= len(cells) // 4


def test_the_sibling_cell_still_meets_what_it_met_but_the_count_of_cells():
    """``test_bench_swa_lm.py``'s test of the same name, which also counts
    four cells and is expected to fail since this PR's fifth
    (``tests/conftest.py``): its other checks, here."""
    from test_bench_contract import check_cell

    cell = next(w for w in BENCH["workloads"]
                if w["name"] == "mellum2_ep4_stream_s8k")
    check_cell(BENCH, ROOT, cell)
    assert (cell["chips"], cell["traffic"]) == (1, "lm_stream_s8192_b2")
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "mlp_layer_types", "num_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/JetBrains/"
                               "Mellum2-12B-A2.5B-Instruct/blob/main/"
                               "config.json")
    assert [w["name"] for w in BENCH["workloads"]][-2:] == [
        "mellum2_ep4_stream_s8k", CELL]


def test_the_file_holds_the_catalog_keys_and_states_the_cut():
    """Every published key under its own name; what differs is listed in
    ``reduced``, with the published value and the deployment beside it; no
    width among them."""
    cfg = load("configs", CONFIG)
    cut = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544,
           "layer_types": PUBLISHED["layer_types"][:5],
           "mlp_layer_types": PUBLISHED["mlp_layer_types"][:5],
           "num_attention_heads_per_layer": [48, 64, 64, 64, 48]}
    assert sorted(cut) == sorted(cfg["reduced"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == cut.get(key, value), key
        assert cfg["published"].get(key, value) == value, key
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])
    # floors: a whole period and four blocks after the dense one, 8 routed
    # experts, an eighth of the vocabulary
    assert cut["layer_types"][1:] == PERIOD[1:] + PERIOD[:1]
    assert cut["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cut["num_experts"] >= 8 and cut["vocab_size"] * 8 == 100352
    p = cfg["model_config"]["train"]["params"]
    # the program's keys: the router keeps its published width and is told
    # what is held; of how many shards is the file's to state, in words
    assert (p["num_experts"], p["experts_held"], p["vocab_size"]) == (
        256, [0, 32], 12544)
    for key, value in PUBLISHED.items():
        assert p.get(key, value) == cut.get(key, value) or key in (
            "num_experts",), key
    assert (p["hidden_act"], p["scoring_func"], p["n_shared_experts"]) == (
        "silu", "sigmoid", 1)
    assert p["expert_tile"] == 384 and p["LearningRate"] == 1e-05
    assert "8 ways" in cfg["deployment"]
    assert "691,034,112 parameters x 16 B" in cfg["deployment"]
    assert "11.06 GB" in cfg["deployment"]
    assert "an eighth" in cfg["load"] or "1/8" in cfg["load"]
    assert {"hidden_act", "gating", "router", "shared_expert", "heads",
            "rotary", "qk_norm_and_bias", "optimizer", "dtype",
            "initializer_range", "rows", "recomputation", "expert_tile",
            "attention_tile"} <= set(cfg["assumed"])
    assert "left out, by name" in cfg["assumed"]["gating"]
    assert cfg["data"] == {"tokens_per_row": 8192, "id_below": 12544}
    assert cfg["check"]["reference"] == "mixed_gqa_moe_lm"


def test_the_configurations_arithmetic():
    """691,034,112 weights (and 1,024 correction-bias elements that rest),
    11.06 GB at 16 B each, block by block as the file's ``deployment``
    writes them."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    attn = {k: shapes_mixed_lm.attention_parameters(p, k)
            for k in (FULL, SLIDING)}
    assert attn == {FULL: 29_360_128, SLIDING: 37_748_736}
    dense = shapes_mixed_lm.feed_forward_parameters(p, "dense")
    sparse = shapes_mixed_lm.feed_forward_parameters(p, "sparse")
    assert dense == 3 * 2048 * 8192 == 50_331_648
    assert sparse - 256 == 524_288 + 32 * 3_145_728 + 3_145_728
    norms = 2 * 2048
    assert attn[FULL] + dense + norms == 79_695_872
    assert attn[SLIDING] + sparse - 256 + norms == 142_086_144
    assert attn[FULL] + sparse - 256 + norms == 133_697_536
    weights = shapes_mixed_lm.parameter_count(p) - 4 * 256
    assert weights == 691_034_112
    assert weights == (79_695_872 + 3 * 142_086_144 + 133_697_536
                       + 2 * 12544 * 2048 + 2048)
    assert round(weights * 16 / 1e9, 2) == 11.06
    # the uncut model by the same functions: 33.44 B against the 33.4 B
    # the model's card states
    whole = dict(p, **{k: PUBLISHED[k] for k in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "vocab_size")}, experts_held=[0, 256])
    assert shapes_mixed_lm.parameter_count(whole) == pytest.approx(
        33.44e9, rel=1e-3)


def test_the_counts_are_the_hand_worked_numbers():
    """19.7 TFLOP a step of one row of 8,192 at uniform routing: products
    outside the experts 12.9, the two full cores 4.95 (48 heads, 4,096.5
    keys a query), the three window cores 1.20 (64 heads, 496.03 keys), the
    held experts 0.62 (8,192 pairs a layer)."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    tokens = seq = 8192
    assert shapes_mixed_lm.train_step_flops(p, tokens, seq) == pytest.approx(
        19.68e12, rel=1e-3)
    full = shapes_mixed_lm.attention_flops(p, tokens, seq, FULL)
    band = shapes_mixed_lm.attention_flops(p, tokens, seq, SLIDING)
    assert full == 3 * (8192 * 8193 // 2) * 48 * 512
    assert 2 * full == pytest.approx(4.948e12, rel=1e-3)
    assert 3 * band == pytest.approx(1.198e12, rel=1e-3)
    assert shapes_mixed_lm.keys_seen(8192, 512) / 8192 == pytest.approx(
        496.03, abs=0.01)
    assert shapes_mixed_lm.keys_seen(8192, None) == 8192 * 8193 // 2
    assert shapes_mixed_lm.held_pairs(p, tokens) == 8192
    experts = shapes_mixed_lm.experts_flops(p, 8192)
    assert experts == 3 * 8192 * 2 * 3 * 2048 * 512
    assert 4 * experts == pytest.approx(0.618e12, rel=1e-3)
    outside = (shapes_mixed_lm.train_step_flops(p, tokens, seq)
               - 2 * full - 3 * band - 4 * experts)
    assert outside == pytest.approx(12.91e12, rel=1e-3)
    assert outside == 6 * tokens * (
        2 * 29_360_128 + 3 * 37_748_736 + 50_331_648
        + 4 * (2048 * 256 + 3_145_728) + 12544 * 2048)
    # a layer's held weights, read twice and written once: 1.208 GB, 1.47
    # ms at 819 GB/s, against 0.78 ms for 8,192 pairs' products: bytes bind
    assert shapes_mixed_lm.experts_bytes(p, 0) == 3 * 4 * 32 * 3_145_728
    from benchmark import peaks, shapes_lm

    v5e = peaks.lookup("TPU v5 lite")
    assert shapes_lm.least_seconds(
        experts, shapes_mixed_lm.experts_bytes(p, 8192), v5e
    ) == shapes_mixed_lm.experts_bytes(p, 8192) / v5e["bytes_per_s"]
    assert (shapes_mixed_lm.heads_of(p, FULL),
            shapes_mixed_lm.heads_of(p, SLIDING)) == (48, 64)
    assert (shapes_mixed_lm.blocks_of(p, SLIDING),
            shapes_mixed_lm.blocks_of(p, "sparse"),
            shapes_mixed_lm.blocks_of(p, "dense")) == (3, 4, 1)


def test_plane_resolves_what_the_file_records(tmp_path):
    cfg = load("configs", CONFIG)
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
    assert model_config.batch_size == tr["batch"] == 1
    assert (model_config.params.hybrid_lm.hybrid_override_pattern
            == "*DWEWEWE*E")
    assert (tr["rows"], tr["shards"], tr["warm_epochs"],
            tr["check_steps"]) == (16, 4, 1, 2)
    assert train_lm_stream_ref.reference_of(cfg).__name__ == (
        "benchmark.reference.mixed_gqa_moe_lm")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "mixed_gqa_moe_lm.py")) as f:
        source = f.read()
    assert "shifu_tensorflow_tpu" not in source.split('"""', 2)[2]
    for line in source.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in (
                "__future__", "math", "jax"), line


@pytest.mark.parametrize("blocks", [2, 5, 1])
def test_parameter_count_equals_the_initialised_tree(blocks):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    kinds = (PERIOD + PERIOD)[:blocks]
    p = dict(TINY_PARAMS, layer_types=kinds, num_hidden_layers=blocks,
             mlp_layer_types=(["dense"] + ["sparse"] * 4)[:blocks],
             num_attention_heads_per_layer=[
                 {FULL: 4, SLIDING: 6}[k] for k in kinds])
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert shapes_mixed_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, config=CONFIG):
    from benchmark import peaks

    cfg = load("configs", config)
    tr = load("workloads", {CONFIG: TRAFFIC,
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


PHASES = {"attn.window.fwd": 12.0, "attn.window.bwd": 28.0,
          "attn.core.fwd": 30.0, "attn.core.bwd": 70.0,
          "attn.rope.fwd": 2.0, "attn.rope.bwd": 3.0,
          "moe.experts.fwd": 10.0, "moe.experts.bwd": 20.0,
          "moe.route.fwd": 6.0, "moe.route.bwd": 1.0,
          "moe.shared.fwd": 3.0, "moe.shared.bwd": 6.0,
          "mlp.dense.fwd": 5.0, "mlp.dense.bwd": 11.0}
COUNTERS = {"moe_held_pairs": [32768.0, 32768.0],
            "moe_held_max": [300.0, 340.0]}


@pytest.mark.parametrize("name", MIXED_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scope, a capture off the TPU,
    another family's cell, and the other decoders' cells, which have an
    ``attn.window``, a ``moe.route`` and counters of their own: nothing,
    and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0})) is None
    for other in ("wdl_criteo", "nemotron3_nano_ep16", "mellum2_ep4"):
        assert reader.read(_reading(PHASES, COUNTERS, config=other)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    r = _reading(PHASES, COUNTERS)
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in MIXED_METRICS}
    assert read["attn_window64_ms"] == 40.0
    assert read["attn_full48_ms"] == 100.0
    assert read["partial_rope_ms"] == 5.0 and read["route256_ms"] == 7.0
    assert read["small_experts_ms"] == 30.0
    assert read["shared_expert_ms"] == 9.0 and read["dense_mlp_ms"] == 16.0
    # three window layers' least time: 1.812 GB a layer / 819 GB/s = 2.212
    # ms, the bytes (the products, 0.3994 TFLOP / 197 TFLOP/s = 2.027 ms,
    # do not bind at 64 heads inside a 512 window)
    assert read["attn_window64_roofline"] == pytest.approx(
        100 * 3 * 2.2124 / 40, rel=1e-3)
    # 32,768 pairs over 4 layers = the uniform 8,192 a layer: 4 x (1.2080
    # GB of weights + 0.2684 GB of rows) / 819 GB/s = 7.211 ms
    assert read["small_experts_roofline"] == pytest.approx(
        100 * 7.211 / 30, rel=1e-3)
    # (300 / 256 + 340 / 256) / 2
    assert read["route256_held_max_over_mean"] == pytest.approx(1.25)
    assert read["mixed_lm_step_mfu"] is None  # no step program in this trace
    assert all(v is None or v < 100 for n, v in read.items()
               if n.endswith(("_roofline", "_mfu")))
    # no pair on a held expert: the weights' bytes alone, 4 x 1.475 ms
    idle = _reading(PHASES, {"moe_held_pairs": [0.0], "moe_held_max": [0.0]})
    from benchmark.metrics import (
        route256_held_max_over_mean,
        small_experts_roofline,
    )

    assert small_experts_roofline.read(_reading(PHASES)) is None
    assert route256_held_max_over_mean.read(_reading(PHASES)) is None
    assert route256_held_max_over_mean.read(idle) is None
    assert small_experts_roofline.read(idle) == pytest.approx(
        100 * 4 * 1.4750 / 30, rel=1e-3)


def test_step_mfu_reads_the_step_programs_time():
    """19.68 TFLOP / 197 TFLOP/s = 99.9 ms at peak: a 500 ms step is
    19.98%."""
    from benchmark import xplane
    from benchmark.metrics import mixed_lm_step_mfu

    r = _reading(PHASES, COUNTERS)
    r["step_pattern"] = "jit_train_step"
    r["window_ns"] = (0, 2 * 10**9)
    r["trace"] = {"devices": {0: {xplane.MODULE_LINE: [
        ("jit_train_step(1)", i * 6 * 10**8, 5 * 10**8) for i in range(3)]}},
        "host": []}
    assert xplane.step_device_ms(r["trace"], "jit_train_step",
                                 r["window_ns"]) == pytest.approx(500.0)
    assert mixed_lm_step_mfu.read(r) == pytest.approx(19.98, rel=1e-3)
    r["config"] = load("configs", "mellum2_ep4")
    assert mixed_lm_step_mfu.read(r) is None


def test_every_metric_of_the_cell_is_an_entry_of_its_own():
    """No accepted entry's ``workloads`` list was touched: each reading of
    the new cell is a new entry with a new reader."""
    from benchmark import run

    for name in MIXED_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
        assert m["source"] == ("program_counter" if "held_max" in name
                               else "device_trace")
        assert (m["unit"] == "%") == name.endswith(("_roofline", "_mfu"))
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == set(MIXED_METRICS) | {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert not any(CELL in m.get("workloads", ())
                   for m in BENCH["per_layer"]
                   if m["name"] not in MIXED_METRICS)
    assert [m["name"] for m in BENCH["per_layer"]][-len(MIXED_METRICS):] == (
        list(MIXED_METRICS))


@pytest.fixture(scope="module")
def tiny_mixed_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, the dense block and
    a sliding sparse block, 8 experts of which 4 held, vocabulary 256, rows
    of 64, one row a step."""
    root = tmp_path_factory.mktemp("tiny_mixed_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", CONFIG)
    config["name"] = "tiny_mixed"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 64, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_mixed.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_lm_stream_ref", "rows": 8, "shards": 2,
           "batch": 1, "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", TRAFFIC + "_bf16")
    for name, traffic in (("tiny_mixed_mix", mix),
                          ("tiny_mixed_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_mixed", "source": "tests",
                         "why": "tiny", "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_mixed.json"}]
    bench["workloads"] = [{"name": "tiny_mixed_cell", "config": "tiny_mixed",
                           "traffic": "tiny_mixed_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_mixed_bf16_cell",
                           "config": "tiny_mixed",
                           "traffic": "tiny_mixed_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_mixed_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_mixed_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _note(lines):
    return next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_mixed_root):
    proc, lines = _run(tiny_mixed_root, "--rehearse")
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
    for leaf in ("shared/gate/kernel", "experts/gate",
                 "layers_1/mixer/gate/kernel", "e_score_correction_bias"):
        assert any(n.endswith(leaf) for n in check["leaf_update_rel_err"])
    assert note["info"]["settings"]["stream_feature_dtype"] == "float32"
    assert note["info"]["counters_last_epoch"]["moe_held_pairs"]
    assert note["info"]["batch"] == 1


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_mixed_root):
    """The control traffic is the cell's with the step taken at ``--dtype
    bfloat16``, the nearest precision below the stated one; the comparison
    has to refuse it (on the chip under the shipped limits, PERF.md
    section 2; here under the tiny copy's)."""
    cell, control = (load("workloads", n)
                     for n in (TRAFFIC, TRAFFIC + "_bf16"))
    assert control.pop("dtype") == "bfloat16"
    assert {**control, "what": ""} == {**cell, "what": ""}
    assert not any(w["traffic"] == TRAFFIC + "_bf16"
                   for w in BENCH["workloads"])
    proc, lines = _run(tiny_mixed_root, "--rehearse",
                       cell="tiny_mixed_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = _note(lines)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    assert not note["info"]["check"]["ok"]


def test_a_traced_rehearsal_reads_spans_counters_and_no_device_phase(
        tiny_mixed_root):
    proc, lines = _run(tiny_mixed_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the shares read nothing and the line leaves them out; the spans and
    # the step's counters are there
    assert {"dispatch_ms", "infeed_put_ms",
            "route256_held_max_over_mean"} <= set(last["metrics"])
    assert 1.0 <= last["metrics"]["route256_held_max_over_mean"]["value"] < 4
    assert set(MIXED_METRICS) & set(last["metrics"]) == {
        "route256_held_max_over_mean"}


def test_the_parents_reader_ends_in_prepare(tmp_path, monkeypatch, capsys):
    """The parent of this PR on the new cell: ``pattern_of`` raises on
    ``dense``, so exit 3 from ``prepare``, before JAX is touched and before
    anything is written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", CONFIG)

    def parents(params):
        mlps = set(params.get("mlp_layer_types", ())) - {"sparse"}
        if mlps:
            raise ValueError(f"mlp_layer_types {sorted(mlps)} are not "
                             "implemented (sparse: the family has no dense "
                             "gated feed-forward)")

    monkeypatch.setattr(model_config.HybridLMConfig, "from_json",
                        staticmethod(parents))
    with pytest.raises(SystemExit) as e:
        train_lm_stream_ref.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    err = capsys.readouterr().err
    assert "no result" in err and "dense" in err
