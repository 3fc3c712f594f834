"""The latent-attention decoder's cell (``glm47_flash_ep8_stream_s8k``): its
entries against the contract, its file against the catalog's keys and the
cut, its arithmetic, its plane's settings against the file,
``shapes_mla_lm.py`` against hand-worked numbers and the initialised tree,
its readers on readings with and without their phases, a tiny copy of the
cell and of its control end to end on the CPU through ``run.py``; and, for
the accepted tests that stop at a line a sixth cell or a new last entry
breaks (``tests/conftest.py``), their other checks."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_mla_lm
from benchmark.planes import train_lm_stream, train_lm_stream_ref, train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm47_flash_ep8_stream_s8k"
CONFIG = "glm47_flash_ep8"
TRAFFIC = "lm_stream_s8192_b1"
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
MLA_METRICS = (
    "mla_lm_step_mfu", "mla_latent_ms", "mla_core_ms", "mla_core_roofline",
    "mla_rope_ms", "mtp_ms", "mla_route_ms", "mla_experts_ms",
    "mla_experts_roofline", "mla_held_max_over_mean")
#: the catalog's ``config`` for the model (model-configs guide,
#: ``architectures.jsonl``), by key
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}
CUT = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360}
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 1, "hidden_size": 64, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "rope_theta": 100, "rope_scaling": None,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 256,
    "n_routed_experts": 8, "experts_held": [0, 4], "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
    "scoring_func": "sigmoid", "num_nextn_predict_layers": 1,
    "mtp_loss_weight": 0.3}


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
    assert "512 pairs (deployed 4,096)" in cell["why"]
    assert "attention over share" in cell["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == SOURCE == load("configs", CONFIG)["source"]
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) == 1 <= len(cells) // 4
    assert sum(w["config"] == CONFIG for w in cells) == 1  # no second cell


def test_the_sibling_cells_still_meet_what_they_met_but_their_place():
    """``test_bench_mixed_lm.py``'s
    ``test_the_sibling_cell_still_meets_what_it_met_but_the_count_of_cells``
    also wants the Mellum and Laguna cells to be the list's last two and is
    expected to fail since this PR's sixth (``tests/conftest.py``
    ``_LAST_TWO_CELLS``): its other checks, here, and the order the cells
    came in."""
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
    assert [w["name"] for w in BENCH["workloads"]][-3:] == [
        "mellum2_ep4_stream_s8k", "laguna_xs2_ep8_stream_s8k", CELL]


@pytest.mark.parametrize("name", ["epoch_fill_ms", "epoch_drain_ms",
                                  "epoch_turn_ms", "epoch_boundary_idle_ms"])
def test_the_boundary_entries_stand_as_they_stood(name):
    """``test_bench_boundary.py``'s
    ``test_the_entry_lists_the_one_cell_and_stands_at_the_end_of_the_list``
    wants the four boundary entries to be the list's last and is expected
    to fail since this PR's ten were appended (``tests/conftest.py``
    ``_LAST_FOUR``): its other checks, here, and that the four are the
    last the list had before the ten."""
    names = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][names.index(name)]
    assert entry["workloads"] == ["nemotron3_nano_ep16_stream_s4k"]
    assert entry["layer"] == "trainer train/trainer.py"
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "ms", "lower", "train_rows_per_s")
    assert entry["source"] == ("device_trace" if "idle" in name
                               else "program_span")
    before = names[:-len(MLA_METRICS)]
    assert before[-4:] == ["epoch_fill_ms", "epoch_drain_ms",
                           "epoch_turn_ms", "epoch_boundary_idle_ms"]


def test_the_laguna_cells_entries_stand_where_they_stood():
    """``test_bench_boundary.py``'s
    ``test_the_laguna_cells_entries_stand_as_they_stood`` looks for the
    Laguna cell's eleven right before the list's LAST four and is expected
    to fail since this PR's ten (``tests/conftest.py``
    ``_ELEVEN_BEFORE_FOUR``): its other checks, here, and the eleven right
    before the boundary's four."""
    from benchmark import run
    from test_bench_mixed_lm import CELL as laguna, MIXED_METRICS

    per_layer = BENCH["per_layer"]
    for name in MIXED_METRICS:
        m = next(m for m in per_layer if m["name"] == name)
        assert m["workloads"] == [laguna]
        assert m["moves"] == "train_rows_per_s"
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", laguna)}
    assert listed == set(MIXED_METRICS) | {
        m["name"] for m in per_layer if "workloads" not in m}
    assert not any(laguna in m.get("workloads", ()) for m in per_layer
                   if m["name"] not in MIXED_METRICS)
    before = [m["name"] for m in per_layer][:-len(MLA_METRICS) - 4]
    assert before[-len(MIXED_METRICS):] == list(MIXED_METRICS)
    # what the accepted benchmark had, in its order, is this one's start
    assert [m["name"] for m in per_layer][-len(MLA_METRICS):] == list(
        MLA_METRICS)


def test_the_file_holds_the_catalog_keys_and_states_the_cut():
    """Every published key under its own name; what differs is listed in
    ``reduced``, with the published value and the deployment beside it; no
    width among them."""
    cfg = load("configs", CONFIG)
    assert sorted(CUT) == sorted(cfg["reduced"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in CUT}
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cfg["reduced"])
    # floors: the dense block and four after it, 8 routed experts, an
    # eighth of the vocabulary
    assert CUT["num_hidden_layers"] - PUBLISHED["first_k_dense_replace"] >= 4
    assert CUT["n_routed_experts"] >= 8 and CUT["vocab_size"] * 8 == 154880
    p = cfg["model_config"]["train"]["params"]
    # the program's keys: the router keeps its published width and is told
    # what is held; of how many shards is the file's to state, in words
    assert (p["n_routed_experts"], p["experts_held"], p["vocab_size"]) == (
        64, [0, 8], 19360)
    for key, value in PUBLISHED.items():
        assert p.get(key, value) == CUT.get(key, value) or key in (
            "n_routed_experts",), key
    assert (p["hidden_act"], p["scoring_func"], p["n_shared_experts"],
            p["num_nextn_predict_layers"], p["mtp_loss_weight"]) == (
        "silu", "sigmoid", 1, 1, 0.3)
    assert p["expert_tile"] == 768 and p["LearningRate"] == 1e-05
    assert "hybrid_override_pattern" not in p and "layer_types" not in p
    assert "8 ways" in cfg["deployment"]
    assert "706,518,528 parameters x 16 B" in cfg["deployment"]
    assert "11.30 GB" in cfg["deployment"]
    assert "an eighth" in cfg["load"] or "1/8" in cfg["load"]
    assert {"rotary", "mtp_input", "mtp_loss_weight", "mtp_positions",
            "router", "shared_expert", "qk_norm_and_bias", "optimizer",
            "dtype", "initializer_range", "rows", "recomputation",
            "expert_tile", "attention_tile", "head_padding"} <= set(
                cfg["assumed"])
    assert cfg["data"] == {"tokens_per_row": 8192, "id_below": 19360}
    assert cfg["check"]["reference"] == "mla_mtp_moe_lm"


def test_the_configurations_arithmetic():
    """706,518,528 weights (and 320 correction-bias elements that rest),
    11.30 GB at 16 B each, part by part as the file's ``deployment`` and
    ISSUE 36's table write them."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    attn = shapes_mla_lm.attention_parameters(p)
    assert attn == (1_572_864 + 768 + 3_932_160 + 1_179_648 + 512
                    + 4_587_520 + 10_485_760) == 21_759_232
    dense = shapes_mla_lm.gated_parameters(p, "intermediate_size")
    sparse = shapes_mla_lm.sparse_parameters(p) - 64
    assert dense == 3 * 2048 * 10240 == 62_914_560
    assert sparse == 131_072 + 8 * 9_437_184 + 9_437_184
    norms = 2 * 2048
    assert attn + dense + norms == 84_677_888
    assert attn + sparse + norms == 106_829_056
    module = 4_096 + 8_388_608 + 106_829_056 + 2_048
    assert module == 115_223_808
    weights = shapes_mla_lm.parameter_count(p) - 5 * 64
    assert weights == 706_518_528 == (
        84_677_888 + 4 * 106_829_056 + 2 * 19360 * 2048 + 2048 + module)
    assert round(weights * 16 / 1e9, 2) == 11.30
    # the uncut model by the same functions: 30.59 B with the module, 29.94
    # B without, against the "30B-A3B" the family is described as
    whole = dict(p, num_hidden_layers=47, vocab_size=154880,
                 experts_held=[0, 64])
    assert shapes_mla_lm.parameter_count(whole) == pytest.approx(
        30.59e9, rel=1e-3)
    assert shapes_mla_lm.parameter_count(
        dict(whole, num_nextn_predict_layers=0)) == pytest.approx(
            29.94e9, rel=1e-3)


def test_the_counts_are_the_hand_worked_numbers():
    """29.7 TFLOP a step of one row of 8,192 at uniform routing: matrices
    352.58 M parameters a token x 6 x 8,192 = 17.33, six causal cores at
    20 heads of 256 + 256 over 4,096.5 keys a query 12.37."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    tokens = seq = 8192
    core = shapes_mla_lm.core_flops(p, tokens, seq)
    assert core == 3 * (8192 * 8193 // 2) * 20 * 2 * (256 + 256)
    assert core == pytest.approx(2.0618e12, rel=1e-4)
    assert shapes_mla_lm.held_pairs(p, tokens) == 4096  # 512 an expert
    experts = shapes_mla_lm.experts_flops(p, 4096)
    assert experts == 3 * 4096 * 2 * 9_437_184
    matrices = 6 * 21_757_952 + 62_914_560 + 5 * (
        131_072 + 9_437_184) + 2 * 19360 * 2048 + 4096 * 2048
    assert matrices + 5 * 4_718_592 == 352_583_680
    total = shapes_mla_lm.train_step_flops(p, tokens, seq)
    assert total == 6 * tokens * matrices + 6 * core + 5 * experts
    assert total == pytest.approx(29.70e12, rel=1e-3)
    assert 6 * tokens * 352_583_680 == pytest.approx(17.33e12, rel=1e-3)
    # the module: a sixth of the cores, one head pass of two, W_m
    module = shapes_mla_lm.module_flops(p, tokens, seq)
    assert module == 6 * tokens * (
        21_757_952 + 131_072 + 9_437_184 + 19360 * 2048 + 4096 * 2048
    ) + core + experts
    assert module / total == pytest.approx(0.2086, abs=0.001)
    assert shapes_mla_lm.latent_flops(p, tokens) == 6 * tokens * (
        1_572_864 + 3_932_160 + 1_179_648 + 4_587_520)
    # a layer's core: 2.013 GB moved = 2.46 ms at 819 GB/s against 10.47 ms
    # for the products: the products bind.  A layer's held weights, read
    # twice and written once, 0.906 GB + 0.134 GB of rows = 1.27 ms against
    # 1.18 ms for 4,096 pairs' products: the bytes bind, just
    assert shapes_mla_lm.core_bytes(p, tokens) == 4 * 8192 * 20 * 12 * 256
    from benchmark import peaks, shapes_lm

    v5e = peaks.lookup("TPU v5 lite")
    assert shapes_lm.least_seconds(
        core, shapes_mla_lm.core_bytes(p, tokens), v5e) == pytest.approx(
            10.466e-3, rel=1e-3)
    assert shapes_mla_lm.experts_bytes(p, 0) == 3 * 4 * 8 * 9_437_184
    assert shapes_lm.least_seconds(
        experts, shapes_mla_lm.experts_bytes(p, 4096), v5e
    ) == shapes_mla_lm.experts_bytes(p, 4096) / v5e["bytes_per_s"]
    assert (shapes_mla_lm.attention_layers(p),
            shapes_mla_lm.attention_layers(p, False),
            shapes_mla_lm.sparse_layers(p),
            shapes_mla_lm.sparse_layers(p, False),
            shapes_mla_lm.dense_blocks(p)) == (6, 5, 5, 4, 1)


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
            == "LDLELELELE")
    assert train_lm_stream_ref.reference_of(cfg).__name__ == (
        "benchmark.reference.mla_mtp_moe_lm")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "mla_mtp_moe_lm.py")) as f:
        source = f.read()
    assert "shifu_tensorflow_tpu" not in source.split('"""', 2)[2]
    for line in source.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in (
                "__future__", "math", "jax"), line


@pytest.mark.parametrize("blocks,dense,module", [(2, 1, 1), (5, 1, 1),
                                                 (3, 0, 0), (1, 0, 1)])
def test_parameter_count_equals_the_initialised_tree(blocks, dense, module):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    p = dict(TINY_PARAMS, num_hidden_layers=blocks,
             first_k_dense_replace=dense, num_nextn_predict_layers=module)
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert shapes_mla_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, config=CONFIG):
    from benchmark import peaks

    cfg = load("configs", config)
    tr = load("workloads", {CONFIG: TRAFFIC, "laguna_xs2_ep8": TRAFFIC,
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


PHASES = {"attn.latent.fwd": 5.0, "attn.latent.bwd": 12.0,
          "attn.expand.fwd": 9.0, "attn.expand.bwd": 24.0,
          "attn.core.fwd": 60.0, "attn.core.bwd": 140.0,
          "attn.rope.fwd": 4.0, "attn.rope.bwd": 8.0,
          "mtp.merge.fwd": 2.0, "mtp.merge.bwd": 4.0,
          "mtp.block.fwd": 25.0, "mtp.block.bwd": 60.0,
          "mtp.head.fwd": 8.0, "mtp.head.bwd": 16.0,
          "moe.experts.fwd": 8.0, "moe.experts.bwd": 12.0,
          "moe.route.fwd": 6.0, "moe.route.bwd": 1.0}
COUNTERS = {"moe_held_pairs": [20480.0, 20480.0],
            "moe_held_max": [560.0, 592.0],
            "main_loss": [10.0, 10.0], "mtp_loss": [10.1, 10.1]}


@pytest.mark.parametrize("name", MLA_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scope, a capture off the TPU,
    another family's cell, and the other decoders' cells, which have an
    ``attn.core``, a ``moe.route`` and counters of their own: nothing,
    and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0})) is None
    for other in ("wdl_criteo", "nemotron3_nano_ep16", "mellum2_ep4",
                  "laguna_xs2_ep8"):
        assert reader.read(_reading(PHASES, COUNTERS, config=other)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    r = _reading(PHASES, COUNTERS)
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in MLA_METRICS}
    assert read["mla_latent_ms"] == 50.0 and read["mla_core_ms"] == 200.0
    assert read["mla_rope_ms"] == 12.0 and read["mtp_ms"] == 115.0
    assert read["mla_route_ms"] == 7.0 and read["mla_experts_ms"] == 20.0
    # five main blocks' least time: 2.0618 TFLOP / 197 TFLOP/s = 10.466 ms
    assert read["mla_core_roofline"] == pytest.approx(
        100 * 5 * 10.466 / 200, rel=1e-3)
    # 20,480 pairs over 5 sparse layers = the uniform 4,096 a layer; the
    # four main blocks': 4 x (0.9060 GB of weights + 0.1342 GB of rows) /
    # 819 GB/s = 5.080 ms
    assert read["mla_experts_roofline"] == pytest.approx(
        100 * 5.080 / 20, rel=1e-3)
    # (560 / 512 + 592 / 512) / 2
    assert read["mla_held_max_over_mean"] == pytest.approx(1.125)
    assert read["mla_lm_step_mfu"] is None  # no step program in this trace
    assert all(v is None or 0 < v < 100 for n, v in read.items()
               if n.endswith(("_roofline", "_mfu")))
    # a phase of two scopes reads the one the capture has
    only = _reading({"mtp.block.fwd": 25.0, "mtp.block.bwd": 60.0})
    from benchmark.metrics import (
        mla_experts_roofline,
        mla_held_max_over_mean,
        mtp_ms,
    )

    assert mtp_ms.read(only) == 85.0
    assert mla_experts_roofline.read(_reading(PHASES)) is None
    assert mla_held_max_over_mean.read(_reading(PHASES)) is None
    idle = _reading(PHASES, {"moe_held_pairs": [0.0], "moe_held_max": [0.0]})
    assert mla_held_max_over_mean.read(idle) is None
    # no pair on a held expert: the weights' bytes alone, 4 x 1.1062 ms
    assert mla_experts_roofline.read(idle) == pytest.approx(
        100 * 4 * 1.1062 / 20, rel=1e-3)


def test_step_mfu_reads_the_step_programs_time():
    """29.70 TFLOP / 197 TFLOP/s = 150.8 ms at peak: a 600 ms step is
    25.13%."""
    from benchmark import xplane
    from benchmark.metrics import mla_lm_step_mfu

    r = _reading(PHASES, COUNTERS)
    r["step_pattern"] = "jit_train_step"
    r["window_ns"] = (0, 3 * 10**9)
    r["trace"] = {"devices": {0: {xplane.MODULE_LINE: [
        ("jit_train_step(1)", i * 7 * 10**8, 6 * 10**8) for i in range(3)]}},
        "host": []}
    assert xplane.step_device_ms(r["trace"], "jit_train_step",
                                 r["window_ns"]) == pytest.approx(600.0)
    assert mla_lm_step_mfu.read(r) == pytest.approx(25.13, rel=1e-3)
    r["config"] = load("configs", "laguna_xs2_ep8")
    assert mla_lm_step_mfu.read(r) is None


def test_every_metric_of_the_cell_is_an_entry_of_its_own():
    """No accepted entry's ``workloads`` list was touched: each reading of
    the new cell is a new entry with a new reader, at the end of the
    list."""
    from benchmark import run

    for name in MLA_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
        assert m["source"] == ("program_counter" if "held_max" in name
                               else "device_trace")
        assert (m["unit"] == "%") == name.endswith(("_roofline", "_mfu"))
        reader = importlib.import_module("benchmark.metrics." + name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == set(MLA_METRICS) | {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert not any(CELL in m.get("workloads", ())
                   for m in BENCH["per_layer"]
                   if m["name"] not in MLA_METRICS)


@pytest.fixture(scope="module")
def tiny_mla_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, the dense block, a
    sparse block and the module, 8 experts of which 4 held, vocabulary 256,
    rows of 64, one row a step."""
    root = tmp_path_factory.mktemp("tiny_mla_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", CONFIG)
    config["name"] = "tiny_mla"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 64, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_mla.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_lm_stream_ref", "rows": 8, "shards": 2,
           "batch": 1, "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", TRAFFIC + "_bf16")
    for name, traffic in (("tiny_mla_mix", mix),
                          ("tiny_mla_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_mla", "source": "tests",
                         "why": "tiny", "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_mla.json"}]
    bench["workloads"] = [{"name": "tiny_mla_cell", "config": "tiny_mla",
                           "traffic": "tiny_mla_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_mla_bf16_cell",
                           "config": "tiny_mla",
                           "traffic": "tiny_mla_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_mla_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_mla_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3600000019", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _note(lines):
    return next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_mla_root):
    proc, lines = _run(tiny_mla_root, "--rehearse")
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
    for leaf in ("q_a_norm/scale", "kv_b_proj/kernel", "mtp/merge/proj/kernel",
                 "mtp/attn/mixer/kv_a_proj/kernel", "mtp/ffn/mixer/experts/up",
                 "mtp/final_norm/scale", "layers_1/mixer/gate/kernel",
                 "e_score_correction_bias"):
        assert any(n.endswith(leaf) for n in check["leaf_update_rel_err"])
    assert note["info"]["settings"]["stream_feature_dtype"] == "float32"
    counters = note["info"]["counters_last_epoch"]
    assert counters["moe_held_pairs"] and counters["mtp_loss"]
    # the step's loss is the two the counters carry, weighed
    assert counters["main_loss"][0] > 0 < counters["mtp_loss"][0]
    assert note["info"]["batch"] == 1


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_mla_root):
    """The control traffic is the cell's with the step taken at ``--dtype
    bfloat16``, the nearest precision below the stated one; the comparison
    has to refuse it (on the chip under the shipped limits, PERF.md
    section 2; here under the tiny copy's)."""
    assert not any(w["traffic"] == TRAFFIC + "_bf16"
                   for w in BENCH["workloads"])
    proc, lines = _run(tiny_mla_root, "--rehearse",
                       cell="tiny_mla_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = _note(lines)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    assert not note["info"]["check"]["ok"]


def test_a_traced_rehearsal_reads_spans_counters_and_no_device_phase(
        tiny_mla_root):
    proc, lines = _run(tiny_mla_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the shares read nothing and the line leaves them out; the spans and
    # the step's counters are there
    assert {"dispatch_ms", "infeed_put_ms",
            "mla_held_max_over_mean"} <= set(last["metrics"])
    assert 1.0 <= last["metrics"]["mla_held_max_over_mean"]["value"] < 4
    assert set(MLA_METRICS) & set(last["metrics"]) == {
        "mla_held_max_over_mean"}


def test_the_parents_reader_ends_in_prepare(tmp_path, monkeypatch, capsys):
    """The parent of this PR on the new cell: its ``HybridLMConfig`` finds
    neither a pattern nor ``layer_types`` among the keys, so exit 3 from
    ``prepare``, before JAX is touched and before anything is written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", CONFIG)

    def parents(params):
        missing = [k for k in ("hidden_size", "hybrid_override_pattern",
                               "vocab_size") if k not in params]
        if missing and "layer_types" not in params:
            raise ValueError(
                f"ModelType=hybrid_lm needs train.params {missing}")

    monkeypatch.setattr(model_config.HybridLMConfig, "from_json",
                        staticmethod(parents))
    with pytest.raises(SystemExit) as e:
        train_lm_stream_ref.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    err = capsys.readouterr().err
    assert "no result" in err and "hybrid_override_pattern" in err
