"""The short-convolution decoder's cell (``lfm2_24b_ep8_stream_s8k``): its
entries against the contract, its file against the catalog's keys and the
cut, its arithmetic, its plane's settings against the file,
``shapes_conv_lm.py`` against hand-worked numbers and the initialised tree,
its readers on readings with and without their phases, a tiny copy of the
cell and of its control end to end on the CPU through ``run.py``; and, for
the accepted tests that stop at a line a seventh cell or a new last entry
breaks (``tests/conftest.py``), their other checks.

Where an entry stands is asked relative to its own neighbours, never to
the list's end, so that the next cell breaks none of these."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import shapes_conv_lm
from benchmark.planes import train_lm_stream, train_lm_stream_ref, train_stream

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2_24b_ep8_stream_s8k"
CONFIG = "lfm2_24b_ep8"
TRAFFIC = "lm_stream_s8192_b2"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CONV_METRICS = (
    "conv_lm_step_mfu", "short_conv_proj_ms", "short_conv_mix_ms",
    "short_conv_mix_roofline", "attn_core64_ms", "attn_core64_roofline",
    "qk_norm_rope_ms", "conv_dense_mlp_ms", "conv_route_ms",
    "conv_experts_ms", "conv_experts_roofline", "tied_head_ms",
    "conv_held_max_over_mean")
PERIOD = ["conv", "conv", "conv", "full_attention"]
#: the catalog's ``config`` for the model (model-configs guide,
#: ``architectures.jsonl``), by key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention"] + PERIOD * 9 + ["conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
       "vocab_size": 8192,
       "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
TINY_PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 1, "hidden_size": 64, "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "rope_parameters": {"rope_theta": 100, "rope_type": "default"},
    "norm_eps": 1e-5, "tie_word_embeddings": True, "initializer_range": 0.15,
    "vocab_size": 256, "num_experts": 8, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True}


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
    # the load is an eighth of the deployment's, and the why says so
    assert "1,024 pairs (deployed 8,192)" in cell["why"]
    assert "operators over share" in cell["why"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == list(CUT)
    assert entry["source"] == SOURCE == load("configs", CONFIG)["source"]
    assert len(entry["source"]) <= 200
    cells = BENCH["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert sum(w["config"] == CONFIG for w in cells) == 1  # no second cell


def test_the_sibling_cells_still_meet_what_they_met_and_stand_in_order():
    """``test_bench_mla_lm.py``'s
    ``test_the_sibling_cells_still_meet_what_they_met_but_their_place``
    also wants the Mellum, Laguna and GLM cells to be the list's last three
    and is expected to fail since this PR's seventh (``tests/conftest.py``
    ``_LAST_THREE_CELLS``): its other checks, here, and the order the cells
    came in, each after its neighbour."""
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
    _stand_together([w["name"] for w in BENCH["workloads"]], [
        "mellum2_ep4_stream_s8k", "laguna_xs2_ep8_stream_s8k",
        "glm47_flash_ep8_stream_s8k", CELL])
    _stand_together([c["name"] for c in BENCH["configs"]], [
        "mellum2_ep4", "laguna_xs2_ep8", "glm47_flash_ep8", CONFIG])


@pytest.mark.parametrize("name", ["epoch_fill_ms", "epoch_drain_ms",
                                  "epoch_turn_ms", "epoch_boundary_idle_ms"])
def test_the_boundary_entries_stand_between_their_neighbours(name):
    """``test_bench_mla_lm.py``'s
    ``test_the_boundary_entries_stand_as_they_stood`` counts back from the
    list's end and is expected to fail since this PR's thirteen were
    appended (``tests/conftest.py`` ``_TEN_AFTER_FOUR``): its other checks,
    here, and the four between the Laguna cell's last and the GLM cell's
    first."""
    names = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][names.index(name)]
    assert entry["workloads"] == ["nemotron3_nano_ep16_stream_s4k"]
    assert entry["layer"] == "trainer train/trainer.py"
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "ms", "lower", "train_rows_per_s")
    assert entry["source"] == ("device_trace" if "idle" in name
                               else "program_span")
    at = _stand_together(names, ["epoch_fill_ms", "epoch_drain_ms",
                                 "epoch_turn_ms", "epoch_boundary_idle_ms"])
    assert names[at - 1] == "route256_held_max_over_mean"
    assert names[at + 4] == "mla_lm_step_mfu"


def test_the_laguna_and_glm_cells_entries_stand_where_they_stood():
    """``test_bench_mla_lm.py``'s
    ``test_the_laguna_cells_entries_stand_where_they_stood`` counts back
    from the list's end and is expected to fail since this PR's thirteen
    (``tests/conftest.py`` ``_ELEVEN_BEFORE_FOURTEEN``): its other checks,
    here; the Laguna cell's eleven right before the boundary's four, the
    GLM cell's ten right after them and this cell's thirteen right after
    those."""
    from benchmark import run
    from test_bench_mixed_lm import CELL as laguna, MIXED_METRICS
    from test_bench_mla_lm import MLA_METRICS

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
    names = [m["name"] for m in per_layer]
    at = _stand_together(names, MIXED_METRICS)
    assert names[at + len(MIXED_METRICS)] == "epoch_fill_ms"
    at = _stand_together(names, MLA_METRICS)
    assert names[at - 1] == "epoch_boundary_idle_ms"
    assert _stand_together(names, CONV_METRICS) == at + len(MLA_METRICS)


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
    # floors: a leading dense block (the two are of one kind and count
    # once) and a whole period in the published order after it, 8 routed
    # experts, an eighth of the vocabulary
    assert CUT["layer_types"][0] == PUBLISHED["layer_types"][0] == "conv"
    assert CUT["layer_types"][1:] == PUBLISHED["layer_types"][2:6]
    assert sorted(CUT["layer_types"][1:]) == sorted(PERIOD)
    assert CUT["num_hidden_layers"] - CUT["num_dense_layers"] >= 4
    assert CUT["num_experts"] >= 8 and CUT["vocab_size"] * 8 == 65536
    p = cfg["model_config"]["train"]["params"]
    # the program's keys: the router keeps its published width and is told
    # what is held; of how many shards is the file's to state, in words
    assert (p["num_experts"], p["experts_held"], p["vocab_size"]) == (
        64, [0, 8], 8192)
    for key, value in PUBLISHED.items():
        assert p.get(key, value) == CUT.get(key, value) or key in (
            "num_experts",), key
    assert p["tie_word_embeddings"] is True
    assert "head_dim" not in p and "hybrid_override_pattern" not in p
    assert "hidden_act" not in p and "n_shared_experts" not in p
    assert p["expert_tile"] == 1536 and p["LearningRate"] == 1e-05
    assert "8 ways" in cfg["deployment"]
    assert "469,284,992 parameters x 16 B" in cfg["deployment"]
    assert "7.51 GB" in cfg["deployment"]
    assert "an eighth" in cfg["load"] or "1/8" in cfg["load"]
    assert "1,024 pairs" in cfg["load"] and "8,192" in cfg["load"]
    assert {"tied_head", "conv_operator", "qk_norm", "final_norm", "rotary",
            "router", "hidden_act", "optimizer", "dtype",
            "initializer_range", "rows", "recomputation", "expert_tile",
            "attention_tile"} <= set(cfg["assumed"])
    assert cfg["data"] == {"tokens_per_row": 8192, "id_below": 8192}
    assert cfg["check"]["reference"] == "conv_gqa_moe_lm"


def test_the_configurations_arithmetic():
    """469,284,992 weights (and 256 expert-bias elements that rest), 7.51
    GB at 16 B each, part by part as the file's ``deployment`` and ISSUE
    38 write them."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    conv = shapes_conv_lm.conv_parameters(p)
    assert conv == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    attn = shapes_conv_lm.attention_parameters(p)
    assert attn == 2 * 4_194_304 + 2 * 1_048_576 + 64 + 64 == 10_485_888
    dense = shapes_conv_lm.gated_parameters(p, "intermediate_size")
    sparse = shapes_conv_lm.sparse_parameters(p) - 64
    assert dense == 3 * 2048 * 11776 == 72_351_744
    assert sparse == 131_072 + 8 * 9_437_184
    norms = 2 * 2048
    assert conv + dense + norms == 89_139_200
    assert attn + sparse + norms == 86_118_528
    assert conv + sparse + norms == 92_416_000
    weights = shapes_conv_lm.parameter_count(p) - 4 * 64
    assert weights == 469_284_992 == (
        89_139_200 + 86_118_528 + 3 * 92_416_000 + 8192 * 2048 + 2048)
    assert round(weights * 16 / 1e9, 2) == 7.51
    assert round(weights * 12 / 1e9, 2) == 5.63
    # the uncut model by the same functions: 23.8 B against the "24B" the
    # family is described as, of which 2.3 B a token ("A2B")
    whole = dict(p, layer_types=PUBLISHED["layer_types"], num_dense_layers=2,
                 vocab_size=65536, experts_held=[0, 64])
    assert shapes_conv_lm.parameter_count(whole) == pytest.approx(
        23.84e9, rel=1e-3)
    active = (shapes_conv_lm.parameter_count(dict(whole, experts_held=[0, 4]))
              )
    assert active == pytest.approx(2.32e9, rel=5e-3)


def test_the_counts_are_the_hand_worked_numbers():
    """19.95 TFLOP a step of two rows of 8,192 at uniform routing, as ISSUE
    38 counts it: a convolution operator 0.550 TFLOP forward, the attention
    operator 0.344 in projections + 0.550 in the core at 32 x 64, the dense
    layer 2.371, a sparse one 0.155 + the router's 0.004, the head 0.550:
    6.65 forward."""
    p = load("configs", CONFIG)["model_config"]["train"]["params"]
    tokens, seq = 16384, 8192
    assert shapes_conv_lm.heads_and_dim(p) == (32, 8, 64)
    core = shapes_conv_lm.core_flops(p, tokens, seq)
    assert core == 3 * 2 * (8192 * 8193 // 2) * 32 * 2 * (64 + 64)
    assert core / 3 == pytest.approx(0.5498e12, rel=1e-3)
    assert shapes_conv_lm.held_pairs(p, tokens) == 8192  # 1,024 an expert
    experts = shapes_conv_lm.experts_flops(p, 8192)
    assert experts == 3 * 8192 * 2 * 9_437_184
    matrices = (4 * 16_777_216 + 10_485_760 + 72_351_744 + 4 * 131_072
                + 8192 * 2048)
    total = shapes_conv_lm.train_step_flops(p, tokens, seq)
    assert total == 6 * tokens * matrices + core + 4 * experts
    assert total == pytest.approx(19.946e12, rel=1e-4)
    assert 2 * tokens * 16_777_216 == pytest.approx(0.5498e12, rel=1e-3)
    assert 2 * tokens * 72_351_744 == pytest.approx(2.371e12, rel=1e-3)
    # the dense block is 36% of the products and the four convolution
    # operators 33%: one dense block in five here, two in forty published
    assert 6 * tokens * 72_351_744 / total == pytest.approx(0.357, abs=0.002)
    assert 6 * tokens * 4 * 16_777_216 / total == pytest.approx(0.331,
                                                                abs=0.002)
    # the core: 8.37 ms for the products at 32 x 64 against 1.23 ms for
    # 1.007 GB: the products bind.  conv.mix: 11 tensors of 16,384 x 2048
    # float32 = 1.476 GB a layer = 1.80 ms at 819 GB/s.  A layer's held
    # experts at 8,192 pairs: 2.35 ms for the products against 1.43 ms for
    # 0.906 GB of weights + 0.268 GB of rows: the products bind
    from benchmark import peaks, shapes_lm

    v5e = peaks.lookup("TPU v5 lite")
    assert shapes_conv_lm.core_bytes(p, tokens) == 4 * 16384 * 64 * 6 * 40
    assert shapes_lm.least_seconds(
        core, shapes_conv_lm.core_bytes(p, tokens), v5e) == pytest.approx(
            8.373e-3, rel=1e-3)
    assert shapes_conv_lm.mix_bytes(p, tokens) == 11 * 4 * 16384 * 2048
    assert shapes_conv_lm.mix_bytes(p, tokens) / v5e["bytes_per_s"] == (
        pytest.approx(1.8027e-3, rel=1e-3))
    assert shapes_conv_lm.experts_bytes(p, 0) == 3 * 4 * 8 * 9_437_184
    assert shapes_lm.least_seconds(
        experts, shapes_conv_lm.experts_bytes(p, 8192), v5e
    ) == pytest.approx(experts / v5e["flops_per_s"]) == pytest.approx(
        2.3546e-3, rel=1e-3)
    assert (shapes_conv_lm.conv_layers(p), shapes_conv_lm.attention_layers(p),
            shapes_conv_lm.sparse_layers(p),
            shapes_conv_lm.dense_blocks(p)) == (4, 1, 4, 1)


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
    assert model_config.batch_size == tr["batch"] == 2
    c = model_config.params.hybrid_lm
    assert c.hybrid_override_pattern == "CD*ECECECE"
    assert (c.head_dim, c.qk_norm, c.tie_word_embeddings, c.hidden_act,
            c.n_shared_experts, c.scoring_func) == (
        64, True, True, "silu", 0, "sigmoid")
    assert train_lm_stream_ref.reference_of(cfg).__name__ == (
        "benchmark.reference.conv_gqa_moe_lm")


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "conv_gqa_moe_lm.py")) as f:
        source = f.read()
    assert "shifu_tensorflow_tpu" not in source.split('"""', 2)[2]
    for line in source.splitlines():
        if line.lstrip().startswith(("import ", "from ")):
            assert line.split()[1].split(".")[0] in (
                "__future__", "math", "jax"), line


@pytest.mark.parametrize("kinds,dense", [
    (["conv", "full_attention", "conv"], 1),
    (["conv", "full_attention", "conv", "conv", "conv"], 1),
    (["conv", "conv", "full_attention"], 2), (["full_attention"], 0)],
    ids=["tiny", "the-cut", "two-dense", "attention-alone"])
def test_parameter_count_equals_the_initialised_tree(kinds, dense):
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    p = dict(TINY_PARAMS, num_hidden_layers=len(kinds), layer_types=kinds,
             num_dense_layers=dense)
    if "conv" not in kinds:  # what the family's reading would have said
        p.update(hidden_act="silu", n_shared_experts=0, qk_norm=True,
                 head_dim=16)
    model = build_model(ModelConfig.from_json({"train": {"params": p}}))
    tree = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 32)))["params"]
    assert "lm_head" not in tree
    assert shapes_conv_lm.parameter_count(p) == sum(
        x.size for x in jax.tree.leaves(tree))


def _reading(phases=None, counters=None, config=CONFIG):
    from benchmark import peaks

    cfg = load("configs", config)
    tr = load("workloads", {CONFIG: TRAFFIC,
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


PHASES = {"conv.proj.fwd": 20.0, "conv.proj.bwd": 40.0,
          "conv.mix.fwd": 8.0, "conv.mix.bwd": 20.0,
          "attn.core.fwd": 20.0, "attn.core.bwd": 50.0,
          "attn.qknorm.fwd": 1.0, "attn.qknorm.bwd": 3.0,
          "attn.rope.fwd": 2.0, "attn.rope.bwd": 6.0,
          "mlp.dense.fwd": 25.0, "mlp.dense.bwd": 50.0,
          "moe.experts.fwd": 16.0, "moe.experts.bwd": 24.0,
          "moe.route.fwd": 20.0, "moe.route.bwd": 5.0,
          "lm.head.fwd": 6.0, "lm.head.bwd": 14.0}
COUNTERS = {"moe_held_pairs": [32768.0, 32768.0],
            "moe_held_max": [1100.0, 1204.0]}


@pytest.mark.parametrize("name", CONV_METRICS)
def test_reader_reads_nothing_without_its_phase(name):
    """The parent of the PR that added the scope, a capture off the TPU,
    another family's cell, and the other decoders' cells, which have an
    ``attn.core``, a ``moe.route``, an ``mlp.dense``, an ``lm.head`` and
    counters of their own: nothing, and no raise."""
    reader = importlib.import_module("benchmark.metrics." + name)
    assert reader.read(_reading()) is None
    assert reader.read(_reading(phases={"embed.gather.fwd": 1.0})) is None
    for other in ("wdl_criteo", "nemotron3_nano_ep16", "mellum2_ep4",
                  "laguna_xs2_ep8", "glm47_flash_ep8"):
        assert reader.read(_reading(PHASES, COUNTERS, config=other)) is None


def test_phase_readers_sum_both_directions_and_shares_stay_under_100():
    r = _reading(PHASES, COUNTERS)
    read = {n: importlib.import_module("benchmark.metrics." + n).read(r)
            for n in CONV_METRICS}
    assert read["short_conv_proj_ms"] == 60.0
    assert read["short_conv_mix_ms"] == 28.0
    assert read["attn_core64_ms"] == 70.0 and read["qk_norm_rope_ms"] == 12.0
    assert read["conv_dense_mlp_ms"] == 75.0 and read["conv_route_ms"] == 25.0
    assert read["conv_experts_ms"] == 40.0 and read["tied_head_ms"] == 20.0
    # four layers' least time: 1.476 GB / 819 GB/s = 1.8027 ms each
    assert read["short_conv_mix_roofline"] == pytest.approx(
        100 * 4 * 1.8027 / 28, rel=1e-3)
    # the one core: 1.6495 TFLOP / 197 TFLOP/s = 8.373 ms
    assert read["attn_core64_roofline"] == pytest.approx(
        100 * 8.373 / 70, rel=1e-3)
    # 32,768 pairs over 4 sparse layers = the uniform 8,192 a layer, where
    # the products bind: 4 x 2.3546 ms
    assert read["conv_experts_roofline"] == pytest.approx(
        100 * 4 * 2.3546 / 40, rel=1e-3)
    # (1100 / 1024 + 1204 / 1024) / 2
    assert read["conv_held_max_over_mean"] == pytest.approx(1.125)
    assert read["conv_lm_step_mfu"] is None  # no step program in this trace
    assert all(v is None or 0 < v < 100 for n, v in read.items()
               if n.endswith(("_roofline", "_mfu")))
    # a phase of two scopes reads the one the capture has
    only = _reading({"attn.rope.fwd": 2.0, "attn.rope.bwd": 6.0})
    from benchmark.metrics import (
        conv_experts_roofline,
        conv_held_max_over_mean,
        qk_norm_rope_ms,
    )

    assert qk_norm_rope_ms.read(only) == 8.0
    assert conv_experts_roofline.read(_reading(PHASES)) is None
    assert conv_held_max_over_mean.read(_reading(PHASES)) is None
    idle = _reading(PHASES, {"moe_held_pairs": [0.0], "moe_held_max": [0.0]})
    assert conv_held_max_over_mean.read(idle) is None
    # no pair on a held expert: the weights' bytes alone, 4 x 1.1062 ms
    assert conv_experts_roofline.read(idle) == pytest.approx(
        100 * 4 * 1.1062 / 40, rel=1e-3)


def test_step_mfu_reads_the_step_programs_time():
    """19.946 TFLOP / 197 TFLOP/s = 101.25 ms at peak: a 400 ms step is
    25.31%."""
    from benchmark import xplane
    from benchmark.metrics import conv_lm_step_mfu

    r = _reading(PHASES, COUNTERS)
    r["step_pattern"] = "jit_train_step"
    r["window_ns"] = (0, 3 * 10**9)
    r["trace"] = {"devices": {0: {xplane.MODULE_LINE: [
        ("jit_train_step(1)", i * 5 * 10**8, 4 * 10**8) for i in range(3)]}},
        "host": []}
    assert xplane.step_device_ms(r["trace"], "jit_train_step",
                                 r["window_ns"]) == pytest.approx(400.0)
    assert conv_lm_step_mfu.read(r) == pytest.approx(25.31, rel=1e-3)
    r["config"] = load("configs", "mellum2_ep4")
    assert conv_lm_step_mfu.read(r) is None


def test_every_metric_of_the_cell_is_an_entry_of_its_own():
    """No accepted entry's ``workloads`` list was touched: each reading of
    the new cell is a new entry with a new reader, and the thirteen stand
    together."""
    from benchmark import run

    for name in CONV_METRICS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_rows_per_s"
        assert m["source"] == ("program_counter" if "held_max" in name
                               else "device_trace")
        assert (m["unit"] == "%") == name.endswith(("_roofline", "_mfu"))
        reader = importlib.import_module("benchmark.metrics." + name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer", CELL)}
    assert listed == set(CONV_METRICS) | {
        m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert not any(CELL in m.get("workloads", ())
                   for m in BENCH["per_layer"]
                   if m["name"] not in CONV_METRICS)
    _stand_together([m["name"] for m in BENCH["per_layer"]], CONV_METRICS)


@pytest.fixture(scope="module")
def tiny_conv_root(tmp_path_factory):
    """The cell in small, in new files only: hidden 64, a convolution over
    the dense block, attention and a convolution over sparse ones, 8
    experts of which 4 held, vocabulary 256, rows of 64, one row a step."""
    root = tmp_path_factory.mktemp("tiny_conv_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    config = load("configs", CONFIG)
    config["name"] = "tiny_conv"
    config["model_config"]["train"]["params"] = dict(TINY_PARAMS)
    config["data"] = {"tokens_per_row": 64, "id_below": 256}
    config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                           update_rtol=0.05, small_leaf_update_rtol=0.05,
                           pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                           pooled_grad_rtol=0.01)
    (root / "benchmark" / "configs" / "tiny_conv.json").write_text(
        json.dumps(config))
    mix = {"plane": "train_lm_stream_ref", "rows": 8, "shards": 2,
           "batch": 1, "warm_epochs": 1, "check_steps": 2}
    control = load("workloads", TRAFFIC + "_bf16")
    for name, traffic in (("tiny_conv_mix", mix),
                          ("tiny_conv_mix_bf16", {**control, **mix})):
        (root / "benchmark" / "workloads" / f"{name}.json").write_text(
            json.dumps(traffic))
    bench = dict(BENCH)
    bench["configs"] = [{"name": "tiny_conv", "source": "tests",
                         "why": "tiny", "reduced": config["reduced"],
                         "file": "benchmark/configs/tiny_conv.json"}]
    bench["workloads"] = [{"name": "tiny_conv_cell", "config": "tiny_conv",
                           "traffic": "tiny_conv_mix", "chips": 1,
                           "why": "tiny"},
                          {"name": "tiny_conv_bf16_cell",
                           "config": "tiny_conv",
                           "traffic": "tiny_conv_mix_bf16", "chips": 1,
                           "why": "the control"}]
    bench["per_layer"] = [dict(m, workloads=["tiny_conv_cell"])
                          if m.get("workloads") == [CELL] else m
                          for m in BENCH["per_layer"]
                          if m.get("workloads", [CELL]) == [CELL]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, *extra, trace=0, cell="tiny_conv_cell"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3800000019", "--seconds",
         "1", "--trace", str(trace), "--benchmark-json",
         str(root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _note(lines):
    return next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)


def test_a_tiny_copy_of_the_cell_rehearses_on_the_cpu(tiny_conv_root):
    proc, lines = _run(tiny_conv_root, "--rehearse")
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
    assert check["small_leaf_update_rel_err"] < 0.05
    assert check["pooled_update_rel_err"] < 0.02
    assert check["grad_norm_rel_err"] < 1e-3
    assert check["pooled_grad_rel_err"] < 1e-3
    assert len(check["sys_losses"]) == 2
    for leaf in ("layers_0/mixer/in_proj/kernel", "layers_0/mixer/conv/kernel",
                 "layers_4/mixer/out_proj/kernel", "q_norm/scale",
                 "k_norm/scale", "layers_1/mixer/gate/kernel",
                 "layers_3/mixer/experts/up", "embed/embedding",
                 "final_norm/scale", "e_score_correction_bias"):
        assert any(n.endswith(leaf) for n in check["leaf_update_rel_err"])
    # the head is the embedding's table: no leaf of its own
    assert not any("lm_head" in n for n in check["leaf_update_rel_err"])
    assert note["info"]["settings"]["stream_feature_dtype"] == "float32"
    counters = note["info"]["counters_last_epoch"]
    assert set(counters) == {"moe_held_pairs", "moe_held_max"}
    assert counters["moe_held_pairs"][0] > 0
    assert note["info"]["batch"] == 1


def test_the_control_is_the_cells_traffic_at_bf16_and_is_not_correct(
        tiny_conv_root):
    """The control traffic is the cell's with the step taken at ``--dtype
    bfloat16``, the nearest precision below the stated one; the comparison
    has to refuse it (on the chip under the shipped limits, PERF.md
    section 2; here under the tiny copy's)."""
    assert not any(w["traffic"] == TRAFFIC + "_bf16"
                   for w in BENCH["workloads"])
    proc, lines = _run(tiny_conv_root, "--rehearse",
                       cell="tiny_conv_bf16_cell")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is False and last["failed"] == 0
    note = _note(lines)
    assert note["info"]["settings"]["dtype"] == "bfloat16"
    assert not note["info"]["check"]["ok"]


def test_a_traced_rehearsal_reads_spans_counters_and_no_device_phase(
        tiny_conv_root):
    proc, lines = _run(tiny_conv_root, "--rehearse", trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    # off the TPU the capture has no device plane: the phase readers and
    # the shares read nothing and the line leaves them out; the spans and
    # the step's counters are there
    assert {"dispatch_ms", "infeed_put_ms",
            "conv_held_max_over_mean"} <= set(last["metrics"])
    assert 1.0 <= last["metrics"]["conv_held_max_over_mean"]["value"] < 4
    assert set(CONV_METRICS) & set(last["metrics"]) == {
        "conv_held_max_over_mean"}


def test_the_parents_reader_ends_in_prepare(tmp_path, monkeypatch, capsys):
    """The parent of this PR on the new cell: its ``HybridLMConfig`` knows
    no ``conv`` among ``layer_types``, so exit 3 from ``prepare``, before
    JAX is touched and before anything is written."""
    from shifu_tensorflow_tpu.config import model_config

    cfg = load("configs", CONFIG)

    def parents(params):
        bad = sorted(set(params["layer_types"])
                     - {"full_attention", "sliding_attention"})
        if bad:
            raise ValueError(
                f"layer_types {bad} are not implemented (full_attention | "
                "sliding_attention)")

    monkeypatch.setattr(model_config.HybridLMConfig, "from_json",
                        staticmethod(parents))
    with pytest.raises(SystemExit) as e:
        train_lm_stream_ref.prepare(FakeCtx(cfg, {}, str(tmp_path)))
    assert e.value.code == 3 and not os.listdir(tmp_path)
    err = capsys.readouterr().err
    assert "no result" in err and "conv" in err
