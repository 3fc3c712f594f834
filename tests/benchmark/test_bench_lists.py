"""The tests of ``conftest.py``'s ``tiny_root``, on a root whose per-layer
``workloads`` lists follow the cells the tiny ones stand for.

``conftest.py`` asserts that every such list names four-chip cells only,
which held while the collectives alone had one.  PR 26 gave lists to
metrics of one-chip cells (the language-model cell's own, and
``train_step_roofline``, whose reader computes a tabular step), so that
fixture stops at its assertion, a PR of another kind may not edit it, and
the nine tests that ask for it cannot start.  Their bodies run here
unchanged, on the fixture below, which overrides ``tiny_root`` for this
module: each tiny cell stands for the shipped ``wdl`` cell of its chips
and is listed wherever that cell is; a metric that lists neither is
another family's and is left out.

For the ``benchmark`` PR that takes the assertion out of ``conftest.py``:
move this fixture there and delete the re-bound tests below (two modules
running the same tiny cells at once would share ``.bench_work/<cell>``).
"""

import json
import os

import pytest
import test_bench_contract
import test_bench_run

ROOT = test_bench_run.ROOT
#: name, mesh, chips, global batch, the shipped cell it stands for
TINY = (("tiny_wdl", None, 1, 256, "wdl_criteo_stream"),
        ("tiny_wdl_x4", "data:2,model:2", 4, 512, "wdl_criteo_x4_stream"),
        ("tiny_wdl_m4", "data:1,model:4", 4, 256, "wdl_criteo_x4_stream"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_bench_lists")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"], bench["workloads"], per_layer = [], [], []
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"{name}_cell" for name, *_, stands_for in TINY
                              if stands_for in m["workloads"]]
            if not m["workloads"]:
                continue
        per_layer.append(m)
    bench["per_layer"] = per_layer
    for name, mesh, chips, batch, _ in TINY:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "wdl_criteo.json")) as f:
            config = json.load(f)
        config["name"], config["mesh"] = name, mesh
        config["reduced"] = ["EmbeddingHashSize", "CrossHashSize"]
        config["model_config"]["train"]["params"].update(
            NumHiddenNodes=[32, 16, 8], EmbeddingHashSize=4096,
            CrossHashSize=1024, EmbeddingDim=4)
        config["data"]["cardinality_max"] = 100_000
        config["check"].update(loss_rtol=1e-4, stated_loss_rtol=1e-4,
                               update_rtol=1e-2)
        (root / "benchmark" / "configs" / f"{name}.json").write_text(
            json.dumps(config))
        (root / "benchmark" / "workloads" / f"{name}_mix.json").write_text(
            json.dumps({"plane": "train_stream", "rows": 4096, "shards": 2,
                        "batch": batch, "warm_epochs": 1, "check_steps": 4}))
        bench["configs"].append({
            "name": name, "source": "tests", "why": "tiny",
            "reduced": config["reduced"],
            "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({
            "name": f"{name}_cell", "config": name, "traffic": f"{name}_mix",
            "chips": chips, "why": "tiny"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_tiny_cell_is_listed_wherever_the_cell_it_stands_for_is(tiny_root):
    from benchmark import run

    with open(tiny_root / "BENCHMARK.json") as f:
        tiny = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        shipped = json.load(f)
    for name, *_, stands_for in TINY:
        listed = {m["name"] for m in run.metrics_for(
            tiny, "per_layer", f"{name}_cell")}
        assert listed == {m["name"] for m in run.metrics_for(
            shipped, "per_layer", stands_for)}
    assert "train_step_roofline" in listed and "collective_ms" in listed
    assert not {m["name"] for m in tiny["per_layer"]} & {
        "lm_step_mfu", "ssm_scan_ms", "moe_held_max_over_mean"}


test_an_added_cell_meets_the_same_checks = (
    test_bench_contract.test_an_added_cell_meets_the_same_checks)
test_one_chip_cell_untraced = test_bench_run.test_one_chip_cell_untraced
test_four_chip_cell_traced_on_a_virtual_mesh = (
    test_bench_run.test_four_chip_cell_traced_on_a_virtual_mesh)
test_another_mesh_on_four_chips_needs_no_edit = (
    test_bench_run.test_another_mesh_on_four_chips_needs_no_edit)
test_too_few_chips_gives_no_result = (
    test_bench_run.test_too_few_chips_gives_no_result)
test_without_a_tpu_no_result = test_bench_run.test_without_a_tpu_no_result
test_unknown_cell_gives_no_result = (
    test_bench_run.test_unknown_cell_gives_no_result)
