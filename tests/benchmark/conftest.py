"""A benchmark root of the tests' own, made of new files only: what a
later PR that adds cells does.  ``test_bench_run.py`` runs two of its
cells through ``run.py``'s discovery; ``test_bench_contract.py`` holds all
three to the checks the shipped cells pass."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: name, mesh, chips, global batch.  The third is PERF.md's open cell
#: ``wdl_criteo_x4_m4`` in small: another mesh on four chips.
TINY = (("tiny_wdl", None, 1, 256),
        ("tiny_wdl_x4", "data:2,model:2", 4, 512),
        ("tiny_wdl_m4", "data:1,model:4", 4, 256))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_bench")
    (root / "benchmark" / "configs").mkdir(parents=True)
    (root / "benchmark" / "workloads").mkdir()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    four = {w["name"] for w in bench["workloads"] if w["chips"] == 4}
    bench["configs"], bench["workloads"] = [], []
    # a metric that exists only across chips names its cells: the PR that
    # adds a four-chip cell adds the cell's name to those lists
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert set(m["workloads"]) <= four
            m["workloads"] = [f"{name}_cell" for name, _, chips, _ in TINY
                              if chips == 4]
    for name, mesh, chips, batch in TINY:
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
