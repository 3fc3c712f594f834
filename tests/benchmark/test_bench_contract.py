"""BENCHMARK.json against the contract's limits, and the files it names."""

import importlib
import json
import math
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)


def check_cell(bench, root, cell):
    """What every cell has to meet, the shipped ones and any a later PR
    adds: nothing here names a cell, a configuration or a mesh."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["reduced"]) <= 16 and all(map(NAME.match,
                                                   entry["reduced"]))
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["reduced"] == entry["reduced"]
    bench_dir = os.path.dirname(os.path.dirname(entry["file"]))
    with open(os.path.join(root, bench_dir, "workloads",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "planes", traffic["plane"] + ".py"))
    if traffic["plane"] == "train_stream":  # no remainder batch to compile
        assert traffic["rows"] % traffic["batch"] == 0
    # the chips the cell asks for are the chips the configuration's mesh
    # spans (no mesh: one chip)
    sizes = [int(a.split(":")[1])
             for a in (config.get("mesh") or "").split(",") if a]
    assert all(n > 0 for n in sizes)
    assert math.prod(sizes) == cell["chips"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_files(cell):
    check_cell(BENCH, ROOT, cell)


@pytest.mark.parametrize("name", ["tiny_wdl_cell", "tiny_wdl_x4_cell",
                                  "tiny_wdl_m4_cell"])
def test_an_added_cell_meets_the_same_checks(tiny_root, name):
    """A cell added by new files only — a non-empty ``reduced``, another
    mesh on four chips — passes what the shipped cells pass."""
    with open(tiny_root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    check_cell(bench, str(tiny_root), cell)


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


def test_setup_s_is_there():
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] == 0.1 and setup["better"] == "lower"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry_has_a_reader_that_agrees(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in SOURCES
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    reader = importlib.import_module("benchmark.metrics." + metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
        metric["layer"], metric["unit"], metric["source"], metric["moves"])
    assert callable(reader.read)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def recorded_cells():
    """(cell, fixture path) for every recorded TPU trace under
    ``benchmark/fixtures`` that is named for a cell:
    ``<cell name>_<n>steps.json.gz``."""
    fixtures = os.path.join(ROOT, "benchmark", "fixtures")
    for f in sorted(os.listdir(fixtures)):
        for cell in BENCH["workloads"]:
            if re.fullmatch(re.escape(cell["name"]) + r"_\d+steps\.json\.gz",
                            f):
                yield pytest.param(cell, os.path.join(fixtures, f),
                                   id=cell["name"])


@pytest.mark.parametrize("cell,fixture", recorded_cells())
def test_a_recorded_trace_gives_the_cell_exactly_its_listed_metrics(
        cell, fixture):
    """What the driver holds a traced line to: every per-layer metric
    ``BENCHMARK.json`` lists for the cell, and no other.  The readers run
    on the trace recorded on the chip in that cell, so a metric that
    exists only across chips (the collectives) has to name its cells
    under ``workloads``: PR 22's first edition listed none and was refused
    on the one-chip cell's traced run."""
    from benchmark import peaks, run, xplane

    _, config, traffic = run.find_cell(BENCH, ROOT, cell["name"])
    trace = xplane.load_json(fixture)
    window = xplane.window_of(trace)
    span = {"count": 4, "total_s": 0.004, "mean_s": 0.001}
    reading = {
        "trace": trace, "window_ns": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "spans": {n: span for n in ("step.infeed.wait", "step.infeed.put",
                                    "step.dispatch")},
        "cell": cell, "config": config, "traffic": traffic,
        "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 1 << 33},
        "peaks": peaks.lookup("TPU v5 lite"),
        "step_pattern": "jit_train_step",
    }
    assert len(trace["devices"]) > 1 or cell["chips"] == 1
    read = {m["name"] for m in BENCH["per_layer"]
            if importlib.import_module("benchmark.metrics." + m["name"])
            .read(reading) is not None}
    listed = {m["name"] for m in run.metrics_for(BENCH, "per_layer",
                                                 cell["name"])}
    assert read == listed


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_a_metric_lists_only_cells_that_exist_and_every_cell_has_some(group):
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH[group]:
        assert set(m.get("workloads", cells)) <= cells and m.get(
            "workloads", cells)
    from benchmark import run

    for name in cells:
        listed = {m["name"] for m in run.metrics_for(BENCH, group, name)}
        assert listed - {"setup_s"}
        assert group == "per_layer" or "setup_s" in listed


def test_every_reader_file_is_listed():
    listed = {m["name"] for m in BENCH["per_layer"]}
    here = {f[:-3] for f in os.listdir(os.path.join(ROOT, "benchmark",
                                                    "metrics"))
            if f.endswith(".py") and f != "__init__.py"}
    assert here == listed


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), ROOT))
