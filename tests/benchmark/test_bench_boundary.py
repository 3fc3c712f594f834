"""The four readers of the epoch's boundary (PR 34): ``epoch_fill_ms``,
``epoch_drain_ms`` and ``epoch_turn_ms`` from the program's spans,
``epoch_boundary_idle_ms`` from the trace through the program's own
reduction (``obs.profile.boundaries``).  Each lists the one cell whose
tests let a PR of another kind add an entry, and stands at the end of the
list, where the driver takes a new entry (PERF.md section 7)."""

import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = "nemotron3_nano_ep16_stream_s4k"
BOUNDARY_METRICS = ("epoch_fill_ms", "epoch_drain_ms", "epoch_turn_ms",
                    "epoch_boundary_idle_ms")


def reader(name):
    return importlib.import_module("benchmark.metrics." + name)


def span(count, total_s):
    return {"count": count, "total_s": total_s, "mean_s": total_s / count}


def reading(spans=None, trace=None, window=None):
    return {"spans": spans or {}, "trace": trace or {"devices": {},
                                                     "host": []},
            "window_ns": window, "step_pattern": "jit_train_step"}


@pytest.mark.parametrize("name", BOUNDARY_METRICS)
def test_the_entry_lists_the_one_cell_and_stands_at_the_end_of_the_list(
        name):
    names = [m["name"] for m in BENCH["per_layer"]]
    entry = BENCH["per_layer"][names.index(name)]
    assert entry["workloads"] == [CELL]
    assert entry["layer"] == "trainer train/trainer.py"
    assert (entry["unit"], entry["better"], entry["moves"]) == (
        "ms", "lower", "train_rows_per_s")
    assert entry["source"] == ("device_trace" if "idle" in name
                               else "program_span")
    assert names[-len(BOUNDARY_METRICS):] == list(BOUNDARY_METRICS)


def test_the_laguna_cells_entries_stand_as_they_stood():
    """What ``test_bench_mixed_lm.py``'s
    ``test_every_metric_of_the_cell_is_an_entry_of_its_own`` checks before
    the line it stops at since the four were appended (tests/conftest.py
    ``_LAST_ELEVEN``): the cell's eleven are its own, nobody else names the
    cell, and they are the last the list had before this PR's four."""
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
    before = [m["name"] for m in per_layer][:-len(BOUNDARY_METRICS)]
    assert before[-len(MIXED_METRICS):] == list(MIXED_METRICS)


def test_the_span_readers_give_ms_an_epoch():
    r = reading({"epoch.fill": span(2, 0.030), "epoch.drain": span(2, 0.9),
                 # three turns an epoch of fit_stream, six in the window
                 "epoch.turn": span(6, 0.012)})
    assert reader("epoch_fill_ms").read(r) == pytest.approx(15.0)
    assert reader("epoch_drain_ms").read(r) == pytest.approx(450.0)
    assert reader("epoch_turn_ms").read(r) == pytest.approx(6.0)


@pytest.mark.parametrize("name", BOUNDARY_METRICS)
def test_a_program_without_the_spans_reads_nothing(name):
    """The parent of PR 34 under this PR's benchmark files: `epoch.turn`
    alone, no boundary in the trace."""
    r = reading({"epoch.turn": span(6, 0.012),
                 "step.dispatch": span(32, 0.3)})
    assert reader(name).read(r) is None
    assert reader(name).read(reading()) is None


def trace_of_three_epochs():
    """``xplane.load``'s shape: two steps an epoch, boundaries of 100 and
    160 ns with a 10 ns program of another kind in the first."""
    steps = [100, 200, 400, 500, 760, 860]
    ops = [["fusion.1 f32[8]", s, 100] for s in steps]
    ops.append(["convert.3 f32[8]", 330, 10])
    modules = [["jit_train_step(1)", s, 100] for s in steps]
    modules.append(["jit_convert(2)", 330, 10])
    host = [["bench.window", 40, 960],
            ["step.block", 295, 35], ["step.infeed.put", 371, 21],
            ["step.block", 601, 29], ["step.infeed.put", 681, 64],
            ["step.block", 955, 35]]
    return {"devices": {0: {"XLA Ops": sorted(ops, key=lambda e: e[1]),
                            "XLA Modules": sorted(modules,
                                                  key=lambda e: e[1])}},
            "host": host}


def test_the_boundary_reader_is_the_programs_reduction_on_the_trace():
    from shifu_tensorflow_tpu.obs import profile

    mod = reader("epoch_boundary_idle_ms")
    r = reading(trace=trace_of_three_epochs(), window=(40, 1000))
    capture = mod.capture_of(r)
    assert capture["devices"][0]["steps"] == [
        [s, 100] for s in (100, 200, 400, 500, 760, 860)]
    found = profile.boundaries(capture)
    assert found["boundaries"] == 2
    # idle 90 and 160 ns: the median, in ms
    assert mod.read(r) == found["idle_ms"]["median"] == pytest.approx(
        125e-6)
    assert found["each"][1]["split_ms"]["step.infeed.put"] == pytest.approx(
        64e-6)


def test_the_boundary_reader_reads_nothing_without_a_boundary(monkeypatch):
    mod = reader("epoch_boundary_idle_ms")
    one = trace_of_three_epochs()
    one["host"] = [h for h in one["host"] if h[0] != "step.block"]
    assert mod.read(reading(trace=one, window=(40, 1000))) is None
    # one epoch inside the window: no second step program after a fetch
    assert mod.read(reading(trace=trace_of_three_epochs(),
                            window=(40, 310))) is None
    # a program that has no such reduction (the parent)
    from shifu_tensorflow_tpu.obs import profile

    monkeypatch.delattr(profile, "boundaries")
    assert mod.read(reading(trace=trace_of_three_epochs(),
                            window=(40, 1000))) is None
