"""The reduction from a trace to numbers: arithmetic on hand-made
intervals, then the recorded TPU trace kept under ``benchmark/fixtures``."""

import os

import pytest

from benchmark import xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
STEP = r"jit_train_step"


def made():
    """Two devices, two steps of 100 ns each starting at 0 and 150.  Per
    step on device 0: op a [0,40), all-reduce-start [40,45), op b [45,70)
    overlapping the collective, all-reduce-done [70,90) of which the wait
    is exposed, op c [90,100).  Device 1 runs one op per step."""
    ops0, mods0 = [], []
    for t in (0, 150):
        mods0.append(["jit_train_step(1)", t, 100])
        ops0 += [["fusion.a", t, 40], ["all-reduce-start.1", t + 40, 5],
                 ["fusion.b", t + 45, 25], ["all-reduce-done.1", t + 70, 20],
                 ["copy.c", t + 90, 10]]
    ops1 = [["fusion.a", 0, 50], ["fusion.a", 150, 50]]
    mods1 = [["jit_train_step(1)", 0, 50], ["jit_train_step(1)", 150, 50]]
    host = [["bench.window", 0, 250], ["step.dispatch", 95, 30],
            ["step.infeed.wait", 125, 20], ["epoch", 90, 70]]
    return {"devices": {0: {xplane.OP_LINE: ops0, xplane.MODULE_LINE: mods0},
                        1: {xplane.OP_LINE: ops1, xplane.MODULE_LINE: mods1}},
            "host": host}


def test_interval_arithmetic():
    assert xplane.merge([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert xplane.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert xplane.subtract_ns([(0, 10), (20, 30)], [(5, 22), (28, 40)]) == 11
    assert xplane.subtract_ns([(0, 10)], []) == 10
    assert xplane.median([3, 1, 2]) == 2 and xplane.median([4, 1]) == 2.5
    assert xplane.median([]) is None


def test_window_and_clip():
    t = made()
    assert xplane.window_of(t) == (0, 250)
    assert xplane.clip([["x", 0, 10], ["y", 8, 10], ["z", 30, 5]],
                       (5, 12)) == [["x", 5, 5], ["y", 8, 4]]


def test_busy_and_idle_share_use_the_op_line_only():
    t = made()
    win = xplane.window_of(t)
    busy = xplane.busy_seconds(t, win)
    assert busy == {0: 200e-9, 1: 100e-9}  # not the modules' 200 and 100
    assert xplane.idle_share(t, win) == pytest.approx(1 - 150 / 250)


def test_step_time_and_launch_gaps():
    t = made()
    assert xplane.step_device_ms(t, STEP) == pytest.approx(75e-6)
    assert xplane.launch_gaps_ms(t, STEP) == [pytest.approx(50e-6)]
    assert xplane.launch_gaps_ms(t, "no_such_program") == []
    assert xplane.step_device_ms(t, "no_such_program") is None
    # a window that cuts the second step keeps whole executions only
    assert len(xplane.module_events(t, 0, STEP, (0, 200))) == 1


def test_collectives_in_flight_and_exposed():
    t = made()
    ops = t["devices"][0][xplane.OP_LINE]
    assert xplane.collective_intervals(ops) == [(40, 90), (190, 240)]
    stats = xplane.collective_stats(t, STEP, xplane.window_of(t))
    # in flight 50 ns a step; b hides 25 of them: half is exposed
    assert stats["collective_ms"] == pytest.approx(50e-6)
    assert stats["exposed_pct"] == pytest.approx(50.0)
    one_chip = {"devices": {0: {xplane.OP_LINE: [["fusion", 0, 5]],
                                xplane.MODULE_LINE: []}}, "host": []}
    assert xplane.collective_stats(one_chip, STEP) is None


def test_synchronous_collective_counts_its_own_interval():
    ops = [["all-reduce.3", 10, 5], ["fusion", 15, 5],
           ["all-gather-start", 20, 1], ["all-gather-done", 30, 2]]
    assert xplane.collective_intervals(ops) == [(10, 15), (20, 32)]


def test_top_ops_and_idle_gaps_by_host_span():
    t = made()
    win = xplane.window_of(t)
    top = dict(xplane.top_ops(t, win))
    assert top["fusion.a"] == pytest.approx((80 + 100) / 2 * 1e-9)
    assert top["all-reduce-done.1"] == pytest.approx(20e-9)
    gaps = dict(xplane.idle_gaps(t, win))
    # device 0 idles in [100,150) and [250,250): dispatch [100,125),
    # infeed wait [125,145), and the epoch span keeps what they leave
    assert gaps["step.dispatch"] == pytest.approx(25e-9)
    assert gaps["step.infeed.wait"] == pytest.approx(20e-9)
    assert gaps["epoch"] == pytest.approx(5e-9)
    assert "(no span)" not in gaps
    assert xplane.idle_gaps({"devices": {}, "host": []}) == []


def test_json_round_trip(tmp_path):
    t = made()
    path = str(tmp_path / "t.json.gz")
    xplane.save_json(t, path)
    assert xplane.load_json(path) == t


def test_load_reads_a_profile_with_nothing_but_jax(tmp_path):
    """``load`` on a trace recorded here (CPU: host planes only): the
    harness's window span and a program span come back on one clock."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("step.dispatch"):
                jnp.ones((8, 8)).sum().block_until_ready()
    trace = xplane.load(xplane.find_xplane(str(tmp_path)),
                        host_names={"step.dispatch",
                                    xplane.WINDOW_SPAN}.__contains__)
    assert trace["devices"] == {}
    names = [e[0] for e in trace["host"]]
    assert names.count(xplane.WINDOW_SPAN) == 1 and "step.dispatch" in names
    lo, hi = xplane.window_of(trace)
    inner = next(e for e in trace["host"] if e[0] == "step.dispatch")
    assert lo <= inner[1] and inner[1] + inner[2] <= hi
    assert "/host:CPU" in xplane.describe(xplane.find_xplane(str(tmp_path)))


# ---- the recorded trace: four train steps of wdl_criteo_stream on one
# TPU v5e chip, cut from a traced run of this benchmark (PR 22), with the
# turn-round between two epochs after the second step

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "wdl_criteo_stream_4steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return xplane.load_json(FIXTURE)


def test_recorded_trace_shape(recorded):
    lines = recorded["devices"][0]
    assert len(lines[xplane.MODULE_LINE]) == 4
    assert len(lines[xplane.OP_LINE]) == 905
    assert all(e[0].startswith("jit_train_step(")
               for e in lines[xplane.MODULE_LINE])
    assert xplane.window_of(recorded) == (0, 515_640_939)


def test_recorded_busy_idle_and_steps(recorded):
    win = xplane.window_of(recorded)
    assert xplane.busy_seconds(recorded, win)[0] == pytest.approx(
        0.49015035, rel=1e-9)
    assert xplane.idle_share(recorded, win) == pytest.approx(
        0.0494347657, rel=1e-6)
    assert xplane.step_device_ms(recorded, STEP, win) == pytest.approx(
        122.492823)
    gaps = xplane.launch_gaps_ms(recorded, STEP, win)
    # back to back inside an epoch; 25 ms at the epoch's turn-round
    assert gaps == [pytest.approx(0.004007), pytest.approx(25.11205),
                    pytest.approx(0.003998)]
    assert xplane.median(gaps) == pytest.approx(0.004007)
    # in this cell the step program has no gap inside it: its ops fill the
    # module line's intervals to within 0.01%
    modules = xplane.union_ns(xplane.spans(
        recorded["devices"][0][xplane.MODULE_LINE]))
    assert modules / 1e9 == pytest.approx(
        xplane.busy_seconds(recorded, win)[0], rel=1e-4)


def test_recorded_top_ops_and_gaps(recorded):
    win = xplane.window_of(recorded)
    top = xplane.top_ops(recorded, win, 4)
    assert [n for n, _ in top] == [
        "fusion.6 f32[4194304,32]{0,1:T(8,128)}",   # scatter-add of grads
        "cond.40 (tuple)",                          # apply_gradients (Adam)
        "fusion.7 (tuple)",                         # Adam over the table
        "fusion.2 f32[425984,32]{0,1:T(8,128)S(1)}",  # the gather
    ]
    assert top[0][1] == pytest.approx(0.207694266)
    gaps = dict(xplane.idle_gaps(recorded, win))
    assert sum(gaps.values()) == pytest.approx(
        (win[1] - win[0]) / 1e9 - 0.49015035, rel=1e-6)
    assert gaps["step.host.produce"] == pytest.approx(0.009268527)
    assert xplane.collective_stats(recorded, STEP, win) is None  # one chip


def test_short_names():
    text = ("%fusion.6 = f32[4194304,32]{0,1:T(8,128)} fusion(f32[4194304,32]"
            "{0,1:T(8,128)} %broadcast_in_dim.10), kind=kCustom")
    assert xplane.short_name(text) == "fusion.6 f32[4194304,32]{0,1:T(8,128)}"
    assert xplane.short_name(
        "%cond.40 = (s32[]{:T(128)}, f32[1024]{0}) conditional(...)"
    ) == "cond.40 (tuple)"
    assert xplane.short_name("jit_train_step(123)") == "jit_train_step(123)"
    assert xplane.op_name("all-reduce-start.1 f32[8]{0}") == \
        "all-reduce-start.1"


def test_innermost_segments():
    host = [["outer", 0, 100], ["inner", 20, 10], ["other", 90, 30]]
    assert xplane.innermost_segments(host) == [
        (0, 20, "outer"), (20, 30, "inner"), (30, 90, "outer"),
        (90, 120, "other")]
    assert xplane.innermost_segments([]) == []


def test_reduction_is_linear_in_the_trace():
    """A 20 s trace of sub-millisecond steps has ~10^5 gaps and spans:
    the first shape of this code took gaps x spans and never finished."""
    import time

    n = 60_000
    ops = [["fusion", 1000 * i, 400] for i in range(n)]
    mods = [["jit_train_step(1)", 1000 * i, 400] for i in range(n)]
    host = [["step.dispatch", 1000 * i + 350, 300] for i in range(n)]
    host += [["step.infeed.wait", 1000 * i + 700, 250] for i in range(n)]
    host.append([xplane.WINDOW_SPAN, 0, 1000 * n])
    host.sort(key=lambda e: e[1])
    trace = {"devices": {0: {xplane.OP_LINE: ops,
                             xplane.MODULE_LINE: mods}}, "host": host}
    t0 = time.perf_counter()
    win = xplane.window_of(trace)
    gaps = dict(xplane.idle_gaps(trace, win))
    xplane.top_ops(trace, win)
    xplane.idle_share(trace, win)
    xplane.launch_gaps_ms(trace, STEP, win)
    assert time.perf_counter() - t0 < 10
    assert gaps["step.dispatch"] == pytest.approx(n * 250e-9)
    assert gaps["step.infeed.wait"] == pytest.approx(n * 250e-9)
    assert gaps["(no span)"] == pytest.approx(n * 100e-9)


# ---- two steps of wdl_criteo_x4_stream on a data:2,model:2 mesh of four
# TPU v5e chips (devices 0 and 3 kept), cut from a traced run (PR 22)

def test_recorded_mesh_trace_collectives():
    trace = xplane.load_json(os.path.join(
        ROOT, "benchmark", "fixtures", "wdl_criteo_x4_stream_2steps.json.gz"))
    win = xplane.window_of(trace)
    assert sorted(trace["devices"]) == [0, 3]
    kinds = {xplane.op_name(n).split(".")[0]
             for n, _, _ in trace["devices"][0][xplane.OP_LINE]
             if xplane.COLLECTIVE.match(n)}
    assert kinds == {"all-reduce"}  # synchronous: no -start / -done pair
    in_flight = [xplane.union_ns(xplane.collective_intervals(
        xplane.clip(trace["devices"][d][xplane.OP_LINE], win)))
        for d in (0, 3)]
    assert in_flight == [46_829_962, 46_573_818]
    stats = xplane.collective_stats(trace, STEP, win)
    assert stats["collective_ms"] == pytest.approx(23.349507)
    assert stats["exposed_pct"] == pytest.approx(100.0)
    assert xplane.step_device_ms(trace, STEP, win) == pytest.approx(
        147.495866)
    top = [n for n, _ in xplane.top_ops(trace, win, 6)]
    assert "all-reduce.14 f32[4194304,32]{0,1:T(8,128)}" in top
