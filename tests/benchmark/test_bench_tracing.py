"""The benchmark's tracer: the program's spans, also as profiler
annotations, with sums the program's own draining cannot take away."""

import pytest

from benchmark import xplane
from benchmark.tracing import AnnotatingTracer
from shifu_tensorflow_tpu.obs import trace as obs_trace


def test_spans_survive_the_programs_draining():
    tracer = AnnotatingTracer()
    with tracer.span("step.dispatch"):
        pass
    timed = tracer.timed("step.infeed.put", lambda x: x + 1)
    assert timed(1) == 2
    assert list(tracer.wrap_iter("step.host.produce", [1, 2, 3])) == [1, 2, 3]
    drained = tracer.take_summary()  # what the autotuner / journal do
    assert drained["step.host.produce"]["count"] == 3
    assert tracer.summary() == {}
    mine = tracer.cumulative()
    assert {n: v["count"] for n, v in mine.items()} == {
        "step.dispatch": 1, "step.infeed.put": 1, "step.host.produce": 3}
    assert tracer.names == set(mine)
    assert all(v["total_s"] >= 0 and v["mean_s"] >= 0 for v in mine.values())


def test_a_span_records_when_its_body_raises():
    tracer = AnnotatingTracer()
    with pytest.raises(KeyError):
        with tracer.span("serve.dispatch"):
            raise KeyError("x")
    assert tracer.cumulative()["serve.dispatch"]["count"] == 1


def test_the_program_picks_it_up_and_spans_land_in_the_profile(tmp_path):
    import jax

    tracer = obs_trace.install(AnnotatingTracer())
    try:
        assert obs_trace.active() is tracer
        with jax.profiler.trace(str(tmp_path)):
            with obs_trace.span("checkpoint.save"):  # a module-level seam
                pass
            with obs_trace.maybe_span(tracer, "step.block"):  # a trainer seam
                pass
    finally:
        obs_trace.uninstall()
    trace = xplane.load(xplane.find_xplane(str(tmp_path)),
                        host_names=tracer.names.__contains__)
    assert {e[0] for e in trace["host"]} == {"checkpoint.save", "step.block"}
