"""Profiling utilities (SURVEY.md §5.1 — the reference had none)."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tensorflow_tpu.obs.trace import Tracer
from shifu_tensorflow_tpu.utils.profiling import (
    StepTimer,
    trace_if,
    true_sync,
)


def test_true_sync_probes_every_array_leaf():
    """true_sync is the measurement-integrity primitive (a value fetch
    proves completion on any backend): it must
    fetch one element of EVERY array leaf — each leaf is an independent
    device buffer — and tolerate every pytree shape benches throw at it."""
    true_sync(jnp.ones(()))                       # scalar
    true_sync(jnp.arange(12).reshape(3, 4))       # array
    true_sync({"x": jnp.ones((8, 3)), "y": jnp.zeros((8, 1)),
               "w": jnp.ones((8, 1))})            # device_put-style batch
    true_sync([jnp.ones((2, 2)), jnp.zeros(())])  # list
    true_sync([])                                 # no leaves: no-op
    true_sync((1.0, "x", None))                   # no array leaves
    # forces REAL completion: the fetched value must be correct
    out = jax.jit(lambda a: a * 3.0)(jnp.full((4,), 2.0))
    true_sync(out)
    assert float(out[0]) == 6.0


def test_step_timer_counts_and_rates():
    timer = StepTimer(sync_every=2)
    x = jnp.ones((4,))
    for _ in range(5):
        timer.step(x * 2, rows=4)
    s = timer.summary()
    assert s["steps"] == 5
    assert s["rows_per_sec"] > 0
    assert s["elapsed_s"] > 0
    assert abs(s["steps_per_sec"] * s["step_time_s"] - 1.0) < 1e-6
    timer.reset()
    assert timer.summary()["steps"] == 0


def test_trace_if_none_is_noop():
    with trace_if(None):
        pass  # must not require jax import side effects


def test_trace_if_writes_profile(tmp_path):
    d = str(tmp_path / "trace")
    with trace_if(d):
        with Tracer().span("unit_test.region"):
            jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    # jax writes <dir>/plugins/profile/<ts>/*.xplane.pb
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    assert found, f"no trace written under {d}"


def test_trace_if_capture_holds_the_tracers_spans(tmp_path):
    """Host-side regions reach the timeline as the obs tracer's spans
    (what `annotate` was for)."""
    from shifu_tensorflow_tpu.obs import profile as obs_profile

    d = str(tmp_path / "trace")
    tracer = Tracer()
    with trace_if(d):
        with tracer.span("unit_test.region"):
            jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))).block_until_ready()
    host = obs_profile.load_capture(obs_profile.find_xplane(d),
                                    obs_profile.STEP_PROGRAM)["host"]
    (name, _, dur_ns), = [h for h in host if h[0] == "unit_test.region"]
    assert 0 < dur_ns / 1e9 <= tracer.summary()[name]["total_s"] * 1.5 + 1e-3


def test_trainer_step_timer_integration(model_config_json):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train.trainer import Trainer

    trainer = Trainer(ModelConfig.from_json(model_config_json), 4)
    trainer.step_timer = StepTimer(sync_every=2)
    rng = np.random.default_rng(0)
    batches = [
        {
            "x": rng.normal(size=(8, 4)).astype(np.float32),
            "y": np.ones((8, 1), np.float32),
            "w": np.ones((8, 1), np.float32),
        }
        for _ in range(3)
    ]
    trainer.train_epoch(iter(batches))
    s = trainer.step_timer.summary()
    assert s["steps"] == 3
    assert trainer.step_timer.n_rows == 24
