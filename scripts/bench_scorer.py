"""Micro-benchmark: batch-scoring throughput per eval backend.

The reference scores through TF-Java/JNI one row at a time
(TensorflowModel.compute, TensorflowModel.java:53-94).  This measures the
TPU-native replacements on an exported flagship-DNN artifact:

- ``native``  — flax forward (jit-compiled), the Python serving path;
- ``cpp``     — cpp/stpu_scorer.cc via ctypes, the zero-Python-runtime
                path matching the reference's JNI evaluator;
- per-row ``compute`` vs batched ``compute_batch`` for each, quantifying
  what the reference's row-at-a-time Computable contract costs.

Writes BENCH_SCORER.json at the repo root.  CPU-only — scoring parity
with the reference's CPU JNI eval; run anywhere.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

NUM_FEATURES = 30
BATCH_ROWS = 4096
PER_ROW_SAMPLES = 500
REPS = 20


def _export_flagship(export_dir: str):
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.saved_model import export_model
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json(
        {"train": {"numTrainEpochs": 1, "params": {
            "NumHiddenLayers": 3, "NumHiddenNodes": [256, 128, 64],
            "ActivationFunc": ["relu", "relu", "tanh"],
            "LearningRate": 0.05}}}
    )
    trainer = Trainer(mc, NUM_FEATURES,
                      feature_columns=tuple(range(NUM_FEATURES)))
    return export_model(export_dir, trainer,
                        feature_columns=tuple(range(NUM_FEATURES)))


def bench_backend(model_dir: str, backend: str, x: np.ndarray) -> dict:
    from shifu_tensorflow_tpu.export.eval_model import EvalModel

    model = EvalModel(model_dir, backend=backend)
    try:
        # batched path
        out = model.compute_batch(x)
        assert out.shape[0] == x.shape[0]
        t0 = time.perf_counter()
        for _ in range(REPS):
            model.compute_batch(x)
        batch_rows_s = REPS * x.shape[0] / (time.perf_counter() - t0)

        # per-row path (the reference's Computable contract)
        model.compute(x[0])
        t0 = time.perf_counter()
        for i in range(PER_ROW_SAMPLES):
            model.compute(x[i % x.shape[0]])
        row_rows_s = PER_ROW_SAMPLES / (time.perf_counter() - t0)
    finally:
        model.release()
    return {
        "backend": backend,
        "batch_rows_per_sec": round(batch_rows_s, 0),
        "per_row_rows_per_sec": round(row_rows_s, 0),
        "batch_speedup_over_per_row": round(batch_rows_s / row_rows_s, 1),
    }


def main() -> None:
    rng = np.random.default_rng(0)
    x = rng.normal(size=(BATCH_ROWS, NUM_FEATURES)).astype(np.float32)
    results = []
    with tempfile.TemporaryDirectory(prefix="stpu-scorer-") as root:
        wrote = _export_flagship(root)
        backends = ["native"]
        from shifu_tensorflow_tpu.export import eval_model as _em

        try:
            _em.EvalModel(root, backend="cpp").release()
            backends.append("cpp")
        except Exception as e:
            print(f"cpp backend unavailable: {e}", file=sys.stderr)
        for backend in backends:
            case = bench_backend(root, backend, x)
            print(json.dumps(case), flush=True)
            results.append(case)
    artifact = {
        "model": "flagship DNN 30->256->128->64->1",
        "batch_rows": BATCH_ROWS,
        "exported": wrote,
        "cases": results,
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_SCORER.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
