"""Checkpoint-at-scale measurement (r04 verdict item 8).

``NpzCheckpointer`` gathers the full state tree through one host per
save.  With a model-sharded >=1GB embedding table that round-trip is the
concern: device->host fetch of the whole table, one np.savez stream, and
the mirror on restore.  This measures save (sync and async enqueue/drain)
and restore wall-clock at that size — on an 8-device virtual CPU mesh
with the table sharded over the 'model' axis when run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the script
re-execs itself with that flag set; it must precede the first jax
import) — and writes BENCH_CHECKPOINT.json.  The artifact either
justifies keeping the single-writer design (save hidden behind
async_save and small next to an epoch) or makes the case for per-shard
files.

Env knobs: CKPT_HASH_SIZE (8388608), CKPT_DIM (32)  ->  1.07 GB fp32.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HASH_SIZE = int(float(os.environ.get("CKPT_HASH_SIZE", 8_388_608)))
DIM = int(os.environ.get("CKPT_DIM", 32))
NUM_FEATURES = 10

if (os.environ.get("_STPU_CKPT_CHILD") != "1"
        and os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"):
    # CPU run: re-exec with the virtual multi-device flag (must be set
    # before jax loads) so the table shards over a model axis.  On TPU
    # (JAX_PLATFORMS unset) no re-exec: one chip gets a 1-device mesh
    # and the measurement is the HBM->host gather, the round-trip the
    # single-writer checkpoint design must justify.
    env = dict(os.environ)
    env["_STPU_CKPT_CHILD"] = "1"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)

import numpy as np  # noqa: E402


def _note(msg):
    import sys as _s
    print(f"[ckpt] {msg}", file=_s.stderr, flush=True)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        REPO, "BENCH_CHECKPOINT.json"))
    args = ap.parse_args()
    _note("importing jax...")
    import jax

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer
    from shifu_tensorflow_tpu.train.trainer import Trainer

    out_path = args.out
    mc = ModelConfig.from_json({"train": {"params": {
        "NumHiddenLayers": 1, "NumHiddenNodes": [16],
        "ActivationFunc": ["relu"], "LearningRate": 0.05,
        "Optimizer": "adam",
        "EmbeddingColumnNums": list(range(1, 6)),
        "EmbeddingHashSize": HASH_SIZE, "EmbeddingDim": DIM,
    }}})
    _note(f"devices: {jax.devices()}")
    mesh_spec = "data:4,model:2" if jax.device_count() >= 8 else "data:-1"
    mesh = make_mesh(mesh_spec)
    t_build0 = time.perf_counter()
    trainer = Trainer(mc, NUM_FEATURES, mesh=mesh,
                      feature_columns=tuple(range(1, NUM_FEATURES + 1)))
    build_s = time.perf_counter() - t_build0
    _note(f"trainer built in {build_s:.1f}s")
    table_bytes = HASH_SIZE * DIM * 4
    # Adam state doubles the table twice over (mu, nu)
    leaves = jax.tree_util.tree_leaves(trainer.state.params)
    params_bytes = sum(l.size * l.dtype.itemsize for l in leaves
                      if hasattr(l, "size"))

    result = {
        "metric": "checkpoint_at_scale",
        "platform": jax.devices()[0].platform,
        "n_devices": jax.device_count(),
        "mesh": mesh_spec,
        "hash_size": HASH_SIZE, "dim": DIM,
        "table_gb": round(table_bytes / 2**30, 2),
        "params_gb": round(params_bytes / 2**30, 2),
        "trainer_build_s": round(build_s, 1),
    }

    with tempfile.TemporaryDirectory(prefix="stpu-ckpt-") as d:
        # sync save
        ck = NpzCheckpointer(d, max_to_keep=2)
        t0 = time.perf_counter()
        _note("sync save...")
        ck.save(1, trainer.state)
        result["sync_save_s"] = round(time.perf_counter() - t0, 2)
        ckpt_file = [f for f in os.listdir(d) if f.endswith(".npz")][0]
        result["ckpt_gb"] = round(
            os.path.getsize(os.path.join(d, ckpt_file)) / 2**30, 2)

        # restore
        t0 = time.perf_counter()
        _note("restore...")
        restored, _next = ck.restore_latest(trainer.state)
        result["restore_s"] = round(time.perf_counter() - t0, 2)
        assert restored is not None
        ck.close()

        # async save: what the epoch loop actually pays (enqueue = the
        # inline device->host fetch) vs the hidden background write
        ck = NpzCheckpointer(d, max_to_keep=2, async_save=True)
        t0 = time.perf_counter()
        ck.save(2, trainer.state)
        result["async_enqueue_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        ck.wait()
        result["async_drain_s"] = round(time.perf_counter() - t0, 2)
        ck.close()

    # verdict criterion: is the single-writer gather a problem?  Compare
    # against the warm 20M-row epoch (BENCH_E2E.json) when present.
    e2e = os.path.join(REPO, "BENCH_E2E.json")
    if os.path.exists(e2e):
        try:
            e2e_data = json.load(open(e2e))
            warm = e2e_data.get("warm_epoch_s")
            # same-platform comparisons only: a TPU checkpoint run must
            # not ratio itself against a CPU epoch
            if warm and e2e_data.get("platform") == result["platform"]:
                result["warm_epoch_s_for_scale"] = warm
                result["async_enqueue_frac_of_epoch"] = round(
                    result["async_enqueue_s"] / warm, 3)
        except Exception:
            pass

    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
