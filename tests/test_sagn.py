"""SAGN local-SGD trainer (reference parity: SAGN.py / sagn_monitor.py).

Covers SURVEY.md §2.2 component #21: communication windows of local steps,
averaged-gradient global apply, single all-reduce per window.
"""

import jax
import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.parallel.mesh import make_mesh
from shifu_tensorflow_tpu.train import make_trainer
from shifu_tensorflow_tpu.train.sagn import SAGNTrainer
from shifu_tensorflow_tpu.train.trainer import Trainer

N_FEATS = 10


def _mc(window: int, optimizer: str = "sgd", epochs: int = 3) -> ModelConfig:
    return ModelConfig.from_json(
        {
            "train": {
                "numTrainEpochs": epochs,
                "validSetRate": 0.2,
                "params": {
                    "NumHiddenLayers": 2,
                    "NumHiddenNodes": [16, 8],
                    "ActivationFunc": ["relu", "tanh"],
                    "LearningRate": 0.05,
                    "Optimizer": optimizer,
                    "UpdateWindow": window,
                    "Algorithm": "sagn",
                },
            }
        }
    )


def _synth(n_rows: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=N_FEATS)
    x = rng.normal(size=(n_rows, N_FEATS)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-(x @ w_true)))
    y = (rng.random(n_rows) < p).astype(np.float32)[:, None]
    return {"x": x, "y": y, "w": np.ones((n_rows, 1), np.float32)}


def _batches(data, batch_size):
    n = data["x"].shape[0]
    for i in range(0, n - n % batch_size, batch_size):
        yield {k: v[i : i + batch_size] for k, v in data.items()}


def _flat(params):
    return np.concatenate(
        [np.asarray(l).ravel() for l in jax.tree_util.tree_leaves(params)]
    )


def test_factory_dispatch():
    t = make_trainer(_mc(window=4), N_FEATS)
    assert isinstance(t, SAGNTrainer)
    t2 = make_trainer(
        ModelConfig.from_json({"train": {"params": {"Algorithm": "ssgd"}}}),
        N_FEATS,
    )
    assert isinstance(t2, Trainer) and not isinstance(t2, SAGNTrainer)
    with pytest.raises(ValueError):
        make_trainer(
            ModelConfig.from_json({"train": {"params": {"Algorithm": "nope"}}}),
            N_FEATS,
        )


def test_window1_matches_plain_step():
    """A window of 1 is exactly one synchronous step: same grads, same
    global apply — SAGN must coincide with the plain trainer."""
    data = _synth(64)
    sagn = SAGNTrainer(_mc(window=1), N_FEATS, seed=7)
    plain = Trainer(_mc(window=1), N_FEATS, seed=7)
    batch = {k: v[:32] for k, v in data.items()}
    sagn.train_epoch(iter([batch]))
    plain.train_epoch(iter([batch]))
    np.testing.assert_allclose(
        _flat(sagn.state.params), _flat(plain.state.params), rtol=1e-5, atol=1e-6
    )


def test_sagn_converges():
    data = _synth(512)
    trainer = SAGNTrainer(_mc(window=4, optimizer="adam", epochs=1), N_FEATS, seed=3)
    first = trainer.train_epoch(_batches(data, 32))[0]
    for _ in range(4):
        last = trainer.train_epoch(_batches(data, 32))[0]
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first, f"SAGN did not reduce loss: {first} -> {last}"


def test_partial_window_fallback():
    """7 batches with window 4 => one SAGN window + 3 plain steps; nothing
    dropped."""
    data = _synth(7 * 16)
    trainer = SAGNTrainer(_mc(window=4), N_FEATS)
    loss, n_micro = trainer.train_epoch(_batches(data, 16))
    assert n_micro == 7
    assert np.isfinite(loss)


def test_mesh_sagn_runs_and_drifts_locally():
    """On an 8-device mesh each shard runs its own local window; the result
    must differ from the single-worker window (true per-shard drift) while
    both remain finite and both converge."""
    mesh = make_mesh("data:8")
    data = _synth(8 * 32)
    single = SAGNTrainer(_mc(window=3), N_FEATS, seed=11)
    sharded = SAGNTrainer(_mc(window=3), N_FEATS, seed=11, mesh=mesh)

    batches = list(_batches(data, 64))[:3]
    single.train_epoch(iter(batches))
    sharded.train_epoch(iter(batches))

    a, b = _flat(single.state.params), _flat(sharded.state.params)
    assert np.all(np.isfinite(a)) and np.all(np.isfinite(b))
    # same data, same seed: local drift must make the sharded window differ
    assert not np.allclose(a, b, rtol=1e-6, atol=1e-7)
    # but they solve the same problem: both should be close in loss
    ev_a = single.evaluate(iter(batches))
    ev_b = sharded.evaluate(iter(batches))
    assert abs(ev_a["loss"] - ev_b["loss"]) < 0.1


@pytest.mark.parametrize("rows", [64, 60])
def test_mesh_window1_matches_unsharded(rows):
    """With window=1 the count-weighted psum of per-shard grads is exactly
    the full-batch weighted gradient — including when the batch does not
    divide the mesh (60 rows -> 4 zero-weight pad rows land on one shard)."""
    mesh = make_mesh("data:8")
    data = _synth(128)
    single = SAGNTrainer(_mc(window=1), N_FEATS, seed=5)
    sharded = SAGNTrainer(_mc(window=1), N_FEATS, seed=5, mesh=mesh)
    batch = {k: v[:rows] for k, v in data.items()}
    single.train_epoch(iter([batch]))
    sharded.train_epoch(iter([batch]))
    np.testing.assert_allclose(
        _flat(single.state.params),
        _flat(sharded.state.params),
        rtol=1e-4,
        atol=1e-5,
    )


@pytest.mark.parametrize("window", [1, 2])
def test_mesh_with_a_replicated_embedding_table_matches_unsharded(window):
    """``EmbeddingDim`` 8 takes the lookup's own backward
    (models/embeddings.py ``take_rows``), which opens a ``shard_map`` of
    its own on a mesh.  SAGN differentiates inside one already: there the
    backward must run on the shard's lookups as on one device."""
    mc = _mc(window=window)
    params = dict(mc.raw["train"]["params"], EmbeddingColumnNums=[8, 9],
                  EmbeddingHashSize=256, EmbeddingDim=8)
    mc = ModelConfig.from_json({"train": {"params": params}})
    data = _synth(128)
    data["x"][:, 8:] = np.random.default_rng(1).integers(
        0, 40, (128, 2)).astype(np.float32)
    columns = tuple(range(N_FEATS))
    single = SAGNTrainer(mc, N_FEATS, seed=5, feature_columns=columns)
    sharded = SAGNTrainer(mc, N_FEATS, seed=5, feature_columns=columns,
                          mesh=make_mesh("data:4", jax.devices()[:4]))
    if window == 1:
        # one step: the count-weighted sum of the shards' gradients is the
        # batch's (local steps of a longer window see a shard's rows only)
        batches = [{k: v[:64] for k, v in data.items()}]
        single.train_epoch(iter(batches))
        sharded.train_epoch(iter(batches))
        np.testing.assert_allclose(_flat(single.state.params),
                                   _flat(sharded.state.params),
                                   rtol=1e-4, atol=1e-5)
    else:
        before = _flat(sharded.state.params)
        sharded.train_epoch(_batches(data, 32))
        after = _flat(sharded.state.params)
        assert np.isfinite(after).all() and np.abs(after - before).max() > 0


def test_sagn_rejects_partitioned_params_on_mesh():
    mc = ModelConfig.from_json(
        {
            "train": {
                "params": {
                    "Algorithm": "sagn",
                    "UpdateWindow": 2,
                    "EmbeddingColumnNums": [8, 9],
                    "EmbeddingHashSize": 64,
                    "EmbeddingDim": 4,
                }
            }
        }
    )
    mesh = make_mesh("data:4,model:2")
    with pytest.raises(ValueError, match="Partitioned"):
        SAGNTrainer(
            mc, N_FEATS, mesh=mesh, feature_columns=tuple(range(N_FEATS))
        )
