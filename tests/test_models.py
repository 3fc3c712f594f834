"""Model-zoo tests: factory dispatch, activation-map parity, shapes,
embedding hashing (SURVEY.md §7.1 step 3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.models.dnn import ShifuDNN, activation_fn
from shifu_tensorflow_tpu.models.embeddings import HashedEmbedding, hash_to_buckets
from shifu_tensorflow_tpu.models.factory import build_model
from shifu_tensorflow_tpu.models.multi_task import MultiTaskDNN
from shifu_tensorflow_tpu.models.wide_deep import WideDeep


def _mc(params=None, **train_extra):
    train = {"numTrainEpochs": 1, "validSetRate": 0.1,
             "params": params or {"NumHiddenLayers": 2,
                                  "NumHiddenNodes": [8, 4],
                                  "ActivationFunc": ["relu", "tanh"],
                                  "LearningRate": 0.1}}
    train.update(train_extra)
    return ModelConfig.from_json({"train": train})


def test_activation_map_parity():
    # exact fallback semantics of ssgd_monitor.py:74-88
    import flax.linen as nn

    assert activation_fn("sigmoid") is nn.sigmoid
    assert activation_fn("TANH") is nn.tanh
    assert activation_fn("relu") is nn.relu
    assert activation_fn("LeakyReLU") is nn.leaky_relu
    assert activation_fn("bogus") is nn.leaky_relu
    assert activation_fn(None) is nn.leaky_relu


def test_dnn_output_shape_and_range():
    model = ShifuDNN(hidden_nodes=(8, 4), activations=("relu", "tanh"))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 10)), jnp.float32)
    params = model.init(jax.random.key(0), x)["params"]
    y = model.apply({"params": params}, x)
    assert y.shape == (5, 1)
    assert ((y >= 0) & (y <= 1)).all()  # sigmoid head
    # configured layer structure materialized
    assert params["trunk"]["hidden_layer0"]["kernel"].shape == (10, 8)
    assert params["trunk"]["hidden_layer1"]["kernel"].shape == (8, 4)
    assert params["shifu_output_0"]["kernel"].shape == (4, 1)


def test_factory_default_dnn():
    model = build_model(_mc())
    assert isinstance(model, ShifuDNN)


def test_factory_wide_deep():
    mc = _mc(params={"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                     "ActivationFunc": ["relu"], "ModelType": "wide_deep",
                     "WideColumnNums": [2, 3], "LearningRate": 0.1})
    model = build_model(mc, feature_columns=(1, 2, 3, 4))
    assert isinstance(model, WideDeep)
    assert model.wide_indices == (1, 2)  # positions of cols 2,3 in features
    x = jnp.ones((4, 4), jnp.float32)
    params = model.init(jax.random.key(0), x)["params"]
    y = model.apply({"params": params}, x)
    assert y.shape == (4, 1)


def test_factory_multi_task():
    mc = _mc(params={"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                     "ActivationFunc": ["relu"], "ModelType": "multi_task",
                     "NumTasks": 3, "LearningRate": 0.1})
    model = build_model(mc)
    assert isinstance(model, MultiTaskDNN)
    x = jnp.ones((4, 6), jnp.float32)
    params = model.init(jax.random.key(0), x)["params"]
    y = model.apply({"params": params}, x)
    assert y.shape == (4, 3)
    assert params["task_heads"]["kernel"].shape == (8, 3)


def test_factory_embedding_augmented():
    mc = _mc(params={"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                     "ActivationFunc": ["relu"],
                     "EmbeddingColumnNums": [5, 6],
                     "EmbeddingHashSize": 64, "EmbeddingDim": 4,
                     "LearningRate": 0.1})
    model = build_model(mc, feature_columns=(1, 2, 5, 6))
    x = jnp.ones((4, 4), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    y = model.apply(variables, x)
    assert y.shape == (4, 1)
    # table annotated for model-axis sharding
    import flax.linen as nn

    table = variables["params"]["hashed_columns"]["table"]
    assert isinstance(table, nn.Partitioned)
    assert table.names == ("model", None)
    assert table.value.shape == (64, 4)


def test_hash_to_buckets_range_and_spread():
    vals = jnp.asarray(np.arange(1000, dtype=np.float32))
    ids = np.asarray(hash_to_buckets(vals, 128))
    assert ids.min() >= 0 and ids.max() < 128
    assert len(np.unique(ids)) > 100  # decent spread over buckets


def test_hashed_embedding_column_salting():
    emb = HashedEmbedding(hash_size=256, features=2)
    # same value in two different columns should (generally) embed differently
    x = jnp.asarray([[7.0, 7.0]], jnp.float32)
    variables = emb.init(jax.random.key(0), x)
    out = emb.apply(variables, x).reshape(2, 2)
    assert not np.allclose(out[0], out[1])


def test_wide_deep_with_hashed_cross():
    # regression: cross table must initialize (was a crash pre-review)
    mc = _mc(params={"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                     "ActivationFunc": ["relu"], "ModelType": "wide_deep",
                     "WideColumnNums": [2, 3], "CrossHashSize": 128,
                     "LearningRate": 0.1})
    model = build_model(mc, feature_columns=(1, 2, 3, 4))
    x = jnp.ones((4, 4), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    y = model.apply(variables, x)
    assert y.shape == (4, 1)
    table = variables["params"]["wide_cross"]["table"]
    assert table.value.shape == (128, 1)


def test_wide_deep_keeps_embedding_columns():
    # regression: EmbeddingColumnNums no longer silently dropped for wide_deep
    mc = _mc(params={"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                     "ActivationFunc": ["relu"], "ModelType": "wide_deep",
                     "WideColumnNums": [2], "EmbeddingColumnNums": [3],
                     "EmbeddingHashSize": 32, "EmbeddingDim": 4,
                     "LearningRate": 0.1})
    from shifu_tensorflow_tpu.models.factory import EmbeddingAugmented

    model = build_model(mc, feature_columns=(1, 2, 3))
    assert isinstance(model, EmbeddingAugmented)
    x = jnp.ones((2, 3), jnp.float32)
    variables = model.init(jax.random.key(0), x)
    assert model.apply(variables, x).shape == (2, 1)


def test_trainer_with_embeddings_still_trains(model_config_json):
    """The factory threads shard_embeddings through; a trainer without a
    'model' axis must build and train the embedding-augmented model."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = dict(model_config_json)
    mc["train"] = dict(mc["train"])
    mc["train"]["params"] = dict(
        mc["train"]["params"],
        EmbeddingColumnNums=[2, 3],
        EmbeddingHashSize=64,
        EmbeddingDim=4,
    )
    trainer = Trainer(ModelConfig.from_json(mc), 4,
                      feature_columns=(0, 1, 2, 3))
    rng = np.random.default_rng(1)
    batch = {
        "x": rng.normal(size=(32, 4)).astype(np.float32),
        "y": (rng.random((32, 1)) < 0.5).astype(np.float32),
        "w": np.ones((32, 1), np.float32),
    }
    loss, n = trainer.train_epoch(iter([batch]))
    assert n == 1 and np.isfinite(loss)
