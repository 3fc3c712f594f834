"""The plain references against the program at small size, on the CPU:
DNN, and Wide&Deep + hashed embeddings + hashed cross; the numpy bucket
hash against ``ops/hashing``; and the comparison that decides ``correct``
against the faults it has to catch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark import gen
from benchmark.planes import train_stream
from benchmark.reference import tabular
from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.ops import hashing
from shifu_tensorflow_tpu.train.trainer import Trainer

DATA = {"numeric": 5, "categorical": 6, "zipf_s": 1.05,
        "cardinality_min": 10, "cardinality_max": 100_000,
        "code_scale": 1e-6}
FEATURES = tuple(range(1, 12))
WDL = {"NumHiddenLayers": 2, "NumHiddenNodes": [32, 16],
       "ActivationFunc": ["relu", "tanh"], "LearningRate": 0.01,
       "Optimizer": "adam", "ModelType": "wide_deep",
       "WideColumnNums": [6, 7, 8], "CrossHashSize": 512,
       "EmbeddingColumnNums": [6, 7, 8, 9, 10, 11],
       "EmbeddingHashSize": 2048, "EmbeddingDim": 4}
DNN = {"NumHiddenLayers": 3, "NumHiddenNodes": [16, 8, 4],
       "ActivationFunc": ["relu", "relu", "tanh"], "LearningRate": 0.01,
       "Optimizer": "adam"}
CHECK = {"loss_rtol": 1e-5, "stated_loss_rtol": 1e-5, "update_rtol": 1e-3,
         "small_leaf_update_rtol": 1e-3}


def batches(n=4, rows=256, data=DATA):
    out = []
    for i in range(n):
        x, y = gen.synth_rows(np.random.default_rng([3, i]), rows, data)
        out.append({"x": x, "y": y[:, None].astype(np.float32),
                    "w": np.ones((rows, 1), np.float32)})
    return out


def run_both(params_cfg, data=DATA, features=FEATURES, mutate=None):
    bs = batches(data=data)
    nf = data["numeric"] + data["categorical"]
    trainer = Trainer(ModelConfig.from_json({"train": {"params": params_cfg}}),
                      nf, feature_columns=features, seed=5)
    rows = train_stream._probe_rows(params_cfg, features, bs[0]["x"])
    live = meta.unbox(trainer.state.params)
    init = train_stream._probe(live, rows)
    ref_cfg = dict(params_cfg, **(mutate or {}))
    truth, _ = tabular.reference_steps(
        jax.tree.map(jnp.copy, live), ref_cfg, features, bs, "highest")
    stated_losses, ref_params = tabular.reference_steps(
        jax.tree.map(jnp.copy, live), ref_cfg, features, bs, "default")
    stated = train_stream._probe(ref_params, rows)
    sys_losses = [trainer.train_epoch([b])[0] for b in bs]
    sys = train_stream._probe(meta.unbox(trainer.state.params), rows)
    return train_stream.compare(truth, stated_losses, sys_losses, init,
                                stated, sys, CHECK)


@pytest.mark.parametrize("params_cfg,data,features", [
    (DNN, dict(DATA, numeric=11, categorical=0), FEATURES),
    (WDL, DATA, FEATURES),
    (dict(DNN, EmbeddingColumnNums=[9, 10, 11], EmbeddingHashSize=1024,
          EmbeddingDim=4), DATA, FEATURES),
], ids=["dnn", "wide_deep_hashed_cross", "dnn_hashed"])
def test_reference_agrees_with_the_program(params_cfg, data, features):
    got = run_both(params_cfg, data, features)
    assert got["ok"], got
    assert got["loss_rel_err"] < 1e-5 and got["update_rel_err"] < 1e-3


@pytest.mark.parametrize("mutate,what", [
    ({"CrossHashSize": 0}, "a dropped cross term"),
    ({"WideColumnNums": [9, 10, 11]}, "the wrong wide columns"),
    ({"LearningRate": 0.02}, "a wrong step size"),
    ({"EmbeddingColumnNums": [7, 6, 8, 9, 10, 11]}, "wrong column salts"),
    ({"ActivationFunc": ["relu", "relu"]}, "a wrong activation"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(mutate, what):
    """The reference is given a different model than the program runs: the
    comparison must say so (here the 'fault' is on the reference's side,
    which is the same disagreement)."""
    got = run_both(WDL, mutate=mutate)
    assert not got["ok"], (what, got)


def test_first_loss_alone_would_miss_a_dropped_cross_term():
    """Why the updates are compared too: the wide and cross weights start
    at zero, so the first loss cannot see them."""
    got = run_both(WDL, mutate={"CrossHashSize": 0})
    assert abs(got["sys_losses"][0] - got["truth_losses"][0]) < 1e-6
    assert got["update_rel_err"] > 0.5  # the cross table never moved


def test_small_leaves_have_their_own_limit():
    """The 3-wide linear kernel and the output bias are judged by
    ``small_leaf_update_rtol``; a wrong wide part still fails it."""
    got = run_both(WDL, mutate={"WideColumnNums": [9, 10, 11]})
    assert got["small_leaf_update_rel_err"] > 0.25
    a = np.ones(8)
    loose = dict(CHECK, small_leaf_update_rtol=0.4)
    args = ([0.5], [0.5], [0.5], {"b": a * 0}, {"b": a}, {"b": a * 1.3})
    assert train_stream.compare(*args, loose)["ok"]
    assert not train_stream.compare(*args, CHECK)["ok"]
    big = [{"b": np.zeros(64)}, {"b": np.ones(64)}, {"b": np.ones(64) * 1.3}]
    assert not train_stream.compare([0.5], [0.5], [0.5], *big, loose)["ok"]


@pytest.mark.parametrize("hash_size", [97, 4096, 4_194_304])
def test_numpy_hash_is_the_programs(hash_size):
    x, _ = gen.synth_rows(np.random.default_rng(9), 512, DATA)
    cats = np.concatenate([x[:, 5:], -x[:, 5:6], np.zeros((512, 1), "f4")],
                          axis=1)
    np.testing.assert_array_equal(
        tabular.salted_bucket_ids(cats, hash_size),
        np.asarray(hashing.salted_bucket_ids(jnp.asarray(cats), hash_size)))
    np.testing.assert_array_equal(
        tabular.crossed_bucket_ids(cats[:, :3], hash_size),
        np.asarray(hashing.crossed_bucket_ids(jnp.asarray(cats[:, :3]),
                                              hash_size)))

