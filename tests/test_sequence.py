"""Sequence model family: transformer encoder over event sequences, with
ring/Ulysses sequence-parallel attention as first-class consumers of
parallel/ring.py (SURVEY.md §5.7 beyond-parity capability)."""

import jax
import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.data.dataset import InMemoryDataset
from shifu_tensorflow_tpu.data.reader import ParsedBlock, RecordSchema
from shifu_tensorflow_tpu.models.factory import build_model
from shifu_tensorflow_tpu.parallel.mesh import make_mesh
from shifu_tensorflow_tpu.train.trainer import Trainer

SEQ_LEN, STEP_F = 8, 4
NUM_FEATURES = SEQ_LEN * STEP_F


def _mc(epochs=3, attention="auto", **extra):
    params = {
        "NumHiddenLayers": 1, "NumHiddenNodes": [8],
        "ActivationFunc": ["relu"],
        "LearningRate": 0.003, "Optimizer": "adam",
        "ModelType": "sequence", "SeqLen": SEQ_LEN,
        "SeqDModel": 32, "SeqHeads": 4, "SeqBlocks": 2,
        "SeqAttention": attention,
    }
    params.update(extra)
    return ModelConfig.from_json(
        {"train": {"numTrainEpochs": epochs, "validSetRate": 0.2,
                   "params": params}}
    )


def _seq_dataset(rows=600, seed=0):
    """Label depends on a cross-step aggregate (mean of step feature 0
    gated by feature 1's trajectory) — only a model that sees the sequence
    can separate it."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, SEQ_LEN, STEP_F)).astype(np.float32)
    agg = x[:, :, 0].mean(axis=1) + 0.8 * np.sign(
        x[:, -1, 1] - x[:, 0, 1]
    )
    y = (agg > 0).astype(np.float32)  # deterministic: separability is the
    # point; label noise would cap the AUC the test asserts on
    flat = x.reshape(rows, NUM_FEATURES)
    n_valid = rows // 5
    schema = RecordSchema(
        feature_columns=tuple(range(1, NUM_FEATURES + 1)), target_column=0
    )
    mk = lambda lo, hi: ParsedBlock(
        flat[lo:hi], y[lo:hi, None], np.ones((hi - lo, 1), np.float32)
    )
    return InMemoryDataset(mk(n_valid, rows), mk(0, n_valid), schema)


def test_factory_builds_sequence_model_and_forward_shape():
    model = build_model(_mc(), tuple(range(1, NUM_FEATURES + 1)))
    x = np.random.default_rng(0).normal(size=(6, NUM_FEATURES)).astype(
        np.float32
    )
    params = model.init(jax.random.key(0), x)["params"]
    out = model.apply({"params": params}, x)
    assert out.shape == (6, 1)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.all((np.asarray(out) >= 0) & (np.asarray(out) <= 1))


def test_sequence_composes_with_keep_best_and_early_stop():
    """The round-4 training features are family-agnostic: the sequence
    family under keep-best=ks + early-stop must track its best epoch and
    stop at the target like the DNN gate test does."""
    from shifu_tensorflow_tpu.train.trainer import EarlyStopper

    ds = _seq_dataset(rows=5000)
    trainer = Trainer(_mc(epochs=10, LearningRate=0.003), NUM_FEATURES,
                      seed=3, keep_best="ks")
    history = trainer.fit(ds, batch_size=128,
                          early_stop=EarlyStopper(target_ks=0.45))
    assert trainer.stop_reason, "sequence family never hit KS 0.45"
    assert history[-1].ks >= 0.45
    assert trainer.best_metric >= 0.45


def test_seq_remat_is_numerically_invisible():
    """SeqRemat changes WHERE activations come from in the backward
    (recompute vs store), never the numbers: loss and grads must match
    the non-remat model exactly on the same params."""
    from shifu_tensorflow_tpu.models.factory import build_model as bm

    cols = tuple(range(1, NUM_FEATURES + 1))
    base = bm(_mc(), cols)
    remat = bm(_mc(SeqRemat="true"), cols)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, NUM_FEATURES)).astype(np.float32)
    y = (rng.random((32, 1)) < 0.5).astype(np.float32)
    params = base.init(jax.random.key(0), x)

    def loss(model):
        def f(p):
            out = model.apply(p, x)
            return ((out - y) ** 2).mean()

        return f

    l0, g0 = jax.value_and_grad(loss(base))(params)
    l1, g1 = jax.value_and_grad(loss(remat))(params)
    assert float(l0) == float(l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_seq_remat_config_parsing():
    assert _mc(SeqRemat="true").params.seq_remat is True
    assert _mc(SeqRemat=True).params.seq_remat is True
    # same token set as Conf.get_bool: "on"/"1" are true everywhere
    assert _mc(SeqRemat="on").params.seq_remat is True
    assert _mc(SeqRemat="1").params.seq_remat is True
    assert _mc(SeqRemat="false").params.seq_remat is False
    assert _mc().params.seq_remat is False


@pytest.mark.parametrize("attention", [
    "chunked",
    # the flash variant runs the Pallas kernel in interpret mode on the
    # CPU backend: ~400 s wall for a wiring check the chunked variant
    # covers identically (kernel parity itself is pinned fast in
    # tests/test_flash.py) — nearly half the tier-1 wall-clock budget,
    # so it runs under -m slow only
    pytest.param("flash", marks=pytest.mark.slow),
])
def test_config_level_memory_safe_attention_trains(attention,
                                                   pallas_interpret):
    """SeqAttention=chunked|flash resolve from ModelConfig params and
    train end-to-end through the Trainer (the long-S single-device
    paths; parity is pinned in tests/test_flash.py — here the wiring)."""
    ds = _seq_dataset(rows=400)
    trainer = Trainer(_mc(epochs=2, attention=attention), NUM_FEATURES,
                      seed=1)
    history = trainer.fit(ds, batch_size=64)
    assert len(history) == 2
    assert np.isfinite(history[-1].valid_loss)


def test_sequence_model_learns_sequence_signal():
    # 5K rows: transformers are data-hungry; at 600 rows this plateaus at
    # AUC ~0.55, at 5K it reaches ~0.98 by epoch 8 (measured)
    ds = _seq_dataset(rows=5000)
    trainer = Trainer(_mc(epochs=8, LearningRate=0.003), NUM_FEATURES,
                      seed=3)
    history = trainer.fit(ds, batch_size=128)
    assert history[-1].valid_loss < history[0].valid_loss
    assert history[-1].auc > 0.9


def test_ring_attention_forward_parity_with_full():
    """Same params, same input: ring-sharded attention over a data x seq
    mesh must reproduce single-device full attention."""
    mesh = make_mesh("data:2,seq:4")
    model_full = build_model(_mc(attention="full"),
                             tuple(range(1, NUM_FEATURES + 1)))
    model_ring = build_model(_mc(attention="ring"),
                             tuple(range(1, NUM_FEATURES + 1)), mesh=mesh)
    x = np.random.default_rng(1).normal(size=(8, NUM_FEATURES)).astype(
        np.float32
    )
    params = model_full.init(jax.random.key(7), x)["params"]
    a = np.asarray(model_full.apply({"params": params}, x))
    b = np.asarray(model_ring.apply({"params": params}, x))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_sequence_trains_on_seq_parallel_mesh():
    mesh = make_mesh("data:2,seq:4")
    ds = _seq_dataset(rows=256)
    trainer = Trainer(_mc(epochs=2, attention="ring"), NUM_FEATURES,
                      mesh=mesh, seed=3)
    history = trainer.fit(ds, batch_size=64)
    assert np.isfinite(history[-1].training_loss)
    # auto resolves to ring on a seq mesh: same path, one epoch sanity
    t_auto = Trainer(_mc(epochs=1, attention="auto"), NUM_FEATURES,
                     mesh=mesh, seed=3)
    h = t_auto.fit(ds, batch_size=64)
    assert np.isfinite(h[-1].training_loss)
    # SeqRemat composes with ring: jax.checkpoint over the shard_map'd
    # attention — the one remat composition not covered elsewhere
    t_remat = Trainer(_mc(epochs=1, attention="ring", SeqRemat="true"),
                      NUM_FEATURES, mesh=mesh, seed=3)
    hr = t_remat.fit(ds, batch_size=64)
    assert np.isfinite(hr[-1].training_loss)


def test_sequence_config_errors():
    with pytest.raises(ValueError, match="SeqLen"):
        build_model(_mc(SeqLen=0), tuple(range(1, NUM_FEATURES + 1)))
    with pytest.raises(ValueError, match="seq"):
        # ring without a seq mesh axis
        build_model(_mc(attention="ring"),
                    tuple(range(1, NUM_FEATURES + 1)), mesh=None)
    with pytest.raises(ValueError, match="divisible"):
        model = build_model(_mc(), tuple(range(1, NUM_FEATURES + 1)))
        bad = np.zeros((2, NUM_FEATURES + 3), np.float32)
        model.init(jax.random.key(0), bad)


def test_sequence_export_native_roundtrip(tmp_path):
    """Exported sequence bundles carry the Seq* arch params (serving pins
    full attention) and rescore exactly through the native backend."""
    from shifu_tensorflow_tpu.export.eval_model import EvalModel
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle

    ds = _seq_dataset(rows=256)
    trainer = Trainer(_mc(epochs=1), NUM_FEATURES, seed=5)
    trainer.fit(ds, batch_size=64)
    export_dir = str(tmp_path / "seq-model")
    export_native_bundle(
        export_dir, trainer.state.params, trainer.model_config,
        NUM_FEATURES, feature_columns=tuple(range(1, NUM_FEATURES + 1)),
    )
    with EvalModel(export_dir, backend="native") as em:
        x = ds.valid.features[:32]
        got = em.compute_batch(x)
        want = trainer.predict(x)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sequence_config_validation_names_keys():
    # conflicting ModelType + SeqLen
    with pytest.raises(ValueError, match="conflicts"):
        build_model(_mc(ModelType="multi_task"),
                    tuple(range(1, NUM_FEATURES + 1)))
    # uneven heads
    with pytest.raises(ValueError, match="SeqDModel"):
        build_model(_mc(SeqDModel=32, SeqHeads=6),
                    tuple(range(1, NUM_FEATURES + 1)))
    # seq axis must divide SeqLen
    with pytest.raises(ValueError, match="SeqLen"):
        build_model(_mc(attention="ring", SeqLen=6),
                    tuple(range(1, 6 * STEP_F + 1)),
                    mesh=make_mesh("data:2,seq:4"))
    # ulysses head divisibility
    with pytest.raises(ValueError, match="SeqHeads"):
        build_model(_mc(attention="ulysses", SeqHeads=3),
                    tuple(range(1, NUM_FEATURES + 1)),
                    mesh=make_mesh("data:2,seq:4"))


def test_ring_trained_model_exports_saved_model(tmp_path):
    """Review regression: export_model must rebuild the serving function
    mesh-less — a ring-trained sequence model's shard_map attention must
    not be traced into the jax2tf SavedModel."""
    pytest.importorskip("tensorflow")
    from shifu_tensorflow_tpu.export.saved_model import export_model

    mesh = make_mesh("data:2,seq:4")
    ds = _seq_dataset(rows=128)
    trainer = Trainer(_mc(epochs=1, attention="ring"), NUM_FEATURES,
                      mesh=mesh, seed=5)
    trainer.fit(ds, batch_size=64)
    status = export_model(str(tmp_path / "ring-export"), trainer,
                          feature_columns=tuple(range(1, NUM_FEATURES + 1)))
    assert status["native"] and status["saved_model"]


def test_ulysses_attention_forward_parity_with_full():
    """Ulysses all-to-all attention (heads re-sharded over the seq axis)
    must also reproduce full attention at the model level (seq:4 | heads=4)."""
    mesh = make_mesh("data:2,seq:4")
    model_full = build_model(_mc(attention="full"),
                             tuple(range(1, NUM_FEATURES + 1)))
    model_uly = build_model(_mc(attention="ulysses"),
                            tuple(range(1, NUM_FEATURES + 1)), mesh=mesh)
    x = np.random.default_rng(2).normal(size=(8, NUM_FEATURES)).astype(
        np.float32
    )
    params = model_full.init(jax.random.key(9), x)["params"]
    a = np.asarray(model_full.apply({"params": params}, x))
    b = np.asarray(model_uly.apply({"params": params}, x))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
