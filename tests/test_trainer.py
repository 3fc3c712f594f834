"""Trainer tests: loss parity semantics, end-to-end convergence on the
synthetic PSV dataset, checkpoint/resume epoch accounting, mesh-sharded DP
(SURVEY.md §7.1 step 4-5; §4 test-strategy items 3 and 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.data.dataset import InMemoryDataset
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.ops.losses import (
    l2_penalty,
    weighted_bce,
    weighted_mse,
)
from shifu_tensorflow_tpu.parallel.mesh import make_mesh
from shifu_tensorflow_tpu.train.checkpoint import Checkpointer
from shifu_tensorflow_tpu.train import sagn, trainer as trainer_mod
from shifu_tensorflow_tpu.train.trainer import Trainer


def _mc(epochs=3, opt="adam", lr=0.05, **params_extra):
    params = {"NumHiddenLayers": 2, "NumHiddenNodes": [16, 8],
              "ActivationFunc": ["relu", "tanh"], "LearningRate": lr,
              "Optimizer": opt}
    params.update(params_extra)
    return ModelConfig.from_json(
        {"train": {"numTrainEpochs": epochs, "validSetRate": 0.2,
                   "params": params}}
    )


def _dataset(psv_dataset, valid_rate=0.2):
    schema = RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )
    return InMemoryDataset.load(psv_dataset["paths"], schema, valid_rate)


# ---- loss semantics ----

def test_weighted_mse_nonzero_weight_normalization():
    # TF1 SUM_BY_NONZERO_WEIGHTS parity: denominator = count of w != 0
    pred = jnp.asarray([[0.0], [1.0], [0.5]])
    target = jnp.asarray([[1.0], [1.0], [0.0]])
    w = jnp.asarray([[2.0], [0.0], [1.0]])
    # sum = 2*1 + 0 + 0.25 = 2.25; nonzero count = 2
    assert np.isclose(float(weighted_mse(pred, target, w)), 2.25 / 2)


def test_weighted_mse_padding_free():
    pred = jnp.asarray([[0.2], [0.9]])
    target = jnp.asarray([[0.0], [1.0]])
    w1 = jnp.asarray([[1.0], [1.0]])
    base = float(weighted_mse(pred, target, w1))
    # appending zero-weight padding rows must not change the loss
    pred2 = jnp.concatenate([pred, jnp.zeros((3, 1))])
    target2 = jnp.concatenate([target, jnp.zeros((3, 1))])
    w2 = jnp.concatenate([w1, jnp.zeros((3, 1))])
    assert np.isclose(float(weighted_mse(pred2, target2, w2)), base)


def test_weighted_bce_range():
    pred = jnp.asarray([[0.999], [0.001]])
    target = jnp.asarray([[1.0], [0.0]])
    w = jnp.ones((2, 1))
    assert float(weighted_bce(pred, target, w)) < 0.01


# ---- end-to-end convergence (the minimum end-to-end slice, §7.1) ----

def test_fit_learns_and_reports(psv_dataset):
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=5), ds.schema.num_features, worker_index=0)
    seen = []
    history = trainer.fit(ds, batch_size=50, on_epoch=seen.append)
    assert len(history) == 5
    assert seen == history
    # learns: training loss drops, KS/AUC clearly better than chance
    assert history[-1].training_loss < history[0].training_loss
    assert np.isfinite(history[-1].valid_loss)
    assert history[-1].auc > 0.75
    assert history[-1].ks > 0.3
    # global step advances by steps-per-epoch each epoch
    assert history[0].global_step > 0
    assert history[-1].global_step == 5 * history[0].global_step


def test_adadelta_default_runs(psv_dataset):
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=1, opt="adadelta", lr=1.0),
                      ds.schema.num_features)
    history = trainer.fit(ds, batch_size=100)
    assert np.isfinite(history[0].training_loss)


def test_predict_shape(psv_dataset):
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=1), ds.schema.num_features)
    scores = trainer.predict(ds.valid.features)
    assert scores.shape == (len(ds.valid), 1)
    assert ((scores >= 0) & (scores <= 1)).all()


# ---- checkpoint / resume (fixes reference backup.py:30 TODO) ----

def test_checkpoint_resume_epoch_accounting(psv_dataset, tmp_path):
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=4)

    with Checkpointer(str(tmp_path / "ckpt"), every_epochs=1) as ckpt:
        t1 = Trainer(mc, ds.schema.num_features, seed=3)
        t1.fit(ds, batch_size=50, epochs=2, checkpointer=ckpt)
        ckpt.wait()
        assert ckpt.latest_epoch() == 1

    # new process simulation: fresh trainer restores and resumes at epoch 2
    with Checkpointer(str(tmp_path / "ckpt")) as ckpt2:
        t2 = Trainer(mc, ds.schema.num_features, seed=99)  # different init
        next_epoch = t2.restore(ckpt2)
        assert next_epoch == 2
        # restored params equal the saved ones, not the fresh init
        np.testing.assert_allclose(
            jax.device_get(t2.state.params["shifu_output_0"]["kernel"]),
            jax.device_get(t1.state.params["shifu_output_0"]["kernel"]),
        )
        assert int(t2.state.step) == int(t1.state.step)
        history = t2.fit(ds, batch_size=50, start_epoch=next_epoch,
                         checkpointer=ckpt2)
        # trains exactly the remaining budget: epochs 2 and 3
        assert [h.current_epoch for h in history] == [2, 3]


def test_checkpoint_every_n(tmp_path, psv_dataset):
    ds = _dataset(psv_dataset)
    with Checkpointer(str(tmp_path / "c2"), every_epochs=2) as ckpt:
        t = Trainer(_mc(epochs=4), ds.schema.num_features)
        t.fit(ds, batch_size=100, checkpointer=ckpt)
        ckpt.wait()
        assert ckpt.latest_epoch() == 3  # epochs 1 and 3 saved (0-indexed)


# ---- mesh-sharded data parallelism (§4 item 3) ----

def test_mesh_dp_training_eight_devices(psv_dataset):
    assert jax.device_count() == 8, "conftest must force 8 cpu devices"
    mesh = make_mesh("data:8")
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=2), ds.schema.num_features, mesh=mesh)
    history = trainer.fit(ds, batch_size=64)  # 64 rows / 8 devices
    assert np.isfinite(history[-1].training_loss)
    assert history[-1].valid_loss <= history[0].valid_loss * 1.5


def test_mesh_dp_matches_single_device(psv_dataset):
    """Sharded and unsharded training produce the same result — sync-DP
    semantic parity (SURVEY.md §7.2 item 3): the all-reduced sharded grad
    equals the full-batch grad."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=1, opt="sgd", lr=0.1)

    t_single = Trainer(mc, ds.schema.num_features, seed=7)
    t_single.fit(ds, batch_size=64)

    mesh = make_mesh("data:8")
    t_mesh = Trainer(mc, ds.schema.num_features, seed=7, mesh=mesh)
    t_mesh.fit(ds, batch_size=64)

    a = jax.device_get(t_single.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_mesh.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_mesh_indivisible_batch_padded(psv_dataset):
    # regression: batch 100 on an 8-device mesh must not crash (review finding)
    ds = _dataset(psv_dataset)
    mesh = make_mesh("data:8")
    trainer = Trainer(_mc(epochs=1), ds.schema.num_features, mesh=mesh)
    assert trainer.align_batch_size(100) == 104
    history = trainer.fit(ds, batch_size=100)
    assert np.isfinite(history[0].training_loss)


def test_checkpoint_cross_mesh_restore(psv_dataset, tmp_path):
    """A checkpoint written by a model-parallel trainer (nn.Partitioned
    boxed embedding table) must restore into a mesh-less trainer and vice
    versa — the chief-export path builds exactly such a mesh-less Trainer.
    The on-disk tree is canonical (unboxed); the restoring template decides
    boxing."""
    mc = _mc(epochs=1, EmbeddingColumnNums=[2, 3], EmbeddingHashSize=64,
             EmbeddingDim=4)
    ds = _dataset(psv_dataset)
    feats = tuple(psv_dataset["feature_cols"])

    sharded = Trainer(mc, len(feats), feature_columns=feats,
                      mesh=make_mesh("data:4,model:2"))
    sharded.fit(ds, epochs=1, batch_size=100)
    with Checkpointer(str(tmp_path / "xmesh")) as ckpt:
        ckpt.save(0, sharded.state)
        ckpt.wait()

        plain = Trainer(mc, len(feats), feature_columns=feats)
        next_epoch = plain.restore(ckpt)
    assert next_epoch == 1
    # predictions agree between the two trainers after restore
    x = ds.valid.features[:32]
    np.testing.assert_allclose(
        plain.predict(x), sharded.predict(x), rtol=1e-5, atol=1e-6
    )

    # and the reverse direction: plain checkpoint into a sharded template
    with Checkpointer(str(tmp_path / "xmesh2")) as ckpt2:
        ckpt2.save(0, plain.state)
        ckpt2.wait()
        sharded2 = Trainer(mc, len(feats), feature_columns=feats,
                           mesh=make_mesh("data:4,model:2"))
        assert sharded2.restore(ckpt2) == 1
    np.testing.assert_allclose(
        sharded2.predict(x), plain.predict(x), rtol=1e-5, atol=1e-6
    )


# ---- chunked-scan epochs (shifu.tpu.scan-steps) ----

def test_scan_epoch_matches_per_step(psv_dataset):
    """scan_steps=K runs the same body in the same order as the per-step
    path — final params and reported epoch losses must match."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=2, opt="adam", lr=0.05)

    t_step = Trainer(mc, ds.schema.num_features, seed=3)
    h_step = t_step.fit(ds, batch_size=64)

    t_scan = Trainer(mc, ds.schema.num_features, seed=3, scan_steps=4)
    h_scan = t_scan.fit(ds, batch_size=64)

    a = jax.device_get(t_step.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_scan.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    for hs, hc in zip(h_step, h_scan):
        assert np.isclose(hs.training_loss, hc.training_loss,
                          rtol=1e-5, atol=1e-6)
        assert hs.global_step == hc.global_step


def test_scan_epoch_tail_padding_counts():
    """A batch count not divisible by K pads the last chunk with no-op
    batches: the reported batch count and global step must count only the
    real batches, and the loss mean must ignore the padding."""
    mc = _mc(epochs=1)
    rng_ = np.random.default_rng(5)
    trainer = Trainer(mc, 6, seed=1, scan_steps=4)
    batches = [
        {
            "x": rng_.normal(size=(32, 6)).astype(np.float32),
            "y": (rng_.random((32, 1)) < 0.4).astype(np.float32),
            "w": np.ones((32, 1), np.float32),
        }
        for _ in range(7)  # 1 full chunk + tail of 3
    ]
    loss, n = trainer.train_epoch(iter(batches))
    assert n == 7
    assert int(jax.device_get(trainer.state.step)) == 7
    assert np.isfinite(loss)

    # parity with the per-step path on the identical batch sequence
    t_ref = Trainer(mc, 6, seed=1)
    loss_ref, n_ref = t_ref.train_epoch(iter(batches))
    assert n_ref == 7
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5, atol=1e-6)
    a = jax.device_get(trainer.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_ref.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ---- the all-padding guard, held by every step builder ----

_GUARD_L2 = 0.01  # l2 gradients are nonzero on an all-padding batch

# every step that holds the guard: (build(apply_fn), stacked), where
# stacked = the batch takes a leading axis of one microbatch / window / step
_GUARDED_STEPS = [
    pytest.param(lambda f: trainer_mod.make_train_step(f, "mse", _GUARD_L2),
                 False, id="per_batch"),
    pytest.param(lambda f: trainer_mod.make_train_step(
        f, "mse", _GUARD_L2, with_grad_norm=True),
                 False, id="per_batch_grad_norm"),
    pytest.param(lambda f: trainer_mod.make_scan_epoch(f, "mse", _GUARD_L2),
                 True, id="scan"),
    pytest.param(lambda f: trainer_mod.make_accum_step(f, "mse", _GUARD_L2),
                 True, id="accumulation"),
    pytest.param(lambda f: sagn.make_sagn_step(
        f, optax.sgd(0.1), loss_name="mse", l2=_GUARD_L2),
                 True, id="sagn"),
    pytest.param(lambda f: trainer_mod.make_host_emb_train_step(
        f, 4, "mse", _GUARD_L2),
                 False, id="host_embedding"),
]


@pytest.mark.parametrize("build,stacked", _GUARDED_STEPS)
def test_all_padding_batch_keeps_every_leaf_and_rows_apply_gradients(
        build, stacked):
    """The guard is one select over the whole state (``apply_if_rows``):
    an all-padding batch leaves EVERY leaf bit-identical (step, optax
    count, mu, nu, parameters) though Adam and the l2 term would move
    them, and reports NaN; a batch with rows gives what
    ``state.apply_gradients`` gives when called directly."""
    trainer = Trainer(_mc(epochs=1), 6, seed=3)
    apply_fn = trainer.model.apply
    step = build(apply_fn)
    rng_ = np.random.default_rng(11)
    x = rng_.normal(size=(32, 6)).astype(np.float32)
    y = (rng_.random((32, 1)) < 0.4).astype(np.float32)

    def run(state, w):
        batch = {"x": x, "y": y, "w": w}
        if stacked:
            batch = {k: v[None] for k, v in batch.items()}
        # the steps donate their state: hand each a copy of its own
        return step(jax.tree_util.tree_map(jnp.copy, state), batch)

    # one real update first, so mu, nu, count and step are all nonzero
    state, _ = run(trainer.state, np.ones((32, 1), np.float32))
    before = jax.device_get(state)
    assert int(before.step) == 1

    kept, aux = run(state, np.zeros((32, 1), np.float32))
    assert np.isnan(np.asarray(aux.pop("loss"))).all()  # the loss(es)
    for extra in aux.values():  # gradient norm / embedding gradients
        assert not np.asarray(extra).any()
    old, new = (jax.tree_util.tree_leaves_with_path(t)
                for t in (before, jax.device_get(kept)))
    assert len(old) == len(new) == 20  # step, count, 6 each of p, mu, nu
    for (path, a), (_, b) in zip(old, new):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), path

    w = (rng_.random((32, 1)) < 0.8).astype(np.float32) * 2.0

    def loss(params):
        pred = apply_fn({"params": params}, x)
        return weighted_mse(pred, y, w) + l2_penalty(params, _GUARD_L2)

    want = jax.device_get(state.apply_gradients(
        grads=jax.grad(loss)(state.params)))
    got, aux = run(state, w)
    assert np.isfinite(np.asarray(aux["loss"])).all()
    assert int(got.step) == int(want.step) == 2
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(jax.device_get(got))):
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=1e-7,
                                   err_msg=str(path))


def test_scan_epoch_fixed_shape_and_timer_rows():
    """A stream with varying batch sizes compiles ONE scan shape (fixed
    from the first chunk) as long as no later batch exceeds it, and the
    step timer is fed each chunk's REAL row count — not a later chunk's
    (the prefetch lookahead runs the producer ahead of the consumer)."""
    mc = _mc(epochs=1)
    rng_ = np.random.default_rng(9)

    def mk(n):
        return {
            "x": rng_.normal(size=(n, 6)).astype(np.float32),
            "y": (rng_.random((n, 1)) < 0.4).astype(np.float32),
            "w": np.ones((n, 1), np.float32),
        }

    trainer = Trainer(mc, 6, seed=1, scan_steps=2)
    rows_seen = []

    class _Timer:
        def step(self, loss, rows):
            rows_seen.append(rows)

    trainer.step_timer = _Timer()
    # first chunk fixes rows=32; later smaller batches pad into it
    batches = [mk(32), mk(32), mk(20), mk(8), mk(16)]
    loss, n = trainer.train_epoch(iter(batches))
    assert n == 5
    assert rows_seen == [64, 28, 16]  # real rows per chunk, in order
    assert np.isfinite(loss)
    sizes = trainer._path.step._cache_size()
    assert sizes == 1, f"expected one compiled scan shape, got {sizes}"
    # a LARGER later batch regrows once — exactly one extra compile
    loss2, n2 = trainer.train_epoch(iter([mk(48), mk(32)]))
    assert n2 == 2
    assert trainer._path.step._cache_size() == 2


# ---- gradient accumulation (shifu.tpu.accum-steps) ----

def test_accum_step_equals_big_batch_step():
    """accum_steps=A over A microbatches must produce the SAME update as
    one step on the concatenated batch — including the SUM_BY_NONZERO
    normalization, the tail group (zero-weight pad micros), and the
    l2 term applied once per update."""
    mc = _mc(epochs=1, L2Reg=0.01)
    rng_ = np.random.default_rng(11)

    def mk(n):
        return {
            "x": rng_.normal(size=(n, 6)).astype(np.float32),
            "y": (rng_.random((n, 1)) < 0.4).astype(np.float32),
            "w": (rng_.random((n, 1)) < 0.9).astype(np.float32),  # some 0s
        }

    micros = [mk(32) for _ in range(6)]  # A=4: one full group + tail of 2

    t_acc = Trainer(mc, 6, seed=2, accum_steps=4)
    loss_acc, n = t_acc.train_epoch(iter(micros))
    assert n == 6
    # one update per group: 2 updates
    assert int(jax.device_get(t_acc.state.step)) == 2

    # reference: per-step trainer fed the CONCATENATED groups
    def cat(bs):
        return {k: np.concatenate([b[k] for b in bs]) for k in bs[0]}

    t_big = Trainer(mc, 6, seed=2)
    loss_big, n_big = t_big.train_epoch(
        iter([cat(micros[:4]), cat(micros[4:])])
    )
    assert n_big == 2
    a = jax.device_get(t_acc.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_big.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(loss_acc, loss_big, rtol=1e-5, atol=1e-6)


def test_accum_on_mesh_matches_single_device():
    """The stacked chunk shards the batch dim over the data axis; mesh
    accumulation equals single-device accumulation."""
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh

    mc = _mc(epochs=1, opt="sgd", lr=0.1)
    rng_ = np.random.default_rng(13)

    def mk(n):
        return {
            "x": rng_.normal(size=(n, 6)).astype(np.float32),
            "y": (rng_.random((n, 1)) < 0.4).astype(np.float32),
            "w": np.ones((n, 1), np.float32),
        }

    micros = [mk(64) for _ in range(4)]
    t_mesh = Trainer(mc, 6, seed=5, accum_steps=2, mesh=make_mesh("data:-1"))
    t_mesh.train_epoch(iter(micros))
    t_one = Trainer(mc, 6, seed=5, accum_steps=2)
    t_one.train_epoch(iter(micros))
    a = jax.device_get(t_mesh.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_one.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-6)


def test_accum_and_scan_are_mutually_exclusive():
    with pytest.raises(ValueError, match="mutually exclusive"):
        Trainer(_mc(epochs=1), 6, scan_steps=4, accum_steps=4)


def test_accum_rejects_update_window():
    """Both knobs define gradient accumulation; composing them would wrap
    each accumulated group's apply in a SECOND MultiSteps window — nested
    semantics nobody configured."""
    with pytest.raises(ValueError, match="UpdateWindow"):
        Trainer(_mc(epochs=1, UpdateWindow=4), 6, accum_steps=8)


def test_keep_best_ignores_unreadable_snapshot(tmp_path):
    """A truncated/corrupt keep-best.npz degrades to 'no best yet' with a
    warning — it must never brick resume or the fleet export."""
    d = str(tmp_path)
    (tmp_path / "keep-best.npz").write_bytes(b"not a zip at all")
    t = Trainer(_mc(epochs=1), 6, keep_best="ks")
    with pytest.warns(UserWarning, match="unreadable keep-best"):
        t._restore_best(d)
    assert t.best_params is None
    # absent file: silently none, no warning
    t2 = Trainer(_mc(epochs=1), 6, keep_best="ks")
    t2._restore_best(str(tmp_path / "nowhere"))
    assert t2.best_params is None


def test_keep_best_skips_empty_validation_epochs():
    """ks=0 with NaN valid loss means NO scored rows — absence of a
    measurement must not crown the first epoch as 'best', and the fit
    loop warns once."""
    from shifu_tensorflow_tpu.train.trainer import EpochStats

    t = Trainer(_mc(epochs=1), 6, keep_best="ks")
    empty = EpochStats(0, 0, 0.2, float("nan"), 1.0, 0.1, 1, ks=0.0)
    t._maybe_snapshot_best(empty)
    assert t.best_params is None  # not crowned
    with pytest.warns(UserWarning, match="no scored rows"):
        t._warn_if_validation_empty(empty, None)
    # real 0-KS epochs (with a real loss) still participate
    real = EpochStats(0, 1, 0.2, 0.4, 1.0, 0.1, 2, ks=0.0)
    t._maybe_snapshot_best(real)
    assert t.best_params is not None


def test_sagn_rejects_accum_steps():
    from shifu_tensorflow_tpu.train import make_trainer

    sagn_mc = _mc(epochs=1, Algorithm="sagn")
    with pytest.raises(ValueError, match="accum-steps"):
        make_trainer(sagn_mc, 6, accum_steps=4)


def test_sagn_rejects_lr_schedule():
    """A schedule would apply only to SAGN's global apply while the local
    window steps kept the flat LR — reject the half-applied semantics."""
    from shifu_tensorflow_tpu.train import make_trainer

    with pytest.raises(ValueError, match="LearningRateSchedule"):
        make_trainer(
            _mc(epochs=1, Algorithm="sagn",
                LearningRateSchedule="cosine", DecaySteps=10), 6
        )
    with pytest.raises(ValueError, match="LearningRateSchedule"):
        make_trainer(_mc(epochs=1, Algorithm="sagn", WarmupSteps=5), 6)


# ---- learning-rate schedules (LearningRateSchedule/WarmupSteps/...) ----

def test_make_schedule_shapes_and_errors():
    import pytest

    from shifu_tensorflow_tpu.train.optimizers import make_schedule

    # constant stays a bare float
    assert make_schedule(_mc().params) == 0.05

    cos = make_schedule(_mc(LearningRateSchedule="cosine", DecaySteps=100,
                            DecayRate=0.1, lr=0.2).params)
    np.testing.assert_allclose(float(cos(0)), 0.2, rtol=1e-6)
    np.testing.assert_allclose(float(cos(100)), 0.02, rtol=1e-5)  # alpha*lr

    exp = make_schedule(_mc(LearningRateSchedule="exponential",
                            DecaySteps=10, DecayRate=0.5, lr=0.2).params)
    np.testing.assert_allclose(float(exp(0)), 0.2, rtol=1e-6)
    np.testing.assert_allclose(float(exp(10)), 0.1, rtol=1e-5)
    np.testing.assert_allclose(float(exp(20)), 0.05, rtol=1e-5)

    warm = make_schedule(_mc(LearningRateSchedule="cosine", DecaySteps=100,
                             WarmupSteps=10, lr=0.2).params)
    np.testing.assert_allclose(float(warm(0)), 0.0, atol=1e-9)
    np.testing.assert_allclose(float(warm(10)), 0.2, rtol=1e-5)  # peak
    assert float(warm(110)) < 0.021  # decayed past warmup

    with pytest.raises(ValueError, match="DecaySteps"):
        make_schedule(_mc(LearningRateSchedule="cosine").params)
    with pytest.raises(ValueError, match="unknown LearningRateSchedule"):
        make_schedule(_mc(LearningRateSchedule="triangular",
                          DecaySteps=5).params)


def test_lr_schedule_trains_and_decays():
    """A scheduled trainer runs, and the schedule actually bites: with an
    aggressive exponential decay the post-warmup updates shrink (compare
    param movement per epoch against a constant-LR twin)."""
    mc_sched = _mc(epochs=1, opt="sgd", lr=0.5,
                   LearningRateSchedule="exponential", DecaySteps=1,
                   DecayRate=0.01)
    mc_const = _mc(epochs=1, opt="sgd", lr=0.5)
    rng_ = np.random.default_rng(3)
    batches = [
        {
            "x": rng_.normal(size=(64, 6)).astype(np.float32),
            "y": (rng_.random((64, 1)) < 0.4).astype(np.float32),
            "w": np.ones((64, 1), np.float32),
        }
        for _ in range(8)
    ]
    t_s = Trainer(mc_sched, 6, seed=1)
    t_c = Trainer(mc_const, 6, seed=1)
    k0 = jax.device_get(t_s.state.params["shifu_output_0"]["kernel"]).copy()
    t_s.train_epoch(iter(batches))
    t_c.train_epoch(iter(batches))
    moved_s = np.abs(
        jax.device_get(t_s.state.params["shifu_output_0"]["kernel"]) - k0
    ).sum()
    moved_c = np.abs(
        jax.device_get(t_c.state.params["shifu_output_0"]["kernel"]) - k0
    ).sum()
    # decay 0.01/step collapses the LR after step 1; constant keeps moving
    assert moved_s < moved_c * 0.6, (moved_s, moved_c)


# ---- keep-best (shifu.tpu.keep-best) ----

def test_keep_best_snapshots_and_export_serves_it(tmp_path):
    """The best-validation epoch's params are snapshotted and the export
    serves THEM — scores must match the snapshot, not the (worse) final
    params."""
    import pytest

    from shifu_tensorflow_tpu.export.eval_model import EvalModel
    from shifu_tensorflow_tpu.export.saved_model import export_model
    from shifu_tensorflow_tpu.train.trainer import EpochStats

    with pytest.raises(ValueError, match="keep_best"):
        Trainer(_mc(), 6, keep_best="auc")

    t = Trainer(_mc(epochs=1), 6, seed=2, keep_best="valid_loss")

    def stats(epoch, valid_loss):
        return EpochStats(0, epoch, 0.2, valid_loss, 1.0, 0.1, epoch)

    t._maybe_snapshot_best(stats(0, 0.5))
    assert t.best_epoch == 0 and t.best_metric == 0.5
    best_kernel = t.best_params["shifu_output_0"]["kernel"].copy()
    # make the live params drift (simulates further, worse epochs)
    t.state = t.state.replace(
        params=jax.tree_util.tree_map(lambda p: p + 1.0, t.state.params)
    )
    t._maybe_snapshot_best(stats(1, 0.7))  # worse: no new snapshot
    assert t.best_epoch == 0
    np.testing.assert_array_equal(
        t.best_params["shifu_output_0"]["kernel"], best_kernel
    )
    t._maybe_snapshot_best(stats(2, float("nan")))  # NaN never wins
    assert t.best_epoch == 0

    export_dir = str(tmp_path / "best-model")
    export_model(export_dir, t)
    x = np.random.default_rng(0).random((16, 6)).astype(np.float32)
    want = t.model.apply({"params": t.best_params}, x)
    with EvalModel(export_dir, backend="native") as em:
        np.testing.assert_allclose(em.compute_batch(x), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    # and NOT the drifted live params
    live = np.asarray(t.model.apply({"params": t.state.params}, x))
    assert not np.allclose(np.asarray(want), live)


def test_keep_best_survives_resume(psv_dataset, tmp_path):
    """The best snapshot persists beside the checkpoints: a resumed run
    competes against the TRUE best, not best-since-resume — otherwise the
    export after a crash+resume silently serves a worse model."""
    from shifu_tensorflow_tpu.train.trainer import EpochStats

    ds = _dataset(psv_dataset)
    ckpt_dir = str(tmp_path / "ckpt")
    t1 = Trainer(_mc(epochs=2), ds.schema.num_features, seed=1,
                 keep_best="valid_loss")
    ck = Checkpointer(ckpt_dir)
    t1.fit(ds, batch_size=100, checkpointer=ck)
    assert t1.best_params is not None
    # simulate a much better epoch than a resumed run will ever see
    t1.best_metric = 1e-9
    t1.best_epoch = 1
    t1._persist_best(ck.directory)
    ck.close()

    t2 = Trainer(_mc(epochs=4), ds.schema.num_features, seed=1,
                 keep_best="valid_loss")
    ck2 = Checkpointer(ckpt_dir)
    start = t2.restore(ck2)
    assert start == 2
    assert t2.best_metric == 1e-9 and t2.best_epoch == 1  # true best kept
    np.testing.assert_array_equal(
        t2.best_params["shifu_output_0"]["kernel"],
        t1.best_params["shifu_output_0"]["kernel"],
    )
    # further epochs cannot beat 1e-9: the persisted best stays exported
    t2.fit(ds, batch_size=100, checkpointer=ck2, start_epoch=start)
    assert t2.best_epoch == 1
    ck2.close()
    # a DIFFERENT metric ignores the stale snapshot instead of comparing
    # apples to oranges
    t3 = Trainer(_mc(epochs=4), ds.schema.num_features, seed=1,
                 keep_best="ks")
    t3._restore_best(ckpt_dir)
    assert t3.best_params is None


def test_keep_best_ks_tracks_improvements(psv_dataset):
    """End-to-end fit with keep_best='ks': the snapshot tracks the best-KS
    epoch seen in history."""
    ds = _dataset(psv_dataset)
    t = Trainer(_mc(epochs=4), ds.schema.num_features, seed=1,
                keep_best="ks")
    hist = t.fit(ds, batch_size=100)
    assert t.best_params is not None
    best = max(hist, key=lambda h: h.ks)
    assert t.best_epoch == best.current_epoch
    assert t.best_metric == pytest.approx(best.ks)


# ---- early stopping (shifu.tpu.early-stop-ks / early-stop-patience) ----

def test_early_stop_on_target_ks(psv_dataset):
    """Once validation KS reaches the target the fit loop stops, records
    the reason, and history is shorter than the epoch budget."""
    from shifu_tensorflow_tpu.train.trainer import EarlyStopper

    ds = _dataset(psv_dataset)
    t = Trainer(_mc(epochs=50), ds.schema.num_features, seed=1)
    hist = t.fit(ds, batch_size=100,
                 early_stop=EarlyStopper(target_ks=0.2))
    assert len(hist) < 50
    assert t.stop_reason and "reached target" in t.stop_reason
    assert hist[-1].ks >= 0.2


def test_early_stop_patience_counts_only_real_valid_epochs():
    """NaN validation loss (no validation data) must not feed patience —
    and with real validation, patience stops after N bad epochs."""
    from shifu_tensorflow_tpu.train.trainer import EarlyStopper
    from shifu_tensorflow_tpu.train.trainer import EpochStats

    def stats(epoch, valid_loss, ks=0.0):
        return EpochStats(0, epoch, 0.1, valid_loss, 0.0, 0.0, epoch, ks)

    es = EarlyStopper(patience=2)
    assert es.should_stop(stats(0, float("nan"))) is None
    assert es.should_stop(stats(1, float("nan"))) is None  # NaN never counts
    assert es.should_stop(stats(2, 0.5)) is None   # first real: improves inf
    assert es.should_stop(stats(3, 0.6)) is None   # bad 1
    reason = es.should_stop(stats(4, 0.55))        # bad 2 -> stop
    assert reason and "improvement" in reason
    # improvement resets the counter
    es2 = EarlyStopper(patience=2)
    assert es2.should_stop(stats(0, 0.5)) is None
    assert es2.should_stop(stats(1, 0.6)) is None  # bad 1
    assert es2.should_stop(stats(2, 0.4)) is None  # improves -> reset
    assert es2.should_stop(stats(3, 0.5)) is None  # bad 1
    assert es2.should_stop(stats(4, 0.5)) is not None  # bad 2 -> stop


def test_scan_epoch_on_mesh_matches_per_step(psv_dataset):
    """Stacked chunks shard the batch dim over the data axis; mesh-sharded
    scan training equals mesh-sharded per-step training."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=1, opt="sgd", lr=0.1)

    t_step = Trainer(mc, ds.schema.num_features, seed=7,
                     mesh=make_mesh("data:8"))
    t_step.fit(ds, batch_size=64)

    t_scan = Trainer(mc, ds.schema.num_features, seed=7,
                     mesh=make_mesh("data:8"), scan_steps=3)
    t_scan.fit(ds, batch_size=64)

    a = jax.device_get(t_step.state.params["shifu_output_0"]["kernel"])
    b = jax.device_get(t_scan.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_scan_epoch_indivisible_and_ragged_batches(psv_dataset):
    """Review regression: the scan path must accept exactly what the
    per-step path accepts — batch sizes that don't divide the data axis
    (padded via align_batch_size, like _pad_for_mesh) and a short final
    batch (padded to the chunk's row count)."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=1)

    # 100-row batches on an 8-device mesh, scan chunks of 3
    mesh = make_mesh("data:8")
    t = Trainer(mc, ds.schema.num_features, mesh=mesh, scan_steps=3)
    history = t.fit(ds, batch_size=100)
    assert np.isfinite(history[0].training_loss)

    # ragged iterator: mixed 32/20-row batches, no mesh
    rng_ = np.random.default_rng(9)

    def mk(n):
        return {
            "x": rng_.normal(size=(n, ds.schema.num_features)).astype(np.float32),
            "y": (rng_.random((n, 1)) < 0.4).astype(np.float32),
            "w": np.ones((n, 1), np.float32),
        }

    t2 = Trainer(mc, ds.schema.num_features, scan_steps=4)
    loss, n = t2.train_epoch(iter([mk(32), mk(32), mk(20), mk(32), mk(8)]))
    assert n == 5 and np.isfinite(loss)
    assert int(jax.device_get(t2.state.step)) == 5


# ---- device-resident fit (--device-resident / shifu.tpu.device-resident) ----

def test_device_resident_fit_learns(psv_dataset):
    """Whole-dataset-in-HBM epochs: converges on the synthetic set, counts
    steps correctly (ceil(n/B) per epoch), reports KS/AUC."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=4)
    trainer = Trainer(mc, ds.schema.num_features, seed=2)
    history = trainer.fit_device_resident(ds, batch_size=64)
    assert len(history) == 4
    assert history[-1].valid_loss < history[0].valid_loss
    assert history[-1].ks > 0.3
    steps_per_epoch = -(-len(ds.train) // 64)
    assert history[-1].global_step == 4 * steps_per_epoch


def test_device_resident_fit_deterministic(psv_dataset):
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=2)
    a = Trainer(mc, ds.schema.num_features, seed=11)
    a.fit_device_resident(ds, batch_size=64)
    b = Trainer(mc, ds.schema.num_features, seed=11)
    b.fit_device_resident(ds, batch_size=64)
    ka = jax.device_get(a.state.params["shifu_output_0"]["kernel"])
    kb = jax.device_get(b.state.params["shifu_output_0"]["kernel"])
    np.testing.assert_array_equal(ka, kb)


def test_device_resident_fit_on_mesh(psv_dataset):
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=2)
    trainer = Trainer(mc, ds.schema.num_features, seed=2,
                      mesh=make_mesh("data:8"))
    history = trainer.fit_device_resident(ds, batch_size=64)
    assert np.isfinite(history[-1].training_loss)
    assert history[-1].ks > 0.2


def test_device_resident_checkpoint_interop(psv_dataset, tmp_path):
    """Checkpoints written by the device-resident path restore into the
    per-step path and vice versa — one on-disk contract."""
    ds = _dataset(psv_dataset)
    mc = _mc(epochs=2)
    t1 = Trainer(mc, ds.schema.num_features, seed=4)
    with Checkpointer(str(tmp_path / "dr")) as ckpt:
        t1.fit_device_resident(ds, batch_size=64, checkpointer=ckpt)
        ckpt.wait()
        t2 = Trainer(mc, ds.schema.num_features, seed=99)
        restored, nxt = ckpt.restore_latest(t2.state)
    assert nxt == 2
    ka = jax.device_get(t1.state.params["shifu_output_0"]["kernel"])
    kb = jax.device_get(restored.params["shifu_output_0"]["kernel"])
    np.testing.assert_allclose(ka, kb, rtol=1e-6)


def test_device_resident_rejects_cross_process(psv_dataset):
    from shifu_tensorflow_tpu.parallel.distributed import ProcessTopology

    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=1), ds.schema.num_features,
                      mesh=make_mesh("data:8"),
                      topology=ProcessTopology(num_processes=1, process_id=0))
    with pytest.raises(ValueError, match="single-controller"):
        trainer.fit_device_resident(ds, batch_size=64)


def test_device_resident_rejects_sagn(psv_dataset):
    from shifu_tensorflow_tpu.train import make_trainer

    ds = _dataset(psv_dataset)
    mc = _mc(epochs=1, Algorithm="sagn")
    trainer = make_trainer(mc, ds.schema.num_features)
    with pytest.raises(NotImplementedError, match="SAGN"):
        trainer.fit_device_resident(ds, batch_size=64)


def test_device_resident_multi_task_eval(psv_dataset):
    """Regression: multi-output heads (C>1) must score head 0 for KS/AUC,
    not a flattened (rows*C) vector."""
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=2, ModelType="multi_task", NumTasks=3),
                      ds.schema.num_features, seed=2)
    history = trainer.fit_device_resident(ds, batch_size=64)
    assert np.isfinite(history[-1].valid_loss)
    assert 0.0 <= history[-1].auc <= 1.0


def test_scan_epoch_composes_with_shard_stream(psv_dataset):
    """--stream + --scan-steps: chunked-scan over a deterministic 1-reader
    ShardStream must equal the per-step stream run exactly."""
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    schema = RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )
    mc = _mc(epochs=2)

    def run(scan_steps):
        tr = Trainer(mc, schema.num_features, seed=6, scan_steps=scan_steps)
        tr.fit_stream(
            lambda epoch: ShardStream(
                psv_dataset["paths"], schema, 64,
                valid_rate=0.2, emit="train", n_readers=1,
            ),
            epochs=2,
        )
        return jax.device_get(tr.state.params["shifu_output_0"]["kernel"])

    np.testing.assert_allclose(run(1), run(3), rtol=2e-5, atol=2e-6)


def test_device_resident_bf16(psv_dataset):
    """--device-resident composes with --dtype bfloat16 (fp32 host data
    cast on device; loss finite, metrics sane)."""
    ds = _dataset(psv_dataset)
    trainer = Trainer(_mc(epochs=2), ds.schema.num_features, seed=2,
                      dtype=jnp.bfloat16)
    history = trainer.fit_device_resident(ds, batch_size=64)
    assert np.isfinite(history[-1].training_loss)
    assert 0.0 <= history[-1].auc <= 1.0


# ---- compact bf16 transport, fp32 compute ----

def test_bf16_transport_widens_on_device_fp32_compute():
    """The streaming default ships bf16 features to an fp32 model; the
    jitted step widens on device (_widen_features), so params stay fp32
    and the loss trajectory tracks the fp32-transport run to bf16 input
    quantization error (r04 verdict item 3: transport is KS-neutral)."""
    import ml_dtypes

    rng = np.random.default_rng(7)
    n, f = 512, 6
    x32 = rng.normal(size=(n, f)).astype(np.float32)
    y = (rng.random((n, 1)) < 0.4).astype(np.float32)
    w = np.ones((n, 1), np.float32)
    x16 = x32.astype(ml_dtypes.bfloat16)

    def run(x):
        tr = Trainer(_mc(epochs=1), f, seed=3)
        losses = []
        for i in range(0, n, 128):
            sl = slice(i, i + 128)
            batch = tr._put({"x": x[sl], "y": y[sl], "w": w[sl]})
            tr.state, aux = tr._train_step(tr.state, batch)
            losses.append(float(aux["loss"]))
        return tr, losses

    tr32, l32 = run(x32)
    tr16, l16 = run(x16)
    # params computed fp32 in both runs
    leaves = jax.tree_util.tree_leaves(tr16.state.params)
    assert all(l.dtype == jnp.float32 for l in leaves)
    # bf16 transport tracks fp32 transport closely (input quantization
    # is ~0.4% relative; trajectories stay within a small tolerance)
    np.testing.assert_allclose(l16, l32, rtol=0.05, atol=5e-3)
    # eval path widens too
    ev16 = tr16._eval_step(
        tr16.state.params,
        tr16._put({"x": x16[:128], "y": y[:128], "w": w[:128]}))
    assert np.isfinite(float(ev16[0]))


def test_bf16_transport_ks_parity_streaming(psv_dataset):
    """KS-parity gate for the compact-transport default: streaming the
    demo set with bf16 features yields the same validation KS/AUC as fp32
    transport to within noise (r04 verdict item 3 done-criterion)."""
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.data.reader import RecordSchema

    schema = RecordSchema(
        feature_columns=tuple(psv_dataset["feature_cols"]),
        target_column=psv_dataset["target_col"],
        weight_column=psv_dataset["weight_col"],
    )

    def run(feature_dtype):
        tr = Trainer(_mc(epochs=3), schema.num_features, seed=4)
        history = tr.fit_stream(
            lambda epoch: ShardStream(
                psv_dataset["paths"], schema, 64, valid_rate=0.2,
                emit="train", n_readers=1, feature_dtype=feature_dtype,
            ),
            (lambda: ShardStream(
                psv_dataset["paths"], schema, 64, valid_rate=0.2,
                emit="valid", n_readers=1, feature_dtype=feature_dtype,
            )),
            epochs=3,
        )
        return history[-1]

    f32 = run("float32")
    b16 = run("bfloat16")
    assert np.isfinite(b16.ks) and np.isfinite(b16.auc)
    assert abs(b16.ks - f32.ks) < 0.05
    assert abs(b16.auc - f32.auc) < 0.03


def test_npz_checkpoint_arrays_do_not_alias_device_buffers(tmp_path):
    """CPU-backend device_get is zero-copy: without an explicit copy the
    async checkpoint writer would stream a VIEW of the live XLA buffer
    that the next donated train step may reuse mid-write.  The saved
    bytes must be a stable snapshot: mutate the state with donated steps
    after an async save; the restored checkpoint equals the pre-step
    snapshot."""
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer
    from shifu_tensorflow_tpu.train.trainer import make_train_step

    tr = Trainer(_mc(epochs=1), 6, seed=11)
    rng = np.random.default_rng(0)
    batch = tr._put({
        "x": rng.normal(size=(64, 6)).astype(np.float32),
        "y": (rng.random((64, 1)) < 0.4).astype(np.float32),
        "w": np.ones((64, 1), np.float32),
    })
    snapshot = jax.tree_util.tree_map(
        lambda l: np.array(l, copy=True), jax.device_get(tr.state.params))
    step = make_train_step(tr.model.apply)
    with NpzCheckpointer(str(tmp_path), async_save=True) as ck:
        ck.save(0, tr.state)
        # donated steps churn the buffers while the write may be in flight
        for _ in range(10):
            tr.state, _ = step(tr.state, batch)
        ck.wait()
        restored, _next = ck.restore_latest(tr.state)
    got = jax.device_get(restored.params)
    for path in (("trunk", "hidden_layer0", "kernel"),
                 ("shifu_output_0", "kernel")):
        want = snapshot
        have = got
        for k in path:
            want, have = want[k], have[k]
        np.testing.assert_array_equal(np.asarray(have), want)
