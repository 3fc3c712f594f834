"""The hybrid decoder family (``ModelType: hybrid_lm``) at a small size on
the CPU: each mixer and the whole model against the plain reference
(``benchmark/reference/hybrid_lm.py``), the chunked scan against the
time-step recurrence, the grouped product, the share of an expert-parallel
deployment, the train CLI, the refusals, and the wrong models the
benchmark's comparison must fail."""

import dataclasses
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.planes import train_lm_stream
from benchmark.reference import hybrid_lm as ref
from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    UnsupportedModelType,
)
from shifu_tensorflow_tpu.models.factory import build_model, family_loss
from shifu_tensorflow_tpu.ops import grouped, ssm_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 4,
    "hybrid_override_pattern": "ME*E", "layer_norm_epsilon": 1e-5,
    "vocab_size": 256, "mamba_num_heads": 4, "mamba_head_dim": 16,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "n_routed_experts": 8, "experts_held": [0, 8], "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3_nano_ep16.json")) as _f:
    SHIPPED_CHECK = json.load(_f)["check"]
#: the limits of ``tests/benchmark``'s tiny copy of the cell: what exact
#: float32 products (the CPU's) leave between program and reference.  The
#: shipped limits sit above what ONE bf16 pass moves on the chip (PERF.md
#: section 2), so at this size on the CPU only these can tell a bf16 step
CPU_CHECK = dict(SHIPPED_CHECK, loss_rtol=1e-4, stated_loss_rtol=1e-4,
                 update_rtol=0.05, small_leaf_update_rtol=0.05,
                 pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                 pooled_grad_rtol=0.01)


def params_for(**over):
    p = dict(PARAMS, **over)
    p["num_hidden_layers"] = len(p["hybrid_override_pattern"])
    return p


def model_of(p, dtype=jnp.float32):
    return build_model(ModelConfig.from_json({"train": {"params": p}}),
                       dtype=dtype)


def batch_of(seed=0, rows=2, seq=SEQ, weights=None):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, seq))
    w = np.ones((rows, 1), np.float32) if weights is None else np.asarray(
        weights, np.float32).reshape(rows, 1)
    return {"x": ids.astype(np.float32), "y": np.zeros((rows, 1), np.float32),
            "w": w}


def init(model, seq=SEQ, seed=0):
    return jax.jit(model.init)(jax.random.key(seed),
                               jnp.zeros((1, seq)))["params"]


def system_loss_and_grads(p, batch, dtype=jnp.float32, seed=0):
    model = model_of(p, dtype)
    params = init(model, batch["x"].shape[1], seed)
    loss_of = family_loss(model)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q: loss_of(q, batch)[0]))(params)
    return params, float(loss), grads


def rel(a, b):
    den = float(jnp.linalg.norm(b))
    off = float(jnp.linalg.norm(a - b))
    return off / den if den else off


# ---- the model against the reference

LEAVES = {}


def _both(pattern):
    if pattern not in LEAVES:
        p = params_for(hybrid_override_pattern=pattern,
                       experts_held=[2, 4])
        batch = batch_of(seed=3, seq=40)  # the chunk does not divide 40
        params, loss, grads = system_loss_and_grads(p, batch)
        ref_loss, ref_grads = ref.make_loss(p, "highest", with_grad=True)(
            params, batch)
        LEAVES[pattern] = (loss, float(ref_loss), grads, ref_grads)
    return LEAVES[pattern]


@pytest.mark.parametrize("pattern", ["M", "E", "*", "ME*E"])
def test_loss_and_every_gradient_leaf_equal_the_references(pattern):
    loss, ref_loss, grads, ref_grads = _both(pattern)
    assert loss == pytest.approx(ref_loss, rel=2e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) >= 4
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 2e-5, (jax.tree_util.keystr(path), rel(g, r))


def test_padding_rows_join_neither_sum():
    p = params_for(hybrid_override_pattern="E*")
    model = model_of(p)
    params = init(model)
    loss_of = jax.jit(lambda b: family_loss(model)(params, b)[0])
    batch = batch_of(seed=4, rows=3, weights=[1, 0, 1])
    live = {k: v[[0, 2]] for k, v in batch.items()}
    assert float(loss_of(batch)) == pytest.approx(float(loss_of(live)),
                                                  rel=1e-6)
    other = dict(batch, x=batch["x"].copy())
    other["x"][1] = 7.0  # what a padding row holds changes nothing
    assert float(loss_of(other)) == float(loss_of(batch))
    assert float(ref.loss(params, batch, p)) == pytest.approx(
        float(loss_of(batch)), rel=2e-6)


def test_logits_are_the_references_and_the_step_counts_held_pairs():
    p = params_for(experts_held=[0, 4])
    model = model_of(p)
    params, batch = init(model), batch_of(seed=5)
    logits = model.apply({"params": params}, batch["x"])
    h = ref.hidden_states(params, ref.token_ids(batch["x"]), p)
    np.testing.assert_allclose(logits, h @ params["lm_head"]["kernel"],
                               atol=2e-5)
    _, per_row, counters = family_loss(model)(params, batch)
    assert per_row.shape == (2, 1)
    # two expert layers, every token chooses 2 of 8, half of them held
    pairs = int(counters["moe_held_pairs"])
    assert 0 < pairs < 2 * 2 * SEQ * 2
    assert pairs / 8 <= int(counters["moe_held_max"]) <= 2 * SEQ


# ---- the chunked scan

def stepwise(x, dt, a, b, c):
    """The recurrence one time step at a time (the reference's), with the
    groups' B and C repeated over their heads."""
    r = x.shape[2] // b.shape[2]
    return ref.ssm_recurrence(x, dt, a, jnp.repeat(b, r, axis=2),
                              jnp.repeat(c, r, axis=2))


def _scan_inputs(s, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    b, h, p, g, n = 2, 4, 8, 2, 16
    return (jax.random.normal(k[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, h)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, s, g, n)),
            jax.random.normal(k[4], (b, s, g, n)))


@pytest.mark.parametrize("chunk", [8, 16, 48, 7, 13, 64])
def test_chunked_scan_equals_the_recurrence(chunk):
    """S = 48: 8, 16 and 48 divide it; 7, 13 and 64 do not."""
    args = _scan_inputs(48)
    want = jax.jit(stepwise)(*args)
    got = jax.jit(ssm_scan.ssm_scan_chunked, static_argnums=5)(*args, chunk)
    assert got.shape == want.shape
    assert rel(got, want) < 5e-6


@pytest.mark.parametrize("chunk", [16, 13])
def test_chunked_scans_backward_pass_equals_the_recurrences(chunk):
    args = _scan_inputs(48, seed=1)
    weight = jax.random.normal(jax.random.key(9), (2, 48, 4, 8))

    def total(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a) * weight),
                                argnums=(0, 1, 2, 3, 4)))(*args)

    want = total(stepwise)
    got = total(lambda *a: ssm_scan.ssm_scan_chunked(*a, chunk))
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g))) and rel(g, w) < 2e-5


def test_the_oracle_is_the_written_out_recurrence():
    """S = 70 (the reference's time block pads): H_t = exp(dt_t a) H_{t-1}
    + dt_t x_t (x) B_t, y_t = H_t C_t, in numpy, one head."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in _scan_inputs(70, 2))
    got = np.asarray(stepwise(*_scan_inputs(70, 2)))
    state, want = np.zeros((8, 16)), []
    for t in range(70):  # row 1, head 3 (group 1)
        state = np.exp(dt[1, t, 3] * a[3]) * state + dt[1, t, 3] * np.outer(
            x[1, t, 3], b[1, t, 1])
        want.append(state @ c[1, t, 1])
    np.testing.assert_allclose(got[1, :, 3], np.asarray(want), atol=1e-5)


# ---- the grouped product

def _dense_experts(h, up, down, ids, weights, first, held):
    out = jnp.zeros_like(h)
    for e in range(held):
        gate = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        out = out + gate[:, None] * (
            jnp.square(jnp.maximum(h @ up[e], 0)) @ down[e])
    return out


def _grouped(h, up, down, ids, weights, first, held, tile):
    pair, tile_expert, n_tiles, counts = grouped.plan_tiles(
        ids, first, held, tile)
    k = ids.shape[1]
    token = jnp.where(pair < ids.size, pair // k, h.shape[0])
    gate = jnp.where(pair < ids.size,
                     jnp.take(weights.reshape(-1), pair, mode="clip"), 0.0)
    return grouped.expert_mlp(h, up, down, token, gate, tile_expert, n_tiles,
                              tile), counts, n_tiles


@pytest.mark.parametrize("case", ["an expert with no token", "all absent",
                                  "every pair held", "uneven"])
@pytest.mark.parametrize("tile", [8, 32])
def test_grouped_product_equals_the_dense_loop(case, tile):
    t, d, f, k, n, first, held = 40, 16, 24, 2, 8, 2, 4
    ks = jax.random.split(jax.random.key(7), 5)
    h = jax.random.normal(ks[0], (t, d))
    up = jax.random.normal(ks[1], (held, d, f)) * 0.3
    down = jax.random.normal(ks[2], (held, f, d)) * 0.3
    weights = jax.random.uniform(ks[3], (t, k)) + 0.1
    rng = np.random.default_rng(1)
    if case == "an expert with no token":  # local expert 1 (id 3) unused
        pool = [0, 1, 2, 4, 5, 6, 7]
    elif case == "all absent":
        pool = [0, 1, 6, 7]
    elif case == "every pair held":
        pool = [2, 3, 4, 5]
    else:
        pool = [2, 2, 2, 2, 2, 3, 7]
    ids = jnp.asarray(np.stack([rng.choice(sorted(set(pool)), 2, False)
                                if len(set(pool)) > 1 else [pool[0], 0]
                                for _ in range(t)]), jnp.int32)
    out, counts, n_tiles = jax.jit(_grouped, static_argnums=(5, 6, 7))(
        h, up, down, ids, weights, first, held, tile)
    want = _dense_experts(h, up, down, ids, weights, first, held)
    np.testing.assert_allclose(out, want, atol=1e-5)
    expect = [int(jnp.sum(ids == first + e)) for e in range(held)]
    assert counts.tolist() == expect
    assert int(n_tiles) == sum(-(-c // tile) for c in expect)
    if case == "an expert with no token":
        assert expect[1] == 0
    if case == "all absent":
        assert int(n_tiles) == 0 and not bool(jnp.any(out))

    def total(fn):
        return jax.jit(jax.grad(
            lambda h, u, dn, w: jnp.sum(jnp.sin(fn(h, u, dn, w))),
            argnums=(0, 1, 2, 3)))(h, up, down, weights)

    got = total(lambda h, u, dn, w: _grouped(h, u, dn, ids, w, first, held,
                                             tile)[0])
    ref_g = total(lambda h, u, dn, w: _dense_experts(h, u, dn, ids, w, first,
                                                     held))
    for g, w in zip(got, ref_g):
        np.testing.assert_allclose(g, w, atol=2e-5)


# ---- the share

@pytest.mark.parametrize("shards", [2, 4, 8])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        shards):
    """What every chip of an expert-parallel deployment computes, added up
    — the routed parts of all shards, and the shared expert (which every
    chip computes alike) counted once — is the uncut layer."""
    from shifu_tensorflow_tpu.models.hybrid_lm import MoEMixer

    whole = ModelConfig.from_json(
        {"train": {"params": params_for()}}).params.hybrid_lm
    x = jax.random.normal(jax.random.key(2), (2, SEQ, whole.hidden_size))
    full = MoEMixer(whole)
    variables = jax.jit(full.init)(jax.random.key(1), x)
    want, stats = jax.jit(full.apply)(variables, x)
    assert int(stats[0]) == 2 * SEQ * 2  # every pair is held
    p = variables["params"]
    uncut = ref.moe_mixer(p, x, params_for(), held=(0, 8))
    np.testing.assert_allclose(want, uncut, atol=2e-5)
    shared = ref.moe_mixer(p, x, params_for(), held=(0, 0))
    per, total, pairs = 8 // shards, 0.0, 0
    for s in range(shards):
        cut = dataclasses.replace(whole, experts_held=(s * per, per))
        held = {**p, "experts": {k: v[s * per:(s + 1) * per]
                                 for k, v in p["experts"].items()}}
        out, st = jax.jit(MoEMixer(cut).apply)({"params": held}, x)
        np.testing.assert_allclose(
            out, ref.moe_mixer(held, x, params_for(), held=(s * per, per)),
            atol=2e-5)
        total = total + (out - shared)
        pairs += int(st[0])
    assert pairs == 2 * SEQ * 2
    np.testing.assert_allclose(total + shared, want, atol=5e-5)


# ---- the normal path

def write_token_shards(directory, shards=2, rows=4, seq=SEQ, seed=0):
    rng = np.random.default_rng(seed)
    os.makedirs(directory, exist_ok=True)
    for i in range(shards):
        with gzip.open(os.path.join(directory, f"part-{i:05d}.gz"),
                       "wt") as f:
            for row in rng.integers(0, 256, (rows, seq)):
                f.write("0|" + "|".join(map(str, row)) + "|1.0\n")


def test_stream_cli_trains_two_epochs_saves_and_restores(tmp_path, capsys):
    from shifu_tensorflow_tpu.train import __main__ as cli

    write_token_shards(tmp_path / "shards")
    mc = tmp_path / "ModelConfig.json"
    mc.write_text(json.dumps({"train": {
        "numTrainEpochs": 2, "validSetRate": 0.0,
        "params": params_for(experts_held=[0, 4])}}))
    argv = ["--training-data-path", str(tmp_path / "shards"),
            "--model-config", str(mc), "--feature-columns",
            ",".join(map(str, range(1, SEQ + 1))), "--target-column", "0",
            "--weight-column", str(SEQ + 1), "--stream", "--batch-size", "2",
            "--mesh", "none", "--checkpoint-dir", str(tmp_path / "ckpt")]
    assert cli.main(argv + ["--epochs", "2"]) == 0
    out = capsys.readouterr().out
    epochs = [ln for ln in out.splitlines() if ln.startswith("epoch ")]
    assert len(epochs) == 2 and "ks=nan" in epochs[0]  # absent, not faked
    losses = [float(ln.split("train_loss=")[1].split()[0]) for ln in epochs]
    assert losses[1] < losses[0] < np.log(256) + 0.5
    assert '"state": "finished"' in out and "step=8" in epochs[1]
    # a third epoch picks the checkpoint up where the second left it
    assert cli.main(argv + ["--epochs", "3"]) == 0
    again = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("epoch ")]
    assert len(again) == 1 and again[0].startswith("epoch 2:")
    assert "step=12" in again[0]
    assert float(again[0].split("train_loss=")[1].split()[0]) < losses[1]


def test_checkpoint_round_trip_keeps_every_leaf(tmp_path):
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    mc = ModelConfig.from_json({"train": {"params": params_for()}})
    trainer = make_trainer(mc, SEQ, seed=3)
    loss, n = trainer.train_epoch([batch_of(seed=s) for s in range(3)])
    assert n == 3 and np.isfinite(loss)
    assert set(trainer.epoch_counters) == {"moe_held_pairs", "moe_held_max"}
    assert trainer.epoch_counters["moe_held_pairs"].shape == (3,)
    ckpt = NpzCheckpointer(str(tmp_path))
    ckpt.save(0, trainer.state)
    other = make_trainer(mc, SEQ, seed=4)
    assert other.restore(ckpt) == 1
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        trainer.state.params, other.state.params)
    assert all(jax.tree.leaves(same))
    ev = other.evaluate([batch_of(seed=9)])
    assert np.isfinite(ev["loss"]) and np.isnan(ev["ks"]) and np.isnan(
        ev["auc"])


@pytest.mark.parametrize("kw,what", [
    ({"scan_steps": 2}, "scan"), ({"accum_steps": 2}, "accum")])
def test_other_epoch_paths_refuse_the_family(kw, what):
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json({"train": {"params": params_for()}})
    with pytest.raises(ValueError, match="per-step path"):
        Trainer(mc, SEQ, **kw)


@pytest.mark.parametrize("bad,match", [
    ({"hybrid_override_pattern": "MXE"}, "hybrid_override_pattern"),
    ({"num_hidden_layers": 7}, "num_hidden_layers"),
    ({"n_group": 2}, "n_group"),
    ({"experts_held": [6, 4]}, "experts_held"),
    ({"n_groups": 3}, "n_groups"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
])
def test_a_misconfigured_family_is_a_config_error(bad, match):
    p = dict(PARAMS, **bad)
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_json({"train": {"params": p}})


# ---- token rows stream as float32

@pytest.mark.parametrize("stats", [True, False])
def test_auto_keeps_float32_for_token_rows(stats):
    from shifu_tensorflow_tpu.data.dataset import resolve_stream_feature_dtype

    lm = ModelConfig.from_json({"train": {"params": params_for()}}).params
    assert lm.features_carry_ids and not lm.uses_feature_hashing
    assert resolve_stream_feature_dtype(
        "auto", uses_feature_hashing=lm.features_carry_ids,
        has_normalization_stats=stats) == "float32"
    with pytest.raises(ValueError, match="token ids"):
        resolve_stream_feature_dtype(
            "bfloat16", uses_feature_hashing=lm.features_carry_ids,
            has_normalization_stats=stats)
    dnn = ModelConfig.from_json({"train": {"params": {}}}).params
    assert not dnn.features_carry_ids
    assert np.float32(16383).astype(jnp.bfloat16) != 16383  # why


# ---- the planes that cannot run it say so by name

def test_export_refuses_the_family_by_name(tmp_path):
    from shifu_tensorflow_tpu.export.saved_model import (
        export_model,
        export_native_bundle,
    )
    from shifu_tensorflow_tpu.train import make_trainer

    mc = ModelConfig.from_json({"train": {"params": params_for()}})
    trainer = make_trainer(mc, SEQ)
    with pytest.raises(UnsupportedModelType, match="hybrid_lm") as e:
        export_model(str(tmp_path / "a"), trainer)
    assert e.value.model_type == "hybrid_lm" and e.value.plane == "export"
    with pytest.raises(UnsupportedModelType, match="hybrid_lm"):
        export_native_bundle(str(tmp_path / "b"), trainer.state.params, mc,
                             SEQ)
    assert not os.path.exists(tmp_path / "a")


def test_eval_model_refuses_an_artifact_of_the_family(tmp_path):
    """``serve/`` (ModelStore) and ``score/`` load through ``EvalModel``."""
    from shifu_tensorflow_tpu.export.eval_model import EvalModel
    from shifu_tensorflow_tpu.export.saved_model import (
        GENERIC_CONFIG,
        NATIVE_ARCH,
        generic_model_config_json,
    )

    (tmp_path / GENERIC_CONFIG).write_text(generic_model_config_json())
    (tmp_path / NATIVE_ARCH).write_text(json.dumps({
        "num_features": SEQ,
        "model_config": {"train": {"params": params_for()}}}))
    with pytest.raises(UnsupportedModelType, match="hybrid_lm"):
        EvalModel(str(tmp_path), backend="native")


# ---- the benchmark's comparison fails a wrong model

SYSTEM_RUNS = {}


def _system_run(dtype):
    """The program's first two steps (one trainer a dtype, shared by the
    cases): the parameters each step started from and its loss, then the
    parameters after both and Adam's first moment after the first."""
    from shifu_tensorflow_tpu.train import make_trainer

    if dtype not in SYSTEM_RUNS:
        mc = ModelConfig.from_json({"train": {"params": params_for()}})
        trainer = make_trainer(mc, SEQ, seed=1, dtype=dtype)
        steps, moments = [], []
        for batch in (batch_of(seed=11), batch_of(seed=12)):
            before = jax.device_get(trainer.state.params)
            steps.append((batch, before, trainer.train_epoch([batch])[0]))
            moments.append(jax.device_get(
                train_lm_stream.first_moment(trainer.state.opt_state)))
        SYSTEM_RUNS[dtype] = (steps, jax.device_get(trainer.state.params),
                              moments[0])
    return SYSTEM_RUNS[dtype]


JUDGED = {}


def _judged(dtype, ref_kw):
    """The reference's verdict on the program's two steps, once a case:
    its loss on each step's parameters and its gradient on the first."""
    key = (dtype, repr(sorted(ref_kw.items())))
    if key not in JUDGED:
        judge = ref.make_loss(params_for(), "highest", with_grad=True,
                              **ref_kw)
        (batch, before, _), (batch2, before2, _) = _system_run(dtype)[0]
        loss, grads = train_lm_stream.by_rows(judge, before, batch,
                                              with_grad=True)
        JUDGED[key] = ([loss, train_lm_stream.by_rows(judge, before2,
                                                      batch2)], grads)
    return JUDGED[key]


def _compare(dtype=jnp.float32, check=None, scale=None, **ref_kw):
    """The plane's own check at small size: the program takes two steps;
    the reference (possibly a wrong model) judges them.  On the CPU a
    float32 product is exact, so one reference serves as the truth and as
    the stated precision.  ``scale`` = (part of a leaf's name, factor)
    multiplies the reference's gradient on those leaves: what a backward
    pass that loses a factor looks like from the other side."""
    steps, _, moment = _system_run(dtype)
    ref_l, grads = _judged(dtype, ref_kw)
    if scale:
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g * np.float32(
                scale[1] if scale[0] in train_lm_stream.leaf_name(path)
                else 1.0), grads)
    errors = train_lm_stream.update_errors(
        steps[0][1], grads, steps[1][1],
        float(params_for()["LearningRate"]), moment)
    return train_lm_stream.compare(ref_l, ref_l, [s[2] for s in steps],
                                   errors, check or SHIPPED_CHECK)


def test_comparison_passes_the_program_under_the_shipped_limits():
    got = _compare()
    assert got["ok"], got
    assert got["loss_rel_err"] < 1e-5 and got["update_rel_err"] < 0.02
    assert got["grad_norm_rel_err"] < 1e-3 and got[
        "pooled_grad_rel_err"] < 1e-3


@pytest.mark.parametrize("factor", [0.5, 2.0])
def test_a_gradient_off_by_a_factor_fails_by_its_norm_alone(factor):
    """Adam's first move is ``-lr g / (|g| + eps)``: an expert's up
    projection whose gradient lost (or gained) a factor of two, as a
    ``relu`` where ``2 relu`` belongs in the backward pass would leave it,
    moves every parameter as the right gradient does.  Only the first
    moment carries the size."""
    got = _compare(scale=("experts/up", factor))
    assert not got["ok"]
    assert got["grad_norm_worst_leaf"].endswith("experts/up")
    assert got["grad_norm_rel_err"] == pytest.approx(
        abs(1 / factor - 1), abs=1e-3)
    assert got["update_rel_err"] < 0.02 and got[
        "pooled_update_rel_err"] < 0.005
    loose = dict(SHIPPED_CHECK, grad_norm_rtol=1.5, pooled_grad_rtol=1.5)
    assert _compare(scale=("experts/up", factor), check=loose)["ok"]


@pytest.mark.parametrize("what,kw", [
    ("a dropped shared expert", {"wrong": {"drop_shared": True}}),
    ("top-k weights not renormalised", {"wrong": {"renormalise": False}}),
    ("a missing D x term", {"wrong": {"drop_d_term": True}}),
    ("an unmasked attention", {"wrong": {"causal": False}}),
    ("a loss over the wrong shift", {"shift": 2}),
    ("a bf16 step", {"dtype": jnp.bfloat16, "check": CPU_CHECK}),
], ids=lambda v: v if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(what, kw):
    """The fault is on the reference's side (the same disagreement), but
    for the bf16 step, which the program takes itself (--dtype bfloat16).
    The limits are the shipped cell's, but for the bf16 step's (see
    ``CPU_CHECK``; on the chip the shipped limits refuse it, PERF.md)."""
    got = _compare(**kw)
    assert not got["ok"], (what, got)


def test_the_program_passes_the_limits_that_fail_a_bf16_step():
    got = _compare(check=CPU_CHECK)
    assert got["ok"] and got["pooled_update_rel_err"] < 0.005, got


def test_small_leaves_have_their_own_limit():
    Leaf = train_lm_stream.Leaf
    errors = {"big": Leaf(0.3, 4096, 0.09, 1.0),
              "tiny": Leaf(0.9, 64, 0.0081, 0.01)}
    check = {"loss_rtol": 1, "stated_loss_rtol": 1, "update_rtol": 0.5,
             "small_leaf": 128, "small_leaf_update_rtol": 1.0,
             "pooled_update_rtol": 0.4, "grad_norm_rtol": 0.1,
             "pooled_grad_rtol": 0.1}
    assert train_lm_stream.compare([1.0], [1.0], [1.0], errors, check)["ok"]
    tight = dict(check, small_leaf_update_rtol=0.5)
    got = train_lm_stream.compare([1.0], [1.0], [1.0], errors, tight)
    assert not got["ok"] and got["update_worst_leaf"] == "big"
    assert not train_lm_stream.compare([1.0], [1.0], [float("nan")], errors,
                                       check)["ok"]
    # pooled over every element: sqrt((0.09 + 0.0081) / 1.01) = 0.312
    assert got["pooled_update_rel_err"] == pytest.approx(0.3118, abs=1e-3)
    assert not train_lm_stream.compare(
        [1.0], [1.0], [1.0], errors, dict(check, pooled_update_rtol=0.3))["ok"]
