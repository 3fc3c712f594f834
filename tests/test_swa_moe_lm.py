"""``ModelType: hybrid_lm`` under the public ``mellum`` keys — sliding-window
and full attention mixed, rotary positions (plain and YaRN), softmax-scored
top-k gated experts — at a small size on the CPU: the model against the
plain reference (``benchmark/reference/swa_moe_lm.py``), the banded flash
kernel and the chunked scan against masked dense attention, YaRN against
its written-out formula at the published numbers, the gated grouped
product, the share of an expert-parallel deployment, and the wrong models
the benchmark's comparison must fail."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.planes import train_lm_stream
from benchmark.reference import swa_moe_lm as ref
from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.models.factory import build_model, family_loss
from shifu_tensorflow_tpu.ops import grouped
from shifu_tensorflow_tpu.ops.pallas.flash_attention import flash_attention
from shifu_tensorflow_tpu.parallel import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
ROPE = {
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                       "original_max_position_embeddings": 32,
                       "beta_fast": 32, "beta_slow": 1}}
#: the initialiser is wide (0.02 published) so that at this size the
#: scores and the router's logits are of order 1, as a trained model's: a
#: rotary, a window or a gate that is wrong then moves loss and gradients
PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 2,
    "layer_types": ["sliding_attention", "full_attention"],
    "mlp_layer_types": ["sparse", "sparse"], "sliding_window": 16,
    "rope_parameters": ROPE, "rms_norm_eps": 1e-6, "initializer_range": 0.15,
    "vocab_size": 256, "num_experts": 8, "experts_held": [0, 8],
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 0, "hidden_act": "silu", "scoring_func": "softmax",
    "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mellum2_ep4.json")) as _f:
    SHIPPED = json.load(_f)
SHIPPED_CHECK = SHIPPED["check"]
#: what exact float32 products (the CPU's) leave between program and
#: reference: the shipped limits sit above what ONE bf16 pass moves on the
#: chip (PERF.md section 2), so at this size only these can tell a bf16 step
CPU_CHECK = dict(SHIPPED_CHECK, loss_rtol=1e-4, stated_loss_rtol=1e-4,
                 update_rtol=0.05, small_leaf_update_rtol=0.05,
                 pooled_update_rtol=0.02, grad_norm_rtol=0.01,
                 pooled_grad_rtol=0.01)


def params_for(**over):
    p = dict(PARAMS, **over)
    p["num_hidden_layers"] = len(p["layer_types"])
    p["mlp_layer_types"] = ["sparse"] * len(p["layer_types"])
    return p


def config_of(p):
    return ModelConfig.from_json({"train": {"params": p}})


def batch_of(seed=0, rows=2, seq=SEQ):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, seq))
    return {"x": ids.astype(np.float32), "y": np.zeros((rows, 1), np.float32),
            "w": np.ones((rows, 1), np.float32)}


def rel(a, b):
    den = float(jnp.linalg.norm(b))
    off = float(jnp.linalg.norm(a - b))
    return off / den if den else off


# ---- the configuration's keys

def test_the_public_keys_become_two_layers_a_block():
    cfg = config_of(params_for(layer_types=[
        "sliding_attention", "sliding_attention", "full_attention"]))
    c = cfg.params.hybrid_lm
    assert c.hybrid_override_pattern == "WEWE*E"
    assert (c.n_routed_experts, c.layer_norm_epsilon) == (8, 1e-6)
    assert c.rope_for("W").rope_type == "default"
    assert c.rope_for("*") == RopeParameters(
        "yarn", 10000.0, 4.0, 32, 32.0, 1.0, 0.0)
    hash(c)  # a flax module's attribute
    tree = jax.eval_shape(build_model(cfg).init, jax.random.key(0),
                          jnp.zeros((1, 8)))["params"]
    assert set(tree["layers_1"]["mixer"]) == {"router", "experts"}
    assert set(tree["layers_1"]["mixer"]["experts"]) == {"gate", "up", "down"}
    assert set(tree["layers_0"]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                              "o_proj"}


def test_the_shipped_file_parses_and_the_other_decoders_tree_is_unchanged():
    c = ModelConfig.from_json(SHIPPED["model_config"]).params.hybrid_lm
    assert c.hybrid_override_pattern == "WEWEWE*E"
    assert (c.sliding_window, c.n_routed_experts, c.experts_held) == (
        1024, 64, (0, 16))
    # the recipe's two further ranges and the share's tile, as `assumed`
    # states them: 0.02 / sqrt(2 x 28 published blocks); two tiles hold a
    # quarter more than the uniform 16,384 x 8 / 64 pairs an expert
    assert c.embedding_std == 1.0
    assert c.output_std == pytest.approx(0.02 / math.sqrt(2 * 28), rel=1e-4)
    assert 2 * c.expert_tile == 16384 * 8 // 64 * 5 // 4
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_ep16.json")) as f:
        other = ModelConfig.from_json(json.load(f)["model_config"])
    c = other.params.hybrid_lm
    assert (c.hidden_act, c.scoring_func, c.sliding_window,
            c.rope_parameters) == ("relu2", "sigmoid", 0, ())
    assert (c.embedding_std, c.output_std, c.expert_tile) == (
        c.initializer_range, c.initializer_range, 0)
    tree = jax.eval_shape(build_model(other).init, jax.random.key(0),
                          jnp.zeros((1, 8)))["params"]
    assert set(tree["layers_1"]["mixer"]) == {
        "router", "e_score_correction_bias", "experts", "shared"}
    assert set(tree["layers_1"]["mixer"]["experts"]) == {"up", "down"}


@pytest.mark.parametrize("bad,match", [
    ({"layer_types": ["sliding_attention", "linear_attention"]},
     "layer_types"),
    ({"mlp_layer_types_": ["conv", "sparse"]}, "mlp_layer_types"),
    ({"mlp_layer_types_": ["dense", "sparse"]}, "intermediate_size"),
    ({"hybrid_override_pattern": "WE*EM"}, "hybrid_override_pattern"),
    ({"num_hidden_layers_": 4}, "num_hidden_layers"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"scoring_func": "topk"}, "scoring_func"),
    ({"n_shared_experts": -1}, "n_shared_experts"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_routed_experts": 16}, "num_experts"),
    ({"expert_tile": 100}, "expert_tile"),
    ({"output_initializer_range": -0.01}, "output_initializer_range"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3"}}},
     "rope_type"),
    ({"rope_parameters": {"full_attention": {"rope_type": "yarn",
                                             "rope_theta": 1e4}}}, "yarn"),
    ({"rope_parameters": {"chunked_attention": {"rope_theta": 1e4}}},
     "rope_parameters"),
    ({"rope_parameters": {"full_attention": {"rope_theta": 1e4,
                                             "mscale": 1.0}}}, "mscale"),
])
def test_a_combination_the_code_does_not_implement_is_an_error_by_name(
        bad, match):
    p = params_for()
    p.update({k.rstrip("_"): v for k, v in bad.items()})
    with pytest.raises(ValueError, match=match):
        config_of(p)


def test_the_recipes_ranges_reach_the_leaves_they_name():
    """The embedding at ``embedding_initializer_range``, every projection
    back onto the residual stream at ``output_initializer_range``, every
    other matrix at ``initializer_range``; left out, one range for all."""
    def stds(**over):
        p = params_for(hidden_size=128, vocab_size=1024, **over)
        tree = build_model(config_of(p)).init(
            jax.random.key(0), jnp.zeros((1, 8)))["params"]
        flat = jax.tree_util.tree_leaves_with_path(tree)
        return {"/".join(k.key for k in path): float(jnp.std(leaf))
                for path, leaf in flat if leaf.ndim > 1}

    one = stds()
    assert all(v == pytest.approx(0.15, rel=0.1) for v in one.values()), one
    three = stds(embedding_initializer_range=1.0,
                 output_initializer_range=0.01)
    assert set(three) == set(one)
    for name, std in three.items():
        want = (1.0 if name == "embed/embedding" else
                0.01 if name.endswith(("o_proj/kernel", "experts/down"))
                else 0.15)
        assert std == pytest.approx(want, rel=0.1), name


@pytest.mark.parametrize("tile", [0, 8, 24])
def test_the_configurations_tile_leaves_the_layers_output_as_it_was(tile):
    """``expert_tile`` is how the pairs are laid out, not what is
    computed: loss and counters are those of the family's tile."""
    batch = batch_of(3)
    out = []
    for t in (0, tile):
        model = build_model(config_of(params_for(expert_tile=t)))
        params = model.init(jax.random.key(1), jnp.zeros((1, SEQ)))["params"]
        loss, _, counters = family_loss(model)(params, batch)
        out.append((float(loss), {k: int(v) for k, v in counters.items()}))
    assert out[1][1] == out[0][1]
    assert out[1][0] == pytest.approx(out[0][0], rel=1e-6)


# ---- the model against the reference

def system_loss_and_grads(p, batch, seed=0):
    model = build_model(config_of(p))
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, batch["x"].shape[1])))["params"]
    loss_of = family_loss(model)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q: loss_of(q, batch)[0]))(params)
    return params, float(loss), grads


@pytest.mark.parametrize("kinds,window", [
    ("WF", 16), ("WF", 40), ("WF", 100), ("W", 16), ("W", 40), ("W", 100),
    ("F", 16), ("WWWF", 16)])
def test_loss_and_every_gradient_leaf_equal_the_references(kinds, window):
    """Rows of 40 positions (beyond YaRN's original 32) under a window
    shorter than the row, equal to it and longer; the held experts are
    2 .. 5 of 8."""
    names = {"W": "sliding_attention", "F": "full_attention"}
    p = params_for(layer_types=[names[k] for k in kinds],
                   sliding_window=window, experts_held=[2, 4])
    batch = batch_of(seed=3, seq=40)
    params, loss, grads = system_loss_and_grads(p, batch)
    ref_loss, ref_grads = ref.make_loss(p, "highest", with_grad=True)(
        params, batch)
    assert loss == pytest.approx(float(ref_loss), rel=3e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads)) >= 10
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 3e-5, (jax.tree_util.keystr(path), rel(g, r))


def test_the_window_and_the_rotary_move_the_loss():
    """Guard of the tests above: at this size neither is a no-op."""
    p, batch = params_for(), batch_of(seed=3)
    params, loss, _ = system_loss_and_grads(p, batch)
    for wrong in ({"window": False}, {"rope": False}, {"yarn": False},
                  {"attention_factor": False}, {"gate": False},
                  {"softmax": False}):
        other = float(ref.loss(params, batch, p, wrong))
        assert abs(other - loss) > 1e-4 * loss, wrong


# ---- attention inside a window

def dense_attention(q, k, v, window):
    s = q.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & (i - j < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _qkvg(s, seed=0):
    return tuple(jax.random.normal(k, (2, s, 2, 8))
                 for k in jax.random.split(jax.random.key(seed), 4))


def _grads(fn, q, k, v, g):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * g),
                    (0, 1, 2))(q, k, v)


#: S, query block, key block, window: windows that divide the block, do
#: not, are one key, span blocks of unequal sizes, reach past the row
BANDS = [(64, 16, 16, 16), (64, 16, 16, 20), (70, 16, 16, 33),
         (64, 16, 32, 17), (64, 32, 16, 40), (64, 16, 16, 1),
         (64, 16, 16, 64), (64, 16, 16, 100)]


@pytest.mark.parametrize("s,bq,bk,window", BANDS)
def test_banded_flash_kernels_equal_masked_dense_attention(s, bq, bk,
                                                           window):
    """Forward, dQ and dK/dV kernels in interpret mode."""
    q, k, v, g = _qkvg(s)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, bq, bk, True, window)

    np.testing.assert_allclose(flash(q, k, v),
                               dense_attention(q, k, v, window), atol=2e-6)
    want = _grads(lambda *a: dense_attention(*a, window), q, k, v, g)
    for got, w in zip(_grads(flash, q, k, v, g), want):
        np.testing.assert_allclose(got, w, atol=5e-6)


def test_the_band_leaves_the_blocks_outside_it_out_of_the_grid():
    """At the shipped shape (S 8,192, window 1,024, 512-row tiles) a query
    block walks 3 key blocks of 16 and a key block 3 query blocks."""
    from shifu_tensorflow_tpu.ops.pallas.flash_attention import _Band

    band = _Band(1024, 8192, 512, 512)
    assert (band.nk, band.nq) == (3, 3)
    for qi in range(16):
        seen = [band.key_block(qi, t) for t in range(band.nk)]
        live = [k for k, ok in seen if ok]
        assert live == list(range(max(qi - 2, 0), qi + 1))
        assert all(k == live[0] for k, ok in seen if not ok)
    for ki in range(16):
        seen = [band.query_block(ki, t) for t in range(band.nq)]
        live = [q for q, ok in seen if ok]
        assert live == list(range(ki, min(ki + 2, 15) + 1))
        assert all(q == live[-1] for q, ok in seen if not ok)
    assert _Band(1025, 8192, 512, 512).nk == 3  # key i - 1024: block qi - 2
    assert _Band(1026, 8192, 512, 512).nk == 4
    assert _Band(8192, 8192, 512, 512).nk == 16


@pytest.mark.parametrize("seq_len,window,live,idle", [
    (8192, None, 136, 0),   # the Mellum cell's full layer: 256 in the square
    (4096, None, 36, 0),    # the Nemotron cell's `*` layer: 64 in the square
    (8192, 1024, 45, 3),    # the Mellum cell's window layers, as before
], ids=["mellum-full", "nemotron-full", "mellum-window"])
def test_the_causal_kernels_walk_the_triangle_at_the_cells_shapes(
        seq_len, window, live, idle):
    """Grid steps a head of each of the three kernels at the cells' 512-row
    tiles: the tiles that hold a visible key and no others, folded so that
    the full layers' grids have no idle step."""
    from shifu_tensorflow_tpu.models.sequence import CAUSAL_FLASH_BLOCK
    from shifu_tensorflow_tpu.ops.pallas.flash_attention import grid_steps

    tile = CAUSAL_FLASH_BLOCK
    steps = grid_steps(seq_len, tile, tile, causal=True, window=window)
    assert steps == {k: (live, idle) for k in ("forward", "dq", "dkv")}
    blocks = seq_len // tile
    assert grid_steps(seq_len, tile, tile, causal=False) == {
        k: (blocks * blocks, 0) for k in ("forward", "dq", "dkv")}


@pytest.mark.parametrize("sp,bq,bk", [
    (8192, 512, 512), (256, 32, 64), (256, 64, 32), (192, 64, 96),
    (192, 96, 64), (384, 128, 128), (200, 40, 40), (240, 48, 16)])
def test_the_fold_visits_each_tile_of_the_triangle_once(sp, bq, bk):
    """Every (query block, key block) tile with a visible key is a live
    step of exactly one grid point, between the step that resets its
    block's statistics and the step that writes its block; idle steps
    repeat the block indices of the step before them (no new fetch)."""
    from shifu_tensorflow_tpu.ops.pallas.flash_attention import _Fold

    fold = _Fold(sp, bq, bk)
    want = {(qi, ki) for qi in range(sp // bq) for ki in range(sp // bk)
            if ki * bk <= (qi + 1) * bq - 1}
    for grid, tile, swap in ((fold.key_grid, fold.key_tile, False),
                             (fold.query_grid, fold.query_tile, True)):
        seen, rows, steps = [], *grid
        for row in range(rows):
            open_run, prev = None, None
            for t in range(steps):
                block, walked, live, first, last = tile(row, t)
                if first:
                    assert open_run is None
                    open_run = block
                if live:  # never a tile outside its block's open run
                    assert block == open_run
                    seen.append((walked, block) if swap else (block, walked))
                else:
                    assert (block, walked) == prev
                if last:
                    open_run = None
                prev = (block, walked)
            assert open_run is None
        assert sorted(seen) == sorted(want)  # each once, none missing


@pytest.mark.parametrize("window", [16, 20, 1, 100])
@pytest.mark.parametrize("block", [16, 512], ids=["scan", "one-block"])
def test_chunked_and_full_attention_take_the_window_as_a_mask(window, block):
    q, k, v, g = _qkvg(70, seed=1)

    def chunked(q, k, v):
        return ring.chunked_attention(q, k, v, causal=True, block_size=block,
                                      window=window)

    want = dense_attention(q, k, v, window)
    np.testing.assert_allclose(chunked(q, k, v), want, atol=2e-6)
    np.testing.assert_allclose(
        ring.full_attention(q, k, v, causal=True, window=window), want,
        atol=2e-6)
    for got, w in zip(_grads(chunked, q, k, v, g),
                      _grads(lambda *a: dense_attention(*a, window),
                             q, k, v, g)):
        np.testing.assert_allclose(got, w, atol=5e-6)


def test_a_window_needs_causal_attention():
    from shifu_tensorflow_tpu.models.sequence import make_attention

    q, k, v, _ = _qkvg(16)
    with pytest.raises(ValueError, match="causal"):
        ring.full_attention(q, k, v, window=4)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, 16, 16, True, 4)
    with pytest.raises(ValueError, match="causal"):
        make_attention("chunked", None, window=4)
    windowed = make_attention("full", None, causal=True, window=4)
    np.testing.assert_allclose(windowed(q, k, v),
                               dense_attention(q, k, v, 4), atol=2e-6)


# ---- rotary positions

def test_yarn_at_the_published_numbers_is_the_written_out_formula():
    rope = SHIPPED["model_config"]["train"]["params"]["rope_parameters"]
    full = rope["full_attention"]
    assert (full["factor"], full["original_max_position_embeddings"],
            full["beta_fast"], full["beta_slow"], full["rope_theta"]) == (
        16, 8192, 32, 1, 500000)

    def c(r):  # the dimension that turns r times over 8,192 positions
        return 64 * math.log(8192 / (2 * math.pi * r)) / math.log(500000)

    low, high = math.floor(c(32)), math.ceil(c(1))
    assert (low, high) == (18, 35)
    assert ref.yarn_correction_range(full, 128) == (18, 35)
    want = []
    for m in range(64):
        b = 500000 ** (-m / 64)
        keep = 1 - min(max((m - low) / (high - low), 0), 1)
        want.append((1 - keep) * b / 16 + keep * b)
    scale = 0.1 * math.log(16) + 1
    assert scale == pytest.approx(1.2772588722239782, rel=1e-15)
    for freqs, a in (
            ref.rope_frequencies(full, 128),
            hybrid_lm.rope_frequencies(RopeParameters.from_json(full), 128)):
        np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-12)
        assert a == pytest.approx(scale, rel=1e-15)
    assert want[18] == 500000 ** (-18 / 64)  # at and below low: as published
    assert want[35] == pytest.approx(500000 ** (-35 / 64) / 16)  # from high
    plain, one = hybrid_lm.rope_frequencies(
        RopeParameters.from_json(rope["sliding_attention"]), 128)
    np.testing.assert_allclose(plain, [500000 ** (-m / 64)
                                       for m in range(64)], rtol=1e-12)
    assert one == 1.0 and ref.rope_frequencies(
        rope["sliding_attention"], 128)[1] == 1.0
    # a yarn entry without its attention_factor: 0.1 ln(factor) + 1
    bare = {k: v for k, v in full.items() if k != "attention_factor"}
    assert hybrid_lm.rope_frequencies(
        RopeParameters.from_json(bare), 128)[1] == pytest.approx(scale)


def test_rotation_is_rotate_half_and_keeps_the_products_relative():
    rope = RopeParameters.from_json(ROPE["full_attention"])
    u = jax.random.normal(jax.random.key(0), (1, 40, 2, 16))
    cos, sin = hybrid_lm.rope_tables(rope, 40, 16)
    got = hybrid_lm.apply_rope(u, cos, sin)
    freqs, a = ref.rope_frequencies(ROPE["full_attention"], 16)
    np.testing.assert_allclose(got, ref.apply_rope(u, freqs, a), atol=1e-6)
    # position 0 is the scale alone; a product depends on i - j only
    np.testing.assert_allclose(got[:, 0], u[:, 0] * a, atol=1e-6)
    same = jnp.broadcast_to(u[:, :1], u.shape)
    r = hybrid_lm.apply_rope(same, cos, sin)
    np.testing.assert_allclose(jnp.sum(r[0, 7, 0] * r[0, 4, 0]),
                               jnp.sum(r[0, 30, 0] * r[0, 27, 0]), rtol=1e-4)


# ---- the gated grouped product

def _dense_gated(h, w_gate, up, down, ids, weights, first, held):
    out = jnp.zeros_like(h)
    for e in range(held):
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        out = out + w[:, None] * (
            (jax.nn.silu(h @ w_gate[e]) * (h @ up[e])) @ down[e])
    return out


def _grouped_gated(h, w_gate, up, down, ids, weights, first, held, tile):
    pair, tile_expert, n_tiles, _ = grouped.plan_tiles(ids, first, held, tile)
    k = ids.shape[1]
    token = jnp.where(pair < ids.size, pair // k, h.shape[0])
    gate = jnp.where(pair < ids.size,
                     jnp.take(weights.reshape(-1), pair, mode="clip"), 0.0)
    return grouped.gated_expert_mlp(h, w_gate, up, down, token, gate,
                                    tile_expert, n_tiles, tile)


@pytest.mark.parametrize("pool", [[0, 1, 2, 4, 5, 6, 7], [0, 1, 6, 7],
                                  [2, 3, 4, 5], [2, 3, 7]],
                         ids=["an expert with no token", "all absent",
                              "every pair held", "uneven"])
@pytest.mark.parametrize("tile", [8, 32])
def test_gated_grouped_product_equals_the_dense_loop(pool, tile):
    """Forward and all five gradients: the input's, the three matrices'
    and the gate weight's."""
    t, d, f, first, held = 40, 16, 24, 2, 4
    ks = jax.random.split(jax.random.key(7), 6)
    h = jax.random.normal(ks[0], (t, d))
    w_gate, up = (jax.random.normal(k, (held, d, f)) * 0.3 for k in ks[1:3])
    down = jax.random.normal(ks[3], (held, f, d)) * 0.3
    weights = jax.random.uniform(ks[4], (t, 2)) + 0.1
    rng = np.random.default_rng(1)
    ids = jnp.asarray(np.stack([rng.choice(pool, 2, False)
                                for _ in range(t)]), jnp.int32)
    args = (h, w_gate, up, down, weights)

    def total(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4)))(
                *args)

    got, got_g = total(lambda h, g, u, dn, w: _grouped_gated(
        h, g, u, dn, ids, w, first, held, tile))
    want, want_g = total(lambda h, g, u, dn, w: _dense_gated(
        h, g, u, dn, ids, w, first, held))
    assert float(got) == pytest.approx(float(want), abs=1e-4)
    assert len(got_g) == 5
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, atol=3e-5)
    if pool == [0, 1, 6, 7]:
        assert not any(bool(jnp.any(g)) for g in got_g[:4])


# ---- the share

def test_the_four_shares_routed_parts_are_the_uncut_expert_layer():
    """The shipped deployment in small: 64 experts, 8 a token, four chips
    holding 0-15, 16-31, 32-47 and 48-63.  What they compute, added up,
    is the reference's whole layer (there is no shared expert to count
    once), and every (token, choice) pair lands on exactly one of them."""
    p = params_for(num_experts=64, num_experts_per_tok=8,
                   experts_held=[0, 64], hidden_size=32,
                   moe_intermediate_size=16)
    whole = config_of(p).params.hybrid_lm
    x = jax.random.normal(jax.random.key(2), (2, SEQ, 32))
    full = hybrid_lm.MoEMixer(whole)
    variables = jax.jit(full.init)(jax.random.key(1), x)
    want, stats = jax.jit(full.apply)(variables, x)
    params = variables["params"]
    assert set(params) == {"router", "experts"}
    np.testing.assert_allclose(
        want, ref.moe_layer(params, x, p, held=(0, 64)), atol=2e-5)
    assert int(stats[0]) == 2 * SEQ * 8
    total, pairs = 0.0, 0
    for first in (0, 16, 32, 48):
        cut = dataclasses.replace(whole, experts_held=(first, 16))
        held = {**params, "experts": {k: v[first:first + 16] for k, v in
                                      params["experts"].items()}}
        out, st = jax.jit(hybrid_lm.MoEMixer(cut).apply)({"params": held}, x)
        np.testing.assert_allclose(
            out, ref.moe_layer(held, x, p, held=(first, 16)), atol=2e-5)
        assert float(jnp.abs(out).max()) > 0
        total, pairs = total + out, pairs + int(st[0])
    assert pairs == 2 * SEQ * 8
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_softmax_weights_are_a_softmax_over_the_chosen_logits():
    """``p_top / sum p_top`` of a softmax over all experts is the softmax
    over the k chosen logits: what ``norm_topk_prob`` means here."""
    p = params_for()
    x = jax.random.normal(jax.random.key(4), (24, 64))
    router = {"router": {"kernel": jax.random.normal(jax.random.key(5),
                                                     (64, 8))}}
    ids, weights = ref.route(router, x, p)
    logits = jnp.take_along_axis(x @ router["router"]["kernel"], ids, axis=-1)
    np.testing.assert_allclose(weights, jax.nn.softmax(logits, axis=-1),
                               atol=1e-6)
    assert ids.shape == (24, 2)


# ---- the normal path

def test_trainer_steps_counts_pairs_saves_and_restores(tmp_path):
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    mc = config_of(params_for(experts_held=[0, 4]))
    trainer = make_trainer(mc, SEQ, seed=3)
    losses = [trainer.train_epoch([batch_of(seed=s)])[0] for s in (1, 1, 1)]
    assert losses[2] < losses[0] and np.isfinite(losses).all()
    pairs = trainer.epoch_counters["moe_held_pairs"]
    assert pairs.shape == (1,) and 0 < pairs[0] < 2 * 2 * SEQ * 2
    ckpt = NpzCheckpointer(str(tmp_path))
    ckpt.save(0, trainer.state)
    other = make_trainer(mc, SEQ, seed=4)
    assert other.restore(ckpt) == 1
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                        trainer.state.params, other.state.params)
    assert all(jax.tree.leaves(same))


def test_stream_cli_trains_the_public_keys_saves_and_restores(tmp_path,
                                                              capsys):
    """``python -m shifu_tensorflow_tpu.train --stream`` on a ModelConfig
    that carries the public keys beside ``ModelType``: two epochs through
    ``Trainer.fit_stream``, a checkpoint, and a third epoch from it."""
    import gzip

    from shifu_tensorflow_tpu.train import __main__ as cli

    rng = np.random.default_rng(0)
    os.makedirs(tmp_path / "shards")
    for i in range(2):
        with gzip.open(tmp_path / "shards" / f"part-{i:05d}.gz", "wt") as f:
            for row in rng.integers(0, 256, (4, SEQ)):
                f.write("0|" + "|".join(map(str, row)) + "|1.0\n")
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
    epochs = [ln for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("epoch ")]
    losses = [float(ln.split("train_loss=")[1].split()[0]) for ln in epochs]
    assert len(epochs) == 2 and losses[1] < losses[0]
    assert "step=8" in epochs[1]
    assert cli.main(argv + ["--epochs", "3"]) == 0
    again = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("epoch ")]
    assert len(again) == 1 and again[0].startswith("epoch 2:")
    assert "step=12" in again[0]
    assert float(again[0].split("train_loss=")[1].split()[0]) < losses[1]


def test_export_refuses_it_by_name_as_it_refuses_the_family(tmp_path):
    from shifu_tensorflow_tpu.config.model_config import UnsupportedModelType
    from shifu_tensorflow_tpu.export.saved_model import export_model
    from shifu_tensorflow_tpu.train import make_trainer

    trainer = make_trainer(config_of(params_for()), SEQ)
    with pytest.raises(UnsupportedModelType, match="hybrid_lm"):
        export_model(str(tmp_path / "a"), trainer)


# ---- the benchmark's comparison fails a wrong model

SYSTEM_RUNS, JUDGED = {}, {}


def _system_run(dtype):
    """The program's first two steps (one trainer a dtype, shared by the
    cases): the parameters each step started from and its loss, and Adam's
    first moment after the first."""
    from shifu_tensorflow_tpu.train import make_trainer

    if dtype not in SYSTEM_RUNS:
        trainer = make_trainer(config_of(params_for()), SEQ, seed=1,
                               dtype=dtype)
        steps, moment = [], None
        for batch in (batch_of(seed=11), batch_of(seed=12)):
            before = jax.device_get(trainer.state.params)
            steps.append((batch, before, trainer.train_epoch([batch])[0]))
            if moment is None:
                moment = jax.device_get(
                    train_lm_stream.first_moment(trainer.state.opt_state))
        SYSTEM_RUNS[dtype] = (steps, moment)
    return SYSTEM_RUNS[dtype]


def _compare(dtype=jnp.float32, check=None, scale=None, **ref_kw):
    """The plane's own check at small size: the program takes two steps;
    the reference (possibly a wrong model) judges them.  On the CPU a
    float32 product is exact, so one reference serves as the truth and as
    the stated precision.  ``scale`` = (part of a leaf's name, factor)
    multiplies the reference's gradient on those leaves."""
    steps, moment = _system_run(dtype)
    key = (dtype, repr(sorted(ref_kw.items())))
    if key not in JUDGED:
        judge = ref.make_loss(params_for(), "highest", with_grad=True,
                              **ref_kw)
        (batch, before, _), (batch2, before2, _) = steps
        loss, grads = train_lm_stream.by_rows(judge, before, batch,
                                              with_grad=True)
        JUDGED[key] = ([loss, train_lm_stream.by_rows(judge, before2,
                                                      batch2)], grads)
    ref_l, grads = JUDGED[key]
    if scale:
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g * np.float32(
                scale[1] if scale[0] in train_lm_stream.leaf_name(path)
                else 1.0), grads)
    errors = train_lm_stream.update_errors(
        steps[0][1], grads, steps[1][1], float(PARAMS["LearningRate"]),
        moment)
    return train_lm_stream.compare(ref_l, ref_l, [s[2] for s in steps],
                                   errors, check or SHIPPED_CHECK)


def test_comparison_passes_the_program_under_the_shipped_limits():
    got = _compare()
    assert got["ok"], got
    assert got["loss_rel_err"] < 1e-5 and got["update_rel_err"] < 0.02
    assert got["grad_norm_rel_err"] < 1e-3 and got[
        "pooled_grad_rel_err"] < 1e-3
    assert _compare(check=CPU_CHECK)["ok"]


@pytest.mark.parametrize("what,kw", [
    ("a window ignored", {"wrong": {"window": False}}),
    ("a rotary left out", {"wrong": {"rope": False}}),
    ("plain frequencies on the full layers", {"wrong": {"yarn": False}}),
    ("a missing attention_factor", {"wrong": {"attention_factor": False}}),
    ("an expert without its gate", {"wrong": {"gate": False}}),
    ("sigmoid scores", {"wrong": {"softmax": False}}),
    ("top-k weights not renormalised", {"wrong": {"renormalise": False}}),
    ("an unmasked attention", {"wrong": {"causal": False}}),
    ("a loss over the wrong shift", {"shift": 2}),
    ("a bf16 step", {"dtype": jnp.bfloat16, "check": CPU_CHECK}),
    ("a gradient off by two", {"scale": ("experts/gate", 2.0)}),
    ("a gradient off by a half", {"scale": ("q_proj", 0.5)}),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(what, kw):
    """The fault is on the reference's side (the same disagreement), but
    for the bf16 step, which the program takes itself (--dtype bfloat16).
    The limits are the shipped cell's, but for the bf16 step's (see
    ``CPU_CHECK``; on the chip the shipped limits refuse it, PERF.md)."""
    got = _compare(**kw)
    assert not got["ok"], (what, got)
