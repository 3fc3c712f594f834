"""``ModelType: hybrid_lm`` under the public ``laguna`` keys — attention
layers whose head count and rotary differ by layer type (full with a
half-rotary YaRN, sliding with the whole head turned), a leading dense
gated feed-forward, sigmoid-scored top-k gated experts beside a gated
shared expert — at a small size on the CPU: the configuration's keys, the
model against the plain reference
(``benchmark/reference/mixed_gqa_moe_lm.py``), the rotary against its
written-out formula at the published numbers, the share of an
expert-parallel deployment, the normal path, and the wrong models the
benchmark's comparison must fail."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.planes import train_lm_stream
from benchmark.reference import mixed_gqa_moe_lm as ref
from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.models.factory import build_model, family_loss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 64
FULL, SLIDING = "full_attention", "sliding_attention"
ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
           "original_max_position_embeddings": 32, "beta_fast": 32,
           "beta_slow": 1, "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 100,
              "partial_rotary_factor": 1},
    "original_max_position_embeddings": 32}
#: the shipped pattern in small: the leading dense block and one period.
#: The initialiser is wide (0.02 published) so that at this size the
#: scores and the router's logits are of order 1, as a trained model's: a
#: rotary, a window, a head count or a gate that is wrong then moves loss
#: and gradients
PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 5,
    "layer_types": [FULL, SLIDING, SLIDING, SLIDING, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_attention_heads": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 16,
    "rope_parameters": ROPE, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-6, "initializer_range": 0.15, "vocab_size": 256,
    "intermediate_size": 96, "num_experts": 16, "experts_held": [0, 16],
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 24, "n_shared_experts": 1,
    "moe_routed_scaling_factor": 2.5, "gating": True, "hidden_act": "silu",
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "attention_bias": False, "moe_apply_router_weight_on_input": False}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "laguna_xs2_ep8.json")) as _f:
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
    return dict(PARAMS, **over)


def two_blocks(**over):
    """The dense block and one sliding sparse block: every kind of
    feed-forward, head count and rotary, at two fifths of the compile."""
    return params_for(num_hidden_layers=2, layer_types=[FULL, SLIDING],
                      mlp_layer_types=["dense", "sparse"],
                      num_attention_heads_per_layer=[4, 6], **over)


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


def tree_of(mc, seq=8):
    return jax.eval_shape(build_model(mc).init, jax.random.key(0),
                          jnp.zeros((1, seq)))["params"]


# ---- the configuration's keys

def test_each_new_key_is_read():
    c = config_of(params_for()).params.hybrid_lm
    assert c.hybrid_override_pattern == "*DWEWEWE*E"
    assert (c.heads_for("*"), c.heads_for("W")) == (4, 6)
    assert c.attention_heads_by_type == ((FULL, 4), (SLIDING, 6))
    assert (c.intermediate_size, c.moe_shared_expert_intermediate_size,
            c.routed_scaling_factor, c.n_routed_experts) == (96, 24, 2.5, 16)
    assert c.rope_for("*") == RopeParameters(
        "yarn", 10000.0, 4.0, 32, 32.0, 1.0, 0.0, 0.5)
    assert c.rope_for("W") == RopeParameters(
        "default", 100.0, 1.0, 32, 32.0, 1.0, 0.0, 1.0)
    assert (c.rope_for("*").rotary_dim(32), c.rope_for("W").rotary_dim(32)
            ) == (16, 32)
    hash(c)  # a flax module's attribute
    tree = tree_of(config_of(params_for()))
    assert set(tree["layers_0"]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                              "o_proj"}
    assert tree["layers_0"]["mixer"]["q_proj"]["kernel"].shape == (64, 4 * 32)
    assert tree["layers_2"]["mixer"]["q_proj"]["kernel"].shape == (64, 6 * 32)
    assert tree["layers_2"]["mixer"]["o_proj"]["kernel"].shape == (6 * 32, 64)
    assert tree["layers_2"]["mixer"]["k_proj"]["kernel"].shape == (64, 2 * 32)
    assert {k: v["kernel"].shape for k, v in
            tree["layers_1"]["mixer"].items()} == {
        "gate": (64, 96), "up": (64, 96), "down": (96, 64)}
    assert set(tree["layers_3"]["mixer"]) == {
        "router", "e_score_correction_bias", "experts", "shared"}
    assert set(tree["layers_3"]["mixer"]["experts"]) == {"gate", "up", "down"}
    assert {k: v["kernel"].shape for k, v in
            tree["layers_3"]["mixer"]["shared"].items()} == {
        "gate": (64, 24), "up": (64, 24), "down": (24, 64)}


def test_the_configs_own_partial_rotary_factor_stands_for_an_entry_without():
    rope = {FULL: {"rope_theta": 100}, SLIDING: {
        "rope_theta": 100, "partial_rotary_factor": 1.0}}
    c = config_of(params_for(rope_parameters=rope)).params.hybrid_lm
    assert (c.rope_for("*").partial_rotary_factor,
            c.rope_for("W").partial_rotary_factor) == (0.5, 1.0)
    p = params_for(rope_parameters=rope)
    del p["partial_rotary_factor"]
    assert config_of(p).params.hybrid_lm.rope_for(
        "*").partial_rotary_factor == 1.0
    assert ref.rope_entry(params_for(rope_parameters=rope), FULL) == {
        "rope_theta": 100, "partial_rotary_factor": 0.5}


def test_the_shipped_file_parses_and_the_accepted_trees_are_what_they_were():
    mc = ModelConfig.from_json(SHIPPED["model_config"])
    c = mc.params.hybrid_lm
    assert c.hybrid_override_pattern == "*DWEWEWE*E"
    assert (c.heads_for("*"), c.heads_for("W"), c.num_key_value_heads,
            c.head_dim, c.sliding_window) == (48, 64, 8, 128, 512)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.moe_intermediate_size, c.moe_shared_expert_intermediate_size,
            c.intermediate_size, c.routed_scaling_factor) == (
        256, (0, 32), 8, 512, 512, 8192, 2.5)
    assert (c.hidden_act, c.scoring_func, c.n_shared_experts) == (
        "silu", "sigmoid", 1)
    assert c.rope_for("*").rotary_dim(128) == 64
    # the recipe's ranges and the share's tile, as `assumed` states them:
    # 0.02 / sqrt(2 x 40 published blocks); one tile holds an expert's 256
    # uniform pairs and 8 standard deviations (16 each) more
    assert c.embedding_std == 1.0
    assert c.output_std == pytest.approx(0.02 / math.sqrt(2 * 40), rel=1e-4)
    assert c.expert_tile == 8192 * 8 // 256 + 8 * 16
    leaves = jax.tree.leaves(tree_of(mc))
    assert sum(x.size for x in leaves) == 691_034_112 + 4 * 256
    for name, elements, shared in (("nemotron3_nano_ep16", 666_963_456,
                                    {"up", "down"}),
                                   ("mellum2_ep4", 595_153_152, None)):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            other = ModelConfig.from_json(json.load(f)["model_config"])
        c = other.params.hybrid_lm
        assert (c.intermediate_size, c.attention_heads_by_type,
                c.routed_scaling_factor in (1.0, 2.5)) == (0, (), True)
        assert all(r.partial_rotary_factor == 1.0
                   for _, r in c.rope_parameters)
        tree = tree_of(other)
        assert sum(x.size for x in jax.tree.leaves(tree)) == elements
        experts = next(v["mixer"] for v in tree.values()
                       if "router" in v.get("mixer", {}))
        assert (set(experts["shared"]) if "shared" in experts
                else None) == shared


@pytest.mark.parametrize("bad,match", [
    ({"mlp_layer_types": ["dense", "conv", "sparse", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"intermediate_size": 0}, "intermediate_size"),
    ({"hidden_act": "relu2"}, "hidden_act"),
    ({"hidden_act": "relu2", "mlp_layer_types": ["sparse"] * 5}, "gating"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 4, 4]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 6]},
     "num_attention_heads_per_layer"),
    ({"num_attention_heads_per_layer": [4, 5, 5, 5, 4]},
     "num_key_value_heads"),
    ({"moe_apply_router_weight_on_input": True},
     "moe_apply_router_weight_on_input"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_group": 2}, "n_group"),
    ({"n_shared_experts": -1}, "n_shared_experts"),
    ({"routed_scaling_factor": 1.0}, "moe_routed_scaling_factor"),
    ({"moe_shared_expert_intermediate_size": 32},
     "shared_expert_intermediate_size"),
    ({"rope_parameters": {FULL: {"rope_theta": 1e4,
                                 "partial_rotary_factor": 0.0}}},
     "partial_rotary_factor"),
    ({"rope_parameters": {FULL: {"rope_theta": 1e4,
                                 "partial_rotary_factor": 1.5}}},
     "partial_rotary_factor"),
    ({"rope_parameters": {FULL: {"rope_theta": 1e4,
                                 "partial_rotary_factor": 0.1}}},
     "even number of dimensions"),
    ({"rope_parameters": {FULL: {"rope_theta": 1e4}, "max_position": 64}},
     "max_position"),
    ({"hybrid_override_pattern": "*EWEWEWE*E"}, "hybrid_override_pattern"),
])
def test_a_combination_the_code_does_not_implement_is_an_error_by_name(
        bad, match):
    with pytest.raises(ValueError, match=match):
        config_of(params_for(**bad))


def test_the_dense_layer_is_a_pattern_character_of_its_own():
    """``D`` beside the pattern string's other mixers, no ``layer_types``."""
    p = {k: v for k, v in PARAMS.items() if k not in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "num_hidden_layers")}
    c = config_of(dict(p, hybrid_override_pattern="*DWE")).params.hybrid_lm
    assert c.hybrid_override_pattern == "*DWE" and c.heads_for("W") == 4
    with pytest.raises(ValueError, match="intermediate_size"):
        config_of(dict(p, hybrid_override_pattern="*D", intermediate_size=0))


def test_the_recipes_ranges_reach_the_new_leaves():
    """``gate`` and ``up`` of the dense layer and of the shared expert at
    ``initializer_range``, their ``down`` at ``output_initializer_range``."""
    p = params_for(hidden_size=128, vocab_size=1024, intermediate_size=256,
                   shared_expert_intermediate_size=128,
                   embedding_initializer_range=1.0,
                   output_initializer_range=0.01)
    tree = build_model(config_of(p)).init(
        jax.random.key(0), jnp.zeros((1, 8)))["params"]
    flat = jax.tree_util.tree_leaves_with_path(tree)
    stds = {"/".join(k.key for k in path): float(jnp.std(leaf))
            for path, leaf in flat if leaf.ndim > 1}
    assert any("shared/gate" in n for n in stds)
    for name, std in stds.items():
        want = (1.0 if name == "embed/embedding" else
                0.01 if ("o_proj" in name or "down" in name) else 0.15)
        assert std == pytest.approx(want, rel=0.12), name


# ---- the model against the reference

def system_loss_and_grads(p, batch, seed=0):
    model = build_model(config_of(p))
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, batch["x"].shape[1])))["params"]
    loss_of = family_loss(model)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q: loss_of(q, batch)[0]))(params)
    return params, float(loss), grads


@pytest.mark.parametrize("size,window,held", [
    (params_for, 16, [0, 16]), (two_blocks, 40, [3, 8]),
    (two_blocks, 100, [8, 8]), (two_blocks, 1, [0, 4])],
    ids=["five-16-all", "two-40-8", "two-100-8", "two-1-4"])
def test_loss_and_every_gradient_leaf_equal_the_references(size, window,
                                                           held):
    """The 5-block pattern, and the dense block with one sliding sparse
    block, on rows of 40 positions (beyond YaRN's original 32) under a
    window shorter than the row, equal to it, longer and of one key; all
    the experts held, or a share of them."""
    p = size(sliding_window=window, experts_held=held)
    batch = batch_of(seed=3, seq=40)
    params, loss, grads = system_loss_and_grads(p, batch)
    ref_loss, ref_grads = ref.make_loss(p, "highest", with_grad=True)(
        params, batch)
    assert loss == pytest.approx(float(ref_loss), rel=3e-6)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    leaves = 68 if size is params_for else 26
    assert len(flat) == len(jax.tree.leaves(ref_grads)) == leaves
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 3e-5, (jax.tree_util.keystr(path), rel(g, r))
    # every leaf but the correction biases, which rest; a query that sees
    # one key has no say in its weight (the sliding layer's q and k)
    touched = sum(bool(jnp.any(g)) for _, g in flat)
    biases = 4 if size is params_for else 1
    assert touched == leaves - biases - (2 if window == 1 else 0)


WRONG_MODELS = [
    ("48 heads on a sliding layer", {"heads_by_type": False}),
    ("the whole head rotated on a full layer", {"partial": False}),
    ("the pass-through half scaled by attention_factor",
     {"scale_pass": True}),
    ("plain frequencies for YaRN", {"yarn": False}),
    ("a window twice as long", {"window": 32}),
    ("softmax scores", {"sigmoid": False}),
    ("the 2.5 left out", {"scaling": False}),
    ("the shared expert left out", {"shared": False}),
    ("the shared expert scaled", {"shared_scaled": True}),
    ("a relu2 shared expert", {"shared_gated": False}),
    ("the dense layer routed", {"dense_routed": True}),
    ("an unmasked attention", {"causal": False}),
]


# ---- rotary positions

def test_yarn_at_the_published_numbers_is_the_written_out_formula():
    """Over the 32 frequencies of the 64 dimensions that turn: ``low`` and
    ``high`` recomputed, 5 and 16."""
    rope = SHIPPED["model_config"]["train"]["params"]["rope_parameters"]
    full = rope[FULL]
    assert (full["factor"], full["original_max_position_embeddings"],
            full["beta_fast"], full["beta_slow"], full["rope_theta"],
            full["partial_rotary_factor"]) == (64, 4096, 64, 1, 500000, 0.5)

    def c(r):  # the dimension that turns r times over 4,096 positions
        return 32 * math.log(4096 / (2 * math.pi * r)) / math.log(500000)

    assert (c(64), c(1)) == (pytest.approx(5.66, abs=0.01),
                             pytest.approx(15.80, abs=0.01))
    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    assert ref.yarn_correction_range(full, 64) == (5, 16)
    want = []
    for m in range(32):
        b = 500000 ** (-m / 32)
        keep = 1 - min(max((m - low) / (high - low), 0), 1)
        want.append((1 - keep) * b / 64 + keep * b)
    scale = 0.1 * math.log(64) + 1
    assert scale == pytest.approx(1.4158883083359672, rel=1e-15)
    parsed = RopeParameters.from_json(full)
    assert parsed.rotary_dim(128) == 64
    for freqs, a in (ref.rope_frequencies(full, 64),
                     hybrid_lm.rope_frequencies(parsed, 64)):
        np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-12)
        assert a == pytest.approx(scale, rel=1e-15)
    assert want[5] == 500000 ** (-5 / 32)  # at and below low: as published
    assert want[16] == pytest.approx(500000 ** (-16 / 32) / 64)  # from high
    cos, sin = hybrid_lm.rope_tables(parsed, 16, 128)
    assert cos.shape == sin.shape == (16, 32)
    sliding = RopeParameters.from_json(rope[SLIDING])
    plain, one = hybrid_lm.rope_frequencies(sliding, 128)
    np.testing.assert_allclose(plain, [10000 ** (-m / 64)
                                       for m in range(64)], rtol=1e-12)
    assert one == 1.0 and hybrid_lm.rope_tables(sliding, 16, 128)[0].shape == (
        16, 64)


def test_half_a_head_turns_and_the_other_half_passes_unscaled():
    rope = RopeParameters.from_json(
        {**ROPE[FULL], "original_max_position_embeddings": 32})
    u = jax.random.normal(jax.random.key(0), (1, 40, 2, 32))
    cos, sin = hybrid_lm.rope_tables(rope, 40, 32)
    assert cos.shape == (40, 8)
    got = hybrid_lm.apply_rope(u, cos, sin)
    freqs, a = ref.rope_frequencies(ref.rope_entry(PARAMS, FULL), 16)
    assert a == pytest.approx(0.1 * math.log(4) + 1)
    np.testing.assert_allclose(got, ref.apply_rope(u, freqs, a), atol=1e-6)
    np.testing.assert_array_equal(got[..., 16:], u[..., 16:])
    # position 0 is the scale alone, on the part that turns
    np.testing.assert_allclose(got[:, 0, :, :16], u[:, 0, :, :16] * a,
                               atol=1e-6)
    # dimension m pairs with m + 8: a product depends on i - j only
    same = jnp.broadcast_to(u[:, :1], u.shape)
    r = hybrid_lm.apply_rope(same, cos, sin)[..., :16]
    np.testing.assert_allclose(jnp.sum(r[0, 7, 0] * r[0, 4, 0]),
                               jnp.sum(r[0, 30, 0] * r[0, 27, 0]), rtol=1e-4)
    # the whole head turned is what it was
    whole = RopeParameters.from_json(ROPE[SLIDING])
    cos, sin = hybrid_lm.rope_tables(whole, 40, 32)
    full_turn = hybrid_lm.apply_rope(u, cos, sin)
    f, one = ref.rope_frequencies(ROPE[SLIDING], 32)
    np.testing.assert_allclose(full_turn, ref.apply_rope(u, f, one),
                               atol=1e-6)


# ---- the feed-forwards every token goes through

@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_feed_forward_is_the_written_out_formula(gated):
    x = jax.random.normal(jax.random.key(0), (24, 16))
    module = hybrid_lm.FeedForward(40, 0.3, 0.3, jnp.float32, gated)
    params = module.init(jax.random.key(1), x)["params"]
    assert set(params) == ({"gate", "up", "down"} if gated
                           else {"up", "down"})
    up = x @ params["up"]["kernel"]
    want = ((jax.nn.silu(x @ params["gate"]["kernel"]) * up) if gated
            else jnp.square(jax.nn.relu(up))) @ params["down"]["kernel"]
    np.testing.assert_allclose(module.apply({"params": params}, x), want,
                               atol=1e-6)
    np.testing.assert_allclose(ref.gated_mlp(params, x, gated) if gated
                               else want, want, atol=1e-6)


# ---- the share

def test_the_eight_shares_routed_parts_are_the_uncut_expert_layer():
    """The shipped deployment in small: 64 experts, 8 a token, eight chips
    holding 8 each.  What they compute, added up with the shared expert
    counted once, is the reference's whole layer, and every (token, choice)
    pair lands on exactly one of them."""
    p = params_for(num_experts=64, num_experts_per_tok=8,
                   experts_held=[0, 64], hidden_size=32,
                   moe_intermediate_size=16,
                   shared_expert_intermediate_size=16)
    whole = config_of(p).params.hybrid_lm
    x = jax.random.normal(jax.random.key(2), (2, SEQ, 32))
    full = hybrid_lm.MoEMixer(whole)
    variables = jax.jit(full.init)(jax.random.key(1), x)
    want, stats = jax.jit(full.apply)(variables, x)
    params = variables["params"]
    assert set(params) == {"router", "e_score_correction_bias", "experts",
                           "shared"}
    np.testing.assert_allclose(
        want, ref.moe_layer(params, x, p, held=(0, 64)), atol=2e-5)
    assert int(stats[0]) == 2 * SEQ * 8
    shared = ref.gated_mlp(params["shared"], x)
    assert float(jnp.abs(shared).max()) > 0
    total, pairs = shared, 0
    for first in range(0, 64, 8):
        cut = dataclasses.replace(whole, experts_held=(first, 8))
        held = {**params, "experts": {k: v[first:first + 8] for k, v in
                                      params["experts"].items()}}
        out, st = jax.jit(hybrid_lm.MoEMixer(cut).apply)({"params": held}, x)
        np.testing.assert_allclose(
            out, ref.moe_layer(held, x, p, held=(first, 8)), atol=2e-5)
        routed = ref.moe_layer(held, x, p, held=(first, 8), shared=False)
        np.testing.assert_allclose(out - shared, routed, atol=2e-5)
        assert float(jnp.abs(routed).max()) > 0
        total, pairs = total + routed, pairs + int(st[0])
    assert pairs == 2 * SEQ * 8
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_sigmoid_weights_are_scaled_shares_of_the_chosen_scores():
    p = params_for()
    x = jax.random.normal(jax.random.key(4), (24, 64))
    router = {"router": {"kernel": jax.random.normal(jax.random.key(5),
                                                     (64, 16))},
              "e_score_correction_bias": jnp.zeros((16,))}
    ids, weights = ref.route(router, x, p)
    scores = jax.nn.sigmoid(x @ router["router"]["kernel"])
    assert ids.shape == (24, 2)
    np.testing.assert_array_equal(ids, jax.lax.top_k(scores, 2)[1])
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.5, rtol=1e-6)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(weights[:, 0] / weights[:, 1],
                               chosen[:, 0] / chosen[:, 1], rtol=1e-5)


# ---- the normal path

def test_trainer_steps_counts_pairs_saves_and_restores(tmp_path):
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    mc = config_of(two_blocks(experts_held=[0, 4]))
    trainer = make_trainer(mc, SEQ, seed=3)
    losses = [trainer.train_epoch([batch_of(seed=s)])[0] for s in (1, 1, 1)]
    assert losses[2] < losses[0] and np.isfinite(losses).all()
    pairs = trainer.epoch_counters["moe_held_pairs"]
    # a sparse block of 2 rows x 64 tokens x 2 choices, a quarter held
    assert pairs.shape == (1,) and 0 < pairs[0] < 2 * SEQ * 2
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
        "params": two_blocks(experts_held=[0, 4])}}))
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

    trainer = make_trainer(config_of(two_blocks()), SEQ)
    with pytest.raises(UnsupportedModelType, match="hybrid_lm"):
        export_model(str(tmp_path / "a"), trainer)


# ---- the benchmark's comparison: two Adam steps, and the wrong models

SYSTEM_RUNS, JUDGED = {}, {}


def _system_run(dtype, size):
    """The program's first two steps (one trainer a dtype and size, shared
    by the cases): the parameters each step started from and its loss, and
    Adam's first moment after the first."""
    from shifu_tensorflow_tpu.train import make_trainer

    if (dtype, size) not in SYSTEM_RUNS:
        trainer = make_trainer(config_of(size()), SEQ, seed=1, dtype=dtype)
        steps, moment = [], None
        for batch in (batch_of(seed=11), batch_of(seed=12)):
            before = jax.device_get(trainer.state.params)
            steps.append((batch, before, trainer.train_epoch([batch])[0]))
            if moment is None:
                moment = jax.device_get(
                    train_lm_stream.first_moment(trainer.state.opt_state))
        SYSTEM_RUNS[dtype, size] = (steps, moment)
    return SYSTEM_RUNS[dtype, size]


def _compare(dtype=jnp.float32, check=None, scale=None, size=two_blocks,
             **ref_kw):
    """The plane's own check at small size: the program takes two Adam
    steps; the reference (possibly a wrong model) judges them.  On the CPU
    a float32 product is exact, so one reference serves as the truth and as
    the stated precision.  ``scale`` = (part of a leaf's name, factor)
    multiplies the reference's gradient on those leaves.  ``size`` gives
    the parameters: the two-block pattern, or the five-block one."""
    steps, moment = _system_run(dtype, size)
    key = (dtype, size, repr(sorted(ref_kw.items())))
    if key not in JUDGED:
        judge = ref.make_loss(size(), "highest", with_grad=True, **ref_kw)
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


def test_comparison_passes_the_programs_two_steps_under_the_shipped_limits():
    got = _compare(size=params_for)
    assert got["ok"], got
    assert got["loss_rel_err"] < 1e-5 and got["update_rel_err"] < 0.02
    assert got["grad_norm_rel_err"] < 1e-3 and got[
        "pooled_grad_rel_err"] < 1e-3
    assert len(got["leaf_update_rel_err"]) == 68
    assert _compare(check=CPU_CHECK, size=params_for)["ok"]


@pytest.mark.parametrize("what,kw", [
    *((what, {"wrong": wrong}) for what, wrong in WRONG_MODELS),
    ("a loss over the wrong shift", {"shift": 2}),
    ("a bf16 step", {"dtype": jnp.bfloat16, "check": CPU_CHECK}),
    ("a gradient off by two", {"scale": ("shared/gate", 2.0)}),
    ("a gradient off by a half", {"scale": ("layers_1/mixer/up", 0.5)}),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(what, kw):
    """The dense block and one sliding sparse block.  The fault is on the
    reference's side (the same disagreement), but for the bf16 step, which
    the program takes itself (--dtype bfloat16).
    The limits are the shipped cell's, but for the bf16 step's (see
    ``CPU_CHECK``; on the chip the shipped limits refuse it, PERF.md)."""
    assert _compare(check=kw.get("check"))["ok"]  # the same, but right
    got = _compare(**kw)
    assert not got["ok"], (what, got)
