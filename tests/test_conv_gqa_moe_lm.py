"""``ModelType: hybrid_lm`` under the public ``lfm2_moe`` keys — gated short
convolutions beside grouped-query attention with a norm on every head's q
and k, a leading dense gated feed-forward, sigmoid-scored top-k gated
experts with no shared expert, the head tied to the embedding — at a small
size on the CPU: the configuration's keys, the model against the plain
reference (``benchmark/reference/conv_gqa_moe_lm.py``), the convolution's
causality, the tied head's two gradients, the attention mixer through the
Pallas kernels in the interpreter at a head of 64, the share of an
expert-parallel deployment, the normal path, the accepted decoders' losses
as they were, and the wrong models the benchmark's comparison must fail."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.planes import train_lm_stream
from benchmark.reference import conv_gqa_moe_lm as ref
from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.models.factory import build_model, family_loss
from shifu_tensorflow_tpu.parallel import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 48
#: the shipped shape in small: a convolution over the dense block, attention
#: and a convolution over sparse ones.  The initialiser is wide (0.02
#: published) so that at this size the scores and the router's logits are of
#: order 1, as a trained model's: a tap, a norm, a head count, a shift or a
#: gate that is wrong then moves loss and gradients
PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 3,
    "layer_types": ["conv", "full_attention", "conv"], "num_dense_layers": 1,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "rope_parameters": {"rope_theta": 100, "rope_type": "default"},
    "norm_eps": 1e-5, "initializer_range": 0.15, "vocab_size": 256,
    "num_experts": 16, "experts_held": [0, 16], "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "lfm2_24b_ep8.json")) as _f:
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
    assert c.hybrid_override_pattern == "CD*ECE"
    # what the family says without keys of its own: a head of hidden /
    # heads, a norm on every head's q and k, gated silu experts and no
    # shared expert, the head tied
    assert (c.head_dim, c.qk_norm, c.hidden_act, c.n_shared_experts,
            c.tie_word_embeddings, c.scoring_func) == (
        16, True, "silu", 0, True, "sigmoid")
    assert (c.conv_L_cache, c.layer_norm_epsilon, c.n_routed_experts,
            c.intermediate_size, c.routed_scaling_factor) == (
        3, 1e-5, 16, 96, 1.0)
    # ONE rotary parametrisation, the attention layers'
    assert c.rope_parameters == (("full_attention", RopeParameters(
        "default", 100.0, 1.0, 0, 32.0, 1.0, 0.0, 1.0)),)
    assert c.rope_for("*").rotary_dim(c.head_dim) == 16
    assert (c.heads_for("*"), c.num_key_value_heads) == (4, 2)
    # the same said outright, and under the family's other spelling
    said = params_for(qk_norm=True, hidden_act="silu", n_shared_experts=0,
                      head_dim=16, tie_embedding=True,
                      hybrid_override_pattern="CD*ECE")
    assert config_of(said).params.hybrid_lm == c
    # the head may be its own where the configuration says so
    untied = config_of(params_for(tie_word_embeddings=False))
    assert not untied.params.hybrid_lm.tie_word_embeddings
    assert tree_of(untied)["lm_head"]["kernel"].shape == (64, 256)
    assert "lm_head" not in tree_of(config_of(params_for()))
    # two leading dense blocks, as published
    two = config_of(params_for(num_dense_layers=2)).params.hybrid_lm
    assert two.hybrid_override_pattern == "CD*DCE"


def test_the_shipped_file_parses_to_the_cut():
    mc = ModelConfig.from_json(SHIPPED["model_config"])
    c = mc.params.hybrid_lm
    assert c.hybrid_override_pattern == "CD*ECECECE"
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.qk_norm, c.conv_L_cache) == (
        2048, 32, 8, 64, True, 3)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.moe_intermediate_size, c.n_shared_experts, c.intermediate_size,
            c.routed_scaling_factor, c.hidden_act) == (
        64, (0, 8), 4, 1536, 0, 11776, 1.0, "silu")
    assert (c.tie_word_embeddings, c.layer_norm_epsilon,
            c.num_nextn_predict_layers) == (True, 1e-5, 0)
    assert c.rope_for("*").rope_theta == 1e6
    # the recipe's ranges and the share's tile, as `assumed` states them:
    # 0.02 / sqrt(2 x 40 published blocks); one tile holds an expert's
    # 1,024 uniform pairs and half as many again
    assert c.embedding_std == 1.0
    assert c.output_std == pytest.approx(0.02 / math.sqrt(2 * 40), rel=1e-4)
    assert c.expert_tile >= 1.5 * 16384 * 4 // 64 and not c.expert_tile % 128
    tree = tree_of(mc)
    assert sum(x.size for x in jax.tree.leaves(tree)) == 469_284_992 + 4 * 64
    assert set(tree) == {"embed", "final_norm"} | {
        f"layers_{i}" for i in range(10)}
    conv = tree["layers_0"]["mixer"]
    assert jax.tree.map(jnp.shape, conv) == {
        "in_proj": {"kernel": (2048, 6144)}, "conv": {"kernel": (3, 2048)},
        "out_proj": {"kernel": (2048, 2048)}}
    assert sum(x.size for x in jax.tree.leaves(conv)) == 16_783_360
    attn = tree["layers_2"]["mixer"]
    assert jax.tree.map(jnp.shape, attn) == {
        "q_proj": {"kernel": (2048, 2048)}, "k_proj": {"kernel": (2048, 512)},
        "v_proj": {"kernel": (2048, 512)}, "o_proj": {"kernel": (2048, 2048)},
        "q_norm": {"scale": (64,)}, "k_norm": {"scale": (64,)}}
    assert sum(x.size for x in jax.tree.leaves(attn)) == 10_485_888
    assert set(tree["layers_3"]["mixer"]) == {
        "router", "e_score_correction_bias", "experts"}


@pytest.mark.parametrize("bad,match", [
    ({"conv_bias": True}, "conv_bias"),
    ({"use_expert_bias": False}, "use_expert_bias"),
    ({"hidden_act": "relu2"}, "hidden_act"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"qk_norm": False}, "qk_norm"),
    ({"num_dense_layers": 4}, "num_dense_layers"),
    ({"mlp_layer_types": ["dense", "sparse", "sparse"]}, "mlp_layer_types"),
    ({"layer_types": ["conv", "mamba", "conv"]}, "mamba"),
    ({"hybrid_override_pattern": "CE*ECE"}, "hybrid_override_pattern"),
    ({"num_hidden_layers": 4}, "num_hidden_layers"),
    ({"conv_L_cache": 0}, "conv_L_cache"),
    ({"rope_parameters": {"rope_theta": 100, "rope_type": "linear"}},
     "rope_type"),
    ({"rope_parameters": {"rope_theta": 100, "llama_3": 1}}, "llama_3"),
    ({"num_key_value_heads": 3}, "num_key_value_heads"),
    ({"norm_eps": 1e-5, "layer_norm_epsilon": 1e-6}, "the same number"),
    ({"num_nextn_predict_layers": 1}, "ends in"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else "")
def test_a_combination_the_code_does_not_implement_is_an_error_by_name(
        bad, match):
    with pytest.raises(ValueError, match=match):
        config_of(params_for(**bad))


def test_the_keys_reach_the_other_shapes_too():
    """``num_dense_layers``, one ``rope_parameters`` for every attention
    layer type, ``qk_norm`` and a tied head are read wherever a
    configuration states them, not only beside ``conv`` layers."""
    p = {"ModelType": "hybrid_lm", "hidden_size": 32, "vocab_size": 64,
         "layer_types": ["sliding_attention", "full_attention"],
         "num_dense_layers": 1, "sliding_window": 4, "intermediate_size": 48,
         "hidden_act": "silu", "n_shared_experts": 0, "num_experts": 4,
         "num_experts_per_tok": 2, "moe_intermediate_size": 16,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 8,
         "rope_parameters": {"rope_theta": 100.0}, "qk_norm": True,
         "tie_word_embeddings": True}
    c = config_of(p).params.hybrid_lm
    assert c.hybrid_override_pattern == "WD*E"
    assert [k for k, _ in c.rope_parameters] == ["full_attention",
                                                 "sliding_attention"]
    tree = tree_of(config_of(p))
    assert "lm_head" not in tree
    assert tree["layers_0"]["mixer"]["q_norm"]["scale"].shape == (8,)
    # and without them the attention mixer holds what it held
    plain = tree_of(config_of({k: v for k, v in p.items() if k not in (
        "qk_norm", "tie_word_embeddings")}))
    assert set(plain["layers_0"]["mixer"]) == {"q_proj", "k_proj", "v_proj",
                                               "o_proj"}
    assert plain["lm_head"]["kernel"].shape == (32, 64)


# ---- the model against the reference

def system(p, batch, seed=0):
    model = build_model(config_of(p))
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, batch["x"].shape[1])))["params"]
    return model, params


@pytest.mark.parametrize("kinds,dense,held", [
    (["conv", "full_attention", "conv"], 1, [0, 16]),
    (["conv", "full_attention", "conv", "conv", "conv"], 1, [4, 8]),
    (["conv", "conv", "full_attention"], 2, [8, 8]),
    (["full_attention", "conv"], 0, [0, 4])],
    ids=["three-all", "the-cut-8-of-16", "two-dense-8", "no-dense-4"])
def test_logits_loss_and_every_gradient_leaf_equal_the_references(
        kinds, dense, held):
    """The shipped order in small, the published leading pair, attention
    first: the logits of the tied head, the loss and its gradient on every
    leaf, with all the experts held or a share of them."""
    p = params_for(layer_types=kinds, num_hidden_layers=len(kinds),
                   num_dense_layers=dense, experts_held=held)
    batch = batch_of(seed=3)
    model, params = system(p, batch)
    assert "lm_head" not in params
    ids = ref.token_ids(batch["x"])
    np.testing.assert_allclose(
        jax.jit(model.apply)({"params": params}, batch["x"]),
        ref.logits(params, ids, p), atol=3e-5)
    loss_of = family_loss(model)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda q: (lambda out: (out[0], out[2]))(loss_of(q, batch)),
        has_aux=True))(params)
    ref_loss, ref_grads = ref.make_loss(p, "highest", with_grad=True)(
        params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=3e-6)
    assert set(counters) == {"moe_held_pairs", "moe_held_max"}
    sparse = len(kinds) - dense
    assert 0 < int(counters["moe_held_pairs"]) <= sparse * 2 * SEQ * 2
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads))
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 3e-5, (jax.tree_util.keystr(path), rel(g, r))
    # every leaf but the expert biases, which rest
    assert sum(not bool(jnp.any(g)) for _, g in flat) == sparse


def test_the_convolution_is_causal_three_taps_and_the_last_is_the_present():
    """``c_t`` reads ``g_{t-2}``, ``g_{t-1}`` and ``g_t`` and nothing else:
    a token changed at position 20 leaves the operator's output before 20
    as it was, moves 20, 21 and 22 and nothing after them; with the taps
    set to (0, 0, 1) the operator is ``(C * B * u) W_out``."""
    c = config_of(params_for()).params.hybrid_lm
    x = jax.random.normal(jax.random.key(1), (2, SEQ, 64))
    mixer = hybrid_lm.ShortConvMixer(c)
    params = jax.jit(mixer.init)(jax.random.key(2), x)["params"]
    assert jax.tree.map(jnp.shape, params) == {
        "in_proj": {"kernel": (64, 192)}, "conv": {"kernel": (3, 64)},
        "out_proj": {"kernel": (64, 64)}}
    # torch's Conv1d default for 3 taps a channel: uniform +- 1/sqrt(3)
    assert float(jnp.abs(params["conv"]["kernel"]).max()) <= 1 / math.sqrt(3)
    out = jax.jit(mixer.apply)({"params": params}, x)
    np.testing.assert_allclose(out, ref.conv_operator(params, x, PARAMS),
                               atol=2e-5)
    moved = jnp.any(jnp.abs(jax.jit(mixer.apply)(
        {"params": params}, x.at[:, 20].add(1.0)) - out) > 1e-6, axis=(0, 2))
    assert [int(t) for t in jnp.nonzero(moved)[0]] == [20, 21, 22]
    present = {**params, "conv": {"kernel": jnp.zeros((3, 64)).at[2].set(1)}}
    bcu = x @ params["in_proj"]["kernel"]
    np.testing.assert_allclose(
        jax.jit(mixer.apply)({"params": present}, x),
        (bcu[..., 64:128] * bcu[..., :64] * bcu[..., 128:])
        @ params["out_proj"]["kernel"], atol=2e-5)


def test_the_tables_gradient_is_the_lookups_plus_the_heads():
    """``Emb`` is read twice, by the lookup and, transposed, by the head:
    the step's gradient on it is the sum of the two parts, and both are
    there."""
    p = params_for()
    batch = batch_of(seed=6)
    model, params = system(p, batch)

    def loss_with(lookup, head):
        """The model's loss with the table the lookup reads and the one
        the head reads told apart."""
        def fn(table_l, table_h):
            ids = ref.token_ids(batch["x"])
            q = {**params, "embed": {"embedding": table_l}}
            h = ref.hidden_states(q, ids, p)
            logp = jax.nn.log_softmax(h[:, :-1] @ table_h.T, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, ids[:, 1:, None], axis=-1))
        return fn(lookup, head)

    table = params["embed"]["embedding"]
    by_lookup, by_head = jax.jit(jax.grad(loss_with, (0, 1)))(table, table)
    whole = jax.jit(jax.grad(
        lambda q: family_loss(model)(q, batch)[0]))(params)["embed"][
            "embedding"]
    assert float(jnp.linalg.norm(by_lookup)) > 0 < float(
        jnp.linalg.norm(by_head))
    assert rel(whole, by_lookup + by_head) < 1e-5
    assert rel(whole, by_lookup) > 0.1 and rel(whole, by_head) > 0.1
    # every row of the held vocabulary is scored, so the head's part
    # reaches rows no token of the batch looks up (a row's last token is
    # looked up and, the model being causal, reaches no scored position)
    seen = np.zeros(256, bool)
    seen[batch["x"][:, :-1].astype(int).ravel()] = True
    assert bool(jnp.all(jnp.any(by_lookup != 0, axis=1) == seen))
    assert bool(jnp.all(jnp.any(by_head != 0, axis=1)))


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_the_heads_barrier_maximum_is_the_plain_log_softmax(tied,
                                                            monkeypatch):
    """At the shipped head's 8,192 columns, a width whose row maximum the
    chip's compiler would window, ``LMHead`` takes the maximum through an
    optimization barrier (``hybrid_lm.log_softmax``): its loss and every
    gradient leaf are the plain expression's, finite, and a target at the
    last column is picked as float64 arithmetic picks it."""
    vocab, hidden = hybrid_lm.WINDOWED_ROW, 64
    head = hybrid_lm.LMHead(vocab, hidden, 0.15, tied=tied)
    k = jax.random.split(jax.random.key(39), 3)
    h = jax.random.normal(k[0], (1, 9, hidden))
    ids = jax.random.randint(k[1], (1, 9), 0, vocab).at[0, 4].set(vocab - 1)
    live = jnp.ones((1,))
    table = jax.random.normal(k[2], (vocab, hidden)) * 0.15
    variables = head.init(jax.random.key(0), h, ids, live,
                          table if tied else None)

    def grads():
        """d loss / d (h, the table) tied, d loss / d (the kernel, h)
        untied."""
        def loss(v, h, table):
            return head.apply(v, h, ids, live, table if tied else None)[0]
        return jax.jit(jax.value_and_grad(loss, (1, 2) if tied else (0, 1)))(
            variables, h, table)

    loss, grad = grads()
    monkeypatch.setattr(hybrid_lm, "log_softmax",
                        lambda x: jax.nn.log_softmax(x, axis=-1))
    plain_loss, plain_grad = grads()
    leaves = jax.tree.leaves(grad)
    assert len(leaves) == 2
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
    assert float(loss) == pytest.approx(float(plain_loss), rel=1e-6)
    for g, want in zip(leaves, jax.tree.leaves(plain_grad)):
        assert float(jnp.linalg.norm(want)) > 0
        assert rel(g, want) < 1e-6
    w = np.asarray(table, np.float64).T if tied else np.asarray(
        variables["params"]["kernel"], np.float64)
    logits = np.asarray(h, np.float64)[0, :-1] @ w
    top = logits.max(axis=-1, keepdims=True)
    logp = logits - top - np.log(np.exp(logits - top).sum(-1, keepdims=True))
    picked = logp[np.arange(8), np.asarray(ids)[0, 1:]]
    assert float(loss) == pytest.approx(-picked.sum(), rel=1e-5)
    assert picked[3] == logp[3, vocab - 1]


def test_the_head_norms_scale_every_head_alike_before_the_rotation():
    """One scale of ``head_dim`` for all query heads and one for all key
    heads: the mixer against the reference's operator on scales that are
    not 1, where a norm after the rotation or a scale a head is another
    function."""
    c = config_of(params_for()).params.hybrid_lm
    x = jax.random.normal(jax.random.key(3), (2, SEQ, 64))
    causal = jax.tree_util.Partial(ring.full_attention, causal=True)
    mixer = hybrid_lm.AttentionMixer(c, causal)
    params = jax.jit(mixer.init)(jax.random.key(4), x)["params"]
    for name, key in (("q_norm", 5), ("k_norm", 6)):
        params[name] = {"scale": 1.0 + 0.5 * jax.random.normal(
            jax.random.key(key), (16,))}
    out = jax.jit(mixer.apply)({"params": params}, x)
    np.testing.assert_allclose(
        out, ref.attention_operator(params, x, PARAMS), atol=2e-5)
    for wrong in ({"norm_after_rope": True}, {"scale_a_head": True},
                  {"qk_norm": False}, {"interleaved_groups": True}):
        other = ref.attention_operator(params, x, PARAMS, **wrong)
        assert rel(other, out) > 1e-2, wrong


def test_the_attention_mixer_through_the_flash_kernels_at_a_head_of_64(
        pallas_interpret):
    """The published head of 64, which the kernels pad to 128 lanes
    themselves, 4 query heads over 2 KV heads with the head norms and the
    rotation in front: the three causal flash kernels in the interpreter,
    tiles of 128 over 256 positions, forward and gradient on every leaf,
    against the plain form."""
    from shifu_tensorflow_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    p = params_for(hidden_size=256, num_attention_heads=4,
                   num_key_value_heads=2, initializer_range=0.05,
                   rope_parameters={"rope_theta": 1000000,
                                    "rope_type": "default"})
    c = config_of(p).params.hybrid_lm
    assert c.head_dim == 64
    x = jax.random.normal(jax.random.key(1), (1, 256, 256))
    cot = jax.random.normal(jax.random.key(2), (1, 256, 256))
    mixer = hybrid_lm.AttentionMixer(
        c, lambda q, k, v: flash_attention(q, k, v, True, 128, 128))
    params = jax.jit(mixer.init)(jax.random.key(3), x)["params"]

    def program(q):
        return jnp.sum(mixer.apply({"params": q}, x) * cot)

    def plain(q):
        return jnp.sum(ref.attention_operator(q, x, p) * cot)

    got, grads = jax.jit(jax.value_and_grad(program))(params)
    want, ref_grads = jax.jit(jax.value_and_grad(plain))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    assert len(jax.tree.leaves(grads)) == 6
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 5e-5, (jax.tree_util.keystr(path), rel(g, r))


# ---- the share

def test_the_eight_shares_parts_are_the_uncut_expert_layer():
    """The shipped deployment in small: 64 experts, 4 a token, eight chips
    holding 8 each, no shared expert.  What they compute, added up, is the
    reference's whole layer, and every (token, choice) pair lands on
    exactly one of them."""
    p = params_for(num_experts=64, num_experts_per_tok=4,
                   experts_held=[0, 64], hidden_size=32,
                   moe_intermediate_size=16, num_attention_heads=2)
    whole = config_of(p).params.hybrid_lm
    x = jax.random.normal(jax.random.key(2), (2, SEQ, 32))
    full = hybrid_lm.MoEMixer(whole)
    variables = jax.jit(full.init)(jax.random.key(1), x)
    want, stats = jax.jit(full.apply)(variables, x)
    params = variables["params"]
    assert set(params) == {"router", "e_score_correction_bias", "experts"}
    np.testing.assert_allclose(
        want, ref.moe_layer(params, x, p, held=(0, 64)), atol=2e-5)
    assert int(stats[0]) == 2 * SEQ * 4
    total, pairs = jnp.zeros_like(want), 0
    for first in range(0, 64, 8):
        cut = dataclasses.replace(whole, experts_held=(first, 8))
        held = {**params, "experts": {k: v[first:first + 8] for k, v in
                                      params["experts"].items()}}
        out, st = jax.jit(hybrid_lm.MoEMixer(cut).apply)({"params": held}, x)
        routed = ref.moe_layer(held, x, p, held=(first, 8))
        np.testing.assert_allclose(out, routed, atol=2e-5)
        assert float(jnp.abs(routed).max()) > 0
        total, pairs = total + routed, pairs + int(st[0])
    assert pairs == 2 * SEQ * 4
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_sigmoid_weights_are_shares_of_the_chosen_scores():
    p = params_for()
    x = jax.random.normal(jax.random.key(4), (24, 64))
    router = {"router": {"kernel": jax.random.normal(jax.random.key(5),
                                                     (64, 16))},
              "e_score_correction_bias": jnp.zeros((16,))}
    ids, weights = ref.route(router, x, p)
    scores = jax.nn.sigmoid(x @ router["router"]["kernel"])
    np.testing.assert_array_equal(ids, jax.lax.top_k(scores, 2)[1])
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, rtol=1e-6)
    # the bias moves the choice and not the weight
    router["e_score_correction_bias"] = jnp.zeros((16,)).at[3].set(10.0)
    ids, weights = ref.route(router, x, p)
    assert bool(jnp.all(ids[:, 0] == 3))
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.0, rtol=1e-6)


# ---- the accepted decoders, as they were

#: the four accepted decoders' tiny models (their test files' ``PARAMS``)
#: at the parent commit, parameters from ``jax.random.key(38)``, 2 rows of
#: 32 ids from ``default_rng(38)``, under the tests' own XLA:CPU flags:
#: (loss, global gradient norm) as float hex, and the step's counters
AS_THE_PARENT_HAD_THEM = {
    "test_hybrid_lm": ("0x1.628d920000000p+2", "0x1.5503be0000000p+1",
                       {"moe_held_max": 23, "moe_held_pairs": 256}),
    "test_swa_moe_lm": ("0x1.86cb980000000p+2", "0x1.8eaeda0000000p+2",
                        {"moe_held_max": 33, "moe_held_pairs": 256}),
    "test_mixed_gqa_moe_lm": ("0x1.977bd20000000p+2", "0x1.2209a40000000p+3",
                              {"moe_held_max": 27, "moe_held_pairs": 512}),
    "test_mla_mtp_moe_lm": ("0x1.01393e0000000p+3", "0x1.5041780000000p+2",
                            {"main_loss": "0x1.8ad4560000000p+2",
                             "moe_held_max": 24, "moe_held_pairs": 256,
                             "mtp_loss": "0x1.8eb9d40000000p+2"}),
}


@pytest.mark.parametrize("module", sorted(AS_THE_PARENT_HAD_THEM))
def test_the_accepted_decoders_are_bit_for_bit_the_parents(module):
    """``AttentionMixer``, ``LMHead``, ``Layer`` and ``HybridLMConfig``
    changed under the Nemotron, Mellum, Laguna and GLM tiny models: their
    trees, losses, gradient norms and counters are the parent commit's to
    the last bit."""
    import importlib

    import optax

    p = dict(importlib.import_module(module).PARAMS)
    model = build_model(config_of(p))
    ids = np.random.default_rng(38).integers(0, int(p["vocab_size"]), (2, 32))
    batch = {"x": ids.astype(np.float32), "w": np.ones((2, 1), np.float32)}
    params = jax.jit(model.init)(jax.random.key(38),
                                 jnp.zeros((1, 32)))["params"]
    assert params["lm_head"]["kernel"].shape[1] == int(p["vocab_size"])
    assert not any("q_norm" in jax.tree_util.keystr(path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    loss_of = family_loss(model)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda q: (lambda out: (out[0], out[2]))(loss_of(q, batch)),
        has_aux=True))(params)
    want_loss, want_norm, want_counters = AS_THE_PARENT_HAD_THEM[module]
    assert float(loss).hex() == want_loss
    assert float(optax.global_norm(grads)).hex() == want_norm
    assert {k: float(v).hex() if "loss" in k else int(v)
            for k, v in counters.items()} == want_counters


# ---- the normal path

def test_trainer_steps_counts_pairs_saves_and_restores(tmp_path):
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    mc = config_of(params_for(experts_held=[0, 4]))
    trainer = make_trainer(mc, SEQ, seed=3)
    losses = [trainer.train_epoch([batch_of(seed=s)])[0] for s in (1, 1, 1)]
    assert losses[2] < losses[0] and np.isfinite(losses).all()
    found = trainer.epoch_counters
    assert set(found) == {"moe_held_pairs", "moe_held_max"}
    # the two sparse blocks' 2 rows x 48 tokens x 2 choices, a quarter of
    # the experts held
    assert found["moe_held_pairs"].shape == (1,)
    assert 0 < found["moe_held_pairs"][0] < 2 * 2 * SEQ * 2
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
    assert float(again[0].split("train_loss=")[1].split()[0]) < losses[1]


def test_export_refuses_it_by_name_as_it_refuses_the_family(tmp_path):
    from shifu_tensorflow_tpu.config.model_config import UnsupportedModelType
    from shifu_tensorflow_tpu.export.saved_model import export_model
    from shifu_tensorflow_tpu.train import make_trainer

    trainer = make_trainer(config_of(params_for()), SEQ)
    with pytest.raises(UnsupportedModelType, match="hybrid_lm"):
        export_model(str(tmp_path / "a"), trainer)


# ---- the benchmark's comparison: two Adam steps, and the wrong models

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


def _compare(dtype=jnp.float32, check=None, scale=None, wrong=None,
             shift=1):
    """The plane's own check at small size: the program takes two Adam
    steps; the reference (possibly a wrong model) judges them.  On the CPU
    a float32 product is exact, so one reference serves as the truth and as
    the stated precision.  ``scale`` = (part of a leaf's name, factor)
    multiplies the reference's gradient on those leaves."""
    steps, moment = _system_run(dtype)
    key = (dtype, repr(sorted((wrong or {}).items())), shift)
    if key not in JUDGED:
        judge = ref.make_loss(params_for(), "highest", with_grad=True,
                              wrong=wrong, shift=shift)
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
    got = _compare()
    assert got["ok"], got
    assert got["loss_rel_err"] < 1e-5 and got["update_rel_err"] < 0.02
    assert got["small_leaf_update_rel_err"] < 0.02
    assert got["grad_norm_rel_err"] < 1e-3 and got[
        "pooled_grad_rel_err"] < 1e-3
    assert len(got["leaf_update_rel_err"]) == 33
    assert _compare(check=CPU_CHECK)["ok"]


#: what each moves, at this size, is in PERF.md section 2
WRONG_MODELS = [
    ("the taps reading one token ahead", {"shift": 1}),
    ("the taps lagging one token", {"shift": -1}),
    ("B and C exchanged", {"swap_bc": True}),
    ("the gate B left out", {"gate_b": False}),
    ("a SiLU after the taps", {"activation": True}),
    ("a fourth tap", {"fourth_tap": True}),
    ("a bias on the taps", {"bias": 0.1}),
    ("the head norms left out", {"qk_norm": False}),
    ("the head norms after the rotation", {"norm_after_rope": True}),
    ("one scale a head", {"scale_a_head": True}),
    ("two heads of 32 for four of 16", {"wide_heads": True}),
    ("a KV head serving the wrong query heads", {"interleaved_groups": True}),
    ("an untied head", {"untied": True}),
    ("the softmax's tail dropped from the loss", {"lse_max": True}),
    ("the head's backward without the softmax term", {"lse_constant": True}),
    ("softmax scores", {"sigmoid": False}),
    ("the weights not normalised", {"normalise": False}),
    ("a shared expert added", {"shared": True}),
    ("the final norm left out", {"final_norm": False}),
    ("an unmasked attention", {"causal": False}),
]


@pytest.mark.parametrize("what,kw", [
    *((what, {"wrong": wrong}) for what, wrong in WRONG_MODELS),
    ("a target two tokens on", {"shift": 2}),
    ("a bf16 step", {"dtype": jnp.bfloat16, "check": CPU_CHECK}),
    ("a gradient off by two", {"scale": ("in_proj", 2.0)}),
    ("a gradient off by a half", {"scale": ("q_norm", 0.5)}),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(what, kw):
    """The fault is on the reference's side (the same disagreement), but
    for the bf16 step, which the program takes itself (--dtype bfloat16).
    The limits are the shipped cell's, but for the bf16 step's (see
    ``CPU_CHECK``; on the chip the shipped limits refuse it, PERF.md)."""
    assert _compare(check=kw.get("check"))["ok"]  # the same, but right
    got = _compare(**kw)
    assert not got["ok"], (what, got)
