"""``ModelType: hybrid_lm`` under the public ``glm4_moe_lite`` keys — latent
attention (low-rank query and key/value paths with a norm on each latent,
a rotary part decoupled from the rest of the head, one rotary key for
every head), a leading dense gated feed-forward, sigmoid-scored top-k gated
experts beside a gated shared expert, and one multi-token prediction
module in the loss — at a small size on the CPU: the configuration's keys,
the model against the plain reference
(``benchmark/reference/mla_mtp_moe_lm.py``), the mixer through the Pallas
kernels in the interpreter at a head of 256, the share of an expert-parallel
deployment, the module's shifts and the two losses' gradients, the normal
path, the accepted decoders' losses as they were, and the wrong models the
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
from benchmark.reference import mla_mtp_moe_lm as ref
from shifu_tensorflow_tpu.config.model_config import (
    LATENT,
    ModelConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.models.factory import build_model, family_loss
from shifu_tensorflow_tpu.parallel import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 48
#: the shipped shape in small: the leading dense block, one sparse block
#: and the module.  The initialiser is wide (0.02 published) so that at
#: this size the scores and the router's logits are of order 1, as a
#: trained model's: a rotary, a head count, a shift or a gate that is wrong
#: then moves loss and gradients
PARAMS = {
    "ModelType": "hybrid_lm", "Optimizer": "adam", "LearningRate": 1e-3,
    "MiniBatchs": 2, "hidden_size": 64, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32, "rope_theta": 100,
    "rope_scaling": None, "partial_rotary_factor": 1, "rms_norm_eps": 1e-5,
    "initializer_range": 0.15, "vocab_size": 256, "n_routed_experts": 16,
    "experts_held": [0, 16], "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
    "scoring_func": "sigmoid", "attention_bias": False,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3}
with open(os.path.join(ROOT, "benchmark", "configs",
                       "glm47_flash_ep8.json")) as _f:
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
    c = config_of(params_for(num_hidden_layers=5)).params.hybrid_lm
    assert c.hybrid_override_pattern == "LDLELELELE"
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (24, 16, 24, 8, 32)
    assert (c.num_nextn_predict_layers, c.mtp_loss_weight) == (1, 0.3)
    assert (c.heads_for("L"), c.num_key_value_heads) == (4, 4)
    # no key for the shared expert's width in this family: the experts'
    assert (c.intermediate_size, c.moe_intermediate_size,
            c.moe_shared_expert_intermediate_size, c.n_shared_experts) == (
        96, 32, 32, 1)
    assert (c.scoring_func, c.routed_scaling_factor, c.hidden_act) == (
        "sigmoid", 1.8, "silu")
    assert c.rope_parameters == ((LATENT, RopeParameters(
        "default", 100.0, 1.0, 0, 32.0, 1.0, 0.0, 1.0)),)
    assert c.rope_for("L").rotary_dim(c.qk_rope_head_dim) == 8
    # the keys that only confirm a default may be left out
    bare = {k: v for k, v in params_for().items() if k not in (
        "rope_scaling", "partial_rotary_factor", "topk_method", "n_group",
        "topk_group", "scoring_func", "attention_bias", "mtp_loss_weight")}
    assert config_of(bare).params.hybrid_lm == config_of(
        params_for()).params.hybrid_lm
    # the pattern the keys give may also be stated, and L stand in one
    assert config_of(params_for(
        hybrid_override_pattern="LDLE")).params.hybrid_lm == config_of(
            params_for()).params.hybrid_lm
    no_module = config_of(params_for(num_nextn_predict_layers=0))
    assert "mtp" not in tree_of(no_module)


def test_the_shipped_file_parses_to_the_cut():
    mc = ModelConfig.from_json(SHIPPED["model_config"])
    c = mc.params.hybrid_lm
    assert c.hybrid_override_pattern == "LDLELELELE"
    assert (c.hidden_size, c.num_attention_heads, c.q_lora_rank,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (2048, 20, 768, 512, 192, 64, 256)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_per_tok,
            c.moe_intermediate_size, c.moe_shared_expert_intermediate_size,
            c.intermediate_size, c.routed_scaling_factor) == (
        64, (0, 8), 4, 1536, 1536, 10240, 1.8)
    assert (c.num_nextn_predict_layers, c.mtp_loss_weight,
            c.layer_norm_epsilon) == (1, 0.3, 1e-5)
    assert c.rope_for("L").rope_theta == 1e6
    # the recipe's ranges and the share's tile, as `assumed` states them:
    # 0.02 / sqrt(2 x 47 published blocks); one tile holds an expert's 512
    # uniform pairs and 8 standard deviations (22 each) more
    assert c.embedding_std == 1.0
    assert c.output_std == pytest.approx(0.02 / math.sqrt(2 * 47), rel=1e-4)
    assert c.expert_tile >= 8192 * 4 // 64 + 8 * 22 and not c.expert_tile % 128
    tree = tree_of(mc)
    assert sum(x.size for x in jax.tree.leaves(tree)) == 706_518_528 + 5 * 64
    assert set(tree["mtp"]) == {"merge", "attn", "ffn", "final_norm"}
    assert tree["mtp"]["merge"]["proj"]["kernel"].shape == (4096, 2048)
    mixer = tree["layers_0"]["mixer"]
    assert {k: v["kernel"].shape for k, v in mixer.items()
            if "kernel" in v} == {
        "q_a_proj": (2048, 768), "q_b_proj": (768, 20 * 256),
        "kv_a_proj": (2048, 512 + 64), "kv_b_proj": (512, 20 * (192 + 256)),
        "o_proj": (20 * 256, 2048)}
    assert sum(x.size for x in jax.tree.leaves(mixer)) == 21_759_232


@pytest.mark.parametrize("bad,match", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"layer_types": ["full_attention"] * 2}, "layer_types"),
    ({"num_hidden_layers": None}, "num_hidden_layers"),
    ({"first_k_dense_replace": 3}, "first_k_dense_replace"),
    ({"hybrid_override_pattern": "LELE"}, "hybrid_override_pattern"),
    ({"moe_shared_expert_intermediate_size": 24},
     "moe_shared_expert_intermediate_size"),
    ({"q_lora_rank": 0}, "q_lora_rank"),
    ({"qk_rope_head_dim": 0}, "qk_rope_head_dim"),
    ({"qk_rope_head_dim": 7, "qk_nope_head_dim": 25}, "even number"),
    ({"v_head_dim": 16}, "v_head_dim"),
    ({"num_key_value_heads": 2}, "num_key_value_heads"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"mtp_loss_weight": -0.1}, "mtp_loss_weight"),
    ({"n_group": 2}, "n_group"),
    ({"attention_bias": True}, "attention_bias"),
    ({"scoring_func": "tanh"}, "scoring_func"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else "")
def test_a_combination_the_code_does_not_implement_is_an_error_by_name(
        bad, match):
    p = {k: v for k, v in params_for(**bad).items() if v is not None}
    with pytest.raises(ValueError, match=match):
        config_of(p)


@pytest.mark.parametrize("pattern,over,match", [
    ("ME*M", {"num_nextn_predict_layers": 1}, "ends in"),
    ("*", {"num_nextn_predict_layers": 1}, "ends in"),
    ("LE", {}, "q_lora_rank"),
    ("LE", {"q_lora_rank": 24}, "kv_lora_rank"),
])
def test_a_written_out_pattern_is_held_to_what_its_characters_need(
        pattern, over, match):
    """Under the ``nemotron_h`` shape the pattern is written out: the
    module needs a last block, attention then feed-forward, to copy, and an
    ``L`` the latent keys, which bring the pattern with them."""
    p = {"ModelType": "hybrid_lm", "hidden_size": 32, "vocab_size": 64,
         "hybrid_override_pattern": pattern, "mamba_num_heads": 2,
         "mamba_head_dim": 8, "n_groups": 1, "ssm_state_size": 8,
         "chunk_size": 8, **over}
    with pytest.raises(ValueError, match=match):
        config_of(p)


# ---- the model against the reference

def system(p, batch, seed=0):
    model = build_model(config_of(p))
    params = jax.jit(model.init)(
        jax.random.key(seed), jnp.zeros((1, batch["x"].shape[1])))["params"]
    return model, params


@pytest.mark.parametrize("blocks,dense,held,module", [
    (2, 1, [0, 16], 1), (3, 1, [4, 8], 1), (2, 0, [8, 8], 1),
    (2, 1, [0, 4], 0)],
    ids=["two-all", "three-8-of-16", "no-dense-8", "no-module-4"])
def test_logits_losses_and_every_gradient_leaf_equal_the_references(
        blocks, dense, held, module):
    """The dense block and one or two sparse ones, or sparse ones alone,
    with all the experts held or a share of them, with the module and
    without: the main head's logits, the module's, the two losses, their
    weighted sum and its gradient on every leaf."""
    p = params_for(num_hidden_layers=blocks, first_k_dense_replace=dense,
                   experts_held=held, num_nextn_predict_layers=module)
    batch = batch_of(seed=3)
    model, params = system(p, batch)
    ids = ref.token_ids(batch["x"])
    np.testing.assert_allclose(
        jax.jit(model.apply)({"params": params}, batch["x"]),
        ref.logits(params, ids, p), atol=2e-5)
    main, ahead, per_row, stats = jax.jit(
        lambda q: model.apply({"params": q}, batch["x"], batch["w"],
                              method="losses"))(params)
    ref_main, ref_ahead = ref.losses(params, batch, p)
    assert float(main) == pytest.approx(float(ref_main), rel=3e-6)
    assert float(jnp.mean(per_row)) == pytest.approx(float(main), rel=1e-6)
    sparse = blocks - dense + module
    assert 0 < int(stats[0]) <= sparse * 2 * SEQ * 2
    if module:
        assert float(ahead) == pytest.approx(float(ref_ahead), rel=3e-6)
        got = jax.jit(lambda q: model.apply(
            {"params": q}, batch["x"], method="mtp_logits"))(params)
        assert got.shape == (2, SEQ - 2, 256)
        np.testing.assert_allclose(got, ref.mtp_logits(params, ids, p),
                                   atol=2e-5)
    else:
        assert ahead is None and ref_ahead == 0.0 and "mtp" not in params
    loss_of = family_loss(model)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda q: (lambda out: (out[0], out[2]))(loss_of(q, batch)),
        has_aux=True))(params)
    ref_loss, ref_grads = ref.make_loss(p, "highest", with_grad=True)(
        params, batch)
    assert float(loss) == pytest.approx(float(ref_loss), rel=3e-6)
    assert float(loss) == pytest.approx(
        float(main) + (0.3 * float(ahead) if module else 0.0), rel=1e-6)
    names = {"moe_held_pairs", "moe_held_max"} | (
        {"main_loss", "mtp_loss"} if module else set())
    assert set(counters) == names
    if module:
        assert float(counters["mtp_loss"]) == float(ahead)
        assert float(counters["main_loss"]) == float(main)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads))
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 3e-5, (jax.tree_util.keystr(path), rel(g, r))
    # every leaf but the correction biases, which rest
    assert sum(not bool(jnp.any(g)) for _, g in flat) == sparse


def test_the_module_reads_the_next_tokens_embedding_and_scores_the_one_after():
    """Position ``i`` of the module takes ``Emb(t_{i+1})`` and is scored
    against ``t_{i+2}``: the reference with either shifted by one, with
    ``W_m``'s halves swapped gives another loss.  (``h`` taken after the final norm gives the same
    one while that norm's scale rests at 1, and another gradient: the
    comparison's wrong models, below.)"""
    p = params_for()
    batch = batch_of(seed=5)
    model, params = system(p, batch)
    _, ahead, _, _ = jax.jit(lambda q: model.apply(
        {"params": q}, batch["x"], batch["w"], method="losses"))(params)
    assert float(ahead) == pytest.approx(
        float(ref.losses(params, batch, p)[1]), rel=3e-6)
    for wrong in ({"mtp_embed_shift": 0}, {"mtp_embed_shift": 2},
                  {"mtp_target_shift": 1}, {"mtp_swapped": True}):
        other = float(ref.losses(params, batch, p, wrong)[1])
        assert abs(other - float(ahead)) > 1e-3 * float(ahead), wrong


def test_embedding_and_head_receive_both_losses_gradients():
    """``Emb`` and ``W_head`` are the main model's, shared: the step's
    gradient on each is the next-token loss's + 0.3 x the module's, and
    both parts are there; the module's own leaves take the second alone."""
    p = params_for()
    batch = batch_of(seed=6)
    model, params = system(p, batch)

    def part(i):
        return jax.jit(jax.grad(lambda q: model.apply(
            {"params": q}, batch["x"], batch["w"], method="losses")[i]))(
                params)

    main, ahead = part(0), part(1)
    whole = jax.jit(jax.grad(
        lambda q: family_loss(model)(q, batch)[0]))(params)
    for leaf in (("embed", "embedding"), ("lm_head", "kernel")):
        a, b, w = (t[leaf[0]][leaf[1]] for t in (main, ahead, whole))
        assert float(jnp.linalg.norm(a)) > 0 < float(jnp.linalg.norm(b))
        assert rel(w, a + 0.3 * b) < 1e-5
        assert rel(w, a) > 1e-3  # the module's part is no rounding
    assert not any(bool(jnp.any(g)) for g in jax.tree.leaves(main["mtp"]))
    assert rel(whole["mtp"]["merge"]["proj"]["kernel"],
               0.3 * ahead["mtp"]["merge"]["proj"]["kernel"]) < 1e-5
    # the last block's parameters feed the module through h
    assert float(jnp.linalg.norm(
        ahead["layers_3"]["mixer"]["shared"]["down"]["kernel"])) > 0


def test_the_rotary_keys_gradient_is_the_sum_over_the_heads():
    """``k_r`` has no head axis: every head reads the same one, so what
    reaches ``W_kva``'s rotary columns is the sum of what each head's copy
    would get.  A zero probe added to the keys inside the core gives each
    head's own ``dK``; the transposed rotation and ``x`` carry their sum
    back."""
    c = config_of(params_for()).params.hybrid_lm
    n, d_c, d_n, d_r = 4, c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim
    x = jax.random.normal(jax.random.key(7), (2, SEQ, 64))
    cot = jax.random.normal(jax.random.key(8), (2, SEQ, 64))
    causal = jax.tree_util.Partial(ring.full_attention, causal=True)

    def out(params, probe):
        mixer = hybrid_lm.LatentAttentionMixer(
            c, lambda q, k, v: causal(q, k + probe, v))
        return jnp.sum(mixer.apply({"params": params}, x) * cot)

    params = hybrid_lm.LatentAttentionMixer(c, causal).init(
        jax.random.key(9), x)["params"]
    probe = jnp.zeros((2, SEQ, n, d_n + d_r))
    grads, d_k = jax.jit(jax.grad(out, (0, 1)))(params, probe)
    per_head = d_k[..., d_n:]  # (B, S, heads, d_r), after the rotation
    assert all(rel(per_head[:, :, 0], per_head[:, :, h]) > 0.1
               for h in range(1, n))
    cos, sin = hybrid_lm.rope_tables(c.rope_for("L"), SEQ, d_r)
    back = hybrid_lm.apply_rope(jnp.sum(per_head, axis=2, keepdims=True),
                                cos, -sin)[:, :, 0]
    want = jnp.einsum("bsd,bsr->dr", x, back)
    got = grads["kv_a_proj"]["kernel"][:, d_c:]
    assert rel(got, want) < 1e-5
    one = jnp.einsum("bsd,bsr->dr", x, hybrid_lm.apply_rope(
        per_head[:, :, :1], cos, -sin)[:, :, 0])
    assert rel(got, one) > 0.3  # one head's share is not the sum


# ---- the mixer against the form it had before its heads were laid out
# for the flash kernels (PR 37)

def _parents_mixer(params, x, c, attention):
    """``LatentAttentionMixer`` as the parent commit computed it, written
    out: the rotary parts sliced off and turned apart, q and k built by
    concatenation with the one key broadcast to the heads, ``[k_n ; v]``
    sliced."""
    n, d_c = c.num_attention_heads, c.kv_lora_rank
    d_n, d_r, d_v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    bsz, s, _ = x.shape

    def norm(h, scale):
        return h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True)
                                 + c.layer_norm_epsilon) * scale

    c_q = norm(x @ params["q_a_proj"]["kernel"], params["q_a_norm"]["scale"])
    kv = x @ params["kv_a_proj"]["kernel"]
    c_kv = norm(kv[..., :d_c], params["kv_a_norm"]["scale"])
    k_r = kv[..., d_c:].reshape(bsz, s, 1, d_r)
    q = (c_q @ params["q_b_proj"]["kernel"]).reshape(bsz, s, n, d_n + d_r)
    kv = (c_kv @ params["kv_b_proj"]["kernel"]).reshape(bsz, s, n, d_n + d_v)
    cos, sin = hybrid_lm.rope_tables(c.rope_for("L"), s, d_r)
    q_r = hybrid_lm.apply_rope(q[..., d_n:], cos, sin)
    k_r = hybrid_lm.apply_rope(k_r, cos, sin)
    q = jnp.concatenate([q[..., :d_n], q_r], axis=-1)
    k = jnp.concatenate(
        [kv[..., :d_n], jnp.broadcast_to(k_r, (bsz, s, n, d_r))], axis=-1)
    y = attention(q, k, kv[..., d_n:])
    return y.reshape(bsz, s, n * d_v) @ params["o_proj"]["kernel"]


@pytest.mark.parametrize("over", [
    {}, dict(num_attention_heads=2, num_key_value_heads=2, q_lora_rank=48,
             kv_lora_rank=32, qk_nope_head_dim=192, qk_rope_head_dim=64,
             v_head_dim=256, rope_theta=1000000, initializer_range=0.05)],
    ids=["a-head-of-32", "the-published-head"])
def test_the_mixer_is_the_parents_expression_on_the_same_parameters(over):
    """Output and every parameter's gradient against the parent's form, to
    float32 round-off, at the tiny head (which no rule picks) and at the
    published one (which both rules pick: off the TPU the program still
    holds the expressions); and the parameter tree the parent's by name
    and shape."""
    c = config_of(params_for(**over)).params.hybrid_lm
    n, d_c, d_q = c.num_attention_heads, c.kv_lora_rank, c.q_lora_rank
    d_n, d_r, d_v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    x = jax.random.normal(jax.random.key(11), (2, SEQ, 64))
    cot = jax.random.normal(jax.random.key(12), (2, SEQ, 64))
    causal = jax.tree_util.Partial(ring.full_attention, causal=True)
    mixer = hybrid_lm.LatentAttentionMixer(c, causal)
    params = jax.jit(mixer.init)(jax.random.key(13), x)["params"]
    assert jax.tree.map(jnp.shape, params) == {
        "q_a_proj": {"kernel": (64, d_q)}, "q_a_norm": {"scale": (d_q,)},
        "kv_a_proj": {"kernel": (64, d_c + d_r)},
        "kv_a_norm": {"scale": (d_c,)},
        "q_b_proj": {"kernel": (d_q, n * (d_n + d_r))},
        "kv_b_proj": {"kernel": (d_c, n * (d_n + d_v))},
        "o_proj": {"kernel": (n * d_v, 64)}}

    def program(q):
        return jnp.sum(mixer.apply({"params": q}, x) * cot)

    def parents(q):
        return jnp.sum(_parents_mixer(q, x, c, causal) * cot)

    np.testing.assert_allclose(
        jax.jit(mixer.apply)({"params": params}, x),
        jax.jit(_parents_mixer, static_argnums=(2, 3))(params, x, c, causal),
        atol=1e-6, rtol=1e-6)
    grads = jax.jit(jax.grad(program))(params)
    want = jax.jit(jax.grad(parents))(params)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want)):
        assert rel(g, w) < 2e-6, (jax.tree_util.keystr(path), rel(g, w))


# ---- the mixer through the kernels

def test_the_latent_mixer_through_the_flash_kernels_at_a_head_of_256(
        pallas_interpret):
    """The published head (192 + 64 for queries and keys, 256 for values:
    two whole lane registers, nothing padded) through the three causal
    flash kernels in the interpreter, tiles of 128 over 256 positions (the
    folded triangle's three tiles a head), forward and gradient on every
    leaf, against the plain form."""
    from shifu_tensorflow_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )

    p = params_for(hidden_size=64, num_attention_heads=2,
                   num_key_value_heads=2, q_lora_rank=48, kv_lora_rank=32,
                   qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                   rope_theta=1000000, initializer_range=0.05)
    c = config_of(p).params.hybrid_lm
    x = jax.random.normal(jax.random.key(1), (1, 256, 64))
    cot = jax.random.normal(jax.random.key(2), (1, 256, 64))
    mixer = hybrid_lm.LatentAttentionMixer(
        c, lambda q, k, v: flash_attention(q, k, v, True, 128, 128))
    params = jax.jit(mixer.init)(jax.random.key(3), x)["params"]

    def program(q):
        return jnp.sum(mixer.apply({"params": q}, x) * cot)

    def plain(q):
        return jnp.sum(ref.latent_attention(q, x, p) * cot)

    got, grads = jax.jit(jax.value_and_grad(program))(params)
    want, ref_grads = jax.jit(jax.value_and_grad(plain))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    np.testing.assert_allclose(
        jax.jit(mixer.apply)({"params": params}, x),
        ref.latent_attention(params, x, p), atol=2e-5)
    assert len(jax.tree.leaves(grads)) == 7
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert rel(g, r) < 5e-5, (jax.tree_util.keystr(path), rel(g, r))


# ---- the share

def test_the_eight_shares_routed_parts_are_the_uncut_expert_layer():
    """The shipped deployment in small: 64 experts, 4 a token, eight chips
    holding 8 each.  What they compute, added up with the shared expert
    counted once, is the reference's whole layer, and every (token, choice)
    pair lands on exactly one of them."""
    p = params_for(n_routed_experts=64, num_experts_per_tok=4,
                   experts_held=[0, 64], hidden_size=32,
                   moe_intermediate_size=16)
    whole = config_of(p).params.hybrid_lm
    x = jax.random.normal(jax.random.key(2), (2, SEQ, 32))
    full = hybrid_lm.MoEMixer(whole)
    variables = jax.jit(full.init)(jax.random.key(1), x)
    want, stats = jax.jit(full.apply)(variables, x)
    params = variables["params"]
    assert set(params) == {"router", "e_score_correction_bias", "experts",
                           "shared"}
    assert params["shared"]["up"]["kernel"].shape == (32, 16)
    np.testing.assert_allclose(
        want, ref.moe_layer(params, x, p, held=(0, 64)), atol=2e-5)
    assert int(stats[0]) == 2 * SEQ * 4
    shared = ref.gated_mlp(params["shared"], x)
    assert float(jnp.abs(shared).max()) > 0
    total, pairs = shared, 0
    for first in range(0, 64, 8):
        cut = dataclasses.replace(whole, experts_held=(first, 8))
        held = {**params, "experts": {k: v[first:first + 8] for k, v in
                                      params["experts"].items()}}
        out, st = jax.jit(hybrid_lm.MoEMixer(cut).apply)({"params": held}, x)
        routed = ref.moe_layer(held, x, p, held=(first, 8), shared=False)
        np.testing.assert_allclose(out - shared, routed, atol=2e-5)
        assert float(jnp.abs(routed).max()) > 0
        total, pairs = total + routed, pairs + int(st[0])
    assert pairs == 2 * SEQ * 4
    np.testing.assert_allclose(total, want, atol=5e-5)


def test_sigmoid_weights_are_scaled_shares_of_the_chosen_scores():
    p = params_for()
    x = jax.random.normal(jax.random.key(4), (24, 64))
    router = {"router": {"kernel": jax.random.normal(jax.random.key(5),
                                                     (64, 16))},
              "e_score_correction_bias": jnp.zeros((16,))}
    ids, weights = ref.route(router, x, p)
    scores = jax.nn.sigmoid(x @ router["router"]["kernel"])
    np.testing.assert_array_equal(ids, jax.lax.top_k(scores, 2)[1])
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.8, rtol=1e-6)
    # the bias moves the choice and not the weight
    router["e_score_correction_bias"] = jnp.zeros((16,)).at[3].set(10.0)
    ids, weights = ref.route(router, x, p)
    assert bool(jnp.all(ids[:, 0] == 3))
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 1.8, rtol=1e-6)


# ---- the accepted decoders, as they were

#: the three accepted decoders' tiny models (their test files' ``PARAMS``)
#: at the parent commit, parameters from ``jax.random.key(36)``, 2 rows of
#: 32 ids from ``default_rng(36)``, under the tests' own XLA:CPU flags:
#: (loss, global gradient norm) as float hex, and the step's counters
AS_THE_PARENT_HAD_THEM = {
    "test_hybrid_lm": ("0x1.63d6dc0000000p+2", "0x1.496c0c0000000p+1",
                       {"moe_held_max": 23, "moe_held_pairs": 256}),
    "test_swa_moe_lm": ("0x1.98d0880000000p+2", "0x1.6c4c040000000p+2",
                        {"moe_held_max": 32, "moe_held_pairs": 256}),
    "test_mixed_gqa_moe_lm": ("0x1.9e61c40000000p+2", "0x1.daedd00000000p+2",
                              {"moe_held_max": 24, "moe_held_pairs": 512}),
}


@pytest.mark.parametrize("module", sorted(AS_THE_PARENT_HAD_THEM))
@pytest.mark.parametrize("stated", [False, True], ids=["absent", "zero"])
def test_without_the_module_the_accepted_decoders_are_bit_for_bit_the_parents(
        module, stated):
    """``num_nextn_predict_layers`` absent or 0: the loss, the gradient's
    norm and the counters of the Nemotron, Mellum and Laguna tiny models
    are the parent commit's to the last bit, and the counters hold nothing
    new."""
    import importlib

    import optax

    p = dict(importlib.import_module(module).PARAMS)
    if stated:
        p["num_nextn_predict_layers"] = 0
    model = build_model(config_of(p))
    ids = np.random.default_rng(36).integers(0, int(p["vocab_size"]), (2, 32))
    batch = {"x": ids.astype(np.float32), "w": np.ones((2, 1), np.float32)}
    params = jax.jit(model.init)(jax.random.key(36),
                                 jnp.zeros((1, 32)))["params"]
    assert "mtp" not in params
    loss_of = family_loss(model)
    (loss, counters), grads = jax.jit(jax.value_and_grad(
        lambda q: (lambda out: (out[0], out[2]))(loss_of(q, batch)),
        has_aux=True))(params)
    want_loss, want_norm, want_counters = AS_THE_PARENT_HAD_THEM[module]
    assert float(loss).hex() == want_loss
    assert float(optax.global_norm(grads)).hex() == want_norm
    assert {k: int(v) for k, v in counters.items()} == want_counters


# ---- the normal path

def test_trainer_steps_counts_pairs_and_both_losses_saves_and_restores(
        tmp_path):
    from shifu_tensorflow_tpu.train import make_trainer
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer

    mc = config_of(params_for(experts_held=[0, 4]))
    trainer = make_trainer(mc, SEQ, seed=3)
    losses = [trainer.train_epoch([batch_of(seed=s)])[0] for s in (1, 1, 1)]
    assert losses[2] < losses[0] and np.isfinite(losses).all()
    found = trainer.epoch_counters
    assert set(found) == {"moe_held_pairs", "moe_held_max", "main_loss",
                          "mtp_loss"}
    # the sparse block's and the module's 2 rows x 48 tokens x 2 choices, a
    # quarter of the experts held
    assert found["moe_held_pairs"].shape == (1,)
    assert 0 < found["moe_held_pairs"][0] < 2 * 2 * SEQ * 2
    assert float(found["main_loss"][0] + 0.3 * found["mtp_loss"][0]) == (
        pytest.approx(losses[2], rel=1e-6))
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


def _compare(dtype=jnp.float32, check=None, scale=None, wrong=None):
    """The plane's own check at small size: the program takes two Adam
    steps; the reference (possibly a wrong model) judges them.  On the CPU
    a float32 product is exact, so one reference serves as the truth and as
    the stated precision.  ``scale`` = (part of a leaf's name, factor)
    multiplies the reference's gradient on those leaves."""
    steps, moment = _system_run(dtype)
    key = (dtype, repr(sorted((wrong or {}).items())))
    if key not in JUDGED:
        judge = ref.make_loss(params_for(), "highest", with_grad=True,
                              wrong=wrong)
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
    assert got["grad_norm_rel_err"] < 1e-3 and got[
        "pooled_grad_rel_err"] < 1e-3
    assert len(got["leaf_update_rel_err"]) == 53
    assert _compare(check=CPU_CHECK)["ok"]


#: what each moves, at this size, is in the test's message when it does not
WRONG_MODELS = [
    ("the module's term dropped", {"mtp": False}),
    ("a weight of 0.1 on the module's loss", {"mtp_weight": 0.1}),
    ("a weight of 1 on the module's loss", {"mtp_weight": 1.0}),
    ("three heads of four", {"heads": 3}),
    ("the rotary key divided among the heads", {"shared_key": "mean"}),
    ("the rotary key left unturned", {"shared_key": "unturned"}),
    ("the module scored against the next token", {"mtp_target_shift": 1}),
    ("the module reading its own token's embedding", {"mtp_embed_shift": 0}),
    ("the module reading the final-normed states", {"mtp_normed": True}),
    ("W_m's halves the other way round", {"mtp_swapped": True}),
    ("the 1.8 left out", {"scaling": False}),
    ("the shared expert left out", {"shared": False}),
    ("an unmasked attention", {"causal": False}),
]


@pytest.mark.parametrize("what,kw", [
    *((what, {"wrong": wrong}) for what, wrong in WRONG_MODELS),
    ("a bf16 step", {"dtype": jnp.bfloat16, "check": CPU_CHECK}),
    ("a gradient off by two", {"scale": ("merge/proj", 2.0)}),
    ("a gradient off by a half", {"scale": ("kv_b_proj", 0.5)}),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else "")
def test_comparison_fails_a_wrong_model(what, kw):
    """The fault is on the reference's side (the same disagreement), but
    for the bf16 step, which the program takes itself (--dtype bfloat16).
    The limits are the shipped cell's, but for the bf16 step's (see
    ``CPU_CHECK``; on the chip the shipped limits refuse it, PERF.md)."""
    assert _compare(check=kw.get("check"))["ok"]  # the same, but right
    got = _compare(**kw)
    assert not got["ok"], (what, got)
