"""The rotary kernels (``ops/pallas/rope.py``) against the expressions
they replace where the static shapes say so (``models/hybrid_lm.py``
``apply_rope`` / ``rotate`` / ``latent_heads``): the rotation and its
transpose at the four (query heads, KV heads, head, turning dimensions and
where they start, rope type) the benchmark's cells run, the last of them
latent attention's (a head's LAST 64 of 256 turn, and the keys and values
come from ``[k_n ; v]`` heads and ONE rotary key), at a short sequence
whose last row block is ragged, in interpret mode (asked for here, through
the ``pallas_interpret`` fixture; that the kernels lower for the v5e, and
that the flash kernels read their output where it lies, is
tests/test_tpu_compile.py's and tests/test_mla_lowers.py's)."""

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    RopeParameters,
)
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.ops.pallas import rope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, BLOCK = 72, 32  # row blocks of 32, 32 and 8
HEAD = 128


def _shipped(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return ModelConfig.from_json(
            json.load(f)["model_config"]).params.hybrid_lm


def _rope_of(name, kind) -> RopeParameters:
    return _shipped(name).rope_for(kind)


#: (query heads, KV heads or None where the keys are latent attention's,
#: head, dimensions that turn, the first of them, rope type, whose)
CASES = [
    pytest.param(32, 4, 128, 128, 0, "yarn", ("mellum2_ep4", "*"),
                 id="32-4-whole-yarn"),
    pytest.param(64, 8, 128, 128, 0, "default", ("laguna_xs2_ep8", "W"),
                 id="64-8-whole-default"),
    pytest.param(48, 8, 128, 64, 0, "yarn", ("laguna_xs2_ep8", "*"),
                 id="48-8-half-yarn"),
    pytest.param(20, None, 256, 64, 192, "default", ("glm47_flash_ep8", "L"),
                 id="20-latent-last-quarter-default"),
]


def _qkv(heads, kv_heads, seed=0, head=HEAD):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (1, SEQ, heads, head)),
            jax.random.normal(keys[1], (1, SEQ, kv_heads, head)),
            jax.random.normal(keys[2], (1, SEQ, kv_heads, head)))


def _latent_inputs(heads, nope, turning, value, seed=0):
    """(q, ``[k_n ; v]`` heads, the one rotary key) as the latent
    projections leave them."""
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (1, SEQ, heads, nope + turning)),
            jax.random.normal(keys[1], (1, SEQ, heads, nope + value)),
            jax.random.normal(keys[2], (1, SEQ, 1, turning)))


def _heads(kernel: bool, kv_heads, offset):
    """``(three inputs, cos, sin) -> (q, k, v)`` (B, S, H, D) as the
    mixers order them in front of the core (``AttentionMixer``: rope, then
    the KV heads' repeat; ``LatentAttentionMixer``: q's rotation in place
    and the keys and values off ``[k_n ; v]`` and the one key), by the
    kernels or by the expressions."""
    def turn(u, cos, sin):
        if kernel:
            return rope.rope_lanes(u, cos, sin, BLOCK, offset)
        return hybrid_lm.apply_rope(u, cos, sin, offset)

    def grouped(q, k, v, cos, sin):
        q, k = turn(q, cos, sin), turn(k, cos, sin)
        return (q, *(jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                     for x in (k, v)))

    def latent(q, kv, k_r, cos, sin):
        if kernel:
            return (turn(q, cos, sin),
                    *rope.latent_lanes(kv, k_r, cos, sin, offset, BLOCK))
        k_r = jnp.broadcast_to(hybrid_lm.apply_rope(k_r, cos, sin),
                               kv.shape[:3] + k_r.shape[3:])
        return (turn(q, cos, sin),
                jnp.concatenate([kv[..., :offset], k_r], axis=-1),
                kv[..., offset:])

    return latent if kv_heads is None else grouped


def _attended(q, k, v):
    """Causal attention over the heads :func:`_heads` made."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    seen = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)


@pytest.mark.parametrize(
    "heads,kv_heads,head,turning,offset,rope_type,whose", CASES)
def test_the_kernel_turns_q_and_k_as_the_expression_does(
        pallas_interpret, heads, kv_heads, head, turning, offset, rope_type,
        whose):
    """Forward, and the gradient to the three inputs (q, k and v, or q,
    ``[k_n ; v]`` and the one rotary key, whose gradient is the sum over
    the heads) through the ``custom_vjp`` against autodiff of the
    expression."""
    params = _rope_of(*whose)
    of = turning if kv_heads is None else head  # the latent key is all R
    assert (params.rope_type, params.rotary_dim(of)) == (rope_type, turning)
    cos, sin = hybrid_lm.rope_tables(params, SEQ, of)
    assert cos.shape == (SEQ, turning // 2)
    inputs = (_latent_inputs(heads, offset, turning, head)
              if kv_heads is None else _qkv(heads, kv_heads, head=head))
    by_kernel, plain = (_heads(kernel, kv_heads, offset)
                        for kernel in (True, False))

    for got, want in zip(by_kernel(*inputs, cos, sin),
                         plain(*inputs, cos, sin)):
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
    weigh = jax.random.normal(jax.random.key(9), inputs[0].shape)

    def loss(heads_of):
        return lambda *x: jnp.sum(
            _attended(*heads_of(*x, cos, sin)) * weigh)

    want = jax.grad(loss(plain), (0, 1, 2))(*inputs)
    got = jax.grad(loss(by_kernel), (0, 1, 2))(*inputs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("whose,head,still", [
    (("laguna_xs2_ep8", "*"), 128, slice(64, None)),
    (("glm47_flash_ep8", "L"), 256, slice(0, 192))],
    ids=["the-second-half", "the-first-three-quarters"])
def test_the_dimensions_that_pass_through_come_back_bit_for_bit(
        pallas_interpret, whose, head, still):
    params = _rope_of(*whose)
    offset = 0 if still.start else still.stop
    cos, sin = hybrid_lm.rope_tables(params, SEQ, 128 if still.start else 64)
    turns = slice(offset, offset + 64)
    u = _qkv(6, 2, seed=3, head=head)[0]
    got = rope.rope_lanes(u, cos, sin, BLOCK, offset)
    np.testing.assert_array_equal(got[..., still], u[..., still])
    assert float(jnp.max(jnp.abs(got[..., turns] - u[..., turns]))) > 0.1
    # ... and so does their cotangent
    back = jax.grad(lambda u: jnp.sum(
        rope.rope_lanes(u, cos, sin, BLOCK, offset) * u))(u)
    np.testing.assert_allclose(back[..., still], 2 * u[..., still],
                               rtol=1e-6)


def test_the_latent_keys_and_values_are_their_parts_bit_for_bit(
        pallas_interpret):
    """``k_n`` and ``v`` pass through the pass untouched, every head's
    last 64 lanes are the ONE rotated key, and the transpose hands the one
    key the sum over the heads."""
    params = _rope_of("glm47_flash_ep8", "L")
    cos, sin = hybrid_lm.rope_tables(params, SEQ, 64)
    _, kv, k_r = _latent_inputs(8, 192, 64, 256, seed=4)
    k, v = rope.latent_lanes(kv, k_r, cos, sin, 192, BLOCK)
    np.testing.assert_array_equal(k[..., :192], kv[..., :192])
    np.testing.assert_array_equal(v, kv[..., 192:])
    turned = hybrid_lm.apply_rope(k_r, cos, sin)
    np.testing.assert_array_equal(
        k[..., 192:], jnp.broadcast_to(turned, (1, SEQ, 8, 64)))
    weigh = jax.random.normal(jax.random.key(5), k.shape)
    dkv, dk_r = jax.grad(lambda kv, k_r: jnp.sum(rope.latent_lanes(
        kv, k_r, cos, sin, 192, BLOCK)[0] * weigh), (0, 1))(kv, k_r)
    np.testing.assert_array_equal(dkv[..., :192], weigh[..., :192])
    np.testing.assert_array_equal(dkv[..., 192:], 0 * kv[..., 192:])
    want = jax.grad(lambda k_r: jnp.sum(
        hybrid_lm.apply_rope(k_r, cos, sin) * jnp.sum(
            weigh[..., 192:], axis=2, keepdims=True)))(k_r)
    np.testing.assert_allclose(dk_r, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("whose", [("mellum2_ep4", "*"),
                                   ("laguna_xs2_ep8", "*")],
                         ids=["whole", "half"])
def test_a_constant_inputs_rotated_norm_is_the_scale(pallas_interpret,
                                                     whose):
    """The same head at every position: a rotation keeps a pair's length,
    so what turns comes back ``attention_factor`` times as long wherever
    it stands, and what passes through as it was."""
    params = _rope_of(*whose)
    turning = params.rotary_dim(HEAD)
    cos, sin = hybrid_lm.rope_tables(params, SEQ, HEAD)
    head = jax.random.normal(jax.random.key(1), (1, 1, 4, HEAD))
    got = rope.rope_lanes(jnp.broadcast_to(head, (1, SEQ, 4, HEAD)),
                          cos, sin, BLOCK)
    np.testing.assert_allclose(
        jnp.linalg.norm(got[..., :turning], axis=-1),
        jnp.broadcast_to(params.attention_factor * jnp.linalg.norm(
            head[..., :turning], axis=-1), (1, SEQ, 4)), rtol=1e-5)
    assert params.attention_factor > 1.2


# ---- which path a program holds: a pure function of static shapes


@pytest.mark.parametrize("name,kind,path", [
    ("mellum2_ep4", "W", "kernel"), ("mellum2_ep4", "*", "kernel"),
    ("laguna_xs2_ep8", "W", "kernel"), ("laguna_xs2_ep8", "*", "kernel"),
    ("glm47_flash_ep8", "L", "kernel"),
    ("nemotron3_nano_ep16", "*", None)])
def test_the_shipped_configurations_heads_pick_the_kernel(name, kind, path):
    cfg = _shipped(name)
    params = cfg.rope_for(kind)
    if path is None:
        assert params is None  # no rotary: nothing to pick
        return
    head, turning, offset = (cfg.head_dim,
                             params.rotary_dim(cfg.head_dim), 0)
    if kind == "L":  # a head's last dimensions turn, and the keys and
        # values come off ``[k_n ; v]`` heads and one rotary key
        offset, turning = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        head = offset + turning
        assert (head, turning, offset) == (256, 64, 192)
        for dtype, pays in ((jnp.float32, True), (jnp.bfloat16, False)):
            assert rope.heads_pay(offset, turning, cfg.v_head_dim,
                                  cfg.num_attention_heads, dtype) == pays
    assert rope.lanes_pay(head, turning, jnp.float32, offset)
    assert not rope.lanes_pay(head, turning, jnp.bfloat16, offset)


@pytest.mark.parametrize("head_dim,turning,dtype,offset,pays", [
    (16, 16, jnp.float32, 0, False), (32, 32, jnp.float32, 0, False),
    (32, 16, jnp.float32, 0, False), (128, 128, jnp.bfloat16, 0, False),
    (128, 96, jnp.float32, 0, False), (128, 128, jnp.float32, 0, True),
    (128, 64, jnp.float32, 0, True), (256, 256, jnp.float32, 0, True),
    (256, 64, jnp.float32, 192, True), (256, 64, jnp.bfloat16, 192, False),
    (256, 64, jnp.float32, 224, False), (192, 64, jnp.float32, 128, False),
    (128, 64, jnp.float32, 64, True)])
def test_the_rule_is_whole_registers_of_float32(head_dim, turning, dtype,
                                                offset, pays):
    assert rope.lanes_pay(head_dim, turning, dtype, offset) == pays


@pytest.mark.parametrize("nope,turning,value,heads,dtype,pays", [
    (192, 64, 256, 20, jnp.float32, True),
    (192, 64, 256, 20, jnp.bfloat16, False),
    (128, 64, 128, 128, jnp.float32, False),  # a key of 1.5 registers
    (192, 64, 256, 3, jnp.float32, False),  # a step's heads end mid-register
    (192, 64, 192, 20, jnp.float32, False), (24, 8, 32, 4, jnp.float32, False)])
def test_the_latent_rule_is_whole_registers_of_float32(
        nope, turning, value, heads, dtype, pays):
    assert rope.heads_pay(nope, turning, value, heads, dtype) == pays


@pytest.mark.parametrize("head_dim,dtype,offset", [
    (16, jnp.float32, 0), (128, jnp.bfloat16, 0), (128, jnp.float32, 0),
    (256, jnp.float32, 192)])
def test_a_program_lowered_for_the_cpu_holds_the_expression(head_dim, dtype,
                                                            offset):
    """``rotate`` off the TPU: no kernel whatever the head (a head the
    rule picks is ``platform_dependent``'s to settle when the program is
    lowered), and ``apply_rope``'s numbers bit for bit."""
    params = _rope_of("mellum2_ep4", "*")
    cos, sin = hybrid_lm.rope_tables(params, SEQ, head_dim - offset)
    u = jax.random.normal(jax.random.key(2), (1, SEQ, 2, head_dim), dtype)
    rotate = jax.jit(partial(hybrid_lm.rotate, offset=offset))
    lowered = rotate.lower(u, cos, sin).as_text()
    assert "rope_lanes" not in lowered and "custom_call" not in lowered
    np.testing.assert_array_equal(
        np.asarray(rotate(u, cos, sin), np.float32),
        np.asarray(jax.jit(partial(hybrid_lm.apply_rope, offset=offset))(
            u, cos, sin), np.float32))


def test_the_latent_heads_lowered_for_the_cpu_hold_the_expression():
    """``latent_heads`` off the TPU at the shipped head (which the rule
    picks): no kernel, and the keys are ``concatenate([k_n,
    broadcast(rot(k_r))])`` bit for bit."""
    params = _rope_of("glm47_flash_ep8", "L")
    cos, sin = hybrid_lm.rope_tables(params, SEQ, 64)
    _, kv, k_r = _latent_inputs(20, 192, 64, 256, seed=6)
    assert rope.heads_pay(192, 64, 256, 20, kv.dtype)
    heads = jax.jit(partial(hybrid_lm.latent_heads, nope=192))
    lowered = heads.lower(kv, k_r, cos, sin).as_text()
    assert "latent_lanes" not in lowered and "custom_call" not in lowered
    k, v = heads(kv, k_r, cos, sin)
    np.testing.assert_array_equal(k, jnp.concatenate(
        [kv[..., :192], jnp.broadcast_to(
            hybrid_lm.apply_rope(k_r, cos, sin), (1, SEQ, 20, 64))], -1))
    np.testing.assert_array_equal(v, kv[..., 192:])
