"""The rotary kernel (``ops/pallas/rope.py``) against the expression it
replaces where the static shapes say so (``models/hybrid_lm.py``
``apply_rope`` / ``rotate``): the rotation and its transpose at the three
(query heads, KV heads, turning dimensions, rope type) the benchmark's
cells run, at a short sequence whose last row block is ragged, in
interpret mode (asked for here, through the ``pallas_interpret`` fixture;
that the kernel lowers for the v5e, and that the flash kernels read its
output where it lies, is tests/test_tpu_compile.py's)."""

import json
import os

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


#: (query heads, KV heads, dimensions that turn, rope type, whose)
CASES = [
    pytest.param(32, 4, 128, "yarn", ("mellum2_ep4", "*"), id="32-4-whole-yarn"),
    pytest.param(64, 8, 128, "default", ("laguna_xs2_ep8", "W"),
                 id="64-8-whole-default"),
    pytest.param(48, 8, 64, "yarn", ("laguna_xs2_ep8", "*"), id="48-8-half-yarn"),
]


def _qkv(heads, kv_heads, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (1, SEQ, heads, HEAD)),
            jax.random.normal(keys[1], (1, SEQ, kv_heads, HEAD)),
            jax.random.normal(keys[2], (1, SEQ, kv_heads, HEAD)))


def _attended(turn, q, k, v, cos, sin):
    """rope -> repeat -> causal attention, as ``AttentionMixer`` orders
    them, the rotation being ``turn``."""
    q, k = turn(q, cos, sin), turn(k, cos, sin)
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * HEAD ** -0.5
    seen = jnp.tril(jnp.ones((SEQ, SEQ), bool))
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(seen, scores, -jnp.inf), axis=-1), v)


@pytest.mark.parametrize("heads,kv_heads,turning,rope_type,whose", CASES)
def test_the_kernel_turns_q_and_k_as_the_expression_does(
        pallas_interpret, heads, kv_heads, turning, rope_type, whose):
    """Forward, and the gradient to q, k and v through the ``custom_vjp``
    against autodiff of the expression."""
    params = _rope_of(*whose)
    assert (params.rope_type, params.rotary_dim(HEAD)) == (rope_type, turning)
    cos, sin = hybrid_lm.rope_tables(params, SEQ, HEAD)
    assert cos.shape == (SEQ, turning // 2)
    q, k, v = _qkv(heads, kv_heads)

    def by_kernel(u, cos, sin):
        return rope.rope_lanes(u, cos, sin, BLOCK)

    for u in (q, k):
        np.testing.assert_allclose(by_kernel(u, cos, sin),
                                   hybrid_lm.apply_rope(u, cos, sin),
                                   atol=1e-6, rtol=1e-6)
    weigh = jax.random.normal(jax.random.key(9), q.shape)

    def loss(turn):
        return lambda q, k, v: jnp.sum(
            _attended(turn, q, k, v, cos, sin) * weigh)

    want = jax.grad(loss(hybrid_lm.apply_rope), (0, 1, 2))(q, k, v)
    got = jax.grad(loss(by_kernel), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5, rtol=1e-4)


def test_the_dimensions_that_pass_through_come_back_bit_for_bit(
        pallas_interpret):
    params = _rope_of("laguna_xs2_ep8", "*")
    cos, sin = hybrid_lm.rope_tables(params, SEQ, HEAD)
    u = _qkv(6, 2, seed=3)[0]
    got = rope.rope_lanes(u, cos, sin, BLOCK)
    np.testing.assert_array_equal(got[..., 64:], u[..., 64:])
    assert float(jnp.max(jnp.abs(got[..., :64] - u[..., :64]))) > 0.1
    # ... and so does their cotangent
    back = jax.grad(lambda u: jnp.sum(rope.rope_lanes(u, cos, sin, BLOCK)
                                      * u))(u)
    np.testing.assert_allclose(back[..., 64:], 2 * u[..., 64:], rtol=1e-6)


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
    ("nemotron3_nano_ep16", "*", None)])
def test_the_shipped_configurations_heads_pick_the_kernel(name, kind, path):
    cfg = _shipped(name)
    params = cfg.rope_for(kind)
    if path is None:
        assert params is None  # no rotary: nothing to pick
        return
    assert rope.lanes_pay(cfg.head_dim, params.rotary_dim(cfg.head_dim),
                          jnp.float32)
    assert not rope.lanes_pay(cfg.head_dim, params.rotary_dim(cfg.head_dim),
                              jnp.bfloat16)


@pytest.mark.parametrize("head_dim,turning,dtype,pays", [
    (16, 16, jnp.float32, False), (32, 32, jnp.float32, False),
    (32, 16, jnp.float32, False), (128, 128, jnp.bfloat16, False),
    (128, 96, jnp.float32, False), (128, 128, jnp.float32, True),
    (128, 64, jnp.float32, True), (256, 256, jnp.float32, True)])
def test_the_rule_is_whole_registers_of_float32(head_dim, turning, dtype,
                                                pays):
    assert rope.lanes_pay(head_dim, turning, dtype) == pays


@pytest.mark.parametrize("head_dim,dtype", [(16, jnp.float32),
                                            (128, jnp.bfloat16),
                                            (128, jnp.float32)])
def test_a_program_lowered_for_the_cpu_holds_the_expression(head_dim, dtype):
    """``rotate`` off the TPU: no kernel whatever the head (a head the
    rule picks is ``platform_dependent``'s to settle when the program is
    lowered), and ``apply_rope``'s numbers bit for bit."""
    params = _rope_of("mellum2_ep4", "*")
    cos, sin = hybrid_lm.rope_tables(params, SEQ, head_dim)
    u = jax.random.normal(jax.random.key(2), (1, SEQ, 2, head_dim), dtype)
    lowered = jax.jit(hybrid_lm.rotate).lower(u, cos, sin).as_text()
    assert "rope_lanes" not in lowered and "custom_call" not in lowered
    np.testing.assert_array_equal(
        np.asarray(jax.jit(hybrid_lm.rotate)(u, cos, sin), np.float32),
        np.asarray(jax.jit(hybrid_lm.apply_rope)(u, cos, sin), np.float32))
