"""The scan kernels (``ops/pallas/ssd_scan.py``) against the expression
they replace where the static shapes say so (``ops/ssm_scan.py``
``ssm_scan_chunked``, through ``models/hybrid_lm.py`` ``chunked_scan``)
and against the recurrence written out one time step at a time: ``y`` and
all five gradients at kernel-eligible shapes of several chunks and
groups, in interpret mode (asked for here, through the
``pallas_interpret`` fixture; that the kernels lower for the v5e, and how
often a compiled step holds them, is tests/test_ssd_kernel_lowers.py's)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import hybrid_lm as ref
from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.models import hybrid_lm
from shifu_tensorflow_tpu.ops import ssm_scan
from shifu_tensorflow_tpu.ops.pallas import ssd_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, STATE = 128, 128

#: (heads, head width, groups): two heads to a 128-lane register (the
#: Nemotron cell's), a head that is one, sixteen to one
SHAPES = [pytest.param(4, 64, 2, id="4x64-2groups"),
          pytest.param(2, 128, 2, id="2x128-2groups"),
          pytest.param(32, 8, 2, id="32x8-2groups")]


def stepwise(x, dt, a, b, c):
    """The recurrence one time step at a time (the reference's), with the
    groups' B and C repeated over their heads."""
    r = x.shape[2] // b.shape[2]
    return ref.ssm_recurrence(x, dt, a, jnp.repeat(b, r, axis=2),
                              jnp.repeat(c, r, axis=2))


def expression(x, dt, a, b, c):
    return ssm_scan.ssm_scan_chunked(x, dt, a, b, c, CHUNK)


def selected(x, dt, a, b, c):
    return hybrid_lm.chunked_scan(x, dt, a, b, c, CHUNK)


@functools.cache
def kernels(products=None):
    """The kernels with products at ``products`` (None: as shipped); one
    function a precision, so :func:`program` compiles each once a shape."""
    if products is None:
        return lambda *v: ssd_scan.ssd_scan(*v, CHUNK)
    return lambda *v: ssd_scan.ssd_scan(*v, CHUNK, products)


def inputs(heads, dim, groups, seq=3 * CHUNK, seed=0, step=-2.0, rate=0.0):
    """x, dt, a, B, C of two rows; ``step`` shifts ``dt``'s softplus and
    ``rate`` ``log(−a)``."""
    k = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(k[0], (2, seq, heads, dim)),
            jax.nn.softplus(jax.random.normal(k[1], (2, seq, heads)) + step),
            -jnp.exp(jax.random.normal(k[2], (heads,)) + rate),
            jax.random.normal(k[3], (2, seq, groups, STATE)),
            jax.random.normal(k[4], (2, seq, groups, STATE)))


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@functools.cache
def program(fn):
    """``fn``'s y and its five gradients under a cotangent, one jitted
    program a function: tests at one shape share its compile (in interpret
    mode the kernels' takes seconds)."""
    def run(weigh, *args):
        y, back = jax.vjp(fn, *args)
        return (y,) + back(weigh)

    return jax.jit(run)


def y_and_gradients(fn, args, seed=9):
    weigh = jax.random.normal(jax.random.key(seed), args[0].shape)
    return program(fn)(weigh, *args)


NAMES = ("y", "dx", "ddt", "da", "dB", "dC")


@pytest.mark.parametrize("heads,dim,groups", SHAPES)
def test_the_kernels_are_the_expression_and_the_recurrence(
        pallas_interpret, heads, dim, groups):
    """Three chunks, two groups, float32 products: every formula of the
    forward and of the backward, to rounding."""
    args = inputs(heads, dim, groups)
    got = y_and_gradients(kernels(jnp.float32), args)
    for want_of, limit in ((expression, 2e-5), (stepwise, 5e-5)):
        want = y_and_gradients(want_of, args)
        for name, g, w in zip(NAMES, got, want):
            assert g.shape == w.shape and rel(g, w) < limit, (
                name, want_of.__name__, rel(g, w))


@pytest.mark.parametrize("heads,dim,groups", SHAPES)
def test_at_the_shipped_precision_every_gradient_is_one_bf16_pass_away(
        pallas_interpret, heads, dim, groups):
    """The program's products round their operands to bfloat16 (what
    XLA:TPU's default precision does to the expression's einsums).  ``a``'s
    gradient is the one that shows a careless backward: dA is a difference
    of two sums that cancel over a chunk, and at 0.03-0.26 from the
    expression it read so until both took the operands as rounded."""
    args = inputs(heads, dim, groups, seed=1)
    got = y_and_gradients(kernels(), args)
    want = y_and_gradients(expression, args)
    for name, g, w in zip(NAMES, got, want):
        assert 1e-4 < rel(g, w) < 0.012, (name, rel(g, w))


def test_a_decay_that_underflows_inside_a_chunk_leaves_everything_finite(
        pallas_interpret):
    """``dt · a`` near −20 a step: ``exp(A_l − A_s)`` underflows two steps
    off the diagonal and ``exp(A)`` inside the first ten rows; above the
    diagonal the exponent is masked before the ``exp``.  ``a``'s
    gradient is ``dA`` weighed by the running sum of ``dt`` (up to 384
    here), a sum that cancels to a hundredth of its terms: float32
    rounding of ``dA`` (1.7e-7 from a float64 reckoning, the expression's
    9.6e-6) shows in it at 1e-4, the expression's less because one
    rounded number enters its row sum and its column sum."""
    args = inputs(4, 64, 2, seed=2, step=3.0, rate=2.0)
    assert float(jnp.mean(args[1] * args[2])) < -15.0
    got = y_and_gradients(kernels(jnp.float32), args)
    want = y_and_gradients(expression, args)
    for name, g, w in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert rel(g, w) < (1e-3 if name == "da" else 2e-5), (name,
                                                              rel(g, w))
    at_bf16 = y_and_gradients(kernels(jnp.bfloat16), args)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in at_bf16)


def test_the_state_is_carried_across_chunks_and_not_reset(pallas_interpret):
    """A sequence of three chunks against its first two (the scan is
    causal) and against its last alone (which starts from no state)."""
    args = inputs(4, 64, 2, seed=3, step=-4.0)  # slow decay: a long memory
    fn = jax.jit(kernels(jnp.float32))
    whole = fn(*args)
    x, dt, a, b, c = args
    cut = slice(0, 2 * CHUNK)
    first_two = fn(x[:, cut], dt[:, cut], a, b[:, cut], c[:, cut])
    np.testing.assert_allclose(whole[:, cut], first_two, rtol=1e-6,
                               atol=1e-6)
    tail = slice(2 * CHUNK, None)
    alone = fn(x[:, tail], dt[:, tail], a, b[:, tail], c[:, tail])
    assert rel(alone, whole[:, tail]) > 0.1
    # ... and the cotangent comes back across them
    back = jax.grad(lambda x: jnp.sum(
        kernels(jnp.float32)(x, dt, a, b, c)[:, tail] ** 2))(x)
    assert float(jnp.linalg.norm(back[:, :CHUNK])) > 0.0


def test_the_states_that_entered_the_chunks_are_the_recurrences(
        pallas_interpret):
    """The forward's second output, the one value of size (chunks, heads,
    p, n) that reaches HBM: chunk ``c``'s is the state after ``c · chunk``
    steps of the recurrence, chunk 0's is zero."""
    heads, dim, groups = 4, 64, 2
    x, dt, a, b, c = inputs(heads, dim, groups, seed=4)
    _, saved = ssd_scan._ssd_chunks_fwd(
        x, dt, jnp.cumsum((dt * a).reshape(2, 3, CHUNK, heads),
                          axis=2).reshape(dt.shape), b, c, CHUNK,
        jnp.float32)
    entering = saved[-1].reshape(2, 3, heads, dim, STATE)
    state, r = jnp.zeros((2, heads, dim, STATE)), heads // groups
    np.testing.assert_array_equal(entering[:, 0], state)
    for t in range(2 * CHUNK):
        state = (jnp.exp(dt[:, t] * a)[..., None, None] * state
                 + jnp.einsum("bhp,bhn->bhpn", x[:, t] * dt[:, t, :, None],
                              jnp.repeat(b[:, t], r, axis=1)))
        if (t + 1) % CHUNK == 0:
            assert rel(entering[:, (t + 1) // CHUNK], state) < 1e-5


# ---- which path a program holds: a pure function of static shapes


def _shipped(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cell = json.load(f)
    return (ModelConfig.from_json(cell["model_config"]).params.hybrid_lm,
            cell["data"]["tokens_per_row"])


def test_the_shipped_nemotron_shapes_pick_the_kernels():
    cfg, seq = _shipped("nemotron3_nano_ep16")
    shape = (cfg.chunk_size, cfg.mamba_head_dim,
             cfg.mamba_num_heads // cfg.n_groups, cfg.ssm_state_size)
    assert shape == (128, 64, 8, 128) and seq == 4096
    assert ssd_scan.ssd_pays(*shape, jnp.float32, seq)
    # the benchmark's control (--dtype bfloat16) keeps the expression
    assert not ssd_scan.ssd_pays(*shape, jnp.bfloat16, seq)


@pytest.mark.parametrize("chunk,dim,in_group,state,dtype,seq,pays", [
    (128, 64, 8, 128, jnp.float32, 4096, True),
    (128, 64, 2, 128, jnp.float32, 256, True),
    (256, 128, 1, 256, jnp.float32, 1024, True),
    (128, 8, 16, 128, jnp.float32, 384, True),
    (128, 64, 8, 128, jnp.bfloat16, 4096, False),   # the control's dtype
    (128, 8, 2, 128, jnp.float32, 4096, False),     # the tests' heads of 8
    (128, 64, 8, 16, jnp.float32, 4096, False),     # the tests' state
    (64, 64, 8, 128, jnp.float32, 4096, False),     # half a register
    (128, 64, 8, 128, jnp.float32, 4000, False),    # 4000 = 31.25 chunks
    (128, 64, 1, 128, jnp.float32, 4096, False),    # a group of half one
    (128, 96, 4, 128, jnp.float32, 4096, False),    # heads astride registers
    (0, 64, 8, 128, jnp.float32, 4096, False)])
def test_the_rule_is_whole_registers_of_float32_and_whole_chunks(
        chunk, dim, in_group, state, dtype, seq, pays):
    assert ssd_scan.ssd_pays(chunk, dim, in_group, state, dtype, seq) == pays


@pytest.mark.parametrize("dim,state,seq,dtype", [
    (64, 128, 2 * CHUNK, jnp.float32),    # a shape the rule picks
    (64, 128, 2 * CHUNK, jnp.bfloat16),
    (8, 16, 2 * CHUNK, jnp.float32),
    (64, 128, 2 * CHUNK - 8, jnp.float32)])
def test_a_program_lowered_for_the_cpu_holds_the_expression(dim, state, seq,
                                                            dtype):
    """``chunked_scan`` off the TPU: no kernel whatever the shape (a shape
    the rule picks is ``platform_dependent``'s to settle when the program
    is lowered), and ``ssm_scan_chunked``'s numbers bit for bit."""
    k = jax.random.split(jax.random.key(5), 5)
    args = (jax.random.normal(k[0], (1, seq, 4, dim), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (1, seq, 4), dtype)),
            -jnp.exp(jax.random.normal(k[2], (4,))),
            jax.random.normal(k[3], (1, seq, 2, state), dtype),
            jax.random.normal(k[4], (1, seq, 2, state), dtype))
    picked = jax.jit(hybrid_lm.chunked_scan, static_argnums=5)
    lowered = picked.lower(*args, CHUNK).as_text()
    assert "ssd_scan" not in lowered and "custom_call" not in lowered
    np.testing.assert_array_equal(
        np.asarray(picked(*args, CHUNK), np.float32),
        np.asarray(jax.jit(ssm_scan.ssm_scan_chunked, static_argnums=5)(
            *args, CHUNK), np.float32))


def test_the_gradient_of_a_cpu_program_is_the_expressions():
    """... and so is its backward: the selector adds no rule of its own."""
    args = inputs(4, 64, 2, seq=2 * CHUNK, seed=6)
    got = y_and_gradients(selected, args)
    want = y_and_gradients(expression, args)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
