"""Chunked + flash attention — numerics vs full attention.

The chunked path (parallel/ring.py chunked_attention) is the XLA
online-softmax scan; the flash path (ops/pallas/flash_attention.py) is
the Pallas TPU kernel, exercised here in interpret mode on CPU (asked
for through the ``pallas_interpret`` fixture; that the same kernel
lowers for the v5e is pinned in tests/test_tpu_compile.py, and that it
runs there by chip_smoke.py).  Tolerances are tight here because CPU
math is uniform; on the TPU MXU, blocked-vs-monolithic f32 matmul
orderings differ at ~1e-3 and checks must be scale-aware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shifu_tensorflow_tpu.models.sequence import make_attention
from shifu_tensorflow_tpu.ops.pallas.flash_attention import flash_attention
from shifu_tensorflow_tpu.parallel.mesh import make_mesh
from shifu_tensorflow_tpu.parallel.ring import (
    chunked_attention,
    full_attention,
)

pytestmark = pytest.mark.usefixtures("pallas_interpret")


def _qkv(b=2, s=96, h=4, d=24, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16, 32, 96, 7])  # 7: non-divisor
def test_chunked_matches_full(causal, block):
    q, k, v = _qkv()
    want = full_attention(q, k, v, causal=causal)
    got = chunked_attention(q, k, v, causal=causal, block_size=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_chunked_prime_seq_pads_instead_of_collapsing(causal):
    # S=127 (prime): a largest-divisor block search would collapse to
    # blk=1 — an S-step scan with an S×carry backward; the padding path
    # must keep the requested block and mask the padded keys
    q, k, v = _qkv(s=127)
    want = full_attention(q, k, v, causal=causal)
    got = chunked_attention(q, k, v, causal=causal, block_size=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [96, 127])  # 127: the padded bwd branch
def test_chunked_grads_match_full(causal, s):
    q, k, v = _qkv(s=s)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    want = jax.grad(
        loss(lambda q, k, v: full_attention(q, k, v, causal=causal)),
        (0, 1, 2))(q, k, v)
    got = jax.grad(
        loss(lambda q, k, v: chunked_attention(
            q, k, v, causal=causal, block_size=32)),
        (0, 1, 2))(q, k, v)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


# tiles smaller than S, so that the causal walk meets tiles wholly above
# the diagonal (not visited), on it and wholly below it; unequal
# blocks both ways; 200 and 320 are S that no block here divides (padded
# keys on the last diagonal tile), and 320 / 128 and 200 / 40 are odd
# counts of blocks (the middle block of the fold stands alone)
BLOCKS = [(128, 128), (32, 32), (32, 64), (64, 32), (40, 40), (48, 16)]
_block_ids = [f"{bq}x{bk}" for bq, bk in BLOCKS]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", BLOCKS, ids=_block_ids)
@pytest.mark.parametrize("s", [64, 96, 320])  # 96/320: pad the blocks
def test_flash_matches_full(causal, s, blocks):
    q, k, v = _qkv(s=s)
    want = full_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, *blocks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_mismatched_blocks_cover_whole_sequence():
    # regression: S must pad to a common multiple of BOTH blocks — with
    # only max(bq, bk) the smaller block's grid dimension floors and
    # trailing rows/keys are silently dropped
    q, k, v = _qkv(s=100)
    want = full_attention(q, k, v)
    for bq, bk in ((64, 96), (96, 64)):
        got = flash_attention(q, k, v, False, bq, bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_flash_grads_match_full():
    q, k, v = _qkv(s=128)
    want = jax.grad(
        lambda q, k, v: jnp.sum(full_attention(q, k, v, causal=True) ** 2),
        (0, 1, 2))(q, k, v)
    got = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True) ** 2),
        (0, 1, 2))(q, k, v)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_flash_under_jit_and_vmapped_model_shapes():
    # the shape the sequence family actually feeds: bf16, D=32
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 256, 4, 32)),
                           jnp.bfloat16) for _ in range(3))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=0.05, atol=0.05)


def test_make_attention_resolution(monkeypatch):
    # default: auto on a single device is ALWAYS full (no win region
    # for chunked is measured on the attached chip)
    assert make_attention("auto", None, seq_len=256,
                          num_heads=4) is full_attention
    assert make_attention("auto", None, seq_len=8192,
                          num_heads=4) is full_attention
    # a measured deployment opts in via the env cutover
    monkeypatch.setenv("STPU_CHUNKED_MIN_SEQ", "2048")
    assert make_attention("auto", None, seq_len=256,
                          num_heads=4) is full_attention
    big = make_attention("auto", None, seq_len=4096, num_heads=4)
    assert big is not full_attention
    q, k, v = _qkv(s=96)
    np.testing.assert_allclose(
        np.asarray(big(q, k, v)),
        np.asarray(full_attention(q, k, v)), rtol=2e-5, atol=2e-5)
    # explicit chunked + flash resolve and agree with full
    for impl in ("chunked", "flash"):
        fn = make_attention(impl, None, seq_len=96, num_heads=4)
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v)),
            np.asarray(full_attention(q, k, v)), rtol=2e-5, atol=2e-5)
    # auto with a seq mesh still picks ring (unchanged behavior)
    mesh = make_mesh("seq:8")
    ring_fn = make_attention("auto", mesh, seq_len=64, num_heads=8)
    q8, k8, v8 = _qkv(s=64, h=8)
    np.testing.assert_allclose(
        np.asarray(ring_fn(q8, k8, v8)),
        np.asarray(full_attention(q8, k8, v8)), rtol=2e-5, atol=2e-5)


def test_sequence_model_trains_with_chunked_attention():
    """SequenceClassifier end-to-end with the chunked path: loss falls."""
    import optax

    from shifu_tensorflow_tpu.models.sequence import SequenceClassifier

    model = SequenceClassifier(
        seq_len=32, d_model=32, num_heads=4, num_blocks=1,
        attention=make_attention("chunked", None, seq_len=32, num_heads=4),
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32 * 4)), jnp.float32)
    y = jnp.asarray((rng.random((64, 1)) < 0.5), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x)
    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(p, s):
        def loss_fn(p):
            return jnp.mean((model.apply(p, x) - y) ** 2)

        l, g = jax.value_and_grad(loss_fn)(p)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    params, opt, l0 = step(params, opt)
    for _ in range(20):
        params, opt, l = step(params, opt)
    assert float(l) < float(l0)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", BLOCKS, ids=_block_ids)
@pytest.mark.parametrize("s", [128, 96, 200])  # 96/200: padded S
def test_pallas_flash_backward_matches_full(causal, s, blocks):
    """The r05 Pallas FlashAttention-2 backward (dQ over key blocks,
    dK/dV over query blocks, P from saved logsumexp): gradients must
    match full attention including zero-padded tails, whichever tiles the
    causal walk leaves out."""
    q, k, v = _qkv(s=s, seed=3)
    want = jax.grad(
        lambda q, k, v: jnp.sum(
            full_attention(q, k, v, causal=causal) ** 2), (0, 1, 2)
    )(q, k, v)
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal, *blocks) ** 2), (0, 1, 2)
    )(q, k, v)
    for w, g in zip(want, got):
        assert not np.isnan(np.asarray(g)).any()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("blocks", [(32, 32), (16, 64), (64, 16)],
                         ids=["32x32", "16x64", "64x16"])
def test_causal_first_row_sees_only_itself_and_stays_finite(blocks):
    """Row 0's one visible key is its own: its output is ``v[0]`` whatever
    the scores, and no gradient picks up a NaN from the tiles left out or
    from a block's rows that have seen nothing yet."""
    q, k, v = _qkv(s=100, seed=11)
    q = q * 30.0  # scores far apart: a stray -inf - -inf would show
    out = flash_attention(q, k, v, True, *blocks)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(v[:, 0]),
                               rtol=1e-6, atol=1e-6)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, True, *blocks)),
        (0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
    # softmax over one key is constant in q and k: no gradient reaches them
    np.testing.assert_allclose(np.asarray(grads[0][:, 0]), 0.0, atol=1e-6)


def test_pallas_flash_backward_ab_matches_chunked_fallback(monkeypatch):
    """STPU_FLASH_BWD=chunked is the A/B seam the sweep uses: both
    gradient paths must agree on the same inputs."""
    q, k, v = _qkv(s=128, seed=5)

    def grads():
        return jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True) ** 2), (0, 1, 2)
        )(q, k, v)

    pallas_g = grads()
    monkeypatch.setenv("STPU_FLASH_BWD", "chunked")
    chunked_g = grads()
    for a, b in zip(pallas_g, chunked_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_flash_backward_bf16():
    """bf16 inputs: the backward computes f32 internally and casts the
    grads back; values track the f32 reference at bf16 tolerance."""
    rng = np.random.default_rng(9)
    qf, kf, vf = (jnp.asarray(rng.normal(size=(2, 128, 2, 32)),
                              jnp.float32) for _ in range(3))
    q, k, v = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
        (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(
            full_attention(q, k, v, causal=True) ** 2), (0, 1, 2)
    )(qf, kf, vf)
    for w, g in zip(want, got):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w), rtol=0.1, atol=0.1)
