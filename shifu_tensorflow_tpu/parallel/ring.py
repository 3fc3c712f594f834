"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence dimension anywhere (fixed-width tabular
vectors, SURVEY.md §5.7), but long-context scaling is first-class in this
framework: when a sequence model family lands, its attention must already
scale past one chip's HBM.  Two standard schemes over a mesh ``seq`` axis:

- **ring attention** (`ring_attention`): Q stays put; K/V blocks rotate
  around the ring via ``jax.lax.ppermute`` while a numerically-stable
  online softmax (running max / normalizer, flash-attention style)
  accumulates the output.  Peak memory per chip is O(S/P) for any total
  sequence length; the K/V transfer rides ICI and overlaps with the next
  block's compute under XLA's scheduler.
- **Ulysses all-to-all** (`ulysses_attention`): ``jax.lax.all_to_all``
  re-shards sequence → heads, runs full local attention on H/P heads, and
  re-shards back.  Cheaper collectives for moderate S; requires P | H.

Both are functional ops designed for ``shard_map`` over the mesh; the
``*_sharded`` wrappers apply the shard_map boilerplate.  Numerics are
validated against single-device full attention in tests/test_ring.py on
the 8-device CPU mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

SEQ_AXIS = "seq"


def _check_window(causal: bool, window: "int | None") -> None:
    if window is not None and (not causal or window <= 0):
        raise ValueError(
            f"window={window} needs causal attention and a window > 0")


def full_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    window: "int | None" = None,
) -> jax.Array:
    """Reference single-device attention.  Shapes (B, S, H, D).
    ``window`` (causal only): key ``j`` is visible to query ``i`` iff
    ``j <= i`` and ``i - j < window``."""
    _check_window(causal, window)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask = jnp.logical_and(
                mask, jnp.triu(jnp.ones((sq, sk), bool),
                               k=sk - sq - window + 1))
        scores = jnp.where(mask, scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def _block_update(q, k, v, acc, m, l, *, scale, mask=None):
    """One online-softmax step against a K/V block.

    acc: (B, Sq, H, D) running numerator; m: (B, H, Sq) running max;
    l: (B, H, Sq) running normalizer.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp(-inf - -inf) guards: where m_new is still -inf nothing has been
    # seen; keep the correction factor at 0 to avoid NaNs
    corr = jnp.where(jnp.isneginf(m_new), 0.0, jnp.exp(m - m_new))
    p = jnp.exp(scores - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_size: int = 512,
    window: "int | None" = None,
) -> jax.Array:
    """Single-device flash-style attention: O(S·block) working memory,
    no S×S materialization, in EITHER direction.

    Forward: ``lax.scan`` over K/V blocks with the same online softmax
    the ring path uses (`_block_update`) — the motivation is full
    attention's (S, S) score matrix, which takes over the step as S
    grows at a fixed token budget.
    Backward: a custom VJP (the standard flash decomposition) that
    saves only ``out`` and the per-row logsumexp — O(B·S·H·D) residuals
    — and recomputes each block's softmax weights inside a second scan.
    (custom_vjp means NO forward-mode autodiff — ``jax.jvp``/``jacfwd``
    through this path raises; use ``full_attention`` for that.)
    Shapes (B, S, H, D); K/V are zero-padded up to a block multiple
    with the padded keys masked out, so any sequence length works.
    ``window`` (causal only) is :func:`full_attention`'s, as a mask: every
    K/V block is still visited (the Pallas flash kernel skips them).
    """
    _check_window(causal, window)
    s = k.shape[1]
    if s <= block_size:  # a single block IS full attention
        return full_attention(q, k, v, causal=causal, window=window)
    return _chunked(q, k, v, causal, min(block_size, s), window)


def _block_mask(blk_idx, sq: int, blk: int, s_real: int,
                causal: bool, padded: bool, window: "int | None" = None):
    """(1, 1, sq, blk) validity mask for one K/V block, or None."""
    if not (causal or padded):
        return None
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, blk), 0)
    k_pos = blk_idx * blk + jax.lax.broadcasted_iota(
        jnp.int32, (sq, blk), 1)
    mask = jnp.ones((sq, blk), bool)
    if padded:
        mask = jnp.logical_and(mask, k_pos < s_real)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask[None, None]


def _split_blocks(x, nblk: int, blk: int):
    """(B, nblk·blk, H, D) -> f32 (nblk, B, blk, H, D) for scan."""
    b, _, h, d = x.shape
    return x.astype(jnp.float32).reshape(b, nblk, blk, h, d).transpose(
        1, 0, 2, 3, 4)


def _prep_blocks(q, k, v, blk: int):
    """Shared fwd/bwd preamble: pad K/V up to a block multiple (rather
    than shrinking the block to a divisor of S — for prime-ish S that
    collapses to blk=1, an S-step scan), split into scan-major blocks,
    cast to f32.  ONE implementation so forward and backward can never
    disagree about the block layout."""
    b, s, h, d = k.shape
    sp = -(-s // blk) * blk
    nblk = sp // blk
    padded = sp != s
    if padded:
        k = jnp.pad(k, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    return (q.astype(jnp.float32), _split_blocks(k, nblk, blk),
            _split_blocks(v, nblk, blk), q.shape[-1] ** -0.5,
            nblk, padded, sp, s)


def _chunked_fwd_impl(q, k, v, causal: bool, blk: int, window=None):
    b, _, h, d = k.shape
    qf, ks, vs, scale, nblk, padded, sp, s = _prep_blocks(q, k, v, blk)
    sq = q.shape[1]

    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)

    def step(carry, xs):
        acc, m, l = carry
        blk_idx, kb, vb = xs
        mask = _block_mask(blk_idx, sq, blk, s, causal, padded, window)
        acc, m, l = _block_update(qf, kb, vb, acc, m, l,
                                  scale=scale, mask=mask)
        return (acc, m, l), None

    (acc, m, l), _ = jax.lax.scan(
        step, (acc, m, l), (jnp.arange(nblk), ks, vs)
    )
    seen = l > 0.0
    l_safe = jnp.where(seen, l, 1.0)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    # logsumexp per row; +inf where a row saw NO valid key, so the
    # backward's exp(scores - lse) is exactly 0 for those rows
    lse = jnp.where(seen, m + jnp.log(l_safe), jnp.inf)
    return out.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _chunked(q, k, v, causal: bool, blk: int, window=None):
    out, _ = _chunked_fwd_impl(q, k, v, causal, blk, window)
    return out


def _chunked_fwd(q, k, v, causal: bool, blk: int, window):
    out, lse = _chunked_fwd_impl(q, k, v, causal, blk, window)
    return out, (q, k, v, out, lse)


def _chunked_bwd(causal: bool, blk: int, window, res, g):
    """Flash backward: recompute each block's weights from (q, k, lse).

    dS = p ∘ (g·vᵀ − D) with D = rowsum(g ∘ out); dq accumulates as the
    scan carry, dk/dv emit per block.  Residual memory is O(B·S·H·D) —
    out + lse + inputs — never the (S, S) matrix.
    """
    q, k, v, out, lse = res
    b, _, h, d = k.shape
    qf, ks, vs, scale, nblk, padded, sp, s = _prep_blocks(q, k, v, blk)
    sq = q.shape[1]
    gf = g.astype(jnp.float32)
    # D_i = Σ_d g_id · out_id, laid out (B, H, Sq) like lse
    D = jnp.sum(gf * out.astype(jnp.float32), axis=-1).transpose(0, 2, 1)

    def step(dq, xs):
        blk_idx, kb, vb = xs
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kb) * scale
        p = jnp.exp(scores - lse[..., None])
        mask = _block_mask(blk_idx, sq, blk, s, causal, padded, window)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_b = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
        dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vb)
        dS = p * (dp - D[..., None])
        dq = dq + scale * jnp.einsum("bhqk,bkhd->bqhd", dS, kb)
        dk_b = scale * jnp.einsum("bhqk,bqhd->bkhd", dS, qf)
        return dq, (dk_b, dv_b)

    dq, (dks, dvs) = jax.lax.scan(
        step, jnp.zeros(q.shape, jnp.float32),
        (jnp.arange(nblk), ks, vs),
    )
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(b, sp, h, d)[:, :s]
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(b, sp, h, d)[:, :s]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
) -> jax.Array:
    """Blockwise ring attention over sequence shards.

    Call inside ``shard_map`` with q/k/v sharded (B, S/P, H, D) along
    ``axis_name``.  K/V blocks rotate ring-wise; each chip accumulates its
    queries' output with an online softmax, so the full attention matrix is
    never materialized and any S runs in O(S/P) memory per chip.
    """
    p_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = q.shape[-1] ** -0.5
    sq = q.shape[1]
    b, _, h, d = q.shape

    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    qf = q.astype(jnp.float32)

    perm = [(i, (i + 1) % p_size) for i in range(p_size)]

    def step(carry, step_idx):
        acc, m, l, kb, vb = carry
        # the block now held arrived from (my_idx - step_idx) around the ring
        src = (my_idx - step_idx) % p_size
        mask = None
        if causal:
            sk = kb.shape[1]
            q_pos = my_idx * sq + jax.lax.broadcasted_iota(
                jnp.int32, (sq, sk), 0
            )
            k_pos = src * sk + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
            mask = (k_pos <= q_pos)[None, None]
        acc, m, l = _block_update(
            qf, kb.astype(jnp.float32), vb.astype(jnp.float32),
            acc, m, l, scale=scale, mask=mask,
        )
        # rotate K/V to the next chip (skippable on the last step, but a
        # uniform loop body keeps the collective schedule static)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (acc, m, l, kb, vb), None

    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc, m, l, k, v), jnp.arange(p_size)
    )
    # rows that saw no unmasked key (causal, strictly-later queries cannot
    # exist here since every chip sees its own block, but guard anyway)
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses scheme).

    Inside ``shard_map`` with (B, S/P, H, D) shards: all-to-all re-shards to
    (B, S, H/P, D), full attention runs locally over the whole sequence for
    a head subset, and the inverse all-to-all restores sequence sharding.
    Requires the head count to be divisible by the axis size.
    """
    # (B, S/P, H, D) -> (B, S, H/P, D): split heads, concat sequence
    # (tiled: concatenate into the existing axis rather than stacking a new
    # leading P dimension)
    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    qh = a2a(q, split_axis=2, concat_axis=1)
    kh = a2a(k, split_axis=2, concat_axis=1)
    vh = a2a(v, split_axis=2, concat_axis=1)
    out = full_attention(qh, kh, vh, causal=causal)
    # back: split sequence, concat heads
    return a2a(out, split_axis=1, concat_axis=2)


def _sharded(fn, mesh, axis_name, comm_label=None):
    from shifu_tensorflow_tpu.parallel.shmap import shard_map

    spec = P(None, axis_name, None, None)
    return shard_map(
        fn, mesh, in_specs=(spec, spec, spec), out_specs=spec,
        comm_label=comm_label,
    )


def _nbytes(*arrays) -> int:
    return sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays)


def ring_attention_sharded(
    mesh, q, k, v, *, axis_name: str = SEQ_AXIS, causal: bool = False
):
    """shard_map-wrapped ring attention: q/k/v are global (B, S, H, D)
    arrays; S is sharded over ``axis_name`` of ``mesh``.

    The call runs under an obs comm region (``comm.ring_attention``
    tracer span + compile-attribution frame + bytes-moved counter): the
    ring rotates the full K/V once per step for ``P`` steps, so the
    static bytes-moved estimate is ``(|K| + |V|) * P`` — attribution,
    not a NIC counter.  Counted per HOST call: eager use counts every
    invocation; from inside an enclosing ``jit`` (the sequence model's
    attention fn) the region runs at trace time, i.e. once per compile
    (obs/fleet.comm_region)."""
    from shifu_tensorflow_tpu.obs import fleet as obs_fleet

    fn = partial(ring_attention, axis_name=axis_name, causal=causal)
    p = int(mesh.shape[axis_name])
    with obs_fleet.comm_region("ring_attention",
                               nbytes=_nbytes(k, v) * max(1, p)):
        return _sharded(fn, mesh, axis_name, comm_label=None)(q, k, v)


def ulysses_attention_sharded(
    mesh, q, k, v, *, axis_name: str = SEQ_AXIS, causal: bool = False
):
    """Ulysses all-to-all under ``comm.all_to_all``: four re-shards
    (q/k/v in, out back), each moving ~(P-1)/P of its tensor — the
    static estimate charges the four tensors once."""
    from shifu_tensorflow_tpu.obs import fleet as obs_fleet

    fn = partial(ulysses_attention, axis_name=axis_name, causal=causal)
    with obs_fleet.comm_region("all_to_all",
                               nbytes=_nbytes(q, k, v) + _nbytes(q)):
        return _sharded(fn, mesh, axis_name, comm_label=None)(q, k, v)
