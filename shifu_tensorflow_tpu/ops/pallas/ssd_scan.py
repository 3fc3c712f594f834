"""The chunked state-space scan with the chunk kept on the chip.

``ops/ssm_scan.py`` ``ssm_scan_chunked`` writes the algorithm as an
expression, and XLA:TPU gives it what an expression asks for: a (chunk,
chunk) decay mask a head and chunk in HBM (268 MB a layer at the
Nemotron cell's shape, and the backward's largest saved value), the
chunks' states several times over around a ``lax.scan``, and three layout
copies of x, whose head of 64 is half a 128-lane register: 9.9 GB a layer
and step for 0.88 GB of numbers (PERF.md section 6, PR 35).  Here one
kernel a direction does the same arithmetic and nothing of size (chunk,
chunk) a head or (chunks, heads, p, n) reaches HBM but the one value the
backward needs, the state that entered each chunk.

**Grid** ``(batch, group, chunk)``, the chunk axis sequential
(``"arbitrary"``) and innermost.  A grid step sees token-major,
lane-dense blocks as the projections leave them, nothing turned around in
HBM first: x and y ``(chunk, r·p)`` of ``(B, S, h·p)`` (``r`` the heads of
a group, ``p`` a head's width), B and C ``(chunk, n)`` of ``(B, S, g·n)``,
and for the group's ``r`` heads ``dt`` and the chunk's running sum ``A`` of
``dt · a`` as columns ``(chunk, r)`` and ``A`` once more as rows ``(r,
chunk)`` (2 MB tensors, laid out by ``jax.numpy`` around the call).

**In VMEM**: the group's state ``(r·p, n)`` float32, a scratch that is
zeroed at chunk 0 and lives across the chunk axis; the scores ``G = C Bᵀ``
once a group; a head's mask ``Λ[l, s] = exp(A_l − A_s)`` for ``s ≤ l`` and
0 above (the exponent masked BEFORE the ``exp``: no ``inf · 0``).  With
``u = x · dt``::

    y   = (G ∘ Λ) u + exp(A) ∘ (C Hᵀ)
    H  <- exp(A_last) H + (u ∘ exp(A_last − A))ᵀ B

**To HBM**: y, and the state that ENTERED each chunk (``f32[B, chunks,
h·p, n]``, written once, read once by the backward, alive only inside the
rematerialised layer).

**Backward**: the same grid walked with the chunk axis reversed, the
state's cotangent ``dH`` in the scratch.  From ``dy``, the entering state
and the carried ``dH``, with ``E = exp(A_last − A)``::

    dW  = dy uᵀ                    dG = Σ_heads dW ∘ Λ
    du  = (G ∘ Λ)ᵀ dy + E ∘ (B dHᵀ)
    dC  = dG B  + (exp(A) ∘ dy) H_in
    dB  = dGᵀ C + (u ∘ E) dH
    dH <- exp(A_last) dH + (exp(A) ∘ dy)ᵀ C
    dx  = du · dt        d dt = Σ_p du · x
    dA  = Σ_p [dy · (W° u) − u · (W°ᵀ dy) + dy · exp(A) · (C H_inᵀ)
               − u · E · (B dHᵀ)]
    dA_last += Σ_{l < last} Σ_p u · E · (B dHᵀ) + exp(A_last) <dH, H_in>

``A`` enters three times (``Λ``, ``exp(A)``, ``E``).  The mask's share is
the row sums of ``M = dW ∘ G ∘ Λ`` less its column sums, which are ``Σ_p
dy · (W u)`` and ``Σ_p u · (Wᵀ dy)`` with ``W = G ∘ Λ``: no reduction of
a (chunk, chunk) square.  ``a``'s gradient weighs ``dA`` by the running
sum of ``dt``, a sum that cancels to a hundredth of its terms, so the two
sides must cancel as they do in exact arithmetic: both take ``dy``, ``u``
and ``W`` as the products rounded them (what one rounded ``M`` gives the
expression), and both leave out the diagonal (``W°``: one number with two
signs, beside which a strong decay leaves nothing), as ``E``'s share
leaves out the last row (``E = exp(0)`` there).  B and C are shared by a
group's heads and never repeated: dB and dC are summed over them in VMEM.

**Precision**: a product rounds its operands to ``products`` (bfloat16:
what XLA:TPU's default matmul precision does to the expression's float32
einsums, one bf16 pass) where the expression's einsums stand, and
accumulates in float32; the exponents, the mask, the state's carry and
decay and every other element-wise step are float32.  A test that checks
the formulas passes ``products=jnp.float32``.

``softplus``, ``dt · a``, the running sum over a chunk and the ``D · x``
skip stay in ``jax.numpy`` (``ssd_scan`` and its caller), so ``a``'s and
``dt_bias``'s gradients are JAX's transpose of those few lines.

Interpret mode is a test's to ask for (tests/conftest.py
``pallas_interpret``); the program never picks it.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def ssd_pays(chunk: int, head_dim: int, heads_in_group: int, state: int,
             dtype, seq: int) -> bool:
    """Whether the scan of such shapes is the kernel's (where the program
    is lowered for the TPU): float32, a chunk and a state of whole
    128-lane registers, a group's heads filling whole registers with no
    head astride two, a sequence of whole chunks.  A pure function of
    static shapes."""
    return dtype == jnp.float32 \
        and chunk > 0 and chunk % LANES == 0 and state % LANES == 0 \
        and (heads_in_group * head_dim) % LANES == 0 \
        and (LANES % head_dim == 0 or head_dim % LANES == 0) \
        and seq % chunk == 0


# ---- inside a grid step

def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _slabs(heads: int, dim: int):
    """The group's ``heads · dim`` lanes cut into slabs of whole registers
    that hold whole heads: ``(lanes, ((head, first lane in the slab), ...))``
    a slab."""
    width = max(LANES, dim)
    per = width // dim
    return [(slice(s * width, (s + 1) * width),
             tuple((s * per + k, k * dim) for k in range(per)))
            for s in range(heads * dim // width)]


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _masks(members, dim: int, shape):
    """``{head: its lanes of a slab}``, ``None`` where the slab is the
    head."""
    if dim == shape[1]:
        return {head: None for head, _ in members}
    at = _lane(shape)
    return {head: (at >= lane) & (at < lane + dim) for head, lane in members}


def _only(t, mask):
    """``t`` with every lane outside the head's zeroed."""
    return t if mask is None else jnp.where(mask, t, 0.0)


def _spread(cols, members, width: int):
    """``(L, width)`` of ``cols`` ``(L, r)``: a slab's lanes each hold
    their head's column."""
    (first, _), *rest = members
    out = jnp.broadcast_to(cols[:, first:first + 1],
                           (cols.shape[0], width))
    for head, lane in rest:
        out = jnp.where(_lane(out.shape) >= lane,
                        cols[:, head:head + 1], out)
    return out


def _decay(a_cols, a_rows, head: int, causal):
    """``Λ`` of a head: ``exp(A_l − A_s)`` on and below the diagonal, 0
    above, the exponent masked before the ``exp``."""
    diff = a_cols[:, head:head + 1] - a_rows[head:head + 1, :]
    return jnp.exp(jnp.where(causal, diff, -jnp.inf))


def _carry(scr, before, added, last, members, dim: int):
    """A slab's heads of the carried state: ``exp(A_last)`` of the head
    times what it was, plus the chunk's.  The ``exp`` stands between the
    broadcast of ``A_last`` along the lanes and the one along the
    sublanes that the product brings: Mosaic does not lower the two as
    one."""
    for head, lane in members:
        rows = slice(head * dim, (head + 1) * dim)
        kept = jnp.exp(jnp.broadcast_to(last[:, head:head + 1],
                                        (1, before.shape[1])))
        scr[rows, :] = kept * before[rows, :] + added[lane:lane + dim, :]


def _columns(cols):
    """``(L, r)`` whose column ``j`` is ``cols[j]`` ``(L, 1)``."""
    out = jnp.zeros((cols[0].shape[0], len(cols)), jnp.float32)
    for j, col in enumerate(cols):
        out = jnp.where(_lane(out.shape) == j, col, out)
    return out


def _row(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _forward_kernel(x_ref, dt_ref, ac_ref, ar_ref, b_ref, c_ref,
                    y_ref, hin_ref, h_scr, *, heads: int, dim: int,
                    products):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    length = x_ref.shape[1]
    bo, co = b_ref[0].astype(products), c_ref[0].astype(products)
    scores = _nt(co, bo)                               # (L, L), the group's
    h_in = h_scr[...]                                  # (r·p, n)
    hin_ref[0, 0] = h_in
    from_state = _nt(co, h_in.astype(products))        # (L, r·p)
    dt, a_cols, a_rows = dt_ref[0, 0], ac_ref[0, 0], ar_ref[0, 0]
    grow = jnp.exp(a_cols)                             # exp(A)
    last = a_cols[length - 1:length, :]                # (1, r)
    to_end = jnp.exp(last - a_cols)                    # exp(A_last − A)
    causal = _row(scores.shape) >= _lane(scores.shape)
    for lanes, members in _slabs(heads, dim):
        width = lanes.stop - lanes.start
        mask = _masks(members, dim, (length, width))
        u = x_ref[0, :, lanes] * _spread(dt, members, width)
        y = _spread(grow, members, width) * from_state[:, lanes]
        for head, _ in members:
            w = scores * _decay(a_cols, a_rows, head, causal)
            y = y + _nn(w.astype(products),
                        _only(u, mask[head]).astype(products))
        y_ref[0, :, lanes] = y
        added = _tn((u * _spread(to_end, members, width)).astype(products),
                    bo)                                # (width, n)
        _carry(h_scr, h_in, added, last, members, dim)


def _backward_kernel(x_ref, dt_ref, ac_ref, ar_ref, b_ref, c_ref, hin_ref,
                     dy_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                     dh_scr, *, heads: int, dim: int, products):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)

    length = x_ref.shape[1]
    b, c = b_ref[0], c_ref[0]
    bo, co = b.astype(products), c.astype(products)
    scores = _nt(co, bo)
    h_in, dh = hin_ref[0, 0], dh_scr[...]
    ho, dho = h_in.astype(products), dh.astype(products)
    from_state = _nt(co, ho)                           # C H_inᵀ, (L, r·p)
    from_dh = _nt(bo, dho)                             # B dHᵀ
    dt, a_cols, a_rows = dt_ref[0, 0], ac_ref[0, 0], ar_ref[0, 0]
    grow = jnp.exp(a_cols)
    last = a_cols[length - 1:length, :]
    to_end = jnp.exp(last - a_cols)
    row = _row(scores.shape)
    causal = row >= _lane(row.shape)
    off = row != _lane(row.shape)                      # off the diagonal
    at_end = row[:, :1] == length - 1
    # the mask's diagonal is 1 and the products round the scores' to
    # ``products``: G_ll as the forward multiplied by it
    on = jnp.sum(jnp.where(off, 0.0, scores), axis=1, keepdims=True) \
        .astype(products).astype(jnp.float32)
    dscores = jnp.zeros((length, length), jnp.float32)
    dc = jnp.zeros(c.shape, jnp.float32)
    db = jnp.zeros(b.shape, jnp.float32)
    ddt_cols, da_cols = [None] * heads, [None] * heads
    for lanes, members in _slabs(heads, dim):
        width = lanes.stop - lanes.start
        held = slice(lanes.start, lanes.stop)          # the state's rows
        mask = _masks(members, dim, (length, width))
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        dt_s = _spread(dt, members, width)
        end_s = _spread(to_end, members, width)
        grown = dy * _spread(grow, members, width)
        u = x * dt_s
        uo, dyo = u.astype(products), dy.astype(products)
        du_state = end_s * from_dh[:, lanes]
        # (G ∘ Λ) u and (G ∘ Λ)ᵀ dy WITHOUT the diagonal: its terms in
        # dA's row sums and column sums are one number with two signs,
        # and a strong decay leaves nothing else
        y_off = jnp.zeros(u.shape, jnp.float32)
        du_off = jnp.zeros(u.shape, jnp.float32)
        for head, _ in members:
            decay = _decay(a_cols, a_rows, head, causal)
            w_off = jnp.where(off, scores * decay, 0.0).astype(products)
            dy_h = _only(dy, mask[head]).astype(products)
            dscores = dscores + decay * _nt(dy_h, uo)
            du_off = du_off + _tn(w_off, dy_h)
            y_off = y_off + _nn(w_off,
                                _only(u, mask[head]).astype(products))
        du = du_off + on * dyo.astype(jnp.float32) + du_state
        dx_ref[0, :, lanes] = du * dt_s
        dux = du * x
        # ... and of what every row sent to the chunk's end, the last
        # row's own share (exp(0): no A in it)
        ended = jnp.where(at_end, 0.0, u * du_state)
        # dA a row: the mask's row sums less its column sums, each from
        # the operands as the products above rounded them, so the two
        # cancel over a chunk as they do in exact arithmetic; the
        # entering state's term; the state's contribution's term
        da_rows = (dyo.astype(jnp.float32) * y_off
                   - uo.astype(jnp.float32) * du_off
                   + grown * from_state[:, lanes] - ended)
        ended = jnp.sum(ended, axis=0, keepdims=True)  # (1, width)
        for head, _ in members:
            rows = slice(head * dim, (head + 1) * dim)
            in_head = None if mask[head] is None else mask[head][:1]
            ddt_cols[head] = jnp.sum(_only(dux, mask[head]), axis=1,
                                     keepdims=True)
            # the two terms at A_last: what every row sent to the end,
            # and the entering state's decay
            kept = jnp.sum(jnp.sum(dh[rows, :] * h_in[rows, :], axis=0,
                                   keepdims=True), axis=1, keepdims=True)
            at_last = (
                jnp.sum(_only(ended, in_head), axis=1, keepdims=True)
                + grow[length - 1:length, head:head + 1] * kept)
            da_cols[head] = (
                jnp.sum(_only(da_rows, mask[head]), axis=1, keepdims=True)
                + jnp.where(at_end, at_last, 0.0))
        grown = grown.astype(products)
        dc = dc + _nn(grown, ho[held, :])
        db = db + _nn((u * end_s).astype(products), dho[held, :])
        entered = _tn(grown, co)                       # (width, n)
        _carry(dh_scr, dh, entered, last, members, dim)
    ddt_ref[0, 0] = _columns(ddt_cols)
    da_ref[0, 0] = _columns(da_cols)
    dso = dscores.astype(products)
    dc_ref[0] = dc + _nn(dso, bo)
    db_ref[0] = db + _tn(dso, co)


# ---- the two calls

def _specs(chunks: int, length: int, heads: int, dim: int, state: int,
           reverse: bool):
    """Block specs of the walk, by the array's kind."""
    def at(i):
        return chunks - 1 - i if reverse else i

    return {
        "x": pl.BlockSpec((1, length, heads * dim),
                          lambda b, g, i: (b, at(i), g)),
        "bc": pl.BlockSpec((1, length, state),
                           lambda b, g, i: (b, at(i), g)),
        "cols": pl.BlockSpec((1, 1, length, heads),
                             lambda b, g, i: (b, g, at(i), 0)),
        "rows": pl.BlockSpec((1, 1, heads, length),
                             lambda b, g, i: (b, g, 0, at(i))),
        "state": pl.BlockSpec((1, 1, heads * dim, state),
                              lambda b, g, i: (b, at(i), g, 0)),
    }


@functools.cache
def _walks(call, bsz: int, chunks: int, length: int, groups: int,
           heads: int, dim: int, state: int, products):
    """``(forward, backward)``: the two kernel calls of a shape, built
    ONCE.  JAX keeps a call's traced kernel by the callable it was given,
    so a call built anew at every layer traces its kernel anew: four
    traces of the forward kernel and eight of the backward (~700
    ``jax.numpy`` calls each) took 26 s of the Nemotron cell's set-up
    (PERF.md section 6, PR 35); the same callable traces once.  ``call`` is ``pl.pallas_call`` as the caller finds it,
    so a test's interpret-mode patch builds its own."""
    seq, width = chunks * length, groups * heads * dim
    x = jax.ShapeDtypeStruct((bsz, seq, width), jnp.float32)
    bc = jax.ShapeDtypeStruct((bsz, seq, groups * state), jnp.float32)
    cols = jax.ShapeDtypeStruct((bsz, groups, seq, heads), jnp.float32)
    entering = jax.ShapeDtypeStruct((bsz, chunks, width, state),
                                    jnp.float32)
    walk = dict(
        grid=(bsz, groups, chunks),
        scratch_shapes=[pltpu.VMEM((heads * dim, state), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))
    kernel = dict(heads=heads, dim=dim, products=products)
    at = _specs(chunks, length, heads, dim, state, reverse=False)
    forward = call(
        partial(_forward_kernel, **kernel),
        in_specs=[at["x"], at["cols"], at["cols"], at["rows"], at["bc"],
                  at["bc"]],
        out_specs=[at["x"], at["state"]], out_shape=[x, entering],
        name="ssd_scan_fwd", **walk)
    at = _specs(chunks, length, heads, dim, state, reverse=True)
    backward = call(
        partial(_backward_kernel, **kernel),
        in_specs=[at["x"], at["cols"], at["cols"], at["rows"], at["bc"],
                  at["bc"], at["state"], at["x"]],
        out_specs=[at["x"], at["cols"], at["cols"], at["bc"], at["bc"]],
        out_shape=[x, cols, cols, bc, bc],
        name="ssd_scan_bwd", **walk)
    return forward, backward


def _walks_of(x, dt_cols, b, chunk: int, products):
    """:func:`_walks` of the arrays as the calls take them."""
    bsz, seq, width = x.shape
    groups, heads = dt_cols.shape[1], dt_cols.shape[3]
    return _walks(pl.pallas_call, bsz, seq // chunk, chunk, groups, heads,
                  width // (groups * heads), b.shape[2] // groups, products)


# ---- the differentiable scan

def _by_group(v, groups: int):
    """``(B, S, h) -> (B, g, S, r)``: a group's heads as columns."""
    bsz, seq, heads = v.shape
    return v.reshape(bsz, seq, groups, heads // groups).transpose(0, 2, 1, 3)


def _by_token(v):
    """``(B, g, S, r) -> (B, S, h)``, :func:`_by_group`'s inverse."""
    bsz, groups, seq, heads = v.shape
    return v.transpose(0, 2, 1, 3).reshape(bsz, seq, groups * heads)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_chunks(x, dt, a_cum, b, c, chunk: int, products=jnp.bfloat16):
    """``y`` (B, S, h, p) of x (B, S, h, p), ``dt`` and ``a_cum`` (B, S,
    h) (the running sum of ``dt · a`` inside each chunk), ``b`` and ``c``
    (B, S, g, n), all float32 and of shapes for which :func:`ssd_pays`
    holds; differentiable in all five."""
    return _ssd_chunks_fwd(x, dt, a_cum, b, c, chunk, products)[0]


def _ssd_chunks_fwd(x, dt, a_cum, b, c, chunk, products):
    bsz, seq, heads, dim = x.shape
    groups, state = b.shape[2], b.shape[3]
    laid = (x.reshape(bsz, seq, heads * dim), _by_group(dt, groups),
            _by_group(a_cum, groups),
            _by_group(a_cum, groups).transpose(0, 1, 3, 2),
            b.reshape(bsz, seq, groups * state),
            c.reshape(bsz, seq, groups * state))
    forward, _ = _walks_of(laid[0], laid[1], laid[4], chunk, products)
    y, entering = forward(*laid)
    return y.reshape(x.shape), laid + (entering,)


def _ssd_chunks_bwd(chunk, products, saved, dy):
    x, dt_cols = saved[:2]
    bsz, seq, _ = x.shape
    groups = dt_cols.shape[1]
    _, backward = _walks_of(x, dt_cols, saved[4], chunk, products)
    dx, ddt, da, db, dc = backward(*saved, dy.reshape(x.shape))
    return (dx.reshape(dy.shape), _by_token(ddt), _by_token(da),
            db.reshape(bsz, seq, groups, -1),
            dc.reshape(bsz, seq, groups, -1))


ssd_chunks.defvjp(_ssd_chunks_fwd, _ssd_chunks_bwd)


def ssd_scan(x, dt, a, b, c, chunk: int, products=jnp.bfloat16):
    """``ssm_scan_chunked(x, dt, a, b, c, chunk)`` by the kernels, for
    shapes :func:`ssd_pays` admits: ``dt · a`` and its running sum over a
    chunk here, in ``jax.numpy``, so ``a``'s gradient and ``dt``'s share
    through the decay are JAX's transpose of these lines."""
    bsz, seq, heads, _ = x.shape
    log_decay = (dt * a).astype(jnp.float32)
    a_cum = jnp.cumsum(
        log_decay.reshape(bsz, seq // chunk, chunk, heads), axis=2)
    return ssd_chunks(x, dt, a_cum.reshape(bsz, seq, heads), b, c, chunk,
                      products)
