"""The rotary embedding as one pass over whole 128-lane registers.

``models/hybrid_lm.py`` ``apply_rope`` slices a head into halves,
multiplies and concatenates.  XLA:TPU compiles that, at a head of 128, to
three passes over HBM a tensor and direction: the halves written as two
arrays whose 64-wide minor dimension is padded to 128 lanes, the
concatenation, and then the flash kernels' own ``(B, S, H, D) -> (B·H, S,
D)`` transposition: 2.14 GB moved for 0.27 GB of numbers (PERF.md section
6, PR 33).

Here a head stays whole registers.  With ``R`` the dimensions that turn,
``o`` the first of them (0 for the grouped-query heads, 192 for latent
attention's, whose LAST 64 of 256 turn) and ``D`` the head's, dimension
``o + m``, ``m < R / 2``, pairs with ``o + m + R / 2``:

    out = u·C + roll(u, D − R/2)·A + roll(u, R/2)·B

over ``(S, D)`` float32 tables ``C = [1…, cos, cos, 1…]``, ``A = [0…,
−sin, 0, 0…]``, ``B = [0…, 0, sin, 0…]``, the turning dimensions placed at
``o`` (``roll`` as ``jnp.roll`` along the lanes; the tables are zero where
a roll wraps).  At ``R = D`` the two rolls coincide and one table
``[−sin, sin]`` serves.  The same two products and one sum an element as
the expression, in float32; a dimension that passes through is multiplied
by exactly 1.

The kernel reads a block of rows of ``(B, S, H·D)``, as the projection
leaves it, and writes ``(B, H, S, D)``, which it hands back as the logical
``(B, S, H, D)`` through ``.transpose(0, 2, 1, 3)``: the flash kernels'
transposition is that one's inverse and XLA folds the pair, so the
kernel's output is the flash kernel's operand (tests/test_tpu_compile.py
reads that in the compiled program).  The backward is the transposed
rotation by the same kernel in the other direction, from the ``(B, H, S,
D)`` cotangent the dQ / dK kernels leave to ``(B, S, H·D)``:

    du = g·C + roll(g, R/2)·roll(A, R/2) + roll(g, D − R/2)·roll(B, −R/2)

The residuals are ``cos`` and ``sin`` alone.  Heads are innermost in the
grid, so a row block's tables are fetched once for all its heads.  A
block's rows follow from the head's width (:func:`_rows`), so that blocks
in and out, double-buffered, stay inside a v5e's scoped VMEM.

Latent attention's keys and values (:func:`latent_lanes`) take the same
one pass a direction.  One projection leaves ``[k_n ; v]`` a head, 192 +
256 = 3.5 registers, and there is ONE rotary key for all heads; the
expression slices the heads apart, broadcasts the turned key to every
head and concatenates, and its transpose puts the parts back with padded
sums: five to eight passes a layer.  The kernel reads a block of rows of
``(B, S, H·448)`` through static lane offsets (an odd head starts at lane
64 of a register; Mosaic shifts it), turns the one key once a block and
writes k = ``[k_n ; rot(k_r)]`` and v, both ``(B, H, S, 256)``, in the
same grid step; the transposed kernel reads dK and dV where the flash
kernels left them, writes ``[dk_n ; dv]`` a head and carries the sum of
dK's last 64 lanes over the heads (the grid's innermost axis) in VMEM,
turning it back after the last: the one key's gradient, the same 20
terms in head order.

Interpret mode is a test's to ask for (tests/conftest.py
``pallas_interpret``); the program never picks it.
"""

from __future__ import annotations

import math
from functools import cache, partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: rows of a grid step's block
BLOCK_ROWS = 1024
#: heads a grid step takes where the tensor has as many: a row of the
#: block is then 2 KB that lie together, not 512 bytes at a stride
BLOCK_HEADS = 4


def _rows(width: int) -> int:
    """Rows of a block whose row is ``width`` lanes a head: the power of
    two that keeps a block at ``BLOCK_ROWS`` rows of one register's bytes
    (1,024 at 128 lanes, 512 at 256, 256 at 448), so that blocks in and
    out, double-buffered, stay inside the scoped VMEM of a v5e."""
    return 1 << ((BLOCK_ROWS * LANES // width).bit_length() - 1)


def lanes_pay(head_dim: int, rotary_dim: int, dtype, offset: int = 0) -> bool:
    """Whether the rotation of such a head is the kernel's (where the
    program is lowered for the TPU): a head of whole 128-lane registers,
    a distance between partners that divides a register, float32; the
    dimensions that turn are ``offset .. offset + rotary_dim``.  A pure
    function of static shapes."""
    half = rotary_dim // 2
    return 0 < rotary_dim and 0 <= offset \
        and offset + rotary_dim <= head_dim and head_dim % LANES == 0 \
        and rotary_dim == 2 * half and LANES % half == 0 \
        and dtype == jnp.float32


def _tables(cos, sin, head_dim: int, offset: int = 0):
    """``(C, ((shift, table), ...))`` of the forward rotation, each table
    (S, head_dim) float32, the turning dimensions placed at ``offset``."""
    seq, half = cos.shape
    rest = head_dim - offset - 2 * half
    if not (offset or rest):
        return jnp.concatenate([cos, cos], 1), (
            (half, jnp.concatenate([-sin, sin], 1)),)

    def placed(first, second, still):
        before, after = (jnp.full((seq, n), still, jnp.float32)
                         for n in (offset, rest))
        return jnp.concatenate(
            [t for t in (before, first, second, after) if t.shape[1]], 1)

    zero = jnp.zeros((seq, half), jnp.float32)
    return placed(cos, cos, 1.0), (
        (head_dim - half, placed(-sin, zero, 0.0)),
        (half, placed(zero, sin, 0.0)))


def _transposed(turns, head_dim: int):
    """The ``(shift, table)`` pairs of the transposed rotation:
    ``roll(g·T, −s) = roll(g, −s)·roll(T, −s)``."""
    return tuple(((head_dim - shift) % head_dim,
                  jnp.roll(table, -shift, axis=1))
                 for shift, table in turns)


def _turn(u_ref, c_ref, *refs, shifts, dim: int, to_heads: bool):
    """One block: ``(1, rows, heads·dim) -> (1, heads, rows, dim)`` where
    ``to_heads``, the other way round where not."""
    *t_refs, out_ref = refs
    heads = (out_ref if to_heads else u_ref).shape[1]
    for j in range(heads):
        lanes = slice(j * dim, (j + 1) * dim)
        u = u_ref[0, :, lanes] if to_heads else u_ref[0, j]
        out = u * c_ref[...]
        for shift, t_ref in zip(shifts, t_refs):
            out = out + pltpu.roll(u, shift, 1) * t_ref[...]
        if to_heads:
            out_ref[0, j] = out
        else:
            out_ref[0, :, lanes] = out


def _pass(x, c, turns, *, heads: int, to_heads: bool,
          block_rows: "int | None"):
    """``x·C + Σ roll(x, shift)·T`` a head: ``(B, S, H·D) -> (B, H, S,
    D)`` where ``to_heads``, ``(B, H, S, D) -> (B, S, H·D)`` where not."""
    if to_heads:
        bsz, seq, width = x.shape
        dim = width // heads
    else:
        bsz, _, seq, dim = x.shape
    rows = min(seq, block_rows or _rows(dim))
    step = math.gcd(heads, BLOCK_HEADS)
    token_major = pl.BlockSpec((1, rows, step * dim),
                               lambda b, i, h: (b, i, h))
    head_major = pl.BlockSpec((1, step, rows, dim),
                              lambda b, i, h: (b, h, i, 0))
    table = pl.BlockSpec((rows, dim), lambda b, i, h: (i, 0))
    return pl.pallas_call(
        partial(_turn, shifts=tuple(s for s, _ in turns), dim=dim,
                to_heads=to_heads),
        grid=(bsz, pl.cdiv(seq, rows), heads // step),
        in_specs=[token_major if to_heads else head_major,
                  table, *(table for _ in turns)],
        out_specs=head_major if to_heads else token_major,
        out_shape=jax.ShapeDtypeStruct(
            (bsz, heads, seq, dim) if to_heads else (bsz, seq, heads * dim),
            x.dtype),
        name="rope_lanes" if to_heads else "rope_lanes_t",
    )(x, c, *(t for _, t in turns))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rope_lanes(u, cos, sin, block_rows: "int | None" = None,
               offset: int = 0):
    """``apply_rope(u, cos, sin, offset)`` of a float32 ``(B, S, H, D)``
    for which :func:`lanes_pay` holds, by the kernel; the result's layout
    in memory is ``(B, H, S, D)``.  ``cos`` and ``sin`` take no gradient.
    ``block_rows`` is a test's to give (a short sequence in several
    blocks); left out, :func:`_rows` derives it from the head's width."""
    return _rope_lanes_fwd(u, cos, sin, block_rows, offset)[0]


def _rope_lanes_fwd(u, cos, sin, block_rows, offset):
    bsz, seq, heads, dim = u.shape
    c, turns = _tables(cos, sin, dim, offset)
    out = _pass(u.reshape(bsz, seq, heads * dim), c, turns, heads=heads,
                to_heads=True, block_rows=block_rows)
    return out.transpose(0, 2, 1, 3), (cos, sin)


def _rope_lanes_bwd(block_rows, offset, tables, g):
    cos, sin = tables
    bsz, seq, heads, dim = g.shape
    c, turns = _tables(cos, sin, dim, offset)
    du = _pass(g.transpose(0, 2, 1, 3), c, _transposed(turns, dim),
               heads=heads, to_heads=False, block_rows=block_rows)
    return du.reshape(bsz, seq, heads, dim), None, None


rope_lanes.defvjp(_rope_lanes_fwd, _rope_lanes_bwd)


# ---- latent attention's keys and values: ``[k_n ; v]`` a head as one
# projection leaves them, ONE rotary key for every head


def heads_pay(nope_dim: int, rotary_dim: int, value_dim: int, heads: int,
              dtype) -> bool:
    """Whether :func:`latent_lanes` is the kernels' (where the program is
    lowered for the TPU): float32, a key ``[k_n ; k_r]`` and a value of
    whole 128-lane registers each, and a grid step's heads of ``[k_n ;
    v]`` ending on a register's edge.  A pure function of static shapes."""
    step = math.gcd(heads, BLOCK_HEADS)
    return dtype == jnp.float32 and rotary_dim > 0 and rotary_dim % 2 == 0 \
        and nope_dim > 0 and (nope_dim + rotary_dim) % LANES == 0 \
        and value_dim > 0 and value_dim % LANES == 0 \
        and step * (nope_dim + value_dim) % LANES == 0


def _spread(kv_ref, kr_ref, cos_ref, sin_ref, k_ref, v_ref, *, nope: int,
            value: int):
    """One block: ``(1, rows, heads·(nope + value))`` and the one key
    ``(1, rows, R)`` -> k ``(1, heads, rows, nope + R)``, v ``(1, heads,
    rows, value)``, through static lane offsets."""
    half = cos_ref.shape[1]
    k1, k2 = kr_ref[0, :, :half], kr_ref[0, :, half:]
    cos, sin = cos_ref[...], sin_ref[...]
    first, second = k1 * cos - k2 * sin, k2 * cos + k1 * sin
    for j in range(k_ref.shape[1]):
        at = j * (nope + value)
        k_ref[0, j, :, :nope] = kv_ref[0, :, at:at + nope]
        k_ref[0, j, :, nope:nope + half] = first
        k_ref[0, j, :, nope + half:] = second
        v_ref[0, j] = kv_ref[0, :, at + nope:at + nope + value]


def _gather(dk_ref, dv_ref, cos_ref, sin_ref, dkv_ref, dkr_ref, sum_ref, *,
            nope: int, value: int):
    """:func:`_spread`'s transpose: the heads' ``dk_n`` and ``dv`` back
    beside each other, lanes ``nope ..`` of ``dk`` summed over the heads
    (the innermost grid axis; ``sum_ref`` carries the sum from one step of
    it to the next) and, after the last, turned back."""
    half = cos_ref.shape[1]
    step = pl.program_id(2)
    total = None
    for j in range(dk_ref.shape[1]):
        at = j * (nope + value)
        dkv_ref[0, :, at:at + nope] = dk_ref[0, j, :, :nope]
        dkv_ref[0, :, at + nope:at + nope + value] = dv_ref[0, j]
        part = dk_ref[0, j, :, nope:]
        total = part if total is None else total + part

    @pl.when(step == 0)
    def _():
        sum_ref[...] = total

    @pl.when(step > 0)
    def _():
        sum_ref[...] += total

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        g1, g2 = sum_ref[:, :half], sum_ref[:, half:]
        cos, sin = cos_ref[...], sin_ref[...]
        dkr_ref[0, :, :half] = g1 * cos + g2 * sin
        dkr_ref[0, :, half:] = g2 * cos - g1 * sin


@cache
def _latent_calls(bsz: int, seq: int, heads: int, nope: int, turning: int,
                  value: int, rows: int, call):
    """(forward, transposed) kernel calls of a shape, built once: a call
    built anew at every layer is traced anew (PERF.md section 6, PR 35).
    ``call`` is ``pl.pallas_call`` as found, so that a test's interpret
    mode builds its own."""
    step = math.gcd(heads, BLOCK_HEADS)
    grid = (bsz, pl.cdiv(seq, rows), heads // step)
    both = pl.BlockSpec((1, rows, step * (nope + value)),
                        lambda b, i, h: (b, i, h))
    one_key = pl.BlockSpec((1, rows, turning), lambda b, i, h: (b, i, 0))
    table = pl.BlockSpec((rows, turning // 2), lambda b, i, h: (i, 0))

    def head_major(dim):
        return pl.BlockSpec((1, step, rows, dim),
                            lambda b, i, h: (b, h, i, 0))

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32)

    key = nope + turning
    forward = call(
        partial(_spread, nope=nope, value=value), grid=grid,
        in_specs=[both, one_key, table, table],
        out_specs=[head_major(key), head_major(value)],
        out_shape=[shape(bsz, heads, seq, key),
                   shape(bsz, heads, seq, value)],
        name="latent_lanes")
    transposed = call(
        partial(_gather, nope=nope, value=value), grid=grid,
        in_specs=[head_major(key), head_major(value), table, table],
        out_specs=[both, one_key],
        out_shape=[shape(bsz, seq, heads * (nope + value)),
                   shape(bsz, seq, turning)],
        scratch_shapes=[pltpu.VMEM((rows, turning), jnp.float32)],
        name="latent_lanes_t")
    return forward, transposed


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def latent_lanes(kv, k_r, cos, sin, nope: int,
                 block_rows: "int | None" = None):
    """Latent attention's keys and values as the flash kernels read them,
    for shapes :func:`heads_pay` holds for: from ``kv`` ``(B, S, H, nope +
    V)``, a head ``[k_n ; v]``, and the ONE rotary key ``k_r`` ``(B, S, 1,
    R)``, the keys ``[k_n ; rot(k_r)]`` ``(B, S, H, nope + R)`` and the
    values ``(B, S, H, V)``, both laid out ``(B, H, S, ·)`` in memory, by
    one pass; its transpose, one pass too, hands back ``[dk_n ; dv]`` a
    head and the sum over the heads of the keys' last ``R`` lanes, turned
    back.  ``cos`` and ``sin`` take no gradient and are all that is
    saved."""
    return _latent_lanes_fwd(kv, k_r, cos, sin, nope, block_rows)[0]


def _calls_of(bsz, seq, heads, nope, turning, value, block_rows):
    rows = min(seq, block_rows or _rows(nope + value))
    return _latent_calls(bsz, seq, heads, nope, turning, value, rows,
                         pl.pallas_call)


def _latent_lanes_fwd(kv, k_r, cos, sin, nope, block_rows):
    bsz, seq, heads, both = kv.shape
    turning = k_r.shape[-1]
    forward, _ = _calls_of(bsz, seq, heads, nope, turning, both - nope,
                           block_rows)
    k, v = forward(kv.reshape(bsz, seq, heads * both),
                   k_r.reshape(bsz, seq, turning), cos, sin)
    return (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)), (cos, sin)


def _latent_lanes_bwd(nope, block_rows, tables, g):
    cos, sin = tables
    dk, dv = g
    bsz, seq, heads, key = dk.shape
    value, turning = dv.shape[-1], key - nope
    _, transposed = _calls_of(bsz, seq, heads, nope, turning, value,
                              block_rows)
    dkv, dkr = transposed(dk.transpose(0, 2, 1, 3), dv.transpose(0, 2, 1, 3),
                          cos, sin)
    return (dkv.reshape(bsz, seq, heads, nope + value),
            dkr.reshape(bsz, seq, 1, turning), None, None)


latent_lanes.defvjp(_latent_lanes_fwd, _latent_lanes_bwd)

