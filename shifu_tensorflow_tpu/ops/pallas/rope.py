"""The rotary embedding as one pass over whole 128-lane registers.

``models/hybrid_lm.py`` ``apply_rope`` slices a head into halves,
multiplies and concatenates.  XLA:TPU compiles that, at a head of 128, to
three passes over HBM a tensor and direction: the halves written as two
arrays whose 64-wide minor dimension is padded to 128 lanes, the
concatenation, and then the flash kernels' own ``(B, S, H, D) -> (B·H, S,
D)`` transposition: 2.14 GB moved for 0.27 GB of numbers (PERF.md section
6, PR 33).

Here a head stays whole registers.  With ``R`` the dimensions that turn
and ``D`` the head's, dimension ``m < R / 2`` pairs with ``m + R / 2``:

    out = u·C + roll(u, D − R/2)·A + roll(u, R/2)·B

over ``(S, D)`` float32 tables ``C = [cos, cos, 1…]``, ``A = [−sin, 0,
0…]``, ``B = [0, sin, 0…]`` (``roll`` as ``jnp.roll`` along the lanes; the
tables are zero where a roll wraps).  At ``R = D`` the two rolls coincide
and one table ``[−sin, sin]`` serves.  The same two products and one sum
an element as the expression, in float32; a dimension that passes through
is multiplied by exactly 1.

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
grid, so a row block's tables are fetched once for all its heads.

Interpret mode is a test's to ask for (tests/conftest.py
``pallas_interpret``); the program never picks it.
"""

from __future__ import annotations

import math
from functools import partial

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


def lanes_pay(head_dim: int, rotary_dim: int, dtype) -> bool:
    """Whether the rotation of such a head is the kernel's (where the
    program is lowered for the TPU): a head of whole 128-lane registers,
    a distance between partners that divides a register, float32.  A pure
    function of static shapes."""
    half = rotary_dim // 2
    return 0 < rotary_dim <= head_dim and head_dim % LANES == 0 \
        and rotary_dim == 2 * half and LANES % half == 0 \
        and dtype == jnp.float32


def _tables(cos, sin, head_dim: int):
    """``(C, ((shift, table), ...))`` of the forward rotation, each table
    (S, head_dim) float32."""
    seq, half = cos.shape
    rest = head_dim - 2 * half
    if not rest:
        return jnp.concatenate([cos, cos], 1), (
            (half, jnp.concatenate([-sin, sin], 1)),)
    zero = jnp.zeros((seq, half), jnp.float32)
    still = jnp.zeros((seq, rest), jnp.float32)
    return jnp.concatenate([cos, cos, still + 1.0], 1), (
        (head_dim - half, jnp.concatenate([-sin, zero, still], 1)),
        (half, jnp.concatenate([zero, sin, still], 1)))


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


def _pass(x, c, turns, *, heads: int, to_heads: bool, block_rows: int):
    """``x·C + Σ roll(x, shift)·T`` a head: ``(B, S, H·D) -> (B, H, S,
    D)`` where ``to_heads``, ``(B, H, S, D) -> (B, S, H·D)`` where not."""
    if to_heads:
        bsz, seq, width = x.shape
        dim = width // heads
    else:
        bsz, _, seq, dim = x.shape
    rows = min(seq, block_rows)
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


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def rope_lanes(u, cos, sin, block_rows: int = BLOCK_ROWS):
    """``apply_rope(u, cos, sin)`` of a float32 ``(B, S, H, D)`` for which
    :func:`lanes_pay` holds, by the kernel; the result's layout in memory
    is ``(B, H, S, D)``.  ``cos`` and ``sin`` take no gradient."""
    return _rope_lanes_fwd(u, cos, sin, block_rows)[0]


def _rope_lanes_fwd(u, cos, sin, block_rows):
    bsz, seq, heads, dim = u.shape
    c, turns = _tables(cos, sin, dim)
    out = _pass(u.reshape(bsz, seq, heads * dim), c, turns, heads=heads,
                to_heads=True, block_rows=block_rows)
    return out.transpose(0, 2, 1, 3), (cos, sin)


def _rope_lanes_bwd(block_rows, tables, g):
    cos, sin = tables
    bsz, seq, heads, dim = g.shape
    c, turns = _tables(cos, sin, dim)
    du = _pass(g.transpose(0, 2, 1, 3), c, _transposed(turns, dim),
               heads=heads, to_heads=False, block_rows=block_rows)
    return du.reshape(bsz, seq, heads, dim), None, None


rope_lanes.defvjp(_rope_lanes_fwd, _rope_lanes_bwd)
