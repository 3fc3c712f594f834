"""The dense gradient of an embedding lookup, built in lines of whole lanes.

``jnp.take(table, ids, axis=0)`` transposes to a scatter-add of one
gradient row a lookup into a zeroed table.  On the TPU a ``(rows, 32)``
table and its gradient rest with the ROWS on the lanes
(``{0,1:T(8,128)}``: rows on the sublanes would pad 32 floats to 128
lanes), so a row is 32 strided words, and XLA's scatter spends 126-147 ns
a lookup there, whether the lookup repeats a row or not and whatever it is
told about its indices (PERF.md section 6, PR 27: 53 of the benchmark
step's 77 ms, for 425,984 lookups of which 78% repeat a row).  Into a
buffer of LINES, 128 lanes that hold ``128 // dim`` rows each, the same
scatter costs 27 ns a distinct line when its indices ascend, and next to
nothing for a lookup that repeats the line before it.  So
``dense_row_grad``:

1. gives a lookup a key that says where its row rests in the lines, and
   sorts the keys with an iota (``lax.sort_key_val``): lookups of one row
   become neighbours and the lines ascend;
2. takes the gradient rows in that order, in float32, each at its place
   on a line of zeros;
3. adds them to the zeroed lines in one ``scatter_add`` that is told its
   indices are sorted (a lookup whose row lies outside ``[row_offset,
   row_offset + num_rows)``, another shard's, sorts to the end and goes
   out of bounds there): every distinct line is read and written once,
   the sum over its lookups is made on the way;
4. turns the lines into the table's layout, 128 x 128 floats at a time.

Only the order in which equal ids' rows are added differs from XLA's
transpose.  ``models/embeddings.py`` ``take_rows`` is the lookup that
carries this backward, and runs it per device on a mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128


def rows_a_line(dim: int) -> int:
    """How many table rows share a 128-lane line, or 0 where rows of
    ``dim`` floats do not tile one in whole sublane groups: such a table
    keeps XLA's transpose (sorted lookups scattered into the table's own
    shape cost 59.7 ms and their sort and gather, against its 54.9)."""
    return LANES // dim if dim % 8 == 0 and LANES % dim == 0 else 0


def _rows_from_lines(lines: jax.Array, dim: int) -> jax.Array:
    """``(rows, dim)`` from ``(rows // pack, 128)`` lines.  Row
    ``(q * pack + j) * 128 + l`` rests in line ``q * 128 + l``, lanes
    ``j * dim ...``: a block of 128 lines, transposed, is ``pack`` slabs
    of ``(dim, 128)`` that lie one after the other in the table's
    rows-on-lanes layout, so the result's ``.T`` costs nothing there.
    XLA:TPU takes three passes and 12 s of compiling over the plain
    expression (7-9 ms at 4,194,304 x 32 against the kernel's 3.8 ms,
    PERF.md), so a program lowered for the TPU gets the kernel."""
    return lax.platform_dependent(
        lines, tpu=functools.partial(_turned_by_the_kernel, dim=dim),
        default=functools.partial(_turned_by_xla, dim=dim))


def _turned_by_xla(lines: jax.Array, dim: int) -> jax.Array:
    pack = LANES // dim
    return lines.reshape(lines.shape[0] // LANES, LANES, pack, dim).transpose(
        3, 0, 2, 1).reshape(dim, -1).T


def _turned_by_the_kernel(lines: jax.Array, dim: int) -> jax.Array:
    from jax.experimental import pallas as pl

    pack = LANES // dim
    blocks = lines.shape[0] // LANES
    step = math.gcd(blocks, 8)  # blocks a grid step: 512 KB in, 512 KB out

    def turn(lines_ref, out_ref):
        for q in range(step):
            block = lines_ref[q * LANES:(q + 1) * LANES, :].T
            for j in range(pack):
                at = (q * pack + j) * LANES
                out_ref[:, at:at + LANES] = block[j * dim:(j + 1) * dim, :]

    return pl.pallas_call(
        turn, grid=(blocks // step,),
        in_specs=[pl.BlockSpec((step * LANES, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((dim, step * pack * LANES),
                               lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((dim, lines.shape[0] * pack),
                                       lines.dtype))(lines).T


def dense_row_grad(ids: jax.Array, rows: jax.Array, num_rows: int,
                   row_offset=0) -> jax.Array:
    """``zeros((num_rows, D)).at[ids - row_offset].add(rows)`` for ids
    ``(N,)`` and rows ``(N, D)`` with ``rows_a_line(D)``, ids outside the
    range dropped: sums in float32, cast once to ``rows.dtype``."""
    n, dim = rows.shape
    pack = rows_a_line(dim)
    num_lines = -(-num_rows // (LANES * pack)) * LANES  # whole blocks
    local = ids - row_offset
    # line * pack + place on the line
    key = ((local // (LANES * pack) * LANES + local % LANES) * pack
           + local // LANES % pack)
    mine = (local >= 0) & (local < num_rows)
    key, order = lax.sort_key_val(
        jnp.where(mine, key, num_lines * pack), lax.iota(jnp.int32, n))
    ordered = rows.at[order].get(
        unique_indices=True, mode="promise_in_bounds").astype(jnp.float32)
    place = (key % pack)[:, None] == jnp.arange(pack)[None, :]
    lines = lax.scatter_add(
        jnp.zeros((num_lines, LANES), jnp.float32), (key // pack)[:, None],
        jnp.where(place[:, :, None], ordered[:, None, :], 0).reshape(
            n, LANES),
        lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0,),
            scatter_dims_to_operand_dims=(0,)),
        indices_are_sorted=True, mode=lax.GatherScatterMode.FILL_OR_DROP)
    return _rows_from_lines(lines, dim)[:num_rows].astype(rows.dtype)
