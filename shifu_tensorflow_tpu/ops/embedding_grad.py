"""An embedding table written and read in lines of whole lanes: the dense
gradient of a lookup, and the lookup of many rows.

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

The READ pays the same layout: XLA's gather out of the rows-on-lanes
table costs 38 ns a lookup of 32 floats, a tile at a time.  Where the
lookups are many against the table (``lines_pay``), the forward,
``rows_by_lines``, goes the other way through the same lines (PERF.md
section 6, PR 29):

1. turns the table into lines, once a step, whatever the batch (1.7 ms
   at 4,194,304 x 32: what the table pays for not resting in lines);
2. fetches a lookup's whole line, 512 contiguous bytes, in 10 ns;
3. picks each row off its line and writes the rows on the lanes, which
   is the layout their readers want.

It is a copy of float32 words, equal to ``jnp.take`` bit for bit.
Nothing rests differently: parameters, moments, checkpoints and the
backward keep the ``(rows, dim)`` table.
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


# Turning this many table rows into lines costs what one lookup gains by
# fetching a line: the turn is paid for the whole table whatever the
# batch.  Break-even on the chip is 64-77 at 32 floats a row and 42-64 at
# 16 (PERF.md section 6, PR 29).
ROWS_TURNED_FOR_A_LOOKUP = 64
# ... where a row lies over two (8, 128) tiles of the rows-on-lanes table
# or more, which XLA's gather pays one by one (21 ns a lookup at 16
# floats, 38 at 32).  A row of 8 floats lies in one tile and comes in
# 4-13 ns, a line in 10.
LINES_FROM_DIM = 16


def lines_pay(rows: int, dim: int, dtype, lookups) -> bool:
    """Whether ``lookups`` lookups into a ``(rows, dim)`` table are many
    enough to read it through lines.  A count that is no concrete integer
    (an exported program's symbolic batch) is not."""
    return bool(rows_a_line(dim)) and dim >= LINES_FROM_DIM \
        and dtype == jnp.float32 and isinstance(lookups, int) \
        and lookups * ROWS_TURNED_FOR_A_LOOKUP >= rows


def _blocks(rows: int, pack: int) -> int:
    """Blocks of 128 lines that hold ``rows`` rows, the last one in part."""
    return -(-rows // (LANES * pack))


def _line_of(row: jax.Array, pack: int) -> jax.Array:
    """The line that holds table row ``row``: row ``(q * pack + j) * 128
    + l`` rests in line ``q * 128 + l``."""
    return row // (LANES * pack) * LANES + row % LANES


def _place_of(row: jax.Array, pack: int) -> jax.Array:
    """... and its place ``j`` there: lanes ``j * dim ...``."""
    return row // LANES % pack


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


def _lines_from_rows(table: jax.Array, dim: int) -> jax.Array:
    """``(blocks * 128, 128)`` lines from a ``(rows, dim)`` table, the
    inverse of ``_rows_from_lines`` under the same map; ``blocks`` is
    ``rows / (128 * pack)`` rounded up, and what the last block holds
    beyond ``rows`` is not defined.  The kernel reads ``table.T``, which
    costs nothing in the table's resting layout."""
    return lax.platform_dependent(
        table, tpu=functools.partial(_lined_by_the_kernel, dim=dim),
        default=functools.partial(_lined_by_xla, dim=dim))


def _lined_by_xla(table: jax.Array, dim: int) -> jax.Array:
    pack = LANES // dim
    blocks = _blocks(table.shape[0], pack)
    table = jnp.pad(
        table, ((0, blocks * LANES * pack - table.shape[0]), (0, 0)))
    return table.T.reshape(dim, blocks, pack, LANES).transpose(
        1, 3, 2, 0).reshape(blocks * LANES, LANES)


def _lined_by_the_kernel(table: jax.Array, dim: int) -> jax.Array:
    from jax.experimental import pallas as pl

    pack = LANES // dim
    blocks = _blocks(table.shape[0], pack)
    step = math.gcd(blocks, 8)  # blocks a grid step: 512 KB in, 512 KB out

    def turn(rows_ref, lines_ref):
        for q in range(step):
            at = q * pack * LANES
            lines_ref[q * LANES:(q + 1) * LANES, :] = jnp.concatenate(
                [rows_ref[:, at + j * LANES:at + (j + 1) * LANES]
                 for j in range(pack)], axis=0).T

    return pl.pallas_call(
        turn, grid=(blocks // step,),
        in_specs=[pl.BlockSpec((dim, step * pack * LANES),
                               lambda i: (0, i))],
        out_specs=pl.BlockSpec((step * LANES, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks * LANES, LANES),
                                       table.dtype))(table.T)


def _rows_off_lines(taken: jax.Array, place: jax.Array,
                    dim: int) -> jax.Array:
    """``(N, dim)``: of each fetched line ``taken[n]`` the ``dim`` lanes
    at ``place[n]``, zeros where the place is -1.  A copy, so a select
    and no sum over the places (``-0.0 + 0.0`` is ``+0.0``).  XLA:TPU
    writes the selected rows lane-padded, rows on the sublanes, and then
    copies them into the rows-on-lanes layout every reader wants: 4.3 ms
    for 425,984 lookups, as much as fetching the lines; the kernel turns
    a block of lines in VMEM and writes ``.T`` of the result, 0.45 ms
    (PERF.md section 6, PR 29)."""
    return lax.platform_dependent(
        taken, place, tpu=functools.partial(_picked_by_the_kernel, dim=dim),
        default=functools.partial(_picked_by_xla, dim=dim))


def _picked_by_xla(taken: jax.Array, place: jax.Array,
                   dim: int) -> jax.Array:
    rows = jnp.zeros((taken.shape[0], dim), taken.dtype)
    for j in range(LANES // dim):
        rows = jnp.where(place[:, None] == j,
                         taken[:, j * dim:(j + 1) * dim], rows)
    return rows


def _picked_by_the_kernel(taken: jax.Array, place: jax.Array,
                          dim: int) -> jax.Array:
    from jax.experimental import pallas as pl

    n = taken.shape[0]
    lookups = 2048  # a grid step: 1 MB of lines in, 256 KB of rows out

    def pick(taken_ref, place_ref, rows_ref):
        turned, at = taken_ref[...].T, place_ref[...]  # (128, n), (1, n)
        rows = jnp.zeros(rows_ref.shape, rows_ref.dtype)
        for j in range(LANES // dim):
            rows = jnp.where(at == j, turned[j * dim:(j + 1) * dim], rows)
        rows_ref[...] = rows

    return pl.pallas_call(
        pick, grid=(pl.cdiv(n, lookups),),
        in_specs=[pl.BlockSpec((lookups, LANES), lambda i: (i, 0)),
                  pl.BlockSpec((1, lookups), lambda i: (0, i))],
        out_specs=pl.BlockSpec((dim, lookups), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((dim, n), taken.dtype))(
            taken, place[None, :]).T


def rows_by_lines(table: jax.Array, ids: jax.Array,
                  row_offset=None) -> jax.Array:
    """``table[ids]`` for ids of any shape into a ``(rows, D)`` table with
    ``rows_a_line(D)``, bit for bit: the table is turned into lines once,
    each lookup fetches the 512 contiguous bytes of its line (10 ns, where
    32 words strided over four tiles cost 38), and its row is picked off
    the line.  With a ``row_offset`` the table is the shard that starts at
    that row, and a lookup of another shard's row reads zeros; without,
    every id is promised to be a row of the table."""
    num_rows, dim = table.shape
    pack = rows_a_line(dim)
    with jax.named_scope("lines.turn"):
        lines = _lines_from_rows(table, dim)
    with jax.named_scope("lines.take"):
        local = ids.reshape(-1)
        if row_offset is None:
            place = _place_of(local, pack)
        else:
            local = local - row_offset
            mine = (local >= 0) & (local < num_rows)
            # another shard's lookup fetches some line of this shard and
            # picks nothing off it; spread over the table they cost what
            # the shard's own do, all sent to one line 1 ms more
            local = local % num_rows
            place = jnp.where(mine, _place_of(local, pack), -1)
        taken = lines.at[_line_of(local, pack)].get(
            mode="promise_in_bounds")
        return _rows_off_lines(taken, place, dim).reshape(*ids.shape, dim)


def dense_row_grad(ids: jax.Array, rows: jax.Array, num_rows: int,
                   row_offset=0) -> jax.Array:
    """``zeros((num_rows, D)).at[ids - row_offset].add(rows)`` for ids
    ``(N,)`` and rows ``(N, D)`` with ``rows_a_line(D)``, ids outside the
    range dropped: sums in float32, cast once to ``rows.dtype``."""
    n, dim = rows.shape
    pack = rows_a_line(dim)
    num_lines = _blocks(num_rows, pack) * LANES
    local = ids - row_offset
    key = _line_of(local, pack) * pack + _place_of(local, pack)
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
