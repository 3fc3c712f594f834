"""Hashed embedding tables, shardable over the mesh 'model' axis.

Beyond-reference capability (BASELINE.json config #4): high-cardinality
hashed embedding columns with the table sharded over ICI.  The reference has
no model parallelism at all (SURVEY.md §2.5); this module is the one place
the new framework adds a model-parallel axis.

Design: feature values are hashed on-device (ops/hashing.py; the
host-resident table, models/host_embedding.py, hashes with its numpy twin,
so bucket assignment is bit-identical wherever the table lives), then
gathered from a ``(hash_size, dim)`` table with ``jnp.take``; under pjit the table's
``nn.partitioning`` annotation shards it over the 'model' axis and XLA
handles the collective lookup.  The lookup's BACKWARD is its own
(``take_rows`` below, ``ops/embedding_grad.py``), not XLA's transpose of
the take: the lookups are sorted so that those of one table row are
neighbours, and their gradient rows are added into lines of whole lanes
(where XLA's scatter reads and writes each distinct line once) that a last
pass turns into the table's layout.  On a mesh each device does that for
its own lookups and its own rows under ``shard_map``, and the sum over
'data' is the dense all-reduce.  Rows that do not tile a 128-lane line
(``EmbeddingDim`` other than 8, 16, 32 or 64) keep XLA's transpose.

The FORWARD reads through the same lines where that pays
(``ops/embedding_grad.py`` ``lines_pay``, decided on static shapes: a
float32 table of 16, 32 or 64 floats a row, and at least one lookup for
every 64 rows of the table or of the device's shard of it, which a
training batch has and a scoring request has not): the table is turned
into lines once a step, a lookup fetches its whole line, and a last pass
picks the rows off the lines.  On a mesh each device does that for its
own shard and its own lookups, leaves zeros for the rows of other shards,
and the parts' sum over 'model' is the partitioner's all-reduce, as it is
for the gather it partitions itself.  Every other lookup (a scoring
batch, an exported program's symbolic batch, bfloat16, narrower rows) is
``jnp.take``; both read the same bits.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from shifu_tensorflow_tpu.ops import hashing
from shifu_tensorflow_tpu.ops.embedding_grad import (
    dense_row_grad,
    lines_pay,
    rows_a_line,
    rows_by_lines,
)
from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from shifu_tensorflow_tpu.parallel.sharding import clamp_spec
from shifu_tensorflow_tpu.parallel.shmap import shard_map

# re-exports kept for callers that used the old locations
hash_to_buckets = hashing.hash_to_buckets

TABLE_SPEC = P(MODEL_AXIS, None)  # what ``shard_table`` annotates


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def take_rows(table: jax.Array, ids: jax.Array, mesh=None,
              shard_table: bool = True) -> jax.Array:
    """``jnp.take(table, ids, axis=0)`` for a flat ``ids (N,)``, bit for
    bit, read through lines of whole lanes where the lookups are many
    against the table (``ops/embedding_grad.py`` ``lines_pay``), and
    whose backward is ``dense_row_grad``.  ``mesh`` is the mesh the
    enclosing ``jit`` partitions over, or ``None`` on one device;
    ``shard_table`` says whether the table rests split over its 'model'
    axis."""
    num_rows, dim = table.shape
    if _per_device(mesh):
        if lines_pay(num_rows, dim, table.dtype, ids.size):
            return rows_by_lines(table, ids)
        return jnp.take(table, ids, axis=0)
    table_spec, batch, shards = _placement(mesh, shard_table, table, ids)
    if not lines_pay(num_rows // shards, dim, table.dtype,
                     ids.size // (mesh.shape[DATA_AXIS] if batch else 1)):
        return jnp.take(table, ids, axis=0)  # the partitioner's gather

    def local_rows(table, ids):
        if shards == 1:
            return rows_by_lines(table, ids)
        first = lax.axis_index(MODEL_AXIS) * (num_rows // shards)
        return rows_by_lines(table, ids, first)[None]

    # a device reads the lookups of its data shard that fall in its rows
    # and leaves zeros for the others; the sum of the parts over 'model'
    # is the partitioner's, as the gradient's over 'data' is below
    rows = shard_map(
        local_rows, mesh, in_specs=(table_spec, P(batch)),
        out_specs=P(MODEL_AXIS, batch, None) if shards > 1
        else P(batch, None), comm_label=None)(table, ids)
    return rows.sum(0) if shards > 1 else rows


def _per_device(mesh) -> bool:
    """One device, or a trace that is per device already (SAGN
    differentiates inside its own ``shard_map``, where the ids are the
    shard's and the table is whole)."""
    return (mesh is None or mesh.size == 1
            or bool(jax.sharding.get_abstract_mesh().manual_axes))


def _placement(mesh, shard_table, table, ids):
    """(the table's spec, the axis the lookups are split over or None,
    the number of row shards).  Where things rest is the placement
    rules' to say (parallel/sharding.py): what does not divide stays
    whole."""
    table_spec = clamp_spec(TABLE_SPEC if shard_table else P(None, None),
                            table, mesh)
    batch = clamp_spec(P(DATA_AXIS), ids, mesh)[0]
    return table_spec, batch, mesh.shape[MODEL_AXIS] if table_spec[0] else 1


def _take_rows_fwd(table, ids, mesh, shard_table):
    # the table rides along for its shape alone: nothing reads its values
    return take_rows(table, ids, mesh, shard_table), (table, ids)


def _take_rows_bwd(mesh, shard_table, residuals, rows):
    table, ids = residuals
    return _table_grad(mesh, shard_table, table, ids, rows), None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _table_grad(mesh, shard_table, table, ids, rows):
    num_rows, dim = table.shape
    if not rows_a_line(dim):
        return jnp.zeros_like(table).at[ids].add(
            rows)  # what XLA makes of take's transpose, partitioned by it
    if _per_device(mesh):
        return dense_row_grad(ids, rows, num_rows)
    # a sort along a sharded axis would make the partitioner gather every
    # shard's ids and rows, so each device sorts its own lookups and keeps
    # its own rows
    table_spec, batch, shards = _placement(mesh, shard_table, table, ids)

    def local_table_grad(ids, rows):
        first = lax.axis_index(MODEL_AXIS) * (num_rows // shards) \
            if shards > 1 else 0
        return dense_row_grad(ids, rows, num_rows // shards, first)[None]

    # a part a data shard; their sum is left to the partitioner, whose
    # ``all-reduce`` the traces' readers know by that name (a ``psum``
    # in here is an all-reduce named ``psum``)
    return shard_map(
        local_table_grad, mesh, in_specs=(P(batch), P(batch, None)),
        out_specs=P(batch, *table_spec), comm_label=None)(ids, rows).sum(0)


class HashedEmbedding(nn.Module):
    """Per-column hashed lookup: (B, C) float categories -> (B, C*dim)."""

    hash_size: int
    features: int  # embedding dim per column
    dtype: jnp.dtype = jnp.float32
    shard_table: bool = True  # annotate the table for the 'model' axis
    mesh: "jax.sharding.Mesh | None" = None  # what the step's jit spans

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        init = nn.initializers.normal(stddev=0.05)
        table = self.param(
            "table",
            nn.with_partitioning(init, tuple(TABLE_SPEC)) if self.shard_table
            else init,
            (self.hash_size, self.features),
            self.dtype,
        )
        # the scopes are the phase names `obs profile --phases` reads a
        # device trace by: the backward of the gather (take_rows's: sort,
        # rows, scatter-add) carries transpose(jvp(...embed.gather))
        with jax.named_scope("embed.hash"):
            # one flat index, not a (B, C) one: the same rows in the same
            # order, but XLA:TPU takes 22 s to compile the (B, C)-indexed
            # gather at B=16,384 (super-linear in B) against 2 s for this
            ids = hashing.salted_bucket_ids(x, self.hash_size).reshape(-1)
        with jax.named_scope("embed.gather"):
            emb = take_rows(table, ids, self.mesh,
                            self.shard_table)  # (B*C, dim)
            return emb.reshape(x.shape[0], -1)


class HashedCross(nn.Module):
    """Joint hash of all columns into one id per row -> (B, features).
    The 'crossed column' of classic wide&deep."""

    hash_size: int
    features: int = 1
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        table = self.param(
            "table",
            nn.with_partitioning(
                nn.initializers.zeros_init(), ("model", None)
            ),
            (self.hash_size, self.features),
            self.dtype,
        )
        with jax.named_scope("wide.cross"):
            ids = hashing.crossed_bucket_ids(x, self.hash_size)
            return jnp.take(table, ids, axis=0)
