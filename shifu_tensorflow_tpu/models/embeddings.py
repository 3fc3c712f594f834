"""Hashed embedding tables, shardable over the mesh 'model' axis.

Beyond-reference capability (BASELINE.json config #4): high-cardinality
hashed embedding columns with the table sharded over ICI.  The reference has
no model parallelism at all (SURVEY.md §2.5); this module is the one place
the new framework adds a model-parallel axis.

Design: feature values are hashed on-device (ops/hashing.py — shared with
the Pallas kernel so bucket assignment is bit-identical across
implementations), then gathered from a ``(hash_size, dim)`` table.  Two
lookup implementations:

- ``xla``   — hash + ``jnp.take``; under pjit the table's
  ``nn.partitioning`` annotation shards it over the 'model' axis and XLA
  handles the collective lookup.  The lookup's BACKWARD is its own
  (``take_rows`` below, ``ops/embedding_grad.py``), not XLA's transpose
  of the take: the lookups are sorted so that those of one table row are
  neighbours, and their gradient rows are added into lines of whole
  lanes (where XLA's scatter reads and writes each distinct line once)
  that a last pass turns into the table's layout.  On a mesh each device
  does that for its own lookups and its own rows under ``shard_map``, and
  the sum over 'data' is the dense all-reduce.  Rows that do not tile a
  128-lane line (``EmbeddingDim`` other than 8, 16, 32 or 64) keep XLA's
  transpose;
- ``pallas`` — the fused hash/one-hot-matmul TPU kernel
  (ops/pallas/embedding.py) for the replicated-table case, keeping the
  gather on the MXU.

``impl="auto"`` picks pallas only on TPU, only for a non-mesh-sharded
table, and only within a MEASURED win region (``PALLAS_MAX_HASH_SIZE``,
default 0 = never — see the constant's docstring); xla everywhere else.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from shifu_tensorflow_tpu.ops import hashing
from shifu_tensorflow_tpu.ops.embedding_grad import dense_row_grad, rows_a_line
from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from shifu_tensorflow_tpu.parallel.sharding import clamp_spec
from shifu_tensorflow_tpu.parallel.shmap import shard_map

# re-exports kept for callers that used the old locations
hash_to_buckets = hashing.hash_to_buckets


# The one-hot-matmul kernel sweeps the whole table once per lookup
# (cost ∝ hash_size), so it wins for small tables and loses for large
# ones.  The cutover must come from MEASUREMENT, not the cost model:
# scripts/bench_pallas_embedding.py sweeps table 4K→256K x batch
# {4K,16K} on the chip, asserts bit-parity first, and writes
# BENCH_PALLAS_EMBEDDING.json whose `pallas_wins_up_to_hash_size` field
# is this constant's source of truth.
#
# DEFAULT 0 = auto NEVER picks pallas.  This is now the MEASURED value:
# the round-4 sweep ran on the real chip (TPU v5 lite, 2026-07-31, with
# value-fetch-proven timing — BENCH_PALLAS_EMBEDDING.json) and XLA's
# gather wins at every point in the grid, forward and fwd+bwd (pallas
# 1.3x slower at table 4K up to 44x at 256K, growing with table size
# exactly as the one-hot-matmul cost model predicts).  ``impl="pallas"``
# stays available explicitly, and STPU_PALLAS_MAX_HASH_SIZE can
# re-enable the auto cutover if a future chip/kernel revision changes
# the verdict.
import os as _os


def _env_cutover() -> int:
    raw = _os.environ.get("STPU_PALLAS_MAX_HASH_SIZE", "0")
    try:
        return int(raw)
    except ValueError:
        import warnings

        warnings.warn(
            f"STPU_PALLAS_MAX_HASH_SIZE={raw!r} is not an integer; "
            "keeping the safe default 0 (auto never picks pallas)"
        )
        return 0


PALLAS_MAX_HASH_SIZE = _env_cutover()


def _resolve_impl(impl: str, sharded: bool, hash_size: int = 0) -> str:
    if impl != "auto":
        return impl
    if sharded:
        # a 'model'-sharded table needs XLA's partitioned gather; the pallas
        # kernel has no partitioning rule and would force an all-gather
        return "xla"
    if PALLAS_MAX_HASH_SIZE <= 0 or hash_size > PALLAS_MAX_HASH_SIZE:
        # unmeasured (or out of the measured win region): portable gather
        return "xla"
    return "pallas" if jax.default_backend() == "tpu" else "xla"


TABLE_SPEC = P(MODEL_AXIS, None)  # what ``shard_table`` annotates


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def take_rows(table: jax.Array, ids: jax.Array, mesh=None,
              shard_table: bool = True) -> jax.Array:
    """``jnp.take(table, ids, axis=0)`` for a flat ``ids (N,)``, whose
    backward is ``ops/embedding_grad.py``'s ``dense_row_grad``.  ``mesh``
    is the mesh the enclosing ``jit`` partitions over, or ``None`` on one
    device; ``shard_table`` says whether the table rests split over its
    'model' axis."""
    return jnp.take(table, ids, axis=0)


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
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        # one device, or a trace that is per device already (SAGN
        # differentiates inside its own ``shard_map``, where the ids are
        # the shard's and the table is whole)
        return dense_row_grad(ids, rows, num_rows)
    # a sort along a sharded axis would make the partitioner gather every
    # shard's ids and rows, so each device sorts its own lookups and keeps
    # its own rows.  Where they rest is the placement rules' to say
    # (parallel/sharding.py): what does not divide stays whole
    table_spec = clamp_spec(TABLE_SPEC if shard_table else P(None, None),
                            table, mesh)
    batch = clamp_spec(P(DATA_AXIS), ids, mesh)[0]
    shards = mesh.shape[MODEL_AXIS] if table_spec[0] else 1

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
    impl: str = "auto"  # auto | xla | pallas
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
        impl = _resolve_impl(self.impl, self.shard_table, self.hash_size)
        # the scopes are the phase names `obs profile --phases` reads a
        # device trace by: the backward of the gather (take_rows's: sort,
        # rows, scatter-add) carries transpose(jvp(...embed.gather))
        with jax.named_scope("embed.hash"):
            # one flat index, not a (B, C) one: the same rows in the same
            # order, but XLA:TPU takes 22 s to compile the (B, C)-indexed
            # gather at B=16,384 (super-linear in B) against 2 s for this
            ids = hashing.salted_bucket_ids(x, self.hash_size).reshape(-1)
        with jax.named_scope("embed.gather"):
            if impl == "pallas":
                from shifu_tensorflow_tpu.ops.pallas.embedding import (
                    embedding_gather,
                )

                emb = embedding_gather(ids, table)
            else:
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
