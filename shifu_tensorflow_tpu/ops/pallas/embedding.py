"""Hashed-embedding lookup with a Pallas TPU gather-as-matmul kernel.

SURVEY.md §7.1 item 8 names the embedding gather as the likely XLA gap to
close with Pallas.  The XLA path (models/embeddings.py) lowers
``jnp.take(table, ids)`` to a dynamic gather that runs on the VPU/scalar
units and leaves the MXU idle.  Here the gather is expressed as a one-hot ×
table matmul accumulated over table tiles — the MXU-native formulation —
with the table streamed through VMEM tile by tile:

    out[r, :] = Σ_tiles  onehot(ids[r] - tile_base) @ table_tile

The bucket ids are computed by the caller with ``ops.hashing`` (elementwise
uint32 ops XLA fuses into the surrounding program; Mosaic cannot relayout
the (B, C) → (B·C, 1) id reshape in-kernel, so hashing stays outside).  The
backward pass is the transpose — one-hotᵀ × g, a scatter-add as the same
MXU matmul — via custom_vjp.

Bucket assignment uses ``hashing.salted_bucket_ids`` for both this and the
XLA path, so the two implementations are bit-identical; tests assert exact
equality of outputs and gradients.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from shifu_tensorflow_tpu.ops import hashing


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _gather_kernel(ids_ref, table_ref, out_ref, *, h_tile: int):
    j = pl.program_id(1)  # table-tile position (innermost: accumulation)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    rb = ids_ref.shape[0]
    base = j * h_tile
    iota = jax.lax.broadcasted_iota(jnp.int32, (rb, h_tile), 1)
    onehot = (iota + base == ids_ref[:]).astype(table_ref.dtype)
    # HIGHEST: f32 operands must not be truncated to one bf16 MXU pass —
    # gathered rows (and the bwd scatter sums) must match the XLA path
    out_ref[:] += jnp.dot(
        onehot, table_ref[:], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(out_ref.dtype)


def _scatter_kernel(ids_ref, g_ref, dtable_ref, *, h_tile: int):
    i = pl.program_id(1)  # row-block position (innermost: accumulation)

    @pl.when(i == 0)
    def _():
        dtable_ref[:] = jnp.zeros_like(dtable_ref)

    rb = ids_ref.shape[0]
    base = pl.program_id(0) * h_tile
    iota = jax.lax.broadcasted_iota(jnp.int32, (rb, h_tile), 1)
    onehot = (iota + base == ids_ref[:]).astype(dtable_ref.dtype)
    # onehotᵀ @ g : contract the row axis of both — the scatter-add
    dtable_ref[:] += jax.lax.dot_general(
        onehot, g_ref[:], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(dtable_ref.dtype)


def _block_shapes(n_rows: int, hash_size: int, block_rows: int, h_tile: int):
    rb = min(block_rows, _round_up(max(n_rows, 1), 8))
    ht = min(h_tile, _round_up(hash_size, 128))
    return rb, ht


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def embedding_gather(
    ids: jax.Array,
    table: jax.Array,
    block_rows: int = 1024,
    h_tile: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(N,) int32 bucket ids, (H, D) table -> (N, D) rows, on the MXU.

    ``interpret=True`` runs the kernel in the Pallas interpreter — for
    tests on the CPU mesh only; the program never picks it.
    """
    return _gather_impl(ids, table, block_rows, h_tile, interpret)


def _gather_impl(ids, table, block_rows, h_tile, interpret):
    from shifu_tensorflow_tpu.obs import compile as obs_compile

    (n,) = ids.shape
    hash_size, dim = table.shape
    rb, ht = _block_shapes(n, hash_size, block_rows, h_tile)
    n_pad = _round_up(n, rb)
    h_pad = _round_up(hash_size, ht)
    # pad ids with -1: matches no table row, so padded rows read zeros
    idp = jnp.pad(ids.reshape(n, 1), ((0, n_pad - n), (0, 0)),
                  constant_values=-1)
    tp = jnp.pad(table, ((0, h_pad - hash_size), (0, 0)))

    # compile-attribution region (obs/compile.py): an eager call's
    # kernel compile journals under the pallas name; traced into a
    # jitted step, the compile lands on that step's observed call
    with obs_compile.attribute("pallas.embedding_gather"):
        out = pl.pallas_call(
            partial(_gather_kernel, h_tile=ht),
            grid=(n_pad // rb, h_pad // ht),
            in_specs=[
                pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((ht, dim), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((rb, dim), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_pad, dim), table.dtype),
            interpret=interpret,
        )(idp, tp)
    return out[:n]


def _gather_fwd(ids, table, block_rows, h_tile, interpret):
    return _gather_impl(ids, table, block_rows, h_tile, interpret), (ids, table)


def _gather_bwd(block_rows, h_tile, interpret, res, g):
    ids, table = res
    (n,) = ids.shape
    (hash_size, dim), tdtype = table.shape, table.dtype
    rb, ht = _block_shapes(n, hash_size, block_rows, h_tile)
    n_pad = _round_up(n, rb)
    h_pad = _round_up(hash_size, ht)
    idp = jnp.pad(ids.reshape(n, 1), ((0, n_pad - n), (0, 0)),
                  constant_values=-1)
    # zero-padded gradient rows contribute nothing to the scatter-add
    gp = jnp.pad(g.astype(tdtype), ((0, n_pad - n), (0, 0)))

    dtable = pl.pallas_call(
        partial(_scatter_kernel, h_tile=ht),
        grid=(h_pad // ht, n_pad // rb),
        in_specs=[
            pl.BlockSpec((rb, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((rb, dim), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ht, dim), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((h_pad, dim), tdtype),
        interpret=interpret,
    )(idp, gp)
    # integer ids carry a float0 tangent
    return (np.zeros(ids.shape, jax.dtypes.float0), dtable[:hash_size])


embedding_gather.defvjp(_gather_fwd, _gather_bwd)


def hashed_embedding_lookup(
    x: jax.Array,
    table: jax.Array,
    block_rows: int = 1024,
    h_tile: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(B, C) float categories, (H, D) table -> (B, C*D) embeddings.

    Hash (XLA-fused elementwise) + Pallas MXU gather; drop-in for the XLA
    path in models/embeddings.HashedEmbedding.
    """
    n, c = x.shape
    dim = table.shape[1]
    ids = hashing.salted_bucket_ids(x, table.shape[0]).reshape(n * c)
    rows = embedding_gather(ids, table, block_rows, h_tile, interpret)
    return rows.reshape(n, c * dim)
