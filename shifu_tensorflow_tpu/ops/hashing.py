"""On-device feature hashing for the hashed embedding and cross tables.

One source of truth: `models.embeddings` calls these functions and
`models.host_embedding` mirrors them in numpy (parity pinned by
tests/test_host_embedding.py), so bucket assignment is bit-identical
wherever the table lives — the same parity discipline the data layer
applies to its native/Python parsers.

The hash is multiplicative (Fibonacci) hashing over the raw float bits:
elementwise uint32 ops only, so it fuses into surrounding XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# large odd multipliers for the multiplicative hash
HASH_MULT = 2654435761
HASH_MULT2 = 40503
# per-column salt so the same value in different columns hashes apart
COLUMN_SALT = 0x9E3779B9


def mix(bits: jax.Array) -> jax.Array:
    """Finalizer of the multiplicative hash: uint32 bits -> uint32."""
    h = bits * jnp.uint32(HASH_MULT)
    h = h ^ (h >> 16)
    return h * jnp.uint32(HASH_MULT2)


def float_bits(values: jax.Array) -> jax.Array:
    """Bit-cast floats so distinct raw category codes (e.g. 3.0 vs 4.0)
    hash apart; elementwise and fusable."""
    return jax.lax.bitcast_convert_type(values.astype(jnp.float32), jnp.uint32)


def hash_to_buckets(values: jax.Array, hash_size: int) -> jax.Array:
    """Hash float feature values into [0, hash_size) on device."""
    return (mix(float_bits(values)) % jnp.uint32(hash_size)).astype(jnp.int32)


def salted_bucket_ids(x: jax.Array, hash_size: int) -> jax.Array:
    """(B, C) float categories -> (B, C) int32 bucket ids, column-salted.

    Uses ``broadcasted_iota`` (not ``arange``) for the column index so the
    identical function body is legal inside a Pallas TPU kernel, where 1-D
    iota does not lower.
    """
    cols = jax.lax.broadcasted_iota(jnp.uint32, x.shape, dimension=x.ndim - 1)
    salted = float_bits(x) ^ (cols * jnp.uint32(COLUMN_SALT))
    return (mix(salted) % jnp.uint32(hash_size)).astype(jnp.int32)


def crossed_bucket_ids(x: jax.Array, hash_size: int) -> jax.Array:
    """(B, C) float categories -> (B,) int32: one joint id per row (the
    'crossed column' hash of classic wide&deep)."""
    bits = float_bits(x)
    h = jnp.zeros(x.shape[:1], jnp.uint32)
    for c in range(x.shape[-1]):
        h = (h ^ bits[:, c]) * jnp.uint32(HASH_MULT)
        h = h ^ (h >> 13)
    return (h % jnp.uint32(hash_size)).astype(jnp.int32)
