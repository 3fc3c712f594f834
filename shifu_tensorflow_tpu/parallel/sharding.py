"""Sharding placement rules.

Replaces the reference's ``tf.train.replica_device_setter`` — variables
pinned to PS tasks, activations on workers (ssgd_monitor.py:203-206) — with
declarative JAX shardings:

- batches shard along ``data`` (leading batch dim);
- parameters place by ordered ``(regex, PartitionSpec)`` **partition
  rules** matched against the flattened pytree path
  (``match_partition_rules``, fmengine-style): first match wins, scalars
  never partition, and leaves no rule matches fall back to their
  ``nn.with_partitioning`` annotation (embedding tables carry a
  ``('model', None)`` spec, models/embeddings.py) or replicate;
- the optimizer state inherits its parameter's sharding automatically —
  optax states mirror the param pytree, so the same rules match the same
  ``.../table`` suffixes inside ``mu``/``nu``.

Everything is expressed as NamedSharding so the same step function runs
unsharded on one chip and sharded on a pod without code changes.

flax is imported once at module load with a stdlib-only fallback: the obs
CLIs walk checkpoints on machines without flax, and a per-leaf import
inside the placement loop (the old ``_spec_for_leaf``) both cost time and
raised on such hosts.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from shifu_tensorflow_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

try:  # flax optional: stdlib-only obs CLIs never trip this
    import flax.linen as nn
except Exception:  # pragma: no cover - exercised on flax-less hosts
    nn = None

# Default rule set: embedding tables (models/embeddings.py `table` params)
# shard row-wise along `model`; everything else replicates.  The
# same suffix matches inside optax mu/nu mirrors.
DEFAULT_PARTITION_RULES: tuple[tuple[str, P], ...] = (
    (r"(^|/)table$", P(MODEL_AXIS, None)),
)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard dim 0 (rows) across the data axis; features replicated."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _is_partitioned(leaf) -> bool:
    return nn is not None and isinstance(leaf, nn.Partitioned)


def _leaf_value(leaf):
    return leaf.value if _is_partitioned(leaf) else leaf


def _path_str(path) -> str:
    """'/'-joined flattened pytree path: DictKey('a')/DictKey('b') -> a/b."""
    parts = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "idx", None)
        if key is None:
            key = getattr(entry, "name", None)
        parts.append(str(key) if key is not None else str(entry))
    return "/".join(parts)


def clamp_spec(spec: P, value, mesh: Mesh) -> P:
    """Clamp a rule/annotation spec to what the mesh and leaf can hold.

    Axis names absent from the mesh become None (replicated on that dim);
    a spec longer than the leaf's rank, or a partition that doesn't divide
    its dim, degrades to full replication rather than erroring — small
    tables stay replicated, big ones shard.
    """
    shape = np.shape(value)
    names = tuple(spec)
    if len(names) > len(shape):
        return P()
    out = []
    for dim, name in enumerate(names):
        if name is None:
            out.append(None)
            continue
        axis_names = name if isinstance(name, tuple) else (name,)
        size = 1
        ok = True
        for n in axis_names:
            if n not in mesh.shape:
                ok = False
                break
            size *= mesh.shape[n]
        if not ok or size <= 1 or shape[dim] % size != 0:
            out.append(None)
        else:
            out.append(name)
    return P(*out)


def match_partition_rules(rules, params, mesh: Mesh):
    """Pytree of NamedShardings from ordered ``(regex, PartitionSpec)``.

    Each leaf's flattened path is '/'-joined and tested with
    ``re.search`` against the rules in order; the first hit supplies the
    PartitionSpec.  Scalars (and single-element arrays) never partition.
    Unmatched leaves fall back to their ``nn.with_partitioning``
    annotation when present, else replicate.
    """
    compiled = [(re.compile(pat), spec) for pat, spec in (rules or ())]

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_partitioned
    )
    out = []
    for path, leaf in flat:
        value = _leaf_value(leaf)
        shape = np.shape(value)
        if len(shape) == 0 or int(np.prod(shape)) <= 1:
            out.append(replicate(mesh))
            continue
        name = _path_str(path)
        spec = None
        for pat, rule_spec in compiled:
            if pat.search(name):
                spec = rule_spec
                break
        if spec is None and _is_partitioned(leaf):
            spec = P(*leaf.names)
        if spec is None:
            spec = P()
        out.append(NamedSharding(mesh, clamp_spec(spec, value, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


def _spec_for_leaf(leaf, mesh: Mesh) -> NamedSharding:
    """flax Partitioned boxes carry their axis names; plain arrays
    replicate."""
    if _is_partitioned(leaf):
        spec = clamp_spec(P(*leaf.names), leaf.value, mesh)
        return NamedSharding(mesh, spec)
    return replicate(mesh)


def params_shardings(params, mesh: Mesh, rules=None):
    """Pytree of NamedShardings matching a (possibly Partitioned-annotated)
    param tree.  With ``rules``, path-matched rules take precedence and the
    annotations are the fallback (``match_partition_rules``)."""
    if rules is not None:
        return match_partition_rules(rules, params, mesh)
    return jax.tree_util.tree_map(
        lambda leaf: _spec_for_leaf(leaf, mesh), params, is_leaf=_is_partitioned
    )


def shard_params(state, mesh: Mesh, rules=None):
    """Place a TrainState on the mesh: rule-matched / annotated leaves
    sharded, everything else replicated."""
    shardings = params_shardings(state, mesh, rules=rules)

    def place(leaf, sh):
        if _is_partitioned(leaf):
            return leaf.replace(value=jax.device_put(leaf.value, sh))
        return jax.device_put(leaf, sh)

    return jax.tree_util.tree_map(
        place, state, shardings, is_leaf=_is_partitioned
    )


def model_shard_info(leaf) -> tuple[int, int] | None:
    """``(dim, num_model_shards)`` when a live jax Array is partitioned
    along the ``model`` mesh axis, else None.  Pure attribute inspection —
    never touches device data."""
    sharding = getattr(leaf, "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or mesh.shape.get(MODEL_AXIS, 1) <= 1:
        return None
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return None
    for dim, name in enumerate(spec):
        if name is None:
            continue
        names = name if isinstance(name, tuple) else (name,)
        if MODEL_AXIS in names:
            return dim, mesh.shape[MODEL_AXIS]
    return None


def model_shard_blocks(leaf, dim: int, num: int):
    """Per-model-coordinate host blocks of a model-sharded jax Array —
    the no-gather extraction both the per-shard checkpointer and the
    sharded export use.  Data-axis replicas of the same block share a
    start offset and are deduped.  Returns ``(starts, blocks)`` sorted by
    offset, or None when this process cannot see every block (a
    multi-process mesh where the caller holds a subset) — callers then
    fall back to a gathered path."""
    blocks: dict[int, np.ndarray] = {}
    for s in leaf.addressable_shards:
        st = s.index[dim].start or 0
        if st not in blocks:
            blocks[st] = np.asarray(s.data)
    starts = sorted(blocks)
    gdim = int(leaf.shape[dim])
    ends = [st + blocks[st].shape[dim] for st in starts]
    covered = (
        len(starts) == num
        and starts[0] == 0
        and ends[-1] == gdim
        and all(e == s2 for e, s2 in zip(ends[:-1], starts[1:]))
    )
    if not covered:
        return None
    return starts, [blocks[st] for st in starts]


def gather_params(tree):
    """Full host gather (legacy flat export / debugging ONLY — never on the
    train or restore hot path).  Unboxes Partitioned leaves and returns
    host numpy arrays of the complete, unsharded values."""

    def fetch(leaf):
        return np.asarray(jax.device_get(_leaf_value(leaf)))

    return jax.tree_util.tree_map(fetch, tree, is_leaf=_is_partitioned)


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    sh = batch_sharding(mesh)
    return {k: jax.device_put(v, sh) for k, v in batch.items()}
