"""Serving-artifact export.

Parity surface: at the end of training the reference's chief rebuilds a
clean inference graph, restores the last checkpoint, and writes a TF
SavedModel with signature ``shifu_input_0`` → ``shifu_output_0``, tag
``serve``, plus a ``GenericModelConfig.json`` whose exact contents Java-side
batch eval consumes (reference: ssgd_monitor.py:457-502,
TensorflowModel.java:112-172).

This module writes BOTH:

1. the same TF SavedModel contract via jax2tf (when TensorFlow is
   importable) — drop-in for the reference's Java/JNI scorer;
2. a framework-native bundle — ``shifu_tpu_model.json`` (architecture =
   the ModelConfig train params + feature schema) + ``shifu_tpu_weights.npz``
   (flat param arrays) — loadable with zero TF dependency by the Python
   scorer (export/eval_model.py) and the C++ scorer (cpp/scorer.cc).

``GenericModelConfig.json`` content matches the reference byte-for-byte in
its required fields (export_generic_config, ssgd_monitor.py:476-490).
"""

from __future__ import annotations

import json
import os
from typing import Any, Mapping

import jax
import numpy as np

from shifu_tensorflow_tpu.config.model_config import ModelConfig
from shifu_tensorflow_tpu.utils import fs

# digest + atomic-publish primitives shared with the serving verifier
# (serve/model_store.py) — writer and checker must never drift
from shifu_tensorflow_tpu.utils.integrity import (
    commit_bytes as _commit_bytes,
    digest_entry as _digest_entry,
)

INPUT_NAME = "shifu_input_0"
OUTPUT_NAME = "shifu_output_0"
SERVE_TAG = "serve"
GENERIC_CONFIG = "GenericModelConfig.json"
NATIVE_ARCH = "shifu_tpu_model.json"
NATIVE_WEIGHTS = "shifu_tpu_weights.npz"
#: per-shard weight files of a mesh-aware export (model-sharded trainer):
#: ``shifu_tpu_weights.shard<k>of<M>.npz``, one per model-mesh coordinate,
#: each digested into the manifest like any artifact.  The manifest's
#: ``weights_sharding`` record (num_shards + per-leaf concat dim/offsets)
#: is what reassembles them; the flat NATIVE_WEIGHTS file is absent from
#: such bundles.  The bundle's identity ``sha256`` stays the digest of
#: the LOGICAL flat npz (assembled in memory at export — export is off
#: the training hot path), so identity is invariant to how the trainer
#: happened to be sharded and the AOT generation guard keeps working
#: across a reshard.
NATIVE_WEIGHTS_SHARD_PREFIX = "shifu_tpu_weights.shard"


def native_weights_shard_name(k: int, num: int) -> str:
    return f"{NATIVE_WEIGHTS_SHARD_PREFIX}{k}of{num}.npz"


#: sidecar manifest over the native bundle (size + CRC32 + SHA-256 per
#: file, the PR-2 verified-checkpoint scheme applied to exports): the
#: serving hot-reload path admits a new artifact only after the manifest
#: verifies, so a partially-written or bit-rotted export is never served.
#: Written LAST (after every file it covers commits), so a manifest's
#: presence implies a complete bundle.
NATIVE_MANIFEST = "shifu_tpu_export.manifest.json"
#: training-side per-feature distribution sketch (obs/datastats.py
#: snapshot: count/mean/std/min/max/missing/inf rates + P² quantiles
#: per feature) shipped WITH the bundle — the serve-side skew
#: detector's baseline.  Covered by the manifest like every artifact: a
#: bit-flipped stats file refuses admission, because a model silently
#: drift-checked against corrupt statistics is worse than one not
#: drift-checked at all.  Optional: bundles exported without the obs
#: data leg simply omit it (and serving skips drift detection).
FEATURE_STATS = "feature_stats.json"


def generic_model_config_json() -> str:
    """The exact JSON the reference writes (ssgd_monitor.py:476-490)."""
    return (
        "{\n"
        '    "inputnames": [\n'
        f'        "{INPUT_NAME}"\n'
        "      ],\n"
        '    "properties": {\n'
        '         "algorithm": "tensorflow",\n'
        '         "tags": ["serve"],\n'
        f'         "outputnames": "{OUTPUT_NAME}",\n'
        '         "normtype": "ZSCALE"\n'
        "      }\n"
        "}"
    )


def _flatten_params(params) -> dict[str, np.ndarray]:
    """'/a/b/kernel' -> array; unwraps flax Partitioned boxes."""
    import flax.linen as nn

    flat = {}

    def walk(prefix: str, tree):
        if isinstance(tree, Mapping):
            for k, v in tree.items():
                walk(f"{prefix}/{k}", v)
        else:
            if isinstance(tree, nn.Partitioned):
                tree = tree.value
            flat[prefix] = np.asarray(jax.device_get(tree))

    walk("", params)
    return flat


def _unflatten_params(flat: Mapping[str, np.ndarray]):
    tree: dict[str, Any] = {}
    for path, arr in flat.items():
        parts = [p for p in path.split("/") if p]
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = arr
    return tree


def _split_sharded_params(params):
    """Flatten params into ``(flat_full, shard_flats, sharding_meta,
    mesh_shape)``.

    ``flat_full`` is the complete logical tree ('/a/b/kernel' -> full
    array) — the bundle identity and the AOT compile input.  When any
    leaf is live model-sharded, ``shard_flats[k]`` holds the flat dict
    for model coordinate k (replicated leaves ride in shard 0 only,
    sharded leaves contribute their k-th block) and ``sharding_meta``
    maps each sharded flat name to ``{"dim", "offsets"}``; otherwise
    both are None and ``mesh_shape`` is ``"unsharded"``."""
    import flax.linen as nn

    from shifu_tensorflow_tpu.parallel.sharding import (
        model_shard_blocks,
        model_shard_info,
    )

    leaves: list[tuple[str, Any]] = []

    def walk(prefix: str, tree):
        if isinstance(tree, Mapping):
            for k, v in tree.items():
                walk(f"{prefix}/{k}", v)
        else:
            if isinstance(tree, nn.Partitioned):
                tree = tree.value
            leaves.append((prefix, tree))

    walk("", params)
    infos = {name: model_shard_info(leaf) for name, leaf in leaves}
    num = max((i[1] for i in infos.values() if i is not None), default=1)
    mesh_shape = "unsharded"
    if num > 1:
        for name, leaf in leaves:
            if infos[name] is not None:
                mesh_shape = ",".join(
                    f"{n}:{s}" for n, s in leaf.sharding.mesh.shape.items()
                )
                break
    flat_full: dict[str, np.ndarray] = {}
    shard_flats: list[dict] = [dict() for _ in range(num)]
    sharding_meta: dict[str, dict] = {}
    for name, leaf in leaves:
        info = infos[name]
        extracted = None
        if info is not None and info[1] == num:
            extracted = model_shard_blocks(leaf, info[0], num)
        if extracted is None:
            full = np.asarray(jax.device_get(leaf))
            flat_full[name] = full
            shard_flats[0][name] = full
            continue
        starts, blocks = extracted
        dim = info[0]
        for k, block in enumerate(blocks):
            shard_flats[k][name] = block
        flat_full[name] = (
            np.concatenate(blocks, axis=dim) if len(blocks) > 1 else blocks[0]
        )
        sharding_meta[name] = {
            "dim": dim,
            "offsets": [int(v) for v in starts] + [int(leaf.shape[dim])],
        }
    if not sharding_meta:
        return flat_full, None, None, "unsharded"
    return flat_full, shard_flats, sharding_meta, mesh_shape


def export_native_bundle(
    export_dir: str,
    params,
    model_config: ModelConfig,
    num_features: int,
    feature_columns=None,
    zscale_means=None,
    zscale_stds=None,
    feature_stats=None,
    aot_buckets=None,
    lineage=None,
) -> None:
    """Write the TF-free artifact: architecture JSON + weights npz, plus
    the sidecar manifest (size+CRC32+SHA-256 per file) that the serving
    reload path verifies before admitting the bundle.  Every file commits
    via tmp+rename; the manifest commits last.

    ``lineage`` (optional) is the generation-lineage stamp: a mapping
    with ``parent_sha256`` (the weights digest of the bundle this one
    was retrained FROM — the rollback target, identifiable from
    artifacts alone) and ``generation`` (monotonic int).  Stamped into
    the manifest as a ``lineage`` object; legacy bundles simply lack
    the key and every reader treats absent lineage as generation 0
    with no parent.

    ``feature_stats`` is the training data's per-feature sketch snapshot
    (obs/datastats.DataSketch.snapshot) — written as
    ``feature_stats.json`` and digested into the manifest, so the serve
    admission that verifies the weights verifies the drift baseline with
    them.

    ``aot_buckets`` (a bucket-ladder tuple — export/aot.py) additionally
    compiles the scorer for each bucket on THIS environment and ships
    the serialized executables under ``aot/``, digested into the
    manifest like every artifact: serve admission then deserializes
    instead of compiling, falling back per bucket on any mismatch."""
    from shifu_tensorflow_tpu.config.model_config import require_servable

    require_servable(model_config.params.model_type, "export")
    fs.mkdirs(export_dir)
    arch = {
        "format_version": 1,
        "input_name": INPUT_NAME,
        "output_name": OUTPUT_NAME,
        "num_features": int(num_features),
        "feature_columns": list(feature_columns or range(num_features)),
        "model_config": {
            "train": {
                "numTrainEpochs": model_config.num_train_epochs,
                "validSetRate": model_config.valid_set_rate,
                "params": {
                    "NumHiddenLayers": model_config.params.num_hidden_layers,
                    "NumHiddenNodes": list(model_config.params.num_hidden_nodes),
                    "ActivationFunc": list(model_config.params.activation_funcs),
                    "LearningRate": model_config.params.learning_rate,
                    "Optimizer": model_config.params.optimizer,
                    "ModelType": model_config.params.model_type,
                    "WideColumnNums": list(model_config.params.wide_column_nums),
                    "CrossHashSize": model_config.params.cross_hash_size,
                    "NumTasks": model_config.params.num_tasks,
                    "EmbeddingColumnNums": list(model_config.params.embedding_columns),
                    "EmbeddingHashSize": model_config.params.embedding_hash_size,
                    "EmbeddingDim": model_config.params.embedding_dim,
                    "SeqLen": model_config.params.seq_len,
                    "SeqDModel": model_config.params.seq_d_model,
                    "SeqHeads": model_config.params.seq_heads,
                    "SeqBlocks": model_config.params.seq_blocks,
                    # serving is single-device: full attention always,
                    # and no remat (a training-only memory lever —
                    # jax2tf should not trace through jax.checkpoint)
                    "SeqAttention": "full",
                    "SeqRemat": False,
                },
            }
        },
        "normalization": {
            "normtype": "ZSCALE",
            "means": list(map(float, zscale_means)) if zscale_means is not None else None,
            "stds": list(map(float, zscale_stds)) if zscale_stds is not None else None,
        },
    }
    import io

    from shifu_tensorflow_tpu.utils import faults

    arch_bytes = json.dumps(arch, indent=2).encode("utf-8")
    flat, shard_flats, weights_sharding, mesh_shape = (
        _split_sharded_params(params))
    # serialize the npz to memory first so the manifest digests cover
    # exactly the bytes handed to the filesystem (same rationale as
    # NpzCheckpointer._write): any later divergence between manifest and
    # file IS corruption, by construction.  For a sharded export this
    # LOGICAL flat npz is never written — it exists to give the bundle a
    # sharding-invariant identity digest (and the AOT compile its input)
    buf = io.BytesIO()
    np.savez(buf, **flat)
    weights_bytes = buf.getvalue()
    generic_bytes = generic_model_config_json().encode("utf-8")
    weights_entry = _digest_entry(weights_bytes)  # hash the payload once
    files = {
        NATIVE_ARCH: _digest_entry(arch_bytes),
        GENERIC_CONFIG: _digest_entry(generic_bytes),
    }
    shard_payloads: dict[str, bytes] = {}
    if shard_flats is None:
        files[NATIVE_WEIGHTS] = weights_entry
    else:
        # mesh-aware export: one digested npz per model-mesh coordinate;
        # the serve verifier iterates manifest["files"] generically, so
        # shard files verify exactly like the flat file did
        num = len(shard_flats)
        for k, shard in enumerate(shard_flats):
            sbuf = io.BytesIO()
            np.savez(sbuf, **shard)
            payload = sbuf.getvalue()
            name = native_weights_shard_name(k, num)
            shard_payloads[name] = payload
            files[name] = _digest_entry(payload)
    aot_files: dict[str, bytes] = {}
    if aot_buckets:
        # compile + serialize the ladder FROM the bundle's own
        # representation (arch dict + flat arrays — the exact tree the
        # serve side rebuilds), then digest the files into the manifest
        # so the admission that verifies the weights verifies the
        # executables with them
        from shifu_tensorflow_tpu.export import aot as aot_mod

        aot_files = aot_mod.build_aot_files(
            arch, flat, aot_buckets,
            model_name=(os.path.basename(export_dir.rstrip("/"))
                        or None),
            weights_sha256=weights_entry["sha256"],
            mesh_shape=mesh_shape)
        for name, payload in aot_files.items():
            files[name] = _digest_entry(payload)
    stats_bytes = None
    if feature_stats is not None:
        stats_bytes = json.dumps({
            "format_version": 1,
            "feature_columns": list(feature_columns or
                                    range(num_features)),
            "stats": feature_stats,
        }, indent=2).encode("utf-8")
        files[FEATURE_STATS] = _digest_entry(stats_bytes)
    manifest_doc: dict[str, Any] = {
        "format_version": 1,
        "sha256": weights_entry["sha256"],  # bundle identity
        # the mesh the exporter's params lived on ("unsharded" for any
        # model axis of 1): the AOT loader compares this against the
        # fingerprint its executables were compiled under
        "mesh_shape": mesh_shape,
        "files": files,
        "written_by": str(os.getpid()),
    }
    if weights_sharding is not None:
        manifest_doc["weights_sharding"] = {
            "num_shards": len(shard_flats),
            "leaves": weights_sharding,
        }
    if lineage:
        # generation lineage: who this bundle was retrained from.  Kept
        # to the two documented keys (plus anything the caller stamps)
        # so the manifest stays a flat, diffable record.
        stamp = dict(lineage)
        if stamp.get("parent_sha256") is not None:
            stamp["parent_sha256"] = str(stamp["parent_sha256"])
        if stamp.get("generation") is not None:
            stamp["generation"] = int(stamp["generation"])
        manifest_doc["lineage"] = stamp
    manifest = json.dumps(manifest_doc, indent=2)
    # at-rest corruption seam (chaos drills): applied AFTER the digests,
    # so the manifest records what SHOULD land on disk — the serving
    # reload verification must catch the divergence
    weights_bytes = faults.mutate("export.at-rest", weights_bytes)
    # torn-write seam on every publish: a firing export.commit term
    # leaves a truncated tmp beside the previous intact generation —
    # the admission verifier must keep serving the old one
    _commit_bytes(os.path.join(export_dir, NATIVE_ARCH), arch_bytes,
                  site="export.commit")
    if shard_flats is None:
        _commit_bytes(os.path.join(export_dir, NATIVE_WEIGHTS), weights_bytes,
                      site="export.commit")
    else:
        for name, payload in shard_payloads.items():
            payload = faults.mutate("export.at-rest", payload)
            _commit_bytes(os.path.join(export_dir, name), payload,
                          site="export.commit")
    # a re-export under a different mesh must not leave the OTHER
    # layout's weight files beside a manifest that no longer covers
    # them — a legacy manifest-less reader would happily load the stale
    # flat npz of a bundle whose real weights are the shard files
    try:
        for leftover in os.listdir(export_dir):
            stale_flat = (shard_flats is not None
                          and leftover == NATIVE_WEIGHTS)
            stale_shard = (
                leftover.startswith(NATIVE_WEIGHTS_SHARD_PREFIX)
                and leftover not in shard_payloads)
            if stale_flat or stale_shard:
                os.remove(os.path.join(export_dir, leftover))
    except OSError:
        pass
    _commit_bytes(os.path.join(export_dir, GENERIC_CONFIG), generic_bytes,
                  site="export.commit")
    if aot_files:
        from shifu_tensorflow_tpu.export.aot import AOT_DIR as _AOT_DIR

        fs.mkdirs(os.path.join(export_dir, _AOT_DIR))
        for name, payload in aot_files.items():
            _commit_bytes(os.path.join(export_dir, name), payload)
        # prune bucket files a previous generation wrote that this one
        # did not (a narrower ladder): nothing vouches for them anymore
        # and the weights-generation stamp inside the meta no longer
        # names them
        try:
            for leftover in os.listdir(os.path.join(export_dir, _AOT_DIR)):
                rel = f"{_AOT_DIR}/{leftover}"
                if rel not in aot_files and not leftover.startswith("."):
                    os.remove(os.path.join(export_dir, _AOT_DIR, leftover))
        except OSError:
            pass
    else:
        # a re-export WITHOUT AOT must not leave a previous generation's
        # executables beside weights they were not compiled for: the
        # stamped weights digest would refuse them anyway (EvalModel
        # checks it), but stale artifacts beside a manifest that no
        # longer covers them are exactly the chimera the manifest chain
        # exists to prevent
        import shutil

        from shifu_tensorflow_tpu.export.aot import AOT_DIR as _AOT_DIR

        shutil.rmtree(os.path.join(export_dir, _AOT_DIR),
                      ignore_errors=True)
    if stats_bytes is not None:
        _commit_bytes(os.path.join(export_dir, FEATURE_STATS), stats_bytes)
    else:
        # a re-export WITHOUT stats must not leave a stale baseline from
        # a previous generation beside a manifest that no longer vouches
        # for it (the loader only trusts manifest-covered stats, but a
        # legacy manifest-less reader would happily read the orphan)
        try:
            os.remove(os.path.join(export_dir, FEATURE_STATS))
        except OSError:
            pass
    # manifest LAST: its presence implies every covered file committed
    _commit_bytes(
        os.path.join(export_dir, NATIVE_MANIFEST), manifest.encode("utf-8"),
        site="export.commit",
    )


def load_native_weights(model_dir: str) -> dict[str, np.ndarray]:
    """Flat ``{'/a/b/kernel': array}`` from EITHER bundle layout: the flat
    ``shifu_tpu_weights.npz``, or a mesh-aware export's per-shard files
    reassembled via the manifest's ``weights_sharding`` record.  Loading
    is off the training hot path, so the reassembly concat is the work
    itself, not a contract violation.  Integrity is the caller's
    (manifest verifier's) business, exactly as for the flat file."""
    flat_path = os.path.join(model_dir, NATIVE_WEIGHTS)
    if fs.exists(flat_path):
        with fs.open_read(flat_path) as f:
            npz = np.load(f)
            return {k: npz[k] for k in npz.files}
    try:
        with fs.open_read(os.path.join(model_dir, NATIVE_MANIFEST)) as f:
            manifest = json.loads(f.read().decode("utf-8"))
    except (OSError, ValueError) as e:
        raise FileNotFoundError(
            f"{model_dir}: no {NATIVE_WEIGHTS} and no readable manifest "
            f"({e})"
        ) from e
    ws = manifest.get("weights_sharding")
    if not isinstance(ws, dict):
        raise FileNotFoundError(
            f"{model_dir}: no {NATIVE_WEIGHTS} and the manifest records "
            f"no weights_sharding — not a native bundle"
        )
    num = int(ws.get("num_shards", 0))
    leaves_meta = ws.get("leaves") or {}
    parts: dict[str, list[np.ndarray]] = {}
    for k in range(num):
        path = os.path.join(model_dir, native_weights_shard_name(k, num))
        with fs.open_read(path) as f:
            npz = np.load(f)
            for name in npz.files:
                parts.setdefault(name, []).append(npz[name])
    flat: dict[str, np.ndarray] = {}
    for name, blocks in parts.items():
        ent = leaves_meta.get(name)
        if ent is not None and len(blocks) > 1:
            flat[name] = np.concatenate(blocks, axis=int(ent["dim"]))
        else:
            flat[name] = blocks[0]
    return flat


def is_native_bundle(path: str) -> bool:
    """A directory is a native bundle when it carries the flat weights
    file OR a manifest (mesh-aware exports have no flat npz)."""
    return os.path.isfile(os.path.join(path, NATIVE_WEIGHTS)) or \
        os.path.isfile(os.path.join(path, NATIVE_MANIFEST))


def bundle_lineage(export_dir: str) -> dict[str, Any]:
    """Read a bundle's identity + lineage from its manifest alone:
    ``{"sha256": <weights digest> | None, "parent_sha256": ... | None,
    "generation": int}``.  Legacy bundles (no ``lineage`` key, or no
    manifest at all) come back as generation 0 with no parent — absent
    lineage is not an error, it is the pre-lifecycle world."""
    out: dict[str, Any] = {"sha256": None, "parent_sha256": None,
                           "generation": 0}
    try:
        with open(os.path.join(export_dir, NATIVE_MANIFEST)) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return out
    out["sha256"] = doc.get("sha256")
    lin = doc.get("lineage") or {}
    if isinstance(lin, dict):
        out["parent_sha256"] = lin.get("parent_sha256")
        try:
            out["generation"] = int(lin.get("generation") or 0)
        except (TypeError, ValueError):
            out["generation"] = 0
    return out


def export_saved_model(
    export_dir: str,
    apply_fn,
    params,
    num_features: int,
) -> bool:
    """jax2tf → TF SavedModel with the reference's exact signature.  Returns
    False (skipping quietly) when TensorFlow isn't importable — the native
    bundle is the always-available artifact."""
    try:
        import tensorflow as tf
        from jax.experimental import jax2tf
    except Exception:
        return False

    import flax.linen as nn

    def unboxed(tree):
        return jax.tree_util.tree_map(
            lambda x: x.value if isinstance(x, nn.Partitioned) else x,
            tree,
            is_leaf=lambda x: isinstance(x, nn.Partitioned),
        )

    host_params = jax.device_get(unboxed(params))

    def infer(x):
        return apply_fn({"params": host_params}, x)

    tf_fn = tf.function(
        jax2tf.convert(
            infer,
            with_gradient=False,
            # dynamic batch dimension in the serving signature
            polymorphic_shapes=[f"(b, {num_features})"],
        ),
        autograph=False,
        input_signature=[
            tf.TensorSpec([None, num_features], tf.float32, name=INPUT_NAME)
        ],
    )

    module = tf.Module()
    module.f = tf_fn

    @tf.function(
        input_signature=[
            tf.TensorSpec([None, num_features], tf.float32, name=INPUT_NAME)
        ]
    )
    def serving(x):
        return {OUTPUT_NAME: module.f(x)}

    module.serving = serving
    tf.saved_model.save(
        module,
        export_dir,
        signatures={
            tf.saved_model.DEFAULT_SERVING_SIGNATURE_DEF_KEY: serving
        },
    )
    # atomic commit (same bytes the native bundle wrote, so the export
    # manifest stays valid): an in-place truncate-and-rewrite would hand a
    # concurrently-verifying hot-reload scorer an empty file
    _commit_bytes(
        os.path.join(export_dir, GENERIC_CONFIG),
        generic_model_config_json().encode("utf-8"),
    )
    return True


def export_model(
    export_dir: str,
    trainer,
    *,
    feature_columns=None,
    zscale_means=None,
    zscale_stds=None,
    feature_stats=None,
    aot_buckets=None,
    lineage=None,
) -> dict[str, bool]:
    """One-call export of both artifacts from a Trainer.

    The serving function is REBUILT mesh-less (single-device) instead of
    reusing ``trainer.model.apply``: a trainer on a mesh may have baked
    collective ops into its model — ring/Ulysses attention's shard_map, a
    'model'-sharded embedding's partitioned gather — and jax2tf would trace
    those device-bound collectives into the SavedModel.  The rebuilt module
    resolves to single-device implementations (full attention, local
    lookup); parameters are identical, so scores are too.
    """
    import copy

    from shifu_tensorflow_tpu.config.model_config import require_servable
    from shifu_tensorflow_tpu.models.factory import build_model

    require_servable(trainer.model_config.params.model_type, "export")
    if feature_columns is None:
        # the training graph's column positions ARE the serving contract;
        # fall back to what the trainer was built with
        feature_columns = getattr(trainer, "feature_columns", None)
    # keep-best (Trainer(keep_best=...)): serve the best validation epoch,
    # not the last — that is what "keep best" promises
    export_params = trainer.state.params
    using_best = getattr(trainer, "best_params", None) is not None
    if using_best:
        export_params = trainer.best_params
    if getattr(trainer, "_host_emb", None) is not None:
        # EmbeddingPlacement=host: serving has no host process, so the
        # artifact converts to the standard DEVICE-embedding bundle — the
        # table becomes /hashed_columns/table and the arch (which never
        # carries the placement key) rebuilds EmbeddingAugmented; hashing
        # is bit-identical host/device (models/host_embedding.bucket_ids
        # vs ops/hashing), so scores match across every backend.
        table = (trainer.best_host_table
                 if using_best and trainer.best_host_table is not None
                 else trainer._host_emb.table)
        export_params = {
            "hashed_columns": {"table": np.asarray(table)},
            "base": export_params,
        }
    if feature_stats is None:
        # bundle-shipped drift baseline: the process-wide train data
        # sketch (obs/datastats.py), fed by this trainer's ingest taps —
        # shipped only when its width matches the serving contract (a
        # second trainer of a different width in this process resets the
        # sketch; never ship a mismatched baseline)
        from shifu_tensorflow_tpu.obs import datastats as obs_datastats

        sk = obs_datastats.train_active()
        if sk is not None:
            snap = sk.snapshot()
            if snap is not None and \
                    snap["num_features"] == trainer.num_features:
                feature_stats = snap
    export_native_bundle(
        export_dir,
        export_params,
        trainer.model_config,
        trainer.num_features,
        feature_columns=feature_columns,
        zscale_means=zscale_means,
        zscale_stds=zscale_stds,
        feature_stats=feature_stats,
        aot_buckets=aot_buckets,
        lineage=lineage,
    )
    # deep-copy: ModelConfig.from_json keeps a reference to the nested
    # dicts, so mutating a shallow copy would rewrite the live trainer's
    # config (and every future WorkerConfig/re-export built from it)
    raw = copy.deepcopy(trainer.model_config.raw)
    if getattr(trainer, "_host_emb", None) is not None:
        # the serving graph embeds on-device (the converted bundle above)
        raw.setdefault("train", {}).setdefault(
            "params", {})["EmbeddingPlacement"] = "device"
    if trainer.model_config.params.seq_len > 0:
        # force single-device attention regardless of how training ran,
        # and drop remat (training-only; jax2tf shouldn't trace through
        # jax.checkpoint)
        serve_params = raw.setdefault("train", {}).setdefault("params", {})
        serve_params["SeqAttention"] = "full"
        serve_params["SeqRemat"] = False
    serve_mc = ModelConfig.from_json(raw)
    serve_model = build_model(
        serve_mc,
        tuple(feature_columns) if feature_columns else None,
        shard_embeddings=False,
    )
    from flax.core import meta as flax_meta

    serve_params = jax.tree_util.tree_map(
        lambda x: x.unbox() if isinstance(x, flax_meta.AxisMetadata) else x,
        export_params,  # same tree both artifacts: best epoch when kept
        is_leaf=lambda x: isinstance(x, flax_meta.AxisMetadata),
    )
    ok_tf = export_saved_model(
        export_dir, serve_model.apply, serve_params, trainer.num_features
    )
    return {"native": True, "saved_model": ok_tf}
