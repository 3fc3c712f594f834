"""AOT executable shipping: compile the bucket ladder once, at export.

The serve plane pre-warms the bucketing ladder by *compiling at
admission* (PR-5/9): every restart, hot reload, and SO_REUSEPORT worker
re-pays XLA for programs that never changed, and the cost scales as
tenants x ladder buckets.  The TensorFlow system paper makes XLA AOT
compilation a first-class export artifact for exactly this reason
(PAPERS.md); the PR-10 compile flight recorder tells us which
signatures actually compile in production — the ladder the export
already enumerates (export/bucketing.py).  So: compile those programs
ONCE at export time, serialize the executables
(``jax.experimental.serialize_executable``), and ship them in the
native bundle like any artifact.

Bundle layout (all committed tmp+rename and digested into the PR-3
export manifest, so a torn or bit-rotted executable refuses admission
exactly like corrupt weights)::

    <export_dir>/aot/aot_meta.json     fingerprint + per-bucket index
    <export_dir>/aot/bucket_<n>.bin    pickle((payload, in_tree, out_tree))

A serialized executable is only loadable on the environment that built
it — same jax/jaxlib, same backend, same device kind — so the meta
records a **compile-environment fingerprint**
(:func:`compile_env_fingerprint`).  The load side
(:class:`AotIndex`, consumed by ``EvalModel``) compares fingerprints
and falls back PER BUCKET to a live compile on any mismatch or
deserialization failure: shipping AOT executables must never make a
bundle unservable that could still compile live.  Each bucket file
also carries its own size+CRC32 in the meta, so a standalone
``EvalModel`` (no manifest verification) still refuses a flipped
payload cleanly instead of feeding garbage to the pickle layer.

Fallback ladder at admission, fastest first:

1. **AOT hit** — deserialize the shipped executable (~ms, journaled as
   a ``compile`` event with ``kind=aot_load`` and ``compile_s`` ~ 0);
2. **persistent compilation cache** — a fingerprint-mismatched bucket
   that live-compiles under ``shifu.tpu.compile-cache-dir`` populates
   jax's on-disk cache, so the *next* worker/restart skips XLA anyway
   (:func:`shifu_tensorflow_tpu.obs.compile.apply_persistent_cache`);
3. **live compile** — the PR-5 warm path, journaled ``kind=warm`` (or
   ``kind=aot_fallback`` when AOT promised the bucket and couldn't
   deliver).

Import-light at module top (stdlib + numpy + config/bucketing): the
train CLI resolves ``--export-aot`` before importing jax, and the obs
CLI never imports this module at all.  jax is touched lazily inside
the build/load functions, which only run in jax processes.
"""

from __future__ import annotations

import json
import os
import pickle
import zlib

import numpy as np

from shifu_tensorflow_tpu.config import keys as K
from shifu_tensorflow_tpu.export.bucketing import ladder
from shifu_tensorflow_tpu.utils import fs, logs

log = logs.get("export.aot")

#: bundle subdirectory holding the serialized executables
AOT_DIR = "aot"
#: the per-bundle AOT index: compile-environment fingerprint + one
#: entry per bucket (file name, size, CRC32)
AOT_META = f"{AOT_DIR}/aot_meta.json"

__all__ = [
    "AOT_DIR",
    "AOT_META",
    "AotIndex",
    "AotExportError",
    "AotLoadError",
    "build_aot_files",
    "compile_env_fingerprint",
    "fingerprint_mismatch",
    "resolve_aot_buckets",
]


class AotLoadError(RuntimeError):
    """One shipped executable cannot be loaded (corrupt payload, backend
    refusal).  Scoped to its bucket: the caller falls back to a live
    compile for that bucket and keeps serving."""


class AotExportError(RuntimeError):
    """The export side cannot build AOT artifacts at all (a jax build
    without executable serialization).  Distinct from
    :class:`AotLoadError` — this is a whole-export capability failure,
    not a per-bucket load fallback; ``--export-aot`` fails loudly
    instead of quietly shipping a bundle without what was asked for."""


def compile_env_fingerprint(mesh_shape: str | None = None) -> dict:
    """The environment a serialized executable is valid in: jax +
    jaxlib versions (the serialization format and the XLA build),
    backend platform, the first device's kind (a CPU executable is
    not a TPU executable; a v4 executable is not a v5e one), and the
    weights mesh shape the program was traced against (a program whose
    parameter shapes are per-shard slices cannot score a differently
    sharded — or unsharded — bundle).  Stamped into ``aot_meta.json``
    at export; compared at load."""
    import jax
    import jaxlib

    # a process that cannot name its device raises here: an unknown
    # device must never compare equal to another unknown device
    return {"jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "mesh_shape": mesh_shape or "unsharded"}


def fingerprint_mismatch(
    recorded: dict, mesh_shape: str | None = None
) -> str | None:
    """None when ``recorded`` (from a bundle's meta) matches this
    process's compile environment, else a human-readable reason naming
    the first differing field.  ``mesh_shape`` is the *bundle's*
    current weights layout (its export manifest's ``mesh_shape``,
    default unsharded); it is only compared when the recorded
    fingerprint carries one, so legacy AOT metas admit unchanged."""
    if not isinstance(recorded, dict) or not recorded:
        return "bundle carries no compile-environment fingerprint"
    env = compile_env_fingerprint(mesh_shape=mesh_shape)
    fields = ("jax", "jaxlib", "backend", "device_kind")
    if "mesh_shape" in recorded:
        fields += ("mesh_shape",)
    for field in fields:
        want, have = recorded.get(field), env.get(field)
        if want != have:
            return f"{field} {have!r} != exported {want!r}"
    return None


def bucket_file(bucket: int) -> str:
    return f"{AOT_DIR}/bucket_{int(bucket)}.bin"


def build_aot_files(
    arch: dict,
    flat_params: dict,
    buckets,
    *,
    model_name: str | None = None,
    weights_sha256: str | None = None,
    mesh_shape: str | None = None,
) -> dict[str, bytes]:
    """Compile the scorer for every ladder bucket and serialize the
    executables; returns ``{relative_name: bytes}`` for the export
    writer to commit and digest into the manifest.

    The model and parameter tree are rebuilt FROM the bundle's own
    representation (the arch dict + the flat npz arrays), exactly the
    way ``EvalModel._init_native`` will rebuild them at load — the
    serialized call convention (pytree structure, shapes, dtypes) is
    identical on both sides by construction, not by convention.
    """
    import jax
    import jax.numpy as jnp

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.saved_model import _unflatten_params
    from shifu_tensorflow_tpu.models.factory import build_model
    from shifu_tensorflow_tpu.obs import compile as obs_compile

    try:
        from jax.experimental import serialize_executable as se
    except Exception as e:  # pragma: no cover - jax build without AOT
        raise AotExportError(
            f"this jax build cannot serialize executables: {e}") from e

    mc = ModelConfig.from_json(arch["model_config"])
    feature_columns = tuple(arch.get("feature_columns") or ())
    model = build_model(mc, feature_columns or None)
    num_features = int(arch["num_features"])
    params = jax.device_put(_unflatten_params(
        {k: np.asarray(v) for k, v in flat_params.items()}))

    def fwd(p, x):
        return model.apply({"params": p}, x)

    jitted = jax.jit(fwd)
    files: dict[str, bytes] = {}
    entries: dict[str, dict] = {}
    # export-side compiles attribute to their own callable name: an
    # export running inside an obs-enabled train process journals them
    # as deliberate kind="export" work, never as request-path churn
    with obs_compile.kind_section("export"), \
            obs_compile.attribute("export.aot", model=model_name):
        for b in sorted({int(b) for b in buckets}):
            if b < 1:
                raise ValueError(f"bucket must be >= 1, got {b}")
            x = jnp.zeros((b, num_features), jnp.float32)
            compiled = jitted.lower(params, x).compile()
            payload, in_tree, out_tree = se.serialize(compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
            name = bucket_file(b)
            files[name] = blob
            entries[str(b)] = {
                "file": name,
                "size": len(blob),
                "crc32": zlib.crc32(blob) & 0xFFFFFFFF,
            }
    meta = {
        "format_version": 1,
        "fingerprint": compile_env_fingerprint(mesh_shape=mesh_shape),
        "num_features": num_features,
        "buckets": entries,
        # which weights generation these programs were compiled WITH —
        # a stale aot/ dir beside re-exported weights must refuse, not
        # deserialize programs whose constants/layout assumptions came
        # from different parameters
        **({"weights_sha256": weights_sha256} if weights_sha256 else {}),
    }
    files[AOT_META] = json.dumps(meta, indent=2, sort_keys=True).encode(
        "utf-8")
    log.info("serialized %d AOT executable(s) (%d bytes total)",
             len(entries), sum(len(v) for v in files.values()))
    return files


class AotIndex:
    """Load-side view of a bundle's shipped executables.

    ``load(model_dir)`` returns None when the bundle ships no AOT at
    all (legacy bundles admit byte-identically to today).  A shipped
    bundle whose meta is unreadable or whose fingerprint does not match
    this environment yields an index with ``unusable`` set — every
    promised bucket then falls back to a live compile, journaled
    ``kind=aot_fallback`` with the reason."""

    def __init__(self, model_dir: str, meta: dict | None,
                 unusable: str | None = None):
        self.model_dir = model_dir
        self.meta = meta
        self.unusable = unusable
        self.buckets: dict[int, dict] = {}
        if meta is not None:
            for b, entry in (meta.get("buckets") or {}).items():
                try:
                    self.buckets[int(b)] = dict(entry)
                except (TypeError, ValueError):
                    continue

    @classmethod
    def load(cls, model_dir: str) -> "AotIndex | None":
        path = os.path.join(model_dir, AOT_META)
        if not fs.exists(path):
            return None
        try:
            meta = json.loads(fs.read_text(path))
            if int(meta.get("format_version", 0)) != 1:
                raise ValueError(
                    f"unknown aot format_version "
                    f"{meta.get('format_version')!r}")
        except (OSError, ValueError) as e:
            # shipped but unreadable: PROMISED and broken — every bucket
            # falls back (and journals why), never refuses the bundle
            return cls(model_dir, None,
                       unusable=f"unreadable {AOT_META}: {e}")
        mismatch = fingerprint_mismatch(
            meta.get("fingerprint") or {},
            mesh_shape=cls._bundle_mesh_shape(model_dir))
        if mismatch is None:
            mismatch = cls._generation_mismatch(model_dir, meta)
        return cls(model_dir, meta, unusable=mismatch)

    @staticmethod
    def _bundle_mesh_shape(model_dir: str) -> str:
        """The bundle's CURRENT weights layout from its export manifest
        (``"unsharded"`` for legacy/flat bundles) — compared against the
        mesh the executables were compiled under, so a stale ``aot/``
        dir beside a re-sharded export falls back instead of feeding
        wrong-shape parameters to a serialized program."""
        from shifu_tensorflow_tpu.export.saved_model import NATIVE_MANIFEST

        try:
            mpath = os.path.join(model_dir, NATIVE_MANIFEST)
            if fs.exists(mpath):
                doc = json.loads(fs.read_text(mpath))
                return str(doc.get("mesh_shape") or "unsharded")
        except (OSError, ValueError):
            pass
        return "unsharded"

    @staticmethod
    def _generation_mismatch(model_dir: str, meta: dict) -> str | None:
        """Refuse executables compiled for a DIFFERENT weights
        generation (a stale ``aot/`` dir beside re-exported weights):
        the meta's stamped weights digest must match the bundle's —
        from the export manifest when one exists (one small read), else
        hashed from the weights file directly."""
        want = meta.get("weights_sha256")
        if not want:
            return None
        # lazy: saved_model imports jax at module top, and this module
        # must stay import-light for jax-free config resolution
        from shifu_tensorflow_tpu.export.saved_model import (
            NATIVE_MANIFEST,
            NATIVE_WEIGHTS,
        )

        try:
            mpath = os.path.join(model_dir, NATIVE_MANIFEST)
            if fs.exists(mpath):
                have = json.loads(fs.read_text(mpath)).get("sha256", "")
            else:
                import hashlib

                have = hashlib.sha256(fs.read_bytes(
                    os.path.join(model_dir, NATIVE_WEIGHTS))).hexdigest()
        except (OSError, ValueError) as e:
            return f"cannot establish the weights generation: {e}"
        if have != want:
            return ("executables were compiled for a different weights "
                    f"generation ({str(want)[:12]} != bundle "
                    f"{str(have)[:12]})")
        return None

    def covers(self, bucket: int) -> bool:
        """Whether the bundle promised an executable for this bucket.
        An unreadable meta promises everything: the bundle DID ship
        AOT, so a live compile there is a fallback, not the plan."""
        if self.meta is None:
            return True
        return int(bucket) in self.buckets

    def load_bucket(self, bucket: int):
        """Deserialize one bucket's executable onto the current
        backend.  Raises :class:`AotLoadError` on any failure — corrupt
        payload (size/CRC32 checked against the meta before the pickle
        layer ever sees the bytes), fingerprint mismatch, or a backend
        that refuses the deserialization."""
        if self.unusable:
            raise AotLoadError(self.unusable)
        entry = self.buckets.get(int(bucket))
        if entry is None:
            raise AotLoadError(f"bucket {bucket} not in the AOT index")
        path = os.path.join(self.model_dir, entry.get("file", ""))
        try:
            blob = fs.read_bytes(path)
        except OSError as e:
            raise AotLoadError(f"cannot read {entry.get('file')}: {e}") \
                from e
        if len(blob) != int(entry.get("size", -1)):
            raise AotLoadError(
                f"{entry.get('file')}: size {len(blob)} != recorded "
                f"{entry.get('size')}")
        if (zlib.crc32(blob) & 0xFFFFFFFF) != int(entry.get("crc32", -1)):
            raise AotLoadError(f"{entry.get('file')}: CRC32 mismatch")
        try:
            import jax
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = pickle.loads(blob)
            # the scorer program is compiled for ONE device (the default
            # one, where EvalModel puts the weights); left to its
            # default, the loader binds it to every device of the host
            # and the first call fails for want of one shard per device
            return se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=jax.local_devices()[:1])
        except AotLoadError:
            raise
        except Exception as e:
            raise AotLoadError(
                f"{entry.get('file')}: deserialization failed: "
                f"{type(e).__name__}: {e}") from e


def resolve_aot_buckets(args, conf) -> tuple[int, ...] | None:
    """The export CLI's AOT decision: None when AOT export is off
    (``--export-aot`` / ``shifu.tpu.export-aot``), else the bucket
    ladder up to ``--export-aot-rows`` / ``shifu.tpu.export-aot-rows``
    — by default the same ladder the serve plane warms
    (``ladder(serve-queue-rows)``), so an exported bundle covers every
    bucket a default server's admission bound can reach."""
    enabled = getattr(args, "export_aot", None)
    if enabled is None:
        enabled = conf.get_bool(K.EXPORT_AOT, K.DEFAULT_EXPORT_AOT)
    if not enabled:
        return None
    rows = getattr(args, "export_aot_rows", None)
    if rows is None:
        rows = conf.get_int(K.EXPORT_AOT_ROWS, K.DEFAULT_EXPORT_AOT_ROWS)
    return ladder(int(rows))
