"""Batch scoring against an exported artifact.

Parity surface: the reference's Java ``TensorflowModel implements
Computable`` — ``init(GenericModelConfig)`` loads the SavedModel bundle,
``compute(MLData)`` converts a row of doubles to a float tensor, feeds
``shifu_input_0``, fetches ``shifu_output_0``, returns the scalar
(TensorflowModel.java:32,53-94,112-172).  ``EvalModel`` mirrors that
lifecycle (init → compute/compute_batch → release) with three backends:

- ``native``: rebuilds the flax model from ``shifu_tpu_model.json`` and
  loads ``shifu_tpu_weights.npz`` — zero TF dependency;
- ``saved_model``: loads the TF SavedModel through TensorFlow when
  available, scoring through the exact signature the Java evaluator uses —
  this is the cross-check that the exported artifact honors the contract;
- ``cpp``: the C++ scorer (cpp/stpu_scorer.cc via ctypes) — the
  zero-Python-runtime path matching the reference's JNI evaluator; covers
  every exported family except sequence (dnn, wide&deep, multi-task,
  embedding-augmented).

Thread-safety contract: an ``EvalModel`` instance is internally
synchronized — ``compute``/``compute_batch``/``release`` serialize on a
per-instance lock, because none of the backends tolerates concurrent
entry (the cpp backend shares one ctypes handle, the saved_model backend
one TF session, and ``release`` tears state down under a running call).
Concurrent callers are therefore CORRECT but not parallel; for
throughput, coalesce rows into one ``compute_batch`` call (what the
serving micro-batcher, serve/batcher.py, exists for) or hold one
instance per thread.

The ``native`` backend pads every batch up to the export/bucketing.py
ladder before dispatch, so the jitted scorer compiles once per BUCKET
instead of once per distinct batch length (a free-varying workload would
otherwise re-trace per length, ~19 ms each on the flagship DNN);
``native_trace_count`` exposes the compile count for regression tests.
"""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from shifu_tensorflow_tpu.export.bucketing import bucket_size, pad_rows

from shifu_tensorflow_tpu.config.model_config import (
    ModelConfig,
    require_servable,
)
from shifu_tensorflow_tpu.export.saved_model import (
    GENERIC_CONFIG,
    INPUT_NAME,
    NATIVE_ARCH,
    OUTPUT_NAME,
    _unflatten_params,
    load_native_weights,
)
from shifu_tensorflow_tpu.utils import fs, logs

log = logs.get("export.eval")


class ModelReleasedError(RuntimeError):
    """compute after release(): the instance's backend state is gone.
    Raised as a distinct type so a holder of a stale reference (the
    serving hot-reload swap window) can re-fetch the live model instead
    of surfacing an opaque AttributeError."""


class EvalModel:
    """init/compute/release lifecycle over an exported model dir."""

    def __init__(self, model_dir: str, backend: str = "native"):
        self.model_dir = model_dir
        self.backend = backend
        # serializes compute/compute_batch/release — see the module
        # docstring's thread-safety contract.  RLock: compute() calls
        # compute_batch() on the same thread.
        self._compute_lock = threading.RLock()
        self.generic_config = json.loads(
            fs.read_text(os.path.join(model_dir, GENERIC_CONFIG))
        )
        assert INPUT_NAME in self.generic_config["inputnames"]
        if backend == "native":
            self._init_native()
        elif backend == "saved_model":
            self._init_saved_model()
        elif backend == "cpp":
            self._init_cpp()
        else:
            raise ValueError(f"unknown backend {backend!r}")

    # ---- native backend ----
    def _init_native(self) -> None:
        from shifu_tensorflow_tpu.models.factory import build_model

        arch = json.loads(fs.read_text(os.path.join(self.model_dir, NATIVE_ARCH)))
        self.num_features = int(arch["num_features"])
        mc = ModelConfig.from_json(arch["model_config"])
        require_servable(mc.params.model_type, "EvalModel (serve, score)")
        feature_columns = tuple(arch.get("feature_columns") or ())
        self._model = build_model(mc, feature_columns or None)
        # both layouts: flat npz, or a mesh-aware export's shard files
        self._params = _unflatten_params(load_native_weights(self.model_dir))
        norm = arch.get("normalization") or {}
        self._means = np.asarray(norm["means"], np.float32) if norm.get("means") else None
        self._stds = np.asarray(norm["stds"], np.float32) if norm.get("stds") else None

        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        # weights live on device once — numpy leaves would be re-copied
        # host->device on EVERY dispatch, taxing the per-row path
        self._params = jax.device_put(self._params)
        # jit the forward: un-jitted flax apply re-TRACES the model every
        # call (~19ms for the flagship DNN — measured 53 rows/s on the
        # per-row Computable path); compiled per input shape it serves
        # per-row scoring at tens of microseconds.  Batches pad to the
        # bucketing ladder before dispatch (compute_batch), so the trace
        # count is bounded by the ladder, not by how many distinct batch
        # lengths the workload happens to produce.
        model = self._model
        self._trace_count = 0

        def fwd(params, x):
            # runs at TRACE time only — counts compilations, not calls
            self._trace_count += 1
            return model.apply({"params": params}, x)

        # compile flight recorder seam (obs/compile.py): each bucket's
        # compile journals a `compile` event naming this bundle — with
        # no recorder installed the wrap is one is-None check per call
        from shifu_tensorflow_tpu.obs import compile as obs_compile

        self._model_name = (os.path.basename(self.model_dir.rstrip("/"))
                            or None)
        self._apply = obs_compile.observe(
            jax.jit(fwd), "eval.native_score",
            model=self._model_name,
            bucket_from=lambda params, x: x.shape[0],
        )
        # AOT executable shipping (export/aot.py): when the bundle ships
        # serialized ladder executables, dispatch DESERIALIZES them
        # instead of compiling — per-bucket, falling back to the jitted
        # path (live compile) on any fingerprint/payload mismatch.  A
        # bundle without aot/ behaves byte-identically to before.
        from shifu_tensorflow_tpu.export import aot as aot_mod

        try:
            self._aot = aot_mod.AotIndex.load(self.model_dir)
        except Exception as e:  # the index must never fail the load
            self._aot = aot_mod.AotIndex(
                self.model_dir, None,
                unusable=f"{type(e).__name__}: {e}")
        self._aot_execs: dict[int, object] = {}
        self._aot_failed: dict[int, str] = {}
        self._aot_loads = 0
        self._aot_fallbacks = 0
        if self._aot is not None and self._aot.unusable:
            log.warning(
                "AOT executables at %s unusable (%s): every shipped "
                "bucket will live-compile instead",
                self.model_dir, self._aot.unusable)

    def _init_cpp(self) -> None:
        from shifu_tensorflow_tpu.export.native_scorer import NativeScorer

        self._cpp = NativeScorer(self.model_dir)
        self.num_features = self._cpp.num_features
        # normalization is applied inside the native scorer
        self._means = self._stds = None

    def _init_saved_model(self) -> None:
        import tensorflow as tf

        self._tf = tf
        loaded = tf.saved_model.load(self.model_dir, tags=None)
        self._infer = loaded.signatures["serving_default"]
        # feature count from the signature input spec
        spec = self._infer.structured_input_signature[1]
        (only,) = spec.values()
        self.num_features = int(only.shape[1])
        # normalization stats live in the native arch file alongside the
        # SavedModel; both backends must apply identical ZSCALE
        self._means = self._stds = None
        arch_path = os.path.join(self.model_dir, NATIVE_ARCH)
        if fs.exists(arch_path):
            norm = json.loads(fs.read_text(arch_path)).get("normalization") or {}
            if norm.get("means"):
                self._means = np.asarray(norm["means"], np.float32)
                self._stds = np.asarray(norm["stds"], np.float32)

    # ---- scoring ----
    def compute(self, row) -> float:
        """Score one row of raw doubles (Computable.compute parity)."""
        out = self.compute_batch(np.asarray(row, np.float32)[None, :])
        return float(out[0, 0])

    def compute_batch(self, rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows, np.float32)
        if rows.shape[1] != self.num_features:
            raise ValueError(
                f"expected {self.num_features} features, got {rows.shape[1]}"
            )
        with self._compute_lock:
            if getattr(self, "_released", False):
                # a caller that dereferenced this instance just before a
                # hot-reload swap can land here AFTER release() won the
                # lock; the typed error lets it re-fetch the live model
                raise ModelReleasedError(self.model_dir)
            if self._means is not None:
                rows = (rows - self._means) / np.where(
                    self._stds == 0, 1, self._stds
                )
            if self.backend == "native":
                n = rows.shape[0]
                # pad to the bucket ladder: compile once per bucket, not
                # once per distinct batch length (padded rows sliced off)
                bucket = bucket_size(n)
                padded = pad_rows(rows, bucket)
                out = self._dispatch(self._jnp.asarray(padded), bucket)
                return np.asarray(out)[:n]
            if self.backend == "cpp":
                return self._cpp.score(rows)
            result = self._infer(**{INPUT_NAME: self._tf.constant(rows)})
            return result[OUTPUT_NAME].numpy()

    def _dispatch(self, x, bucket: int):
        """Route one padded batch (caller holds the compute lock): the
        bundle-shipped AOT executable when one deserializes for this
        bucket, else the jitted scorer (whose first call per bucket
        live-compiles).  When AOT *promised* the bucket and could not
        deliver, the live compile journals ``kind=aot_fallback`` with
        the reason — never ``warm``/unmarked — so admission journals
        say what actually happened."""
        fn = None
        reason = None
        if self._aot is not None:
            fn, reason = self._aot_acquire(bucket)
        if fn is not None:
            return fn(self._params, x)
        if reason is not None:
            from shifu_tensorflow_tpu.obs import compile as obs_compile

            with obs_compile.kind_section("aot_fallback",
                                          aot_error=reason):
                return self._apply(self._params, x)
        return self._apply(self._params, x)

    def _aot_acquire(self, bucket: int):
        """(executable, None) on an AOT hit for ``bucket``; (None,
        reason) when the bundle promised the bucket and cannot deliver
        (the caller live-compiles under ``kind=aot_fallback``); (None,
        None) for buckets the bundle never shipped (plain live path).
        A successful deserialization journals a ``compile`` event with
        ``kind=aot_load`` and ``compile_s`` ~ 0 — admission cost
        becomes visible as what it is: a load, not a compile."""
        fn = self._aot_execs.get(bucket)
        if fn is not None:
            return fn, None
        failed = self._aot_failed.get(bucket)
        if failed is not None:
            return None, failed
        if not self._aot.covers(bucket):
            return None, None
        from shifu_tensorflow_tpu.export.aot import AotLoadError
        from shifu_tensorflow_tpu.obs import compile as obs_compile

        t0 = time.perf_counter()
        try:
            fn = self._aot.load_bucket(bucket)
        except AotLoadError as e:
            reason = str(e)
            self._aot_failed[bucket] = reason
            self._aot_fallbacks += 1
            if not self._aot.unusable:
                # per-bucket warnings only for genuinely per-bucket
                # failures (corrupt payload, CRC): an index-wide
                # mismatch already logged ONE summary warning at init —
                # restating it per bucket x tenant x worker would bury
                # a fleet restart's logs
                log.warning("AOT bucket %d at %s refused (%s); falling "
                            "back to live compile", bucket,
                            self.model_dir, reason)
            return None, reason
        wall = time.perf_counter() - t0
        self._aot_execs[bucket] = fn
        self._aot_loads += 1
        rec = obs_compile.active()
        if rec is not None:
            try:
                import jax

                # ShapeDtypeStruct: the signature needs shape+dtype
                # only — no reason to allocate a (bucket, f) device
                # array just to journal what was loaded
                sig = obs_compile.signature_of(
                    (self._params,
                     jax.ShapeDtypeStruct(
                         (bucket, self.num_features),
                         self._jnp.float32)), {})
            except Exception:
                sig = "?"
            rec.record(name="eval.native_score", signature=sig,
                       compile_s=0.0, parts=0, wall_s=wall,
                       bucket=bucket, model=self._model_name,
                       kind="aot_load")
        return fn, None

    @property
    def aot_stats(self) -> dict:
        """What AOT did for this instance: whether the bundle shipped
        executables, how many buckets deserialized vs fell back to a
        live compile, and why the whole index was unusable (fingerprint
        or generation mismatch) if it was.  Read by the serve admission
        path for its logs/journal."""
        if self.backend != "native" or getattr(self, "_aot", None) is None:
            return {"shipped": False, "loads": 0, "fallbacks": 0,
                    "unusable": None}
        return {
            "shipped": True,
            "loads": self._aot_loads,
            "fallbacks": self._aot_fallbacks,
            "unusable": self._aot.unusable,
        }

    def warm(self, buckets) -> int:
        """Pre-compile the jitted native scorer for every ladder bucket
        in ``buckets`` (row counts), so no future ``compute_batch`` ever
        pays a trace+compile on the request path.  Returns the number of
        NEW traces this call caused (0 when everything was already
        compiled — the pinned-``native_trace_count`` serving invariant).

        The cpp and saved_model backends compile nothing per shape, so
        warming them is a free no-op.  Thread-safe under the same
        per-instance lock as compute; raises
        :class:`ModelReleasedError` after release()."""
        if self.backend != "native":
            return 0
        from shifu_tensorflow_tpu.obs import compile as obs_compile

        with self._compute_lock:
            if getattr(self, "_released", False):
                raise ModelReleasedError(self.model_dir)
            before = self._trace_count
            # warm_section: these compiles journal kind="warm" and never
            # count toward a recompile storm — the ladder pre-warm is
            # deliberate churn (and the storm's cure)
            with obs_compile.warm_section():
                for b in sorted({int(b) for b in buckets}):
                    if b < 1:
                        raise ValueError(f"bucket must be >= 1, got {b}")
                    # zeros are fine: compilation keys on SHAPE, and the
                    # scores of a warm-up batch are never observed.  The
                    # value FETCH matters: dispatch alone returns
                    # futures, and a warm() that only enqueued would let
                    # the model be swapped in while its warm-up programs
                    # still occupy the device — the first real request
                    # would queue behind them, re-creating (a smaller)
                    # latency cliff.  _dispatch prefers the
                    # bundle-shipped AOT executable: a hit deserializes
                    # (~ms, journaled kind=aot_load) instead of
                    # compiling, which is the whole point of shipping
                    # them — warming then costs no new traces at all.
                    x = self._jnp.zeros((b, self.num_features),
                                        self._jnp.float32)
                    np.asarray(self._dispatch(x, b))
            return self._trace_count - before

    def device_bytes(self) -> int:
        """Device-resident bytes this model holds (native backend: the
        weight pytree placed by ``device_put``; other backends hold no
        jax buffers and report 0).  Read by the serve tenancy plane's
        memory accountant so the LRU budget's dashboard shows *device*
        bytes per tenant, not just bundle bytes on disk."""
        if self.backend != "native" or getattr(self, "_released", False):
            return 0
        from shifu_tensorflow_tpu.obs.memory import tree_device_bytes

        return tree_device_bytes(getattr(self, "_params", None))

    @property
    def native_trace_count(self) -> int:
        """How many times the jitted native scorer has (re)traced — flat
        across varying batch lengths within one bucket, by construction."""
        return getattr(self, "_trace_count", 0)

    def release(self) -> None:
        """Explicit resource release (closeTensors parity,
        TensorflowModel.java:97-109) — backends hold no leaked handles, so
        this just drops references.  Takes the compute lock: a release
        racing an in-flight compute (the serving hot-reload swap drops the
        OLD model while the batcher may still be scoring on it) waits for
        the call to finish instead of tearing state down under it."""
        with self._compute_lock:
            self._released = True
            if hasattr(self, "_cpp"):
                self._cpp.close()
            for attr in ("_model", "_params", "_infer", "_tf", "_jnp",
                         "_cpp", "_apply", "_aot", "_aot_execs"):
                if hasattr(self, attr):
                    delattr(self, attr)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()
