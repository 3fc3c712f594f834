"""Compile flight recorder: every XLA compilation, journaled and priced.

The host-side obs plane (PRs 4+7) can say *where the step's wall clock
went*; it cannot say *what the compiler did* — how many programs this
process built, how long each took, what they cost in flops and device
bytes, and (the classic production incident) whether an unpadded input
shape is quietly recompiling the same callable hundreds of times.  The
reference had nothing here at all; TensorFlow ships per-op cost/memory
accounting as a first-class runtime subsystem (PAPERS.md), and both
ROADMAP item 1 (sharded SPMD) and item 5 (pipeline parallelism) need
per-stage compile/memory visibility before they can be placed or
benchmarked.  This module is that leg.

How a compilation is *detected*: jax publishes per-compile durations
through ``jax.monitoring`` (``.../backend_compile_duration`` events fire
once per XLA backend compile — a persistent-cache hit included, which
additionally fires ``.../compilation_cache/cache_hits`` — and never on a
dispatch-cache hit; jax 0.9.0).  The recorder registers ONE
process-wide listener pair; the instrumented seams (:func:`observe`-wrapped jitted callables,
:func:`attribute` regions around Pallas entry points) push a
thread-local attribution frame around each call, so whatever the
listener hears lands on the *named callable that caused it*.  A call
during which no compile event fired costs two ``perf_counter`` reads
and a list push/pop; a call that DID compile additionally journals one
``compile`` event:

- ``name`` / ``signature`` — the callable and the abstract
  shape/dtype signature of its arguments (what XLA keys its cache on);
- ``bucket`` / ``model`` / ``kind`` — serving context (ladder bucket,
  tenant, ``warm`` vs request-path);
- ``compile_s`` (the listener's backend-compile seconds) and ``wall_s``
  (the whole call, i.e. compile + first execution);
- ``cache_hits`` — how many of the call's ``parts`` the persistent
  compilation cache served (present only when some were);
- cost/memory analysis where the backend provides it: ``flops`` and
  ``bytes_accessed`` from ``Lowered.cost_analysis()`` (cheap — the
  jaxpr is already cached, nothing recompiles), and argument/output/
  temp/generated-code bytes from ``Compiled.memory_analysis()`` —
  which requires a second backend compile, so it is gated by
  ``shifu.tpu.obs-compile-analysis`` (``full`` | ``cost`` | ``off``)
  and suppressed from its own accounting.  Backends that implement
  neither degrade to the timing fields alone.

The recorder also maintains an in-process executable registry —
``stpu_compile_*`` gauges (live executables, cumulative compile
seconds, per-plane executable bytes) appended to that plane's
``/metrics`` surface — and runs the recompile-storm detector: a
:class:`~shifu_tensorflow_tpu.obs.slo.WindowedCounter` over the
compile-rate signal with an :class:`~shifu_tensorflow_tpu.obs.slo.EwmaZ`
corroborating z-score, journaling ``recompile_storm`` (naming the
churning callable and its last signature) when the windowed rate
crosses the storm threshold and ``recompile_storm_clear`` when it
drains.  Warm-ladder compiles (``kind="warm"``) are *expected* churn
and never count toward a storm — a serve fleet pre-warming ten buckets
at startup is the cure, not the disease.

stdlib-only at import (the obs CLI renders journals on jax-free
hosts); jax is touched lazily from inside the seams, which only run in
jax processes.  Off-by-default-cheap like its siblings: with no
recorder installed every seam is one module-global ``is None`` check.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Callable

from shifu_tensorflow_tpu.obs.registry import MetricsRegistry
from shifu_tensorflow_tpu.utils import logs

log = logs.get("obs")

__all__ = [
    "CompileRecorder",
    "observe",
    "attribute",
    "warm_section",
    "kind_section",
    "apply_persistent_cache",
    "install",
    "uninstall",
    "active",
]

#: compile kinds that are deliberate admission/export work, never
#: request-path churn: the warm ladder, an AOT executable deserialized
#: instead of compiled (kind=aot_load, ~0 compile_s), the per-bucket
#: live-compile fallback when AOT couldn't deliver (kind=aot_fallback),
#: and export-time AOT pre-compilation.  None of these count toward a
#: recompile storm — a 10-tenant fleet restart deserializing (or even
#: re-compiling) its ladders is the cure, not the disease.
ADMISSION_KINDS = frozenset({"warm", "aot_load", "aot_fallback",
                             "export"})

_perf = time.perf_counter
_mono = time.monotonic

#: the directory that holds the package: a source checkout's root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: jax.monitoring event-name suffix that marks one XLA backend compile
#: ("/jax/core/compile/backend_compile_duration"; matched by suffix so
#: a renamed prefix keeps reporting)
_COMPILE_EVENT_SUFFIX = "backend_compile_duration"
#: the event jax records when such a compile was served from the
#: persistent compilation cache instead of running XLA
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _Tls(threading.local):
    def __init__(self):
        # frames: [compile_s, n_compiles, persistent_cache_hits]
        self.stack: list[list] = []
        self.kinds: list[tuple] = []  # kind_section() stack: (kind, fields)
        self.suppress = 0            # self-inflicted compiles (analysis)


_tls = _Tls()
_listener_registered = False
_listener_lock = threading.Lock()


def _on_duration_event(name: str, duration: float, **_kw) -> None:
    """The process-wide jax.monitoring listener.  Listeners cannot be
    individually unregistered, so this one is installed once and stays;
    with no recorder installed (or no frame on this thread) it is a
    suffix check and a global read."""
    if not name.endswith(_COMPILE_EVENT_SUFFIX):
        return
    if _tls.suppress:
        return
    if _tls.stack:
        frame = _tls.stack[-1]
        frame[0] += duration
        frame[1] += 1
        return
    rec = _active
    if rec is not None:
        rec._note_unattributed(duration)


def _on_event(name: str, **_kw) -> None:
    """The second process-wide listener: a compile served from the
    persistent cache lands on the frame of the call that asked for it
    (the duration event for the same compile follows on this thread)."""
    if name == _CACHE_HIT_EVENT and _tls.stack and not _tls.suppress:
        _tls.stack[-1][2] += 1


def _ensure_listener() -> bool:
    """Register the monitoring listener (idempotent).  Called from the
    seams, which by definition run inside jax code paths — never at
    import or install time, which must stay jax-free."""
    global _listener_registered
    if _listener_registered:
        return True
    with _listener_lock:
        if _listener_registered:
            return True
        try:
            import jax.monitoring as monitoring

            monitoring.register_event_duration_secs_listener(
                _on_duration_event)
            monitoring.register_event_listener(_on_event)
        except Exception as e:  # jax absent / API moved: degrade silently
            log.warning("compile recorder cannot listen for compile "
                        "events (%s: %s); compile journaling disabled",
                        type(e).__name__, e)
            _listener_registered = True  # don't retry per call
            return False
        _listener_registered = True
        return True


def _abstract(x: Any) -> str:
    """One argument leaf -> its abstract signature atom (what the XLA
    dispatch cache keys on: shape + dtype; values never matter)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        name = getattr(dtype, "name", None) or str(dtype)
        return f"{name}[{','.join(str(d) for d in shape)}]"
    return type(x).__name__


def signature_of(args: tuple, kw: dict) -> str:
    """Abstract shape/dtype signature of a call's arguments.  Long
    pytrees (a TrainState's every leaf) collapse to the first few atoms
    plus a count — the storm diagnosis needs the *varying* part (batch
    shapes), not a thousand identical param leaves."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kw))
    atoms = [_abstract(l) for l in leaves]
    if len(atoms) > 6:
        head = ";".join(atoms[:3])
        tail = ";".join(atoms[-2:])
        return f"{head};..{len(atoms) - 5}more..;{tail}"
    return ";".join(atoms)


class _StormState:
    """Recompile-storm detector state (one per recorder).

    The compile-rate signal is a windowed count of non-warm compiles;
    the storm opens when the window holds >= ``threshold`` compiles and
    closes when it drains back below half of it (hysteresis by level,
    matching the windowed-signal discipline of obs/slo.py).  EwmaZ rides
    along as the "how abnormal is this" annotation — fed one rate sample
    per tick, its z-score is journaled with the storm event when the
    warm-up has passed."""

    def __init__(self, window_s: float, threshold: int):
        from shifu_tensorflow_tpu.obs.slo import EwmaZ, WindowedCounter

        self.window_s = float(window_s)
        self.threshold = max(2, int(threshold))
        self.counter = WindowedCounter(self.window_s)
        self.by_name: dict[str, Any] = {}   # name -> WindowedCounter
        self.last_sig: dict[str, str] = {}  # name -> last signature
        self.ewma = EwmaZ()
        self.last_z: float | None = None
        self.active = False
        self.since: float | None = None
        self.culprit: str = "?"        # remembered at storm open: the
        self.culprit_sig: str = "?"    # clear event names the STORM's
        self.storms_total = 0          # churner, not the drained window's
        self._counter_cls = WindowedCounter


class CompileRecorder:
    """The per-process flight recorder (one per plane, installed by
    ``obs.install_obs`` next to the tracer/journal/watchdog)."""

    def __init__(self, *, plane: str = "train", worker: int | None = None,
                 analysis: str = "full", storm_window_s: float = 60.0,
                 storm_threshold: int = 8):
        if analysis not in ("full", "cost", "off"):
            raise ValueError(
                f"compile analysis must be full|cost|off, got {analysis!r}")
        self.plane = plane
        self.worker = worker
        self.analysis = analysis
        self._lock = threading.Lock()
        # (name, signature) -> [compiles, compile_s, code_bytes]: the
        # in-process executable registry.  An entry is an executable XLA
        # holds live in its dispatch cache; re-compiles of the SAME
        # signature (cache eviction, donation-variant retrace) bump the
        # count without growing the registry.
        self._executables: dict[tuple[str, str], list] = {}
        self.compiles_total = 0
        self.compile_seconds_total = 0.0
        self.aot_loads_total = 0
        self.unattributed_compiles = 0
        self.unattributed_seconds = 0.0
        self.registry = MetricsRegistry()
        self._storm = _StormState(storm_window_s, storm_threshold)

    # ---- attribution frames (hot path) ----
    def _push(self) -> list:
        frame = [0.0, 0, 0]
        _tls.stack.append(frame)
        return frame

    def _pop(self, frame: list) -> None:
        # pop by identity so a seam that leaks an exception mid-nest
        # cannot leave a stale frame absorbing someone else's compiles
        stack = _tls.stack
        if stack and stack[-1] is frame:
            stack.pop()
        elif frame in stack:
            stack.remove(frame)

    def _note_unattributed(self, duration: float) -> None:
        with self._lock:
            self.unattributed_compiles += 1
            self.unattributed_seconds += duration
            self.compiles_total += 1
            self.compile_seconds_total += duration

    # ---- the observed-call seam ----
    def observed_call(self, fn: Callable, name: str, args: tuple,
                      kw: dict, *, kind: str | None = None,
                      model: str | None = None,
                      bucket_from: Callable | None = None):
        _ensure_listener()
        frame = self._push()
        t0 = _perf()
        try:
            out = fn(*args, **kw)
        finally:
            wall = _perf() - t0
            self._pop(frame)
        if frame[1]:
            try:
                self._record_compiled(fn, name, args, kw, frame, wall,
                                      kind=kind, model=model,
                                      bucket_from=bucket_from)
            except Exception as e:  # recording must never fail the call
                log.warning("compile event for %s dropped (%s: %s)",
                            name, type(e).__name__, e)
        return out

    def _record_compiled(self, fn, name, args, kw, frame, wall_s, *,
                         kind, model, bucket_from) -> None:
        try:
            sig = signature_of(args, kw)
        except Exception:
            sig = "?"
        bucket = None
        if bucket_from is not None:
            try:
                bucket = bucket_from(*args, **kw)
            except Exception:
                bucket = None
        fields = self._analyze(fn, args, kw)
        section_kind, extra = _section(kind)
        self.record(name=name, signature=sig, compile_s=frame[0],
                    parts=frame[1], wall_s=wall_s, bucket=bucket,
                    model=model, kind=section_kind, **_hits(frame),
                    **extra, **fields)

    def _analyze(self, fn, args, kw) -> dict:
        """Cost/memory analysis fields, degrading to {} wherever the
        backend (or the callable) doesn't provide them.  ``full`` pays a
        SECOND backend compile for ``memory_analysis`` — suppressed from
        the listener so the recorder cannot count its own probe."""
        out: dict[str, Any] = {}
        if self.analysis == "off":
            return out
        lower = getattr(fn, "lower", None)
        if lower is None:
            return out
        _tls.suppress += 1
        try:
            lowered = lower(*args, **kw)
            cost = lowered.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if isinstance(cost, dict):
                if "flops" in cost:
                    out["flops"] = float(cost["flops"])
                if "bytes accessed" in cost:
                    out["bytes_accessed"] = float(cost["bytes accessed"])
            if self.analysis == "full":
                mem = lowered.compile().memory_analysis()
                if mem is not None:
                    out["arg_bytes"] = int(mem.argument_size_in_bytes)
                    out["out_bytes"] = int(mem.output_size_in_bytes)
                    out["temp_bytes"] = int(mem.temp_size_in_bytes)
                    out["code_bytes"] = int(
                        mem.generated_code_size_in_bytes)
        except Exception:
            pass  # cost/memory introspection is best-effort by contract
        finally:
            _tls.suppress -= 1
        return out

    # ---- recording (also the direct API for attribute()) ----
    def record(self, *, name: str, signature: str = "?",
               compile_s: float = 0.0, parts: int = 1,
               wall_s: float | None = None, bucket: int | None = None,
               model: str | None = None, kind: str | None = None,
               now: float | None = None, **fields: Any) -> None:
        from shifu_tensorflow_tpu.obs import journal as obs_journal
        from shifu_tensorflow_tpu.obs import slo as obs_slo

        now = _mono() if now is None else now
        with self._lock:
            entry = self._executables.get((name, signature))
            if entry is None:
                entry = self._executables[(name, signature)] = [0, 0.0, 0]
            entry[0] += 1
            entry[1] += compile_s
            if "code_bytes" in fields:
                entry[2] = int(fields["code_bytes"])
            if kind == "aot_load":
                # a deserialized shipped executable: live in the
                # registry (it occupies the device like any program)
                # but NOT a compilation — compiles_total must keep
                # meaning "times XLA ran"
                self.aot_loads_total += 1
            else:
                # counts BACKEND compiles (one jit call can compile
                # several sub-programs — `parts`), matching what
                # _note_unattributed counts for compiles nobody claimed
                self.compiles_total += max(1, parts)
                self.compile_seconds_total += compile_s
        ev: dict[str, Any] = {
            "name": name, "signature": signature,
            "compile_s": round(compile_s, 6), "parts": parts,
        }
        if wall_s is not None:
            ev["wall_s"] = round(wall_s, 6)
        if bucket is not None:
            ev["bucket"] = int(bucket)
        if model is not None:
            ev["model"] = model
        if kind is not None:
            ev["kind"] = kind
        backend = _backend_name()
        if backend is not None:
            ev["backend"] = backend
        for k, v in fields.items():
            ev[k] = round(v, 6) if isinstance(v, float) else v
        obs_journal.emit("compile", plane=self.plane, worker=self.worker,
                         **ev)
        wd = obs_slo.active()
        if wd is not None and kind != "aot_load":
            # the shifu.tpu.slo-compile-s target judges the window MAX
            # of this signal (from_config); one slow compile is the
            # breach, not the average of many fast ones.  A deserialized
            # AOT executable never ran XLA — its ~0 is not a compile
            # sample.
            wd.observe("compile_s", compile_s)
        if kind not in ADMISSION_KINDS:
            self._storm_note(name, signature, now)
        else:
            # even expected churn must let an open storm close
            self._storm_check(now)

    # ---- recompile-storm detection ----
    def _storm_note(self, name: str, signature: str, now: float) -> None:
        st = self._storm
        with self._lock:
            st.counter.add(1, now=now)
            c = st.by_name.get(name)
            if c is None:
                c = st.by_name[name] = st._counter_cls(st.window_s)
            c.add(1, now=now)
            st.last_sig[name] = signature
        self._storm_check(now)

    def _storm_check(self, now: float | None = None) -> list[dict]:
        """Evaluate the storm state machine; returns the events it
        journaled.  Called on every non-warm compile and from
        :meth:`tick` — the clear transition needs a tick, because a
        storm that simply *stops compiling* fires no more events."""
        from shifu_tensorflow_tpu.obs import journal as obs_journal

        now = _mono() if now is None else now
        events: list[dict] = []
        st = self._storm
        with self._lock:
            total = st.counter.total(now=now)
            if not st.active and total >= st.threshold:
                st.active = True
                st.since = now
                st.storms_total += 1
                name, n, sig = self._churn_culprit(now)
                st.culprit, st.culprit_sig = name, sig
                events.append({
                    "event": "recompile_storm",
                    "compiles_in_window": total,
                    "window_s": st.window_s,
                    "threshold": st.threshold,
                    "culprit": name,
                    "culprit_compiles": n,
                    "signature": sig,
                    **({"z": round(st.last_z, 2)}
                       if st.last_z is not None else {}),
                })
            elif st.active and total <= st.threshold // 2:
                st.active = False
                events.append({
                    "event": "recompile_storm_clear",
                    "compiles_in_window": total,
                    "storm_s": round(now - (st.since or now), 3),
                    "culprit": st.culprit,
                    "signature": st.culprit_sig,
                })
                st.since = None
        for ev in events:
            kind = ev.pop("event")
            obs_journal.emit(kind, plane=self.plane, worker=self.worker,
                             **ev)
        return events

    def _churn_culprit(self, now: float) -> tuple[str, int, str]:
        """The callable with the most window compiles + its last
        signature — "which signature churned".  Caller holds the lock."""
        st = self._storm
        best, best_n = "?", 0
        for name, c in st.by_name.items():
            n = c.total(now=now)
            if n > best_n:
                best, best_n = name, n
        return best, best_n, st.last_sig.get(best, "?")

    def tick(self, now: float | None = None) -> list[dict]:
        """Slow-path evaluation (per train epoch / per serve SLO tick):
        feed the EwmaZ rate sample and run the storm state machine so a
        storm whose compiles stopped can clear."""
        now = _mono() if now is None else now
        st = self._storm
        with self._lock:
            z = st.ewma.update(float(st.counter.total(now=now)))
            if z is not None:
                st.last_z = z
        return self._storm_check(now)

    # ---- reading ----
    def executables(self) -> dict[tuple[str, str], dict]:
        with self._lock:
            return {
                key: {"compiles": e[0], "compile_s": e[1],
                      "code_bytes": e[2]}
                for key, e in self._executables.items()
            }

    def state(self) -> dict:
        with self._lock:
            st = self._storm
            return {
                "live_executables": len(self._executables),
                "compiles_total": self.compiles_total,
                "aot_loads_total": self.aot_loads_total,
                "compile_seconds_total": round(
                    self.compile_seconds_total, 6),
                "executable_bytes": sum(
                    e[2] for e in self._executables.values()),
                "unattributed_compiles": self.unattributed_compiles,
                "storm_active": st.active,
                "storms_total": st.storms_total,
            }

    def render_prometheus(self) -> str:
        """``stpu_compile_*`` gauge text, appended by the plane's scrape
        surface (serve ``/metrics``, the coordinator ``metrics`` op) —
        the per-plane executable registry as Prometheus sees it."""
        s = self.state()
        r = self.registry
        r.set_gauge("live_executables", s["live_executables"])
        r.set_gauge("seconds_total", round(s["compile_seconds_total"], 6))
        r.set_gauge("total", s["compiles_total"])
        r.set_gauge("aot_loads_total", s["aot_loads_total"])
        if self.analysis == "full":
            # code bytes come only from memory_analysis: under
            # cost/off the signal is ABSENT, not a measured zero (the
            # accountant's absent-never-zero discipline)
            r.set_gauge("executable_bytes", s["executable_bytes"])
        r.set_gauge("storm_active", int(s["storm_active"]))
        r.set_gauge("storms_total", s["storms_total"])
        return r.render_prometheus("stpu_compile_")


def _backend_name() -> str | None:
    """The initialized jax backend's platform name — WITHOUT initializing
    one (the coordinator plane renders metrics in processes that may
    never touch a device; default_backend() there would pay full backend
    startup inside a scrape)."""
    import sys

    if "jax" not in sys.modules:
        return None
    try:
        xb = sys.modules.get("jax._src.xla_bridge")
        if xb is not None and getattr(xb, "_default_backend", None) is None:
            return None
        import jax

        return jax.default_backend()
    except Exception:
        return None


# ---- module-level seams ----

_active: CompileRecorder | None = None


def install(recorder: CompileRecorder) -> CompileRecorder:
    global _active
    _active = recorder
    return recorder


def uninstall() -> None:
    global _active
    _active = None


def active() -> CompileRecorder | None:
    return _active


class _Observed:
    """The :func:`observe` wrapper: calls route through the recorder
    when one is installed; every OTHER attribute (``lower``,
    ``_cache_size``, ...) proxies to the wrapped jitted callable, so
    callers that introspect the jit object keep working."""

    __slots__ = ("__wrapped__", "_name", "_kind", "_model", "_bucket_from")

    def __init__(self, fn, name, kind, model, bucket_from):
        self.__wrapped__ = fn
        self._name = name
        self._kind = kind
        self._model = model
        self._bucket_from = bucket_from

    def __call__(self, *args, **kw):
        rec = _active
        if rec is None:
            return self.__wrapped__(*args, **kw)
        return rec.observed_call(self.__wrapped__, self._name, args, kw,
                                 kind=self._kind, model=self._model,
                                 bucket_from=self._bucket_from)

    def __getattr__(self, item):
        return getattr(self.__wrapped__, item)


def observe(fn: Callable, name: str, *, kind: str | None = None,
            model: str | None = None,
            bucket_from: Callable | None = None) -> Callable:
    """Wrap a jitted callable so every call that COMPILES journals a
    ``compile`` event attributed to ``name``.  With no recorder
    installed the wrapper is one module-global ``is None`` check; the
    wrapped callable stays reachable as ``.__wrapped__`` and through
    transparent attribute proxying."""
    return _Observed(fn, name, kind, model, bucket_from)


@contextlib.contextmanager
def attribute(name: str, *, kind: str | None = None,
              model: str | None = None):
    """Attribution region for code that compiles WITHOUT an observable
    jitted callable (Pallas entry points, eager-mode first calls):
    compile events fired inside the region journal under ``name`` with
    whatever timing the listener heard (no signature/analysis — there is
    no ``.lower`` to ask)."""
    rec = _active
    if rec is None:
        yield
        return
    _ensure_listener()
    frame = rec._push()
    t0 = _perf()
    try:
        yield
    finally:
        wall = _perf() - t0
        rec._pop(frame)
        if frame[1]:
            try:
                section_kind, extra = _section(kind)
                rec.record(name=name, compile_s=frame[0], parts=frame[1],
                           wall_s=wall, model=model, kind=section_kind,
                           **_hits(frame), **extra)
            except Exception as e:
                log.warning("compile event for %s dropped (%s: %s)",
                            name, type(e).__name__, e)


def _hits(frame: list) -> dict:
    """``cache_hits`` event field: how many of the frame's ``parts``
    the persistent cache served — present only when some were."""
    return {"cache_hits": frame[2]} if frame[2] else {}


def _section(default: str | None) -> tuple[str | None, dict]:
    """The innermost :func:`kind_section`'s (kind, extra fields), or
    ``(default, {})`` when no section is open on this thread."""
    if _tls.kinds:
        return _tls.kinds[-1]
    return default, {}


@contextlib.contextmanager
def kind_section(kind: str, **fields):
    """Mark the dynamic extent where compile events journal with
    ``kind=`` (plus any extra fields — e.g. the AOT fallback's
    ``aot_error`` reason) instead of the seam's default.  Innermost
    section wins; kinds in :data:`ADMISSION_KINDS` are excluded from
    recompile-storm detection."""
    _tls.kinds.append((kind, fields))
    try:
        yield
    finally:
        _tls.kinds.pop()


def warm_section():
    """Mark the dynamic extent of deliberate pre-warming (the serve
    bucket ladder): compiles inside journal with ``kind="warm"`` and are
    EXCLUDED from recompile-storm detection — expected churn, and the
    cure for the storm the detector exists to catch."""
    return kind_section("warm")


def apply_persistent_cache(configured: str = "") -> str:
    """Place jax's persistent compilation cache and return its directory.

    One rule for every entry point (train, serve, export, score, data,
    the fleet worker): where ``JAX_COMPILATION_CACHE_DIR`` is set, that
    directory is used and code sets no other — ``configured``
    (``shifu.tpu.compile-cache-dir`` / ``--compile-cache-dir``) only
    fills the variable when it is unset; with neither, the cache lives
    at ``<checkout>/.jax_cache``.  The directory is part of jax's cache
    key, so it is never derived from a pid, the time or ``tempfile``: a
    cache that moves never hits.

    The cache is the middle tier of the AOT fallback ladder: a bucket
    that live-compiles (AOT mismatch, or no AOT shipped) writes its
    program here, so the NEXT worker/restart on this host deserializes
    instead of re-running XLA.  The min-compile-time floor drops to 0
    because serve-plane scorer programs compile in well under jax's 1s
    default — exactly the programs whose re-compilation scales as
    tenants x buckets.

    The settings land as environment variables, which jax reads at
    import and child processes (serve workers, subprocess fleets)
    inherit — so a process that has NOT imported jax yet (the serve
    supervisor, the coordinator) stays jax-free; one that has is
    updated through ``jax.config`` as well."""
    import sys

    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR") or configured
                 or os.path.join(_CHECKOUT, ".jax_cache"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    if "jax" in sys.modules:
        import jax
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        # the cache object initializes lazily at the FIRST compile and
        # then sticks: a process that compiled anything before this
        # call (an earlier model load, a probe) would silently keep the
        # old cache — reset so the directory takes effect regardless of
        # call order
        _cc.reset_cache()
    return cache_dir
