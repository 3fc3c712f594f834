"""Lightweight span timing for the hot paths.

tf.data (arxiv 2101.12127) showed input-pipeline stall time is the
dominant *invisible* training bottleneck; TF-Replicator (arxiv
1902.00465) showed per-replica timing through a common instrumentation
layer is what makes distributed-SGD regressions diagnosable.  This
module is that layer for the per-step loop.  The spans the trainer
opens (train/trainer.py, data/dataset.py), and the thread each runs on:

- ``step.host``         — producing the next host batch (parse / stack /
  filter) where the path forbids the put thread: consumer thread.  Under
  the pipelined infeed it is ``step.host.produce``, on the put thread,
  overlapping dispatch;
- ``step.infeed``       — device placement (host-side gather/pad +
  transfer) inline: consumer thread.  Pipelined it splits into
  ``step.infeed.put`` (the placement, put thread) and
  ``step.infeed.wait`` (the consumer's wait for the next placed batch);
- ``step.dispatch``     — enqueueing the jitted step: consumer thread;
- ``step.block``        — fetching results (the only true completion wait
  on this backend — see utils/profiling.true_sync): consumer thread, once
  an epoch (once a step on the host-embedding path);
- ``epoch.fill``        — from the moment the epoch loop starts building
  its feed (the put thread's start, the stream's first batch, its
  placement) to the first unit in the consumer's hands: consumer thread;
  it holds the epoch's first ``step.infeed.wait`` (inline: the first
  ``step.host`` + ``step.infeed``);
- ``epoch.drain``       — from the exit of the loop over the feed through
  the feed's close (the join of the put thread) and ``step.block``, which
  it holds, to the epoch's mean: consumer thread;
- ``epoch.turn``        — what the epoch loop does outside ``train_epoch``
  (journal, autotuner, the stream's rebuild, callbacks, checkpoint):
  consumer thread, several an epoch.

The four disjoint ``step.`` phases (host, infeed, dispatch, block) are the
epoch's wall-clock budget (:func:`budget_fields`); the three ``epoch.``
spans between them cover the consumer thread from one epoch's last
dispatch to the next one's first, and ``obs profile --phases`` shares the
device's wait there out among all of them (obs/profile.py ``boundaries``).
There are also named spans around checkpoint save/restore
(train/checkpoint.py), retry backoff sleeps (utils/retry.py), and coordinator RPCs
(coordinator/coordinator.py).  Spans carry the worker index so SPMD
replicas can be compared side by side.

Cost discipline: a disabled site is ONE module-global load + ``is None``
check; an enabled site is two ``perf_counter`` calls and a dict update
under a lock (~1µs).  The trainer's per-step phases run on two threads
at most (consumer and put thread), so contention is slight; the lock is
for them and for the cross-thread spans (retry sleeps on a checkpoint
writer thread, RPC heartbeats).
``sample_every=N`` measures every Nth event per span name — steady-state
ratios stay unbiased while the (already tiny) cost divides by N.

Every measured event is also a ``jax.profiler.TraceAnnotation``, so a
profiler capture (``obs profile --request``, ``--profile-dir``) holds the
program's spans on the same clock as the device's ops.  This module never
imports JAX: the annotation class is taken from ``sys.modules`` once the
process has imported JAX itself, and a process that never does (the
coordinator, the ``obs`` CLI, load clients) has no profiler to annotate
for and records sums only.  With no capture open an annotation costs a
fraction of the span's own microsecond.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Any, Callable, Iterable, Iterator

__all__ = [
    "Tracer",
    "install",
    "uninstall",
    "active",
    "span",
    "record",
]

_perf = time.perf_counter
_NULL_CM = contextlib.nullcontext()


class Tracer:
    """Accumulating span sink: ``add(name, seconds)`` and sugar around it."""

    def __init__(self, worker_index: int = 0, sample_every: int = 1):
        self.worker_index = int(worker_index)
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        # name -> [count, total_s, max_s]; counts are MEASURED events
        # (under sampling, 1/sample_every of the real events)
        self._spans: dict[str, list] = {}
        # per-name call counter driving the sampling decision
        self._calls: dict[str, int] = {}
        # jax.profiler.TraceAnnotation, once this process has imported jax
        self._trace_annotation = None

    # ---- recording ----
    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            s = self._spans.get(name)
            if s is None:
                self._spans[name] = [1, seconds, seconds]
            else:
                s[0] += 1
                s[1] += seconds
                if seconds > s[2]:
                    s[2] = seconds

    def _sampled(self, name: str) -> bool:
        # sampling exists to cut HOT-PATH cost, so it applies only to the
        # per-step phases; auxiliary spans (checkpoint.save, rpc.*, ...)
        # fire a handful of times per epoch and are always measured —
        # scaling them back up in budget_fields would overestimate the
        # rare events sampling never needed to skip
        if self.sample_every == 1 or not name.startswith("step."):
            return True
        with self._lock:
            n = self._calls.get(name, 0)
            self._calls[name] = n + 1
        return n % self.sample_every == 0

    def _annotated(self, name: str):
        """The profiler annotation for one measured event; a shared
        nullcontext while this process has not imported JAX."""
        ann = self._trace_annotation
        if ann is None:
            profiler = getattr(sys.modules.get("jax"), "profiler", None)
            ann = getattr(profiler, "TraceAnnotation", None)
            if ann is None:
                return _NULL_CM
            self._trace_annotation = ann
        return ann(name)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        if not self._sampled(name):
            yield
            return
        t0 = _perf()
        try:
            with self._annotated(name):
                yield
        finally:
            self.add(name, _perf() - t0)

    def timed(self, name: str, fn: Callable) -> Callable:
        """Wrap ``fn`` so each (sampled) call records a span."""

        def wrapper(*a, **kw):
            if not self._sampled(name):
                return fn(*a, **kw)
            t0 = _perf()
            try:
                with self._annotated(name):
                    return fn(*a, **kw)
            finally:
                self.add(name, _perf() - t0)

        return wrapper

    def wrap_iter(self, name: str, it: Iterable) -> Iterator:
        """Time each ``next()`` of ``it`` — how long producing the next
        item stalls the consumer."""
        it = iter(it)
        while True:
            if self._sampled(name):
                t0 = _perf()
                try:
                    with self._annotated(name):
                        item = next(it)
                except StopIteration:
                    return
                self.add(name, _perf() - t0)
            else:
                try:
                    item = next(it)
                except StopIteration:
                    return
            yield item

    # ---- reading ----
    def summary(self) -> dict[str, dict[str, float]]:
        """``name -> {count, total_s, mean_s, max_s}`` snapshot.  Under
        sampling, ``count``/``total_s`` cover the measured subset; the
        ``sampled_every`` field says by how much to scale absolute
        totals (ratios need no scaling)."""
        with self._lock:
            return {
                name: {
                    "count": s[0],
                    "total_s": s[1],
                    "mean_s": s[1] / s[0] if s[0] else 0.0,
                    "max_s": s[2],
                    "sampled_every": self.sample_every,
                }
                for name, s in self._spans.items()
            }

    def take_summary(self) -> dict[str, dict[str, float]]:
        """summary() + reset() under one lock acquisition — the per-epoch
        journal report uses this so no span can fall between the read
        and the clear."""
        with self._lock:
            spans, self._spans = self._spans, {}
            self._calls.clear()
        return {
            name: {
                "count": s[0],
                "total_s": s[1],
                "mean_s": s[1] / s[0] if s[0] else 0.0,
                "max_s": s[2],
                "sampled_every": self.sample_every,
            }
            for name, s in spans.items()
        }

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._calls.clear()


# ---- process-global hook (the instrumented seams call these) ----

_active: Tracer | None = None


def install(tracer: Tracer) -> Tracer:
    global _active
    _active = tracer
    return tracer


def uninstall() -> None:
    global _active
    _active = None


def active() -> Tracer | None:
    return _active


def span(name: str):
    """``with obs_trace.span("checkpoint.save"): ...`` — no-op (a shared
    nullcontext, no allocation) when no tracer is installed."""
    t = _active
    return t.span(name) if t is not None else _NULL_CM


def maybe_span(tracer: Tracer | None, name: str):
    """Span on an explicit (possibly-None) tracer — the trainer's epoch
    paths hold their tracer in a local, so the hot loop pays one local
    load instead of a module-global read per phase."""
    return tracer.span(name) if tracer is not None else _NULL_CM


def record(name: str, seconds: float) -> None:
    """Record an already-measured duration (e.g. a retry backoff sleep
    whose length is known before it happens)."""
    t = _active
    if t is not None:
        t.add(name, seconds)


def budget_fields(summary: dict[str, dict[str, float]]) -> dict[str, Any]:
    """Flatten a tracer summary into the journal's ``step_breakdown``
    event schema: the four step phases as ``*_s`` totals + ``steps``
    (dispatch count), everything else under ``"spans"``.

    Under ``sample_every=N`` the step phases measured 1/N of the real
    events, so their totals and the step count scale back up by N here —
    the journal records unbiased ESTIMATES of the epoch's absolute
    phase times, which the CLI budget divides by the (unsampled) epoch
    wall clock.  Auxiliary spans are never sampled (see ``_sampled``)
    and pass through raw.  ``trace_sample`` is recorded whenever N>1 so
    a reader can tell an estimate from an exact total."""
    phases = {
        "infeed_s": "step.infeed",
        "host_s": "step.host",
        "dispatch_s": "step.dispatch",
        "block_s": "step.block",
    }
    out: dict[str, Any] = {}
    scale = 1
    for field_name, span_name in phases.items():
        s = summary.get(span_name)
        if s:
            scale = max(scale, int(s.get("sampled_every", 1)))
        out[field_name] = (
            round(s["total_s"] * s.get("sampled_every", 1), 6) if s else 0.0
        )
    # pipelined infeed splits the phase: "step.infeed.wait" is the
    # consumer-visible stall (counts toward the budget's infeed slice,
    # additive with any unthreaded step.infeed from other paths) while
    # "step.infeed.put" is put-thread placement work OVERLAPPING dispatch
    # (reported separately, never added to the phase total — the budget
    # divides by wall clock, and overlapped work would double-count).
    # `obs summary` renders the split so "starved" (wait-heavy) and
    # "placement-slow" (put-heavy) are distinguishable.
    w = summary.get("step.infeed.wait")
    if w:
        wait = round(w["total_s"] * w.get("sampled_every", 1), 6)
        out["infeed_wait_s"] = wait
        out["infeed_s"] = round(out["infeed_s"] + wait, 6)
        scale = max(scale, int(w.get("sampled_every", 1)))
    p = summary.get("step.infeed.put")
    if p:
        out["infeed_put_s"] = round(
            p["total_s"] * p.get("sampled_every", 1), 6)
        scale = max(scale, int(p.get("sampled_every", 1)))
    # "step.host.produce" is host-batch production that ran ON the put
    # thread (pipelined infeed) — overlapped with dispatch, so, exactly
    # like infeed_put_s, it reports separately and never joins the
    # disjoint wall-clock phases (host_s stays the consumer-visible
    # stall, which is 0 on that path by construction)
    hp = summary.get("step.host.produce")
    if hp:
        out["host_produce_s"] = round(
            hp["total_s"] * hp.get("sampled_every", 1), 6)
        scale = max(scale, int(hp.get("sampled_every", 1)))
    d = summary.get("step.dispatch")
    out["steps"] = int(d["count"] * d.get("sampled_every", 1)) if d else 0
    if scale > 1:
        out["trace_sample"] = scale
    extra = {
        name: {"count": int(s["count"]), "total_s": round(s["total_s"], 6)}
        for name, s in summary.items()
        if not name.startswith("step.")
    }
    if extra:
        out["spans"] = extra
    return out
