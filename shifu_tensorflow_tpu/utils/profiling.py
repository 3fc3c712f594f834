"""Tracing and per-step timing.

The reference has no profiler integration at all — its only instrumentation
is wall-clock deltas around the epoch loop shipped through the metrics plane
(reference: ssgd_monitor.py:270-277; SURVEY.md §5.1 names this a gap to fill
idiomatically).  This module fills it the TPU way:

- ``trace_if(dir)`` wraps a region in ``jax.profiler.trace`` so the run
  produces a TensorBoard/XPlane trace (op-level timeline, HBM usage) when a
  directory is given, and costs nothing when not;
- host-side regions reach the trace timeline as the obs tracer's spans
  (``obs/trace.py``: every measured span is a ``TraceAnnotation``);
- ``StepTimer`` measures steady-state step time without serializing the
  pipeline: host dispatch time is accumulated every step, and the device is
  synced only every ``sync_every`` steps, so the measured rate amortizes the
  sync instead of turning the async dispatch queue into lock-step.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Iterator


@contextlib.contextmanager
def trace_if(trace_dir: str | None) -> Iterator[None]:
    """``jax.profiler.trace`` when a directory is given; no-op otherwise.

    When the obs journal is installed, the capture is journaled as
    ``profile_capture`` events (start + done, with the dump dir) — the
    same pointer contract as the on-demand window (obs/profile.py), so
    ``obs profile --journal ...`` lists planned-in-advance captures and
    requested ones alike."""
    if not trace_dir:
        yield
        return
    import time as _time

    import jax

    from shifu_tensorflow_tpu.obs import journal as obs_journal

    t0 = _time.time()
    obs_journal.emit("profile_capture", status="started", dir=trace_dir)
    ok = False
    try:
        with jax.profiler.trace(trace_dir):
            yield
            ok = True
    finally:
        obs_journal.emit("profile_capture",
                         status="done" if ok else "failed", dir=trace_dir,
                         wall_s=round(_time.time() - t0, 3))


def true_sync(x: Any) -> None:
    """Force REAL completion of ``x``'s computation — not just enqueue.

    A device→host value fetch cannot lie on any backend: the scalar's
    bytes exist only after everything it depends on has executed.
    Whether ``jax.block_until_ready`` closes a timing loop as well on
    the attached chip is what ``chip_smoke.py``'s ``sync`` line times
    (the same 20-matmul chain closed both ways); until a PR decides
    from that, every timing loop in the tree syncs here.  This fetches ONE
    element of EVERY array leaf (each leaf of a pytree is an independent
    device buffer — e.g. ``device_put`` of a batch dict issues one
    transfer per leaf, so probing only one leaf would leave the others'
    completion unproven), batched into a single ``device_get`` call.
    Amortize the round trip by syncing every N steps, and make sure the
    fetched values depend on the whole computation being timed (a loss
    carried through the step chain does; an output that XLA can slice
    out early may not).
    """
    import jax
    import numpy as np

    # size-0 leaves (e.g. an empty final batch slice) have no element to
    # probe — and nothing to wait for: a zero-byte buffer's "completion"
    # is vacuous, so skipping it cannot unprove the sync
    leaves = [l for l in jax.tree_util.tree_leaves(x)
              if hasattr(l, "dtype") and getattr(l, "size", 1) != 0]
    if not leaves:
        return
    probes = [l.reshape(-1)[0] if getattr(l, "ndim", 0) else l
              for l in leaves]
    for p in jax.device_get(probes):
        np.asarray(p)


@dataclass
class StepTimer:
    """Amortized step-rate measurement.

    Usage::

        timer = StepTimer(sync_every=50)
        for batch in batches:
            state, loss = step(state, batch)
            timer.step(loss, rows=batch["x"].shape[0])
        print(timer.summary())

    ``step`` passes the step's output so the periodic sync has something to
    block on; between syncs only host wall-clock is read.
    """

    sync_every: int = 50
    n_steps: int = 0
    n_rows: int = 0
    _t0: float | None = None
    _elapsed: float = 0.0
    _pending: Any = field(default=None, repr=False)

    def step(self, device_out: Any = None, rows: int = 0) -> None:
        if self._t0 is None:
            self._t0 = time.perf_counter()
        self.n_steps += 1
        self.n_rows += rows
        self._pending = device_out
        if self.sync_every and self.n_steps % self.sync_every == 0:
            self._sync()

    def _sync(self) -> None:
        if self._pending is not None:
            # a sync that only acknowledged enqueue would make this
            # timer report dispatch rate (see true_sync)
            true_sync(self._pending)
            self._pending = None
        if self._t0 is not None:
            self._elapsed = time.perf_counter() - self._t0

    def elapsed_s(self) -> float:
        self._sync()
        return self._elapsed

    def summary(self) -> dict[str, float]:
        elapsed = self.elapsed_s()
        per_step = elapsed / self.n_steps if self.n_steps else 0.0
        return {
            "steps": float(self.n_steps),
            "elapsed_s": elapsed,
            "step_time_s": per_step,
            "steps_per_sec": (self.n_steps / elapsed) if elapsed else 0.0,
            "rows_per_sec": (self.n_rows / elapsed) if elapsed else 0.0,
        }

    def reset(self) -> None:
        self.n_steps = 0
        self.n_rows = 0
        self._t0 = None
        self._elapsed = 0.0
        self._pending = None
