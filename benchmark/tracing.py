"""The program's own spans on the profiler's clock, from outside it.

``AnnotatingTracer`` is ``obs.trace.Tracer`` whose ``span`` / ``timed`` /
``wrap_iter`` also enter ``jax.profiler.TraceAnnotation(name)``.  The
harness installs it (``obs.trace.install``) before it builds the
trainer, which picks the active tracer up, so ``step.infeed.wait``,
``step.dispatch``, ``step.block`` ... land in the same ``.xplane.pb`` as
the device's ops and idle gaps can be named after them.  Only traced
runs install it: an untraced run is the program as users start it.
"""

from __future__ import annotations

import contextlib
import threading
import time

from shifu_tensorflow_tpu.obs.trace import Tracer

_perf = time.perf_counter


class AnnotatingTracer(Tracer):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.names: set[str] = set()
        # name -> [count, seconds] since the harness last cleared it: the
        # program drains the base class's sums every epoch (the ingest
        # autotuner, the epoch journal), so the readers get their own
        self.totals: dict[str, list] = {}
        self._totals_lock = threading.Lock()

    def add(self, name: str, seconds: float) -> None:
        super().add(name, seconds)
        with self._totals_lock:
            t = self.totals.setdefault(name, [0, 0.0])
            t[0] += 1
            t[1] += seconds

    def cumulative(self) -> dict[str, dict[str, float]]:
        with self._totals_lock:
            return {n: {"count": c, "total_s": s,
                        "mean_s": s / c if c else 0.0}
                    for n, (c, s) in self.totals.items()}

    @contextlib.contextmanager
    def span(self, name: str):
        self.names.add(name)
        t0 = _perf()
        try:
            with self._annotate(name):
                yield
        finally:
            self.add(name, _perf() - t0)

    def timed(self, name: str, fn):
        def wrapper(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)

        return wrapper

    def wrap_iter(self, name: str, it):
        self.names.add(name)
        it = iter(it)
        while True:
            t0 = _perf()
            ann = self._annotate(name)
            ann.__enter__()
            try:
                item = next(it)
            except StopIteration:
                return
            finally:
                ann.__exit__(None, None, None)
            self.add(name, _perf() - t0)
            yield item
