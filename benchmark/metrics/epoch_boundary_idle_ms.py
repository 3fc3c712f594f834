"""``epoch_boundary_idle_ms`` — layer: trainer train/trainer.py.  Unit
``ms``, source ``device_trace``; should move ``train_rows_per_s``.

The median over the window's epoch boundaries of the device's idle time
in one: the gap between two consecutive step programs that holds the end
of a ``step.block`` span, less the op line's union inside it.  The term
``step_device_ms`` leaves out of the rate: rows an epoch / (steps x
``step_device_ms`` + this) is ``train_rows_per_s``.  The reduction is the
program's, ``obs.profile.boundaries``; here only the trace's lists are
put in the shape it reads.  ``None`` for a program without the reduction
and for a window with no boundary (one epoch, no ``step.block`` event, no
device plane).
"""

LAYER = "trainer train/trainer.py"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_rows_per_s"

from benchmark import xplane


def capture_of(r) -> dict:
    """The reading's trace as ``obs.profile.load_capture`` would have
    given it: per device the step program's executions and the op line
    (no scope: the boundary needs none), and the host spans."""
    trace, window = r["trace"], r["window_ns"]
    return {"devices": {
        dev: {"steps": [[start, dur] for _, start, dur in
                        xplane.module_events(trace, dev, r["step_pattern"],
                                             window)],
              "ops": [[name, "", start, dur] for name, start, dur in
                      xplane.clip(lines.get(xplane.OP_LINE, []), window)]}
        for dev, lines in trace["devices"].items()}, "host": trace["host"]}


def read(r):
    from shifu_tensorflow_tpu.obs import profile

    reduce = getattr(profile, "boundaries", None)
    if reduce is None or not r["step_pattern"]:
        return None
    found = reduce(capture_of(r))
    return found["idle_ms"]["median"] if found else None
