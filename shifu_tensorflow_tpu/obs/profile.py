"""On-demand ``jax.profiler`` capture windows, journaled.

``utils/profiling.trace_if`` could always wrap a whole run in a
profiler trace — but a *production* question ("why did p99 double five
minutes ago?") needs a capture you can start against a RUNNING fleet,
bounded in time, whose dump you can later find.  This module is that
promotion:

- :func:`request` (what ``obs profile --request`` calls) drops a small
  JSON trigger file beside the fleet's journal base —
  ``<journal>.profile-request`` — naming the dump directory and the
  window length.  Writing a file is the one RPC every plane already
  shares (they all own the journal directory), and it works from a
  jax-free operator CLI.
- :func:`poll` runs on the planes' existing slow ticks (the trainer's
  per-epoch obs hook, the serve SLO evaluator thread).  The first
  poller to see the trigger consumes it (one capture per request, by
  design — ``worker`` in the trigger pins a specific worker index) and
  runs ``jax.profiler.start_trace``/``stop_trace`` for the requested
  window on a background thread, journaling ``profile_capture`` events
  at start and completion with the dump path — the pointer ``obs
  profile`` renders from a dead fleet's files.

Off-by-default-cheap: an un-configured process never stats anything;
a configured one pays one ``os.path.exists`` per slow tick.
stdlib-only at import; jax loads inside the capture thread.

:func:`phases` (``obs profile --phases <dump dir>``) reduces a dump: the
train step's device time split by the program's own ``jax.named_scope``
names (``embed.gather.fwd``, ``embed.gather.bwd``, ``optimizer.update``
...), which survive a recompile that renumbers XLA's ``fusion.N``, and
the program's host spans (``obs/trace.py``) that the capture holds; and,
with :func:`boundaries`, what the device waited for between one epoch's
last step and the next one's first, by the host span open over the wait.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import threading
import time
from typing import Any

from shifu_tensorflow_tpu.utils import logs

log = logs.get("obs")

__all__ = ["configure", "unconfigure", "trigger_path", "request", "poll",
           "PHASE_SCOPES", "phase_of", "phases", "boundaries"]

_lock = threading.Lock()
_trigger: str | None = None     # trigger file this process polls
_worker: int | None = None      # this process's worker index
_plane: str = "train"
_capturing = False


def trigger_path(journal_base: str) -> str:
    """Where a capture request for the fleet journaled at ``base``
    lives (one well-known name: the CLI writes it, the planes poll)."""
    return f"{os.fspath(journal_base)}.profile-request"


def configure(journal_base: str | None, *, plane: str = "train",
              worker: int | None = None) -> None:
    """Arm polling for this process (install_obs calls this whenever a
    journal is configured — the journal base is the rendezvous)."""
    global _trigger, _worker, _plane
    with _lock:
        _trigger = trigger_path(journal_base) if journal_base else None
        _worker = worker
        _plane = plane


def unconfigure() -> None:
    configure(None)


def request(journal_base: str, out_dir: str, *, seconds: float = 5.0,
            worker: int | None = None) -> str:
    """Write the trigger (the ``obs profile --request`` body).  Returns
    the trigger path.  ``worker`` restricts which worker may consume it
    (None = first poller wins)."""
    path = trigger_path(journal_base)
    body: dict[str, Any] = {"dir": os.fspath(out_dir),
                            "seconds": float(seconds),
                            "requested_ts": round(time.time(), 3)}
    if worker is not None:
        body["worker"] = int(worker)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, path)  # atomic: a poller never reads a torn trigger
    return path


def poll() -> bool:
    """One slow-tick check; True when this call consumed a trigger and
    started a capture.  Never raises — a broken trigger file is removed
    and logged, not allowed to wedge the tick that polls it."""
    global _capturing
    trig = _trigger
    if trig is None or not os.path.exists(trig):
        return False
    with _lock:
        if _capturing:
            return False
        try:
            with open(trig) as f:
                body = json.load(f)
        except (OSError, ValueError) as e:
            log.warning("removing unreadable profile trigger %s (%s)",
                        trig, e)
            _remove(trig)
            return False
        want = body.get("worker")
        if want is not None and _worker is not None and int(want) != _worker:
            return False  # addressed to a sibling; leave it for them
        # consume by ATOMIC CLAIM, not unlink: sibling fleet processes
        # poll the same path on independent ticks, and a read-then-unlink
        # window would let two of them both start the capture.  rename is
        # atomic on POSIX — exactly one poller wins; the losers see
        # FileNotFoundError and walk away.
        claim = f"{trig}.claim.{os.getpid()}"
        try:
            os.rename(trig, claim)
        except OSError:
            return False  # a sibling claimed it first
        _remove(claim)
        out_dir = body.get("dir") or os.path.dirname(trig) or "."
        seconds = max(0.1, float(body.get("seconds", 5.0)))
        _capturing = True
    t = threading.Thread(target=_capture, args=(out_dir, seconds),
                         name="obs-profile-capture", daemon=True)
    t.start()
    return True


def _remove(path: str) -> None:
    try:
        os.unlink(path)
    except OSError:
        pass


def _capture(out_dir: str, seconds: float) -> None:
    """The capture window itself (background thread: the profiler traces
    the whole process, so the polling thread need not stall for it)."""
    from shifu_tensorflow_tpu.obs import journal as obs_journal

    global _capturing
    t0 = time.time()
    try:
        import jax

        os.makedirs(out_dir, exist_ok=True)
        obs_journal.emit("profile_capture", plane=_plane, worker=_worker,
                         status="started", dir=out_dir, seconds=seconds)
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        obs_journal.emit("profile_capture", plane=_plane, worker=_worker,
                         status="done", dir=out_dir,
                         wall_s=round(time.time() - t0, 3))
    except Exception as e:
        log.warning("profiler capture to %s failed (%s: %s)",
                    out_dir, type(e).__name__, e)
        obs_journal.emit("profile_capture", plane=_plane, worker=_worker,
                         status="failed", dir=out_dir,
                         error=f"{type(e).__name__}: {e}")
    finally:
        with _lock:
            _capturing = False


# ---- reading a dump: the step's phases ----

#: the ``jax.named_scope`` names the step is written with: the tabular
#: families' (models/embeddings.py, models/wide_deep.py, train/trainer.py,
#: train/sagn.py) and the decoder family's (models/hybrid_lm.py, whose
#: token embedding is ``embed.gather`` too); tests/test_phases.py holds
#: each family's lowered step to its names
TABULAR_SCOPES = ("embed.hash", "embed.gather", "wide.cross", "deep.mlp",
                  "loss", "optimizer.update")
HYBRID_LM_SCOPES = ("embed.gather", "ssm.proj", "ssm.conv", "ssm.scan",
                    "moe.route", "moe.experts", "moe.shared", "mlp.dense",
                    "attn.proj", "attn.rope", "attn.core", "attn.window",
                    "attn.latent", "attn.expand", "mtp.merge", "mtp.block",
                    "mtp.head", "conv.proj", "conv.mix", "attn.qknorm",
                    "lm.head", "loop.exit", "loop.norm", "optimizer.update")
PHASE_SCOPES = TABULAR_SCOPES + tuple(
    s for s in HYBRID_LM_SCOPES if s not in TABULAR_SCOPES)
#: the per-step program's module name (``jax.jit`` of ``train_step``);
#: for the scan or accumulate path pass ``jit_scan_epoch`` / ``jit_accum_step``
STEP_PROGRAM = "jit_train_step"
UNSCOPED = "(unscoped)"
COLLECTIVE = "collective"
_COLLECTIVE_OP = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast)")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_TRANSFORM = re.compile(r"^[a-z_]+\((.*)\)$")
#: the program's span names (``step.infeed.wait``, ``epoch.turn``,
#: ``rpc.heartbeat``) are lower-case dotted words; so are the XLA:CPU
#: backend's op events (``dot_general.19``), which unlike an annotation
#: carry stats (``hlo_op``)
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_?]+)+$")


def phase_of(op_path: str) -> str:
    """The phase an op belongs to, from the name stack XLA keeps as its
    ``op_name``: the first known scope on the path, ``.bwd`` where the
    path holds ``transpose(`` and ``.fwd`` otherwise.
    ``jit(train_step)/transpose(jvp(Model))/hashed_columns/embed.gather/
    jit(_take)/scatter-add`` is ``embed.gather.bwd``; ``optimizer.update``
    has no direction; a path with none of the scopes is ``(unscoped)``."""
    for part in op_path.split("/"):
        while (m := _TRANSFORM.match(part)):  # jvp(x), transpose(jvp(x))
            part = m.group(1)
        if part in PHASE_SCOPES:
            if part == "optimizer.update":
                return part
            return f"{part}.{'bwd' if 'transpose(' in op_path else 'fwd'}"
    return UNSCOPED


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _wire_fields(buf):
    """(field number, wire type, value) of one protobuf message: just
    enough of the wire format to walk to the HLO module a capture embeds
    (``jax.profiler.ProfileData`` shows events and their own stats, not
    the metadata planes)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire in (1, 2, 5):
            size = 8 if wire == 1 else 4
            if wire == 2:
                size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield field, wire, value


def _walk(buf, path: tuple[int, ...]):
    """Every sub-message reached from ``buf`` by following the
    length-delimited fields numbered ``path``, one level each."""
    if not path:
        yield buf
        return
    for field, wire, value in _wire_fields(buf):
        if field == path[0] and wire == 2:
            yield from _walk(value, path[1:])


def _text(buf, field: int) -> str:
    """The first value of a string field of one message, ``""`` if unset."""
    return next((bytes(v).decode() for v in _walk(buf, (field,))), "")


def hlo_op_names(xspace: bytes, step: str) -> dict[str, str]:
    """``{instruction: op_name}`` of the compiled ``step`` program(s), from
    the ``HloProto`` the profiler embeds in the capture's
    ``/host:metadata`` plane (stat ``Hlo Proto`` of the program's event
    metadata).  On the TPU's op line an event carries no scope, but the
    module's instructions do: the train step's Adam fusion reads
    ``jit(train_step)/optimizer.update/jit(_where)/select_n`` (the guard
    is a select fused into the update, train/trainer.py apply_if_rows).
    Field numbers: tsl ``xplane.proto`` (XSpace.planes 1; XPlane.name 2,
    .event_metadata 4; map value 2; XEventMetadata.name 2, .stats 5;
    XStat.bytes_value 6) and xla ``hlo.proto`` (HloProto.hlo_module 1;
    .computations 3; .instructions 2; .name 1, .metadata 7; .op_name 2)."""
    out: dict[str, str] = {}
    for plane in _walk(memoryview(xspace), (1,)):
        if _text(plane, 2) != "/host:metadata":
            continue
        for meta in _walk(plane, (4, 2)):
            if step not in _text(meta, 2):
                continue
            for instruction in _walk(meta, (5, 6, 1, 3, 2)):
                op_name = next((_text(m, 2)
                                for m in _walk(instruction, (7,))), "")
                if op_name:
                    out[_text(instruction, 1)] = op_name
    return out


def find_xplane(dump_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a profiler dump directory."""
    found = glob.glob(os.path.join(glob.escape(dump_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load_capture(path: str, step: str) -> dict:
    """``{"devices": {ordinal: {"steps": [[start_ns, dur_ns], ...], "ops":
    [[name, op_name, start_ns, dur_ns], ...]}}, "host": [[span, start_ns,
    dur_ns], ...]}``: per TPU plane the executions of the ``step`` program
    (``XLA Modules`` line) and every op (``XLA Ops`` line; its event is
    named by the instruction's HLO text, ``%fusion.6 = f32[...] ...``)
    with the name stack the embedded module gives it; of the host planes
    the program's own spans."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(raw, step)
    devices: dict[int, dict[str, list]] = {}
    host: list[list] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"steps": [], "ops": []})
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["steps"].extend(
                        [int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events if step in ev.name)
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name.split(" ", 1)[0].lstrip("%")
                        dev["ops"].append(
                            [name, op_names.get(name, ""),
                             int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if _SPAN_NAME.match(ev.name)
                            and not any(True for _ in ev.stats))
    return {"devices": devices, "host": host}


def phases(dump_dir: str, step: str = STEP_PROGRAM) -> dict:
    """The newest capture under ``dump_dir``, reduced
    (:func:`reduce_phases`); ``{}`` when there is no capture or the step
    program did not run on a TPU in it."""
    path = find_xplane(dump_dir)
    if path is None:
        return {}
    capture = load_capture(path, step)
    out = reduce_phases(capture)
    if out:
        out["step"], out["xplane"] = step, path
        out["boundaries"] = boundaries(capture)
    return out


def _medians_ms(per_device: list[list[dict[str, int]]]) -> dict[str, float]:
    """``{key: ms}``, largest first: the median over devices of the
    median over one device's steps (``{key: ns}`` a step; a key a step
    lacks counts 0 there)."""
    keys = {k for steps in per_device for split in steps for k in split}
    out = {k: statistics.median(
        statistics.median(split.get(k, 0) for split in steps) / 1e6
        for steps in per_device) for k in keys}
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _split_step(ops: list) -> tuple[dict[str, int], dict[str, int]]:
    """One step's device time shared out among phases, each instant once:
    ``({phase: ns}, {unscoped op: ns})``.  The train step's ops each
    carry their own ``op_name`` and do not nest.  Where a program does
    hold a ``conditional`` or a ``while``, it and the ops of its body are
    both on the op line and overlap, so an instant goes to the innermost
    op open at it that has a phase; an op without one (a copy XLA put
    inside the body) inherits the phase of the op around it, and is
    ``(unscoped)`` only where nothing around it has one.  ``ops``:
    ``[name, phase, start_ns, dur_ns]``."""
    edges = []
    for i, (_, _, start, dur) in enumerate(ops):
        if dur > 0:
            edges.append((start, 1, i))
            edges.append((start + dur, 0, i))
    edges.sort()
    by_phase: dict[str, int] = {}
    loose: dict[str, int] = {}
    open_: set[int] = set()
    prev = 0
    for t, opening, i in edges:
        if open_ and t > prev:
            inner_first = sorted(open_, key=lambda k: ops[k][3])
            owner = next((k for k in inner_first if ops[k][1] != UNSCOPED),
                         None)
            if owner is None:
                name = ops[inner_first[0]][0]
                loose[name] = loose.get(name, 0) + t - prev
                phase = UNSCOPED
            else:
                phase = ops[owner][1]
            by_phase[phase] = by_phase.get(phase, 0) + t - prev
        (open_.add if opening else open_.discard)(i)
        prev = t
    return by_phase, loose


def reduce_phases(capture: dict) -> dict:
    """The arithmetic of :func:`phases` on what :func:`load_capture` read
    (plain lists, so a recorded capture kept as JSON reduces the same).
    Per device and per execution of the step program: :func:`_split_step`
    over the ops inside it.  Reported in ms: the median over steps, then
    over devices, of each phase and of the step; ``{}`` when no device
    ran the step program."""
    durations, by_phase, loose = [], [], []
    for dev in sorted(capture["devices"], key=int):
        d = capture["devices"][dev]
        if not d["steps"]:
            continue
        ops = sorted(d["ops"], key=lambda o: o[2])
        starts = [o[2] for o in ops]
        splits = []
        for start, dur in d["steps"]:
            lo = bisect.bisect_left(starts, start)
            hi = bisect.bisect_right(starts, start + dur)
            splits.append(_split_step(
                [[name, COLLECTIVE if _COLLECTIVE_OP.match(name)
                  else phase_of(scope), s, n]
                 for name, scope, s, n in ops[lo:hi] if s + n <= start + dur]))
        durations.append([{"step": dur} for _, dur in d["steps"]])
        by_phase.append([split[0] for split in splits])
        loose.append([split[1] for split in splits])
    if not durations:
        return {}
    host: dict[str, dict[str, float]] = {}
    for name, _, dur in capture["host"]:
        h = host.setdefault(name, {"count": 0, "total_s": 0.0})
        h["count"] += 1
        h["total_s"] += dur / 1e9
    return {
        "step_ms": _medians_ms(durations)["step"],
        "steps": min(len(steps) for steps in durations),
        "devices": len(durations),
        "phases_ms": _medians_ms(by_phase),
        "unscoped_ops_ms": dict(
            [(n, ms) for n, ms in _medians_ms(loose).items() if ms > 0][:12]),
        "host_spans": dict(sorted(host.items())),
    }


# ---- reading a dump: between epochs ----

#: the value fetch an epoch ends with (train/trainer.py): it ends after the
#: device's last step of the epoch and before the next epoch's first (its
#: START does not: the host runs ahead of the device, by up to the epoch)
BLOCK_SPAN = "step.block"
NO_SPAN = "(no span)"
#: the put thread's spans whose first event of an epoch (the stream's first
#: batch, its placement) is on the boundary's path and the others are not
FIRST_OF_EPOCH = ("step.host.produce", "step.infeed.put")


def _merged(intervals) -> list[tuple[int, int]]:
    """Sorted disjoint ``(start, end)`` covering the same instants."""
    out: list[list[int]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(start, end) for start, end in out]


def _inside(lo: int, hi: int, pieces: list, ends: list) -> list:
    """The parts of the sorted disjoint ``pieces`` (``(start, end, ...)``;
    ``ends`` their ends) that lie in ``[lo, hi)``, cut to it."""
    out = []
    k = bisect.bisect_right(ends, lo)
    while k < len(pieces) and pieces[k][0] < hi:
        piece = pieces[k]
        out.append((max(piece[0], lo), min(piece[1], hi), *piece[2:]))
        k += 1
    return out


def _idle(lo: int, hi: int, busy: list, busy_ends: list) -> list:
    """``[lo, hi)`` less the device's busy intervals."""
    out, cur = [], lo
    for start, end in _inside(lo, hi, busy, busy_ends):
        if start > cur:
            out.append((cur, start))
        cur = end
    if cur < hi:
        out.append((cur, hi))
    return out


def _shortest_open(host: list) -> list[tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` pieces of the host spans' union,
    each named after the shortest span open during it.  The spans of one
    thread nest (they are context managers), so on a thread the shortest
    is the innermost: ``epoch.drain`` keeps what ``step.block`` leaves,
    ``epoch.fill`` what the first ``step.infeed.wait`` leaves.  Across
    threads it names the narrower wait: the put thread's
    ``step.host.produce`` and ``step.infeed.put`` inside the consumer's
    wait for them."""
    edges = []
    for i, (_, start, dur) in enumerate(host):
        edges.append((start, 1, i))
        edges.append((start + dur, 0, i))
    edges.sort()
    out: list[tuple[int, int, str]] = []
    open_: set[int] = set()
    prev = 0
    for t, opening, i in edges:
        if open_ and t > prev:
            name = host[min(open_, key=lambda k: host[k][2])][0]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], t, name)
            else:
                out.append((prev, t, name))
        (open_.add if opening else open_.discard)(i)
        prev = t
    return out


def _spread(values: list) -> dict[str, float]:
    """Median and largest of nanoseconds, in ms."""
    return {"median": statistics.median(values) / 1e6,
            "max": max(values) / 1e6}


def _device_boundaries(dev: dict, block_ends: list, segments: list,
                       window: tuple[int, int]) -> dict | None:
    """One device's boundaries (``{"gap", "idle", "split", "block_end",
    "until"}`` each, in ns), the window's two edges and the idle left
    inside and between the steps; ``None`` where the step program ran less
    than twice."""
    steps = sorted(dev["steps"])
    if len(steps) < 2:
        return None
    busy = _merged((start, start + dur) for _, _, start, dur in dev["ops"]
                   if dur > 0)
    busy_ends = [end for _, end in busy]
    seg_ends = [end for _, end, _ in segments]

    def idle_ns(lo, hi):
        return sum(e - s for s, e in _idle(lo, hi, busy, busy_ends))

    found = []
    for (start, dur), (until, _) in zip(steps, steps[1:]):
        lo = start + dur
        k = bisect.bisect_left(block_ends, lo)
        if k == len(block_ends) or block_ends[k] > until:
            continue
        split: dict[str, int] = {}
        idle = 0
        for s, e in _idle(lo, until, busy, busy_ends):
            idle += e - s
            named = 0
            for a, b, name in _inside(s, e, segments, seg_ends):
                split[name] = split.get(name, 0) + b - a
                named += b - a
            if e - s > named:
                split[NO_SPAN] = split.get(NO_SPAN, 0) + e - s - named
        found.append({"gap": until - lo, "idle": idle, "split": split,
                      "block_end": block_ends[k], "until": until})
    first, last = steps[0][0], steps[-1][0] + steps[-1][1]
    lo, hi = min(window[0], first), max(window[1], last)
    return {
        "found": found,
        "open": {"gap": first - lo, "idle": idle_ns(lo, first)},
        "close": {"gap": hi - last, "idle": idle_ns(last, hi)},
        "in_steps": idle_ns(first, last) - sum(b["idle"] for b in found),
    }


def boundaries(capture: dict) -> dict:
    """What the device waited for between epochs, from the same plain
    lists :func:`reduce_phases` reads.  An epoch boundary is the gap
    between two consecutive executions of the step program on a device
    that holds the END of a ``step.block`` span.  For each: the gap, the
    device's idle time inside it (the gap less the union of the op line,
    as the benchmark's ``device_idle_pct`` counts it) and that idle shared
    out by the host span open over it (:func:`_shortest_open`; what no
    span covers is ``(no span)``).  Reported in ms: the median and the
    largest over a device's boundaries, then the median over devices;
    ``each`` lists the lowest device's boundaries one by one.  Beside
    them the epoch's FIRST ``step.host.produce`` and ``step.infeed.put``
    (the first to start after the boundary's ``step.block``) against the
    median of the others: a starved first batch shows there.  The window's
    own two edges (its start to the first step, the last step to its end)
    and the idle inside and between steps are kept apart, so the idle of
    the window is ``idle_ms.sum`` + both edges + ``in_steps_idle_ms``.
    The window is a host span that holds every step (the benchmark's
    ``bench.window``), which then names no idle; without one, the first
    event's start to the last one's end.  ``{}`` for a capture with no
    boundary."""
    steps = [(start, start + dur) for d in capture["devices"].values()
             for start, dur in d["steps"]]
    if not steps:
        return {}
    first, last = min(s for s, _ in steps), max(e for _, e in steps)
    def holds_the_steps(h) -> bool:
        return h[1] <= first and h[1] + h[2] >= last

    around = [h for h in capture["host"] if holds_the_steps(h)]
    host = [h for h in capture["host"]
            if h[2] > 0 and not holds_the_steps(h)]
    window = ((max(h[1] for h in around),
               min(h[1] + h[2] for h in around)) if around else
              (min([first] + [h[1] for h in host]),
               max([last] + [h[1] + h[2] for h in host])))
    block_ends = sorted(h[1] + h[2] for h in host if h[0] == BLOCK_SPAN)
    segments = _shortest_open(host)
    per_device = [b for b in (
        _device_boundaries(capture["devices"][dev], block_ends, segments,
                           window)
        for dev in sorted(capture["devices"], key=int)) if b and b["found"]]
    if not per_device:
        return {}

    def over_devices(read) -> dict[str, float]:
        """``{"median", "max"}``: over a device's boundaries, then the
        median over devices."""
        spreads = [_spread([read(b) for b in d["found"]])
                   for d in per_device]
        return {k: statistics.median(s[k] for s in spreads)
                for k in ("median", "max")}

    def device_median_ms(read) -> float:
        return statistics.median(read(d) for d in per_device) / 1e6

    names = {n for d in per_device for b in d["found"] for n in b["split"]}
    split = {n: over_devices(lambda b, n=n: b["split"].get(n, 0))
             for n in names}
    lowest = per_device[0]["found"]
    out = {
        "boundaries": min(len(d["found"]) for d in per_device),
        "devices": len(per_device),
        "gap_ms": over_devices(lambda b: b["gap"]),
        "idle_ms": dict(
            over_devices(lambda b: b["idle"]),
            sum=device_median_ms(
                lambda d: sum(b["idle"] for b in d["found"]))),
        "idle_split_ms": dict(sorted(
            split.items(), key=lambda kv: -kv[1]["median"])),
        "each": [{"gap_ms": b["gap"] / 1e6, "idle_ms": b["idle"] / 1e6,
                  "split_ms": {n: ns / 1e6 for n, ns in sorted(
                      b["split"].items(), key=lambda kv: -kv[1])}}
                 for b in lowest],
        "edges_ms": {
            edge: {k: device_median_ms(lambda d, k=k: d[edge][k])
                   for k in ("gap", "idle")} for edge in ("open", "close")},
        "in_steps_idle_ms": device_median_ms(lambda d: d["in_steps"]),
        "first_ms": {}, "steady_ms": {},
    }
    for name in FIRST_OF_EPOCH:
        events = sorted((h[1], h[2]) for h in host if h[0] == name)
        starts = [start for start, _ in events]
        firsts = {0} if events and events[0][0] < first else set()
        of_epochs = []
        for b in lowest:
            k = bisect.bisect_left(starts, b["block_end"])
            if k < len(events) and starts[k] < b["until"]:
                firsts.add(k)
                of_epochs.append(events[k][1])
        others = [dur for k, (_, dur) in enumerate(events)
                  if k not in firsts]
        if of_epochs:
            out["first_ms"][name] = _spread(of_epochs)
        if others:
            out["steady_ms"][name] = statistics.median(others) / 1e6
    return out
