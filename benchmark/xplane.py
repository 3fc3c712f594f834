"""From a profiler trace (``.xplane.pb``) to numbers: the yardstick.

``load`` reads the trace with ``jax.profiler.ProfileData`` (nothing but
JAX) into plain dicts; everything else here is arithmetic on those dicts,
so the tests run it on a small recorded trace kept as JSON
(``benchmark/fixtures/``).  A TPU device plane is ``/device:TPU:<n>``; its
``XLA Ops`` line has one event per executed HLO op and its ``XLA Modules``
line one per executed program.  Busy time is the union of the intervals
on the OP line only: step and module lines span their ops, so a union
over every line (``scripts/trace_infeed.py``) overstates it.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|collective-broadcast)")
#: the host span the harness holds open over the measured window
WINDOW_SPAN = "bench.window"


def short_name(text: str) -> str:
    """An op line's event is named by the op's whole HLO text; keep
    ``<name> <result shape>`` (``fusion.6 f32[4194304,32]{0,1:T(8,128)}``;
    a tuple result is ``(tuple)``)."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:160]
    shape = "(tuple)" if rest.startswith("(") else rest.split(" ", 1)[0]
    return f"{name.lstrip('%')} {shape}"


def op_name(event_name: str) -> str:
    """``all-reduce-start.1`` of ``all-reduce-start.1 f32[...]``."""
    return event_name.split(" ", 1)[0]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str, host_names=None) -> dict:
    """``{"devices": {ordinal: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}``.  Device planes keep their
    op and module lines; of the host planes only the events whose name
    passes ``host_names`` (a predicate) are kept: the program's spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict[str, list]] = {}
    host: list[list] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = devices.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in (OP_LINE, MODULE_LINE):
                    lines.setdefault(line.name, []).extend(
                        [short_name(ev.name), int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events)
        elif plane.name.startswith("/host:") and host_names is not None:
            for line in plane.lines:
                host.extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if host_names(ev.name))
    for lines in devices.values():
        for events in lines.values():
            events.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def describe(path: str, top: int = 12) -> dict:
    """Planes, lines and each line's commonest event names: what to look
    at by hand before trusting the reduction on a new machine."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = Counter(ev.name for ev in line.events)
            lines[line.name] = {"events": sum(names.values()),
                                "top": names.most_common(top)}
        out[plane.name] = lines
    return out


def save_json(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"devices": {str(k): v
                               for k, v in trace["devices"].items()},
                   "host": trace["host"]}, f)


def load_json(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): v for k, v in raw["devices"].items()},
            "host": raw["host"]}


# ------------------------------------------------------------ arithmetic

def window_of(trace: dict) -> tuple[int, int] | None:
    """(start_ns, end_ns) of the measured window on the trace's clock:
    the ``bench.window`` host span; None if the trace has none."""
    for name, start, dur in trace["host"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    return None


def clip(events: list, window: tuple[int, int] | None) -> list:
    """Events cut to the window (an event across an edge keeps its part
    inside)."""
    if window is None:
        return list(events)
    lo, hi = window
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def merge(intervals) -> list[tuple[int, int]]:
    """Sorted disjoint (start, end) covering the same points."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals) -> int:
    return sum(e - s for s, e in merge(intervals))


def subtract_ns(a, b) -> int:
    """Length of the part of the union of ``a`` that no interval of ``b``
    covers."""
    a, b = merge(a), merge(b)
    total, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def spans(events) -> list[tuple[int, int]]:
    return [(s, s + d) for _, s, d in events]


def busy_seconds(trace: dict, window=None) -> dict[int, float]:
    """Per device, the seconds in which an op ran, inside the window."""
    return {dev: union_ns(spans(clip(lines.get(OP_LINE, []), window))) / 1e9
            for dev, lines in trace["devices"].items()}


def idle_share(trace: dict, window: tuple[int, int]) -> float | None:
    """1 - busy / window, the mean over devices."""
    busy = busy_seconds(trace, window)
    if not busy or window[1] <= window[0]:
        return None
    length = (window[1] - window[0]) / 1e9
    return 1.0 - sum(busy.values()) / len(busy) / length


def module_events(trace: dict, dev: int, pattern: str, window=None) -> list:
    """The executions of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    events = trace["devices"].get(dev, {}).get(MODULE_LINE, [])
    if window is not None:  # whole executions only
        events = [e for e in events
                  if e[1] >= window[0] and e[1] + e[2] <= window[1]]
    return [e for e in events if rx.search(e[0])]


def median(values):
    v = sorted(values)
    if not v:
        return None
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else (v[mid - 1] + v[mid]) / 2.0


def step_device_ms(trace: dict, pattern: str, window=None) -> float | None:
    """Median device duration of the step program, over devices' medians."""
    per_dev = [median([d for _, _, d in module_events(trace, dev, pattern,
                                                       window)])
               for dev in trace["devices"]]
    per_dev = [v for v in per_dev if v is not None]
    return median(per_dev) / 1e6 if per_dev else None


def launch_gaps_ms(trace: dict, pattern: str, window=None) -> list[float]:
    """On device 0 (or the lowest ordinal), the gaps between the end of
    one step program and the start of the next."""
    if not trace["devices"]:
        return []
    ev = module_events(trace, min(trace["devices"]), pattern, window)
    return [(b[1] - (a[1] + a[2])) / 1e6 for a, b in zip(ev, ev[1:])]


def collective_intervals(ops: list) -> list[tuple[int, int]]:
    """Intervals in which a collective is in flight: a synchronous
    collective op's own interval; for an asynchronous pair, from the start
    of ``<kind>-start[.n]`` to the end of the matching ``<kind>-done[.n]``
    (the next ``-done`` of the same kind and suffix after it)."""
    out, pending = [], {}
    for name, start, dur in ops:
        name = op_name(name)
        if not COLLECTIVE.match(name):
            continue
        m = re.match(r"^(.*)-(start|done)((?:\.\d+)*)$", name)
        if not m:
            out.append((start, start + dur))
        elif m.group(2) == "start":
            pending.setdefault((m.group(1), m.group(3)), []).append(start)
        else:
            waiting = pending.get((m.group(1), m.group(3))) or next(
                (v for (k, _), v in pending.items()
                 if k == m.group(1) and v), None)
            if waiting:
                out.append((waiting.pop(0), start + dur))
            else:
                out.append((start, start + dur))
    return out


def collective_stats(trace: dict, pattern: str, window=None) -> dict | None:
    """``collective_ms``: per step, the time a collective was in flight
    (median over steps, mean over devices).  ``exposed_pct``: the share of
    all collective time in the window during which no other op ran on
    that device."""
    per_step, total, exposed = [], 0, 0
    for dev, lines in trace["devices"].items():
        ops = clip(lines.get(OP_LINE, []), window)
        coll = collective_intervals(ops)
        if not coll:
            continue
        other = [(s, s + d) for n, s, d in ops if not COLLECTIVE.match(n)]
        total += union_ns(coll)
        exposed += subtract_ns(coll, other)
        steps = module_events(trace, dev, pattern, window)
        merged = merge(coll)
        ends = [b for _, b in merged]
        mine = []
        for _, s, d in steps:
            k, inside = bisect.bisect_right(ends, s), 0
            while k < len(merged) and merged[k][0] < s + d:
                inside += min(merged[k][1], s + d) - max(merged[k][0], s)
                k += 1
            mine.append(inside / 1e6)
        if mine:
            per_step.append(median(mine))
    if not total:
        return None
    return {"collective_ms": (sum(per_step) / len(per_step)
                              if per_step else None),
            "exposed_pct": 100.0 * exposed / total}


def top_ops(trace: dict, window=None, n: int = 10) -> list[list]:
    """[[name, seconds], ...] of the device ops that took most time,
    summed over the window and averaged over devices."""
    totals: dict[str, int] = {}
    for lines in trace["devices"].values():
        for name, _, dur in clip(lines.get(OP_LINE, []), window):
            totals[name] = totals.get(name, 0) + dur
    k = max(1, len(trace["devices"]))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9 / k] for name, ns in ranked]


def innermost_segments(host: list) -> list[tuple[int, int, str]]:
    """Disjoint (start, end, name) pieces of the host spans' union, each
    named after the shortest span open during it (a span that contains
    another keeps only what the inner one leaves).  One sweep over the
    spans' edges; the set of open spans stays small (threads x nesting)."""
    edges = []
    for i, (_, start, dur) in enumerate(host):
        if dur > 0:
            edges.append((start, 1, i))
            edges.append((start + dur, 0, i))
    edges.sort()
    out, open_, prev = [], set(), None
    for t, opening, i in edges:
        if open_ and prev is not None and t > prev:
            name = host[min(open_, key=lambda k: host[k][2])][0]
            if out and out[-1][2] == name and out[-1][1] == prev:
                out[-1] = (out[-1][0], t, name)
            else:
                out.append((prev, t, name))
        (open_.add if opening else open_.discard)(i)
        prev = t
    return out


def idle_gaps(trace: dict, window=None, n: int = 10) -> list[list]:
    """[[what the host was doing, seconds], ...]: every idle gap of the
    lowest-ordinal device (between merged op intervals, window edges
    included) is shared out among the program's host spans open during
    it, by overlap (:func:`innermost_segments`); what no span covers goes
    to ``(no span)``.  Linear in gaps + spans."""
    if not trace["devices"]:
        return []
    dev = min(trace["devices"])
    ops = merge(spans(clip(trace["devices"][dev].get(OP_LINE, []), window)))
    if window is None:
        if not ops:
            return []
        window = (ops[0][0], ops[-1][1])
    gaps, cur = [], window[0]
    for s, e in ops:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        gaps.append((cur, window[1]))
    segments = innermost_segments(
        [e for e in clip(trace["host"], window) if e[0] != WINDOW_SPAN])
    totals: dict[str, int] = {}
    j = 0
    for gs, ge in gaps:
        covered = 0
        while j < len(segments) and segments[j][1] <= gs:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < ge:
            s, e, name = segments[k]
            got = min(e, ge) - max(s, gs)
            if got > 0:
                totals[name] = totals.get(name, 0) + got
                covered += got
            k += 1
        if ge - gs > covered:
            totals["(no span)"] = totals.get("(no span)", 0) + (
                ge - gs - covered)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
