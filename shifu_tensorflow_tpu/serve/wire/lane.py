"""Fleet-shared dispatch lane — the sibling worker's side.

With ``--serve-workers N``, each SO_REUSEPORT worker used to own a
private DeviceScheduler: N workers fragment the device into N
uncoordinated batchers, so the coalescing lever and the DRR tenant
weights only ever saw 1/N of the traffic.  The shared lane
re-centralizes DISPATCH without re-centralizing ingress: the
lowest-index worker (the "lane owner", re-elected deterministically by
the supervisor because a crashed worker restarts at its own index and
scale-down always evicts the HIGHEST index) listens on a UNIX domain
socket; every sibling keeps admitting, coalescing and scattering
locally, but instead of dispatching its
packed batch to its private scheduler it forwards the batch — tenant
name + pre-padding row matrix, one frame — down the lane.  The owner
admits the forwarded matrix into its OWN tenant batcher, where it
coalesces with the owner's native traffic and every other sibling's
forwards: one scheduler, fleet-wide DRR, fleet-wide occupancy.  Replies
scatter back by rid.

Degradation is a fallback, never an outage: any failure to reach the
owner (not yet up, crashed, wedged) routes the batch to the sibling's
private dispatch path — strictly the pre-lane behavior — and in-flight
forwards stranded by a dead owner are re-dispatched locally, so a
killed owner loses ZERO requests.  The transitions journal as
``lane_degraded`` / ``lane_restored`` (and the owner's bind as
``lane_owner``), reconstructable from a dead fleet's files via
``obs summary``.
"""

from __future__ import annotations

import socket
import threading
import time

import numpy as np

from shifu_tensorflow_tpu.obs import journal as obs_journal
from shifu_tensorflow_tpu.serve.batcher import RequestTooLarge, ShedLoad
from shifu_tensorflow_tpu.serve.wire import frame as wire
from shifu_tensorflow_tpu.utils import logs

log = logs.get("serve.lane")

#: forwarded batches in flight at once (across all tenants): the lane
#: analogue of the scheduler's MAX_STAGED — past it, forward() falls
#: back to private dispatch rather than queueing unbounded work behind
#: a possibly-wedged owner
MAX_INFLIGHT = 8

#: owner statuses a sibling can serve around locally (fallback) vs the
#: ones that are verdicts on the REQUEST itself (propagate to callers)
_PROPAGATE_STATUSES = (400, 404, 413, 429)


class _Forwarded:
    __slots__ = ("work", "batcher", "t0")

    def __init__(self, work, batcher):
        self.work = work
        self.batcher = batcher
        self.t0 = time.monotonic()


class LaneClient:
    """One per sibling worker process, shared by every tenant batcher
    (``MicroBatcher(lane=...)``).  All public methods are thread-safe;
    ``forward`` is called from pack threads, completion runs on the
    reader thread."""

    def __init__(self, socket_path: str, *,
                 reconnect_interval_s: float = 0.5):
        self.path = socket_path
        self._reconnect_s = reconnect_interval_s
        self._sock: socket.socket | None = None
        self._send_lock = threading.Lock()
        self._state = threading.Condition()
        self._pending: dict[str, _Forwarded] = {}
        self._n = 0
        self._tag = wire.mint_rid()[:6]
        self._sem = threading.Semaphore(MAX_INFLIGHT)
        self._closed = False
        self._had_lane = False   # connected at least once this outage-cycle
        self._forwarded = 0
        self._fallback = 0
        self._reconnects = 0
        self._connector = threading.Thread(target=self._connect_loop,
                                           name="serve-lane-connect",
                                           daemon=True)
        self._connector.start()

    # ---- connection management ----
    def _connect_loop(self) -> None:
        """Background (re)connector: the owner may bind its socket after
        this sibling starts (fleet spawn order is unordered) and is
        respawned at the same index after a crash — keep trying."""
        while True:
            with self._state:
                if self._closed:
                    return
                connected = self._sock is not None
            if not connected:
                try:
                    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                    s.settimeout(2.0)
                    s.connect(self.path)
                    s.settimeout(None)
                except OSError:
                    try:
                        s.close()
                    except OSError:
                        pass
                    s = None
                if s is not None:
                    with self._state:
                        if self._closed:
                            s.close()
                            return
                        self._sock = s
                        self._had_lane = True
                        self._reconnects += 1
                    threading.Thread(target=self._read_loop, args=(s,),
                                     name="serve-lane-reader",
                                     daemon=True).start()
                    # journaled on EVERY successful (re)join, the first
                    # included — the fleet's lane membership record
                    obs_journal.emit("lane_restored", plane="serve",
                                     socket=self.path,
                                     connects=self._reconnects)
                    log.info("joined dispatch lane at %s", self.path)
            with self._state:
                if self._closed:
                    return
                self._state.wait(timeout=self._reconnect_s)

    def connected(self) -> bool:
        with self._state:
            return self._sock is not None

    def stats(self) -> dict:
        with self._state:
            return {
                "connected": self._sock is not None,
                "forwarded": self._forwarded,
                "fallback": self._fallback,
                "reconnects": self._reconnects,
            }

    # ---- forward path (pack threads) ----
    def forward(self, batcher, work) -> bool:
        """Try to send one packed batch down the lane.  True: the work
        now belongs to the lane (its reply — or a dead-owner fallback —
        will land in the batcher's scatter queue).  False: the caller
        dispatches privately."""
        with self._state:
            sock = self._sock
            if sock is None or self._closed:
                self._fallback += 1
                return False
        if not self._sem.acquire(timeout=5.0):
            # owner wedged (accepting but not replying): don't stack
            # more batches behind it
            with self._state:
                self._fallback += 1
            return False
        with self._state:
            self._n += 1
            rid = f"l{self._tag}.{self._n}"
            self._pending[rid] = _Forwarded(work, batcher)
            self._forwarded += 1
        work.queue_delay_s = time.monotonic() - min(
            p.t_enqueue for p in work.batch)
        work.via_lane = True
        head, payload = wire.encode_score_request(
            work.rows, tenant=batcher.model or "", rid=rid)
        try:
            with self._send_lock:
                sock.sendall(head)
                sock.sendall(payload)
        except OSError:
            # the disconnect path re-dispatches every pending forward
            # (this one included) through the private path — the work IS
            # handled, so still True
            self._on_disconnect(sock)
        return True

    # ---- reply path (reader thread) ----
    def _read_loop(self, sock: socket.socket) -> None:
        try:
            while True:
                f = wire.read_frame(sock)
                if f is None:
                    break
                self._complete(f)
        except (OSError, wire.FrameProtocolError):
            pass
        self._on_disconnect(sock)

    def _complete(self, f: wire.Frame) -> None:
        with self._state:
            fwd = self._pending.pop(f.rid, None)
            if fwd is None:
                return  # late reply for a drained/fallen-back work
            self._state.notify_all()
        self._sem.release()
        work, batcher = fwd.work, fwd.batcher
        if f.kind == wire.KIND_SCORES:
            work.scores = f.vector()
            work.dispatch_s = time.monotonic() - fwd.t0
            # device-truth counters (batches/padded rows, serve_batch
            # event, cost ledger) were recorded at the OWNER's dispatch;
            # zero the local pad estimate so nothing double-counts
            work.bucket = work.n
            batcher._scatter_q.put(work)
            return
        if f.kind == wire.KIND_ERROR and f.status in _PROPAGATE_STATUSES:
            if f.status == 429:
                work.error = ShedLoad(max(1, f.retry_after), 0)
            elif f.status == 413:
                work.error = RequestTooLarge(f.message())
            else:
                work.error = RuntimeError(
                    f"lane owner refused batch: {f.status} {f.message()}")
            batcher._scatter_q.put(work)
            return
        # owner can't score right now (cold start, draining, 5xx) but
        # this sibling can: private dispatch, not an error
        log.warning("lane owner returned %d for a forwarded batch; "
                    "dispatching locally", f.status)
        with self._state:
            self._fallback += 1
        batcher._lane_fallback(work)

    def _on_disconnect(self, sock: socket.socket) -> None:
        """The owner went away: fail over every in-flight forward to
        private dispatch (zero lost requests) and journal the outage
        ONCE per connected period."""
        with self._state:
            if self._sock is not sock:
                return  # a racing caller already handled this socket
            self._sock = None
            had = self._had_lane
            self._had_lane = False
            stranded = list(self._pending.values())
            self._pending.clear()
            self._state.notify_all()
        try:
            sock.close()
        except OSError:
            pass
        for _ in stranded:
            self._sem.release()
        if had and not self._closed:
            obs_journal.emit("lane_degraded", plane="serve",
                             socket=self.path,
                             redispatched=len(stranded))
            log.warning("dispatch lane lost (%d in-flight batches "
                        "re-dispatched locally)", len(stranded))
        with self._state:
            self._fallback += len(stranded)
        for fwd in stranded:
            fwd.batcher._lane_fallback(fwd.work)

    # ---- drain / close ----
    def drain(self, batcher, timeout_s: float = 20.0) -> None:
        """Block until no forwarded batch of ``batcher`` is in flight
        (its drain sentinel must not pass its own outstanding work); on
        timeout the leftovers fail over to the private path so their
        callers still get answers."""
        deadline = time.monotonic() + timeout_s
        with self._state:
            while any(f.batcher is batcher for f in self._pending.values()):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._state.wait(timeout=min(remaining, 0.5))
            leftovers = [rid for rid, f in self._pending.items()
                         if f.batcher is batcher]
            stranded = [self._pending.pop(rid) for rid in leftovers]
        for fwd in stranded:
            self._sem.release()
            fwd.batcher._lane_fallback(fwd.work)

    def close(self) -> None:
        with self._state:
            if self._closed:
                return
            self._closed = True
            sock = self._sock
            self._sock = None
            stranded = list(self._pending.values())
            self._pending.clear()
            self._state.notify_all()
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        for fwd in stranded:
            self._sem.release()
            fwd.batcher._lane_fallback(fwd.work)
        self._connector.join(timeout=5.0)
