"""Shared device scheduler: weighted deficit round-robin over per-tenant
dispatch queues.

The device (one jitted scorer per tenant, all funneling through one
process's XLA client) is a single serialized resource — EvalModel's
documented contract is one scoring thread.  With one micro-batcher per
tenant, each tenant's pack/scatter host work stays parallel, but their
*dispatches* must be arbitrated in one place or a hot tenant's backlog
simply occupies the device in arrival order and every other tenant's
latency rides on it.  That arbitration is deficit round-robin (Shreedhar
& Varghese, SIGCOMM '95) with per-tenant weights:

- each tenant queue holds packed batches (``_Work``) in FIFO order;
- the device thread visits tenant queues round-robin; a visited queue
  with backlog earns ``quantum × weight`` deficit ROWS per pass and may
  dispatch while its deficit covers the head batch's *bucket* size (the
  padded row count — what the device actually pays, so a tenant cannot
  launder cost through padding);
- an emptied queue forfeits its deficit (the classic DRR rule: credit
  never accumulates while idle, so a returning tenant gets fairness,
  not a stored burst).

Long-run device rows are therefore shared proportionally to weight
among backlogged tenants, with single-batch granularity — one tenant at
sustained overload delays another's dispatch by at most the in-flight
batch plus its own next quantum, which is the p99-isolation property
``tests/test_tenancy.py`` pins.  When only one tenant has work it gets
the whole device: work-conserving, no reserved idle shares.

Weights come from ``shifu.tpu.serve-tenant-weight-<model>`` (default 1);
the quantum is rows per visit — small enough to interleave tenants
between batches, large enough that a typical coalesced batch clears in
one or two visits.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from shifu_tensorflow_tpu.obs import journal as obs_journal
from shifu_tensorflow_tpu.utils import logs

log = logs.get("serve.sched")

#: deficit rows granted per round-robin visit (× tenant weight).  The
#: smallest ladder bucket: a weight-1 tenant then bursts at most ONE
#: minimum-size batch per pass, which is the tightest latency isolation
#: the batch granularity allows (a bigger quantum lets a hot tenant
#: dispatch quantum/8 small batches back-to-back while a victim waits).
#: Larger buckets accumulate credit over several passes — each pass is
#: lock-held arithmetic, microseconds against millisecond dispatches.
DEFAULT_QUANTUM_ROWS = 8


class _TenantQueue:
    """One tenant's dispatch queue + DRR state.  All fields are guarded
    by the scheduler's condition lock except the batcher reference."""

    __slots__ = ("name", "weight", "batcher", "work", "deficit",
                 "in_flight", "registered", "dispatched_rows",
                 "dispatched_bucket_rows", "dispatched_batches",
                 "dispatched_device_s")

    def __init__(self, name: str, batcher, weight: float):
        self.name = name
        self.weight = float(weight)
        self.batcher = batcher
        self.work: deque = deque()
        self.deficit = 0.0
        self.in_flight = False   # device thread is inside this tenant's
        #                          score_fn right now
        self.registered = True
        self.dispatched_rows = 0
        # bucket (padded) rows actually paid to the device — the
        # denominator of occupancy; rows/bucket_rows < 1 means the
        # ladder padded, fleet fragmentation makes it fall further
        self.dispatched_bucket_rows = 0
        self.dispatched_batches = 0
        # device-seconds this tenant's dispatches consumed (the
        # batcher's dispatch_s, accumulated here so the scheduler's own
        # totals answer "who got the device" without the obs plane)
        self.dispatched_device_s = 0.0


class DeviceScheduler:
    """The one device dispatch thread shared by every tenant batcher.

    Lifecycle: ``register`` (MicroBatcher ctor in scheduler mode) →
    ``submit`` (the tenant's pack thread) → the device thread calls the
    owning batcher's ``_dispatch_one`` (which scores and feeds that
    batcher's scatter queue) → ``drain``/``unregister`` (the pack
    thread's shutdown path, so an evicted tenant leaves no orphaned
    work).  ``close`` stops the device thread after the queues empty.
    """

    def __init__(self, quantum_rows: int = DEFAULT_QUANTUM_ROWS):
        if quantum_rows < 1:
            raise ValueError("quantum_rows must be >= 1")
        self.quantum_rows = int(quantum_rows)
        self._cond = threading.Condition()
        self._tenants: dict[int, _TenantQueue] = {}  # id(handle) keyed
        self._order: list[_TenantQueue] = []         # round-robin ring
        self._rr = 0
        self._closed = False
        self._thread = threading.Thread(
            target=self._device_loop, name="serve-device", daemon=True)
        self._thread.start()

    # ---- tenant side ----
    def register(self, name: str, batcher, weight: float = 1.0):
        """Add a tenant queue; returns the handle ``submit``/``drain``/
        ``unregister`` take.  Weight must be positive — a zero weight
        could never afford any batch and would wedge its own queue."""
        if weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {weight}")
        tq = _TenantQueue(name, batcher, weight)
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            self._tenants[id(tq)] = tq
            self._order.append(tq)
        return tq

    #: per-tenant scheduler-queue depth the pack thread may stage ahead.
    #: BOUNDED handoff is what preserves shed-before-queue: an unbounded
    #: queue here would let the pack thread drain the whole admission
    #: queue into the scheduler and the admission bound would never
    #: overflow — overload would become invisible latency again.  2
    #: mirrors the single-model pipeline depth (one staged + one ahead),
    #: keeping the documented in-flight bound at "admission queue + a
    #: few coalesced batches" per tenant.
    MAX_STAGED = 2

    def submit(self, handle: _TenantQueue, work) -> None:
        """Stage one packed batch; BLOCKS (the tenant's pack thread)
        while the tenant already has MAX_STAGED batches waiting — the
        backpressure that keeps rows countable in the tenant's admission
        queue, where the shed bound can see them."""
        with self._cond:
            while (len(handle.work) >= self.MAX_STAGED
                   and handle.registered and not self._closed):
                self._cond.wait()
            handle.work.append(work)
            self._cond.notify_all()

    def drain(self, handle: _TenantQueue, timeout_s: float = 20.0) -> bool:
        """Block until every batch this tenant submitted has been
        dispatched (its results are already in the tenant's scatter
        queue when this returns — ``in_flight`` clears only after
        ``_dispatch_one`` completes).  Bounded: a wedged scorer must not
        hang an eviction forever — on timeout the tenant unregisters
        anyway and the straggler work's results are dropped.  The
        default stays UNDER MicroBatcher.close()'s 30 s thread join so
        the eviction path observes the drain verdict (success or
        give-up) before the batcher's close returns and the model is
        released — a longer drain here would silently outlive the join
        and the release would race the still-queued batches."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while handle.work or handle.in_flight:
                self._cond.wait(timeout=1.0)
                if (time.monotonic() > deadline
                        and (handle.work or handle.in_flight)):
                    log.warning(
                        "drain of tenant %s timed out with %d batches "
                        "queued", handle.name, len(handle.work))
                    return False
        return True

    def unregister(self, handle: _TenantQueue) -> list:
        """Remove the tenant queue; returns any batches still staged
        (non-empty only after a drain timeout) so the caller can FAIL
        their waiters — silently dropping them would leave every caller
        blocked until its own submit timeout."""
        with self._cond:
            handle.registered = False
            self._tenants.pop(id(handle), None)
            if handle in self._order:
                self._order.remove(handle)
            leftovers = list(handle.work)
            handle.work.clear()
            self._cond.notify_all()
        return leftovers

    def set_weight(self, handle_or_name, weight: float) -> float:
        """Runtime tenant-weight adjustment — the lifecycle ramp's
        actuator.  Takes the handle ``register`` returned or the tenant
        NAME (the ramp controller only knows names); returns the
        previous weight.  Journaled as ``weight_change`` so a ramp step
        is reconstructable from a dead fleet's files like every other
        transition.

        Coordinator-free by design: mutating the DRR weight under the
        scheduler lock re-shares device rows from the very next ring
        pass, so a small ramp step does not pay a rolling restart.  The
        restart path stays for *worker-visible config* (the persisted
        ``serve-tenant-weight-*`` keys new workers resolve at boot) —
        this setter moves live traffic, the config the fleet converges
        to on its next roll."""
        if weight <= 0:
            raise ValueError(f"tenant weight must be > 0, got {weight}")
        with self._cond:
            tq = None
            if isinstance(handle_or_name, _TenantQueue):
                if handle_or_name.registered:
                    tq = handle_or_name
            else:
                for cand in self._order:
                    if cand.name == handle_or_name:
                        tq = cand
                        break
            if tq is None:
                raise KeyError(f"no registered tenant {handle_or_name!r}")
            before = tq.weight
            tq.weight = float(weight)
            # an idle queue's stale deficit is already forfeited on
            # visit; an accumulating one re-earns at the new rate from
            # the next pass — no retroactive credit either way
            self._cond.notify_all()
        if obs_journal.active() is not None:
            obs_journal.emit("weight_change", plane="serve",
                             model=tq.name, weight=float(weight),
                             weight_before=before)
        return before

    # ---- reading ----
    def queue_depths(self) -> dict[str, int]:
        """Tenant name → queued (undispatched) batches, for /healthz."""
        with self._cond:
            return {tq.name: len(tq.work) for tq in self._order}

    def dispatch_totals(self) -> dict[str, dict]:
        with self._cond:
            return {
                tq.name: {"rows": tq.dispatched_rows,
                          "bucket_rows": tq.dispatched_bucket_rows,
                          "batches": tq.dispatched_batches,
                          "device_s": round(tq.dispatched_device_s, 6),
                          "weight": tq.weight}
                for tq in self._order
            }

    def occupancy(self) -> float:
        """Useful rows as a fraction of DISPATCHED (bucket) rows, across
        every tenant this scheduler has ever served — the fleet-level
        reading when this is the lane owner's scheduler.  1.0 when idle
        (no dispatch yet means no padding waste yet)."""
        with self._cond:
            rows = sum(tq.dispatched_rows for tq in self._order)
            bucket = sum(tq.dispatched_bucket_rows for tq in self._order)
        return round(rows / bucket, 6) if bucket else 1.0


    # ---- device thread ----
    def _pick_locked(self) -> _TenantQueue | None:
        """Deficit round-robin: returns the tenant whose head batch to
        dispatch next, having already charged its deficit.  Caller holds
        the lock and guarantees at least one queue is non-empty.

        Terminates because every full ring pass grants quantum×weight>0
        to each backlogged tenant, so some deficit eventually covers its
        head bucket."""
        n = len(self._order)
        while True:
            tq = self._order[self._rr % n]
            if not tq.work:
                # idle queues forfeit credit (DRR: no stored bursts)
                tq.deficit = 0.0
                self._rr += 1
                continue
            cost = tq.work[0].bucket
            if tq.deficit >= cost:
                # affordable: serve and STAY on this tenant (the next
                # pick re-visits it and serves while the deficit lasts —
                # bursts are bounded by quantum×weight rows per pass).
                # Draining the queue does NOT forfeit the remainder: the
                # staged handoff is shallow (MAX_STAGED) and the pack
                # thread refills it mid-dispatch, so a backlogged tenant
                # must keep its leftover credit or its weight advantage
                # would reset every other batch.  A tenant found empty
                # at VISIT time (truly idle) forfeits above — the
                # classic DRR no-stored-bursts rule.
                tq.deficit -= cost
                if len(tq.work) == 1:
                    self._rr += 1
                return tq
            # can't afford the head batch: grant this visit's quantum
            # and move on — the credit accumulates across ring passes
            # until the batch clears (large buckets take several)
            tq.deficit += self.quantum_rows * tq.weight
            self._rr += 1

    def _device_loop(self) -> None:
        while True:
            with self._cond:
                while (not self._closed
                       and not any(tq.work for tq in self._order)):
                    self._cond.wait()
                if self._closed and not any(
                        tq.work for tq in self._order):
                    return
                tq = self._pick_locked()
                work = tq.work.popleft()
                tq.in_flight = True
            try:
                # outside the lock: scoring must not serialize the
                # tenants' pack/scatter threads or submissions.  The
                # lane's busy/idle split is the COST ACCOUNTANT's job
                # (obs/cost.py note_busy, fed inside _dispatch_one) —
                # one ledger, not two that can drift.
                tq.batcher._dispatch_one(work)
            except BaseException as e:  # the device thread must survive
                log.error("dispatch for tenant %s failed outside the "
                          "work envelope: %s: %s", tq.name,
                          type(e).__name__, e)
            finally:
                with self._cond:
                    tq.in_flight = False
                    tq.dispatched_rows += work.n
                    tq.dispatched_bucket_rows += work.bucket
                    tq.dispatched_batches += 1
                    tq.dispatched_device_s += work.dispatch_s
                    self._cond.notify_all()

    def close(self, timeout_s: float = 60.0) -> None:
        """Stop the device thread once the queues drain.  Tenant
        batchers should already be closed (each drains + unregisters on
        its own shutdown path); any straggler work still queued is
        dispatched before the thread exits."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout=timeout_s)
