"""Device-memory accountant: what actually lives in device memory, by
owner, over time.

``jax.live_arrays()`` enumerates every device buffer the process holds;
backend ``memory_stats()`` (where the PJRT backend implements it — TPU
and GPU do, CPU returns None) adds the allocator's own view
(bytes_in_use / reserved / peak / limit; ``bytes_held`` is in use plus
reserved: what a loaded step's temporaries set aside is memory nothing
else can have, and ``peak_bytes_in_use`` leaves it out).  Neither tells
you *whose* bytes those
are — so the accountant takes attribution pytrees from its callers and
buckets the total:

- ``params`` / ``opt_state`` — the trainer passes its TrainState's
  trees per epoch;
- ``infeed`` — in-flight input batches (the pipelined-prefetch buffers);
- ``executable`` — generated-code bytes from the compile flight
  recorder's registry (executables are not jax arrays, so this rides
  BESIDE the live-array total, not inside it; present only under
  ``obs-compile-analysis=full`` — absent otherwise, never zero);
- ``models`` — the serve tenancy plane passes each admitted
  ``EvalModel``'s device-resident weights, so the LRU budget's
  dashboard shows *device* bytes per tenant, not just bundle bytes
  (gauge name ``stpu_devmem_model_bytes_<escaped-name>`` carrying a
  ``model="<name>"`` label — registry gauges are name-keyed, so the
  tenant rides in both);
- ``other`` — live-array bytes nothing above claimed (leaked buffers,
  retained eval outputs, donation ghosts — exactly the bucket an
  operator stares at when a job OOMs "for no reason").

Each snapshot journals one ``device_mem`` event, updates the
``stpu_devmem_*`` gauges (appended to the plane's ``/metrics``), tracks
the high-water mark, and — when the backend reports a bytes limit —
feeds the ``devmem_frac`` SLO signal the ``shifu.tpu.slo-devmem-frac``
watchdog target judges.

Cadence is caller-owned and cheap-by-construction: per epoch on the
train plane, per admission/eviction on the serve plane — never per step
or per request.  A snapshot walks the live-array list once (tens of
arrays on the workloads this repo trains; microseconds).  stdlib-only
at import; jax is imported inside :meth:`snapshot`, which only runs in
jax processes.
"""

from __future__ import annotations

import threading
import time
from typing import Any

from shifu_tensorflow_tpu.obs.registry import (
    MetricsRegistry,
    escape_label_suffix as _escape,  # one escape across every obs leg
)
from shifu_tensorflow_tpu.utils import logs

log = logs.get("obs")

__all__ = [
    "MemoryAccountant",
    "install",
    "uninstall",
    "active",
]


def _array_bytes(a: Any) -> int:
    """This process's bytes for one jax array: addressable-shard bytes
    under sharding (``nbytes`` is the GLOBAL logical size — counting it
    would charge every host for the whole fleet's tables), plain nbytes
    otherwise.  Deleted arrays (donation consumed them) count zero."""
    try:
        if getattr(a, "is_deleted", None) is not None and a.is_deleted():
            return 0
        sharding = getattr(a, "sharding", None)
        if sharding is not None and len(sharding.device_set) > 1:
            return sum(int(s.data.nbytes) for s in a.addressable_shards)
        return int(a.nbytes)
    except Exception:
        return 0


def tree_device_bytes(tree: Any) -> int:
    """Total device bytes of a pytree's jax-array leaves (numpy/host
    leaves count zero — they are not device memory)."""
    if tree is None:
        return 0
    try:
        import jax

        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "addressable_shards") or (
                    hasattr(leaf, "device") and hasattr(leaf, "nbytes")):
                total += _array_bytes(leaf)
        return total
    except Exception:
        return 0


def tree_per_device_bytes(tree: Any) -> dict[int, int]:
    """Device id -> bytes this pytree holds ON that device.  The
    sharding-aware view of :func:`tree_device_bytes`: a table sharded
    over the ``model`` axis charges each device its slice, a replicated
    leaf charges every device the full array — so ``max`` over the
    returned dict is the per-device parameter footprint the mesh-shape
    capacity planning (bench sharding) reasons about."""
    per_dev: dict[int, int] = {}
    if tree is None:
        return per_dev
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(tree):
            if getattr(leaf, "is_deleted", None) is not None \
                    and leaf.is_deleted():
                continue
            shards = getattr(leaf, "addressable_shards", None)
            if shards is not None:
                try:
                    for s in shards:
                        did = int(getattr(s.device, "id", 0))
                        per_dev[did] = per_dev.get(did, 0) \
                            + int(s.data.nbytes)
                    continue
                except Exception:
                    pass
            dev = getattr(leaf, "device", None)
            nbytes = getattr(leaf, "nbytes", None)
            if dev is not None and nbytes is not None:
                d = dev() if callable(dev) else dev
                did = int(getattr(d, "id", 0) or 0)
                per_dev[did] = per_dev.get(did, 0) + int(nbytes)
    except Exception:
        return per_dev
    return per_dev


class MemoryAccountant:
    """Per-plane device-memory snapshots with attribution and
    high-water tracking (installed by ``obs.install_obs`` beside the
    tracer/journal/watchdog/compile recorder)."""

    def __init__(self, *, plane: str = "train", worker: int | None = None):
        self.plane = plane
        self.worker = worker
        self._lock = threading.Lock()
        self.high_water = 0
        self.high_water_ts: float | None = None
        self._model_bytes: dict[str, int] = {}
        self._last: dict[str, Any] = {}
        self.snapshots = 0
        self.registry = MetricsRegistry()

    def snapshot(self, *, params: Any = None, opt_state: Any = None,
                 infeed: Any = None, models: dict[str, Any] | None = None,
                 event: str = "device_mem", **ctx: Any) -> dict | None:
        """One accounting pass; returns (and journals) the bucketed
        record, or None when jax is unavailable in this process."""
        from shifu_tensorflow_tpu.obs import compile as obs_compile
        from shifu_tensorflow_tpu.obs import journal as obs_journal
        from shifu_tensorflow_tpu.obs import slo as obs_slo

        try:
            import jax
        except Exception:
            return None
        try:
            live = jax.live_arrays()
        except Exception as e:
            log.warning("device-memory snapshot failed (%s: %s)",
                        type(e).__name__, e)
            return None
        total = sum(_array_bytes(a) for a in live)
        params_b = tree_device_bytes(params)
        # per-device params footprint (max over local devices): THE
        # capacity signal model-axis sharding moves — a table sharded
        # model:M charges each device 1/M of what replication would
        params_dev_b = max(tree_per_device_bytes(params).values(),
                           default=0) if params is not None else None
        opt_b = tree_device_bytes(opt_state)
        infeed_b = tree_device_bytes(infeed)
        model_b: dict[str, int] = {}
        for name, tree in (models or {}).items():
            # the tenancy store precomputes bytes (EvalModel.device_bytes)
            # so it never hands private param trees across the seam
            model_b[name] = (int(tree) if isinstance(tree, (int, float))
                             else tree_device_bytes(tree))
        # executable bytes come from the compile registry's
        # memory_analysis fields — available only under analysis="full";
        # under cost/off the field is ABSENT, never a measured zero
        exec_b = None
        rec = obs_compile.active()
        if rec is not None and rec.analysis == "full":
            exec_b = rec.state()["executable_bytes"]
        attributed = params_b + opt_b + infeed_b + sum(model_b.values())
        other = max(0, total - attributed)
        out: dict[str, Any] = {
            "total_bytes": total,
            "arrays": len(live),
            "params_bytes": params_b,
            **({"params_dev_bytes": params_dev_b}
               if params_dev_b is not None else {}),
            "opt_bytes": opt_b,
            "infeed_bytes": infeed_b,
            **({"exec_bytes": exec_b} if exec_b is not None else {}),
            "other_bytes": other,
        }
        if model_b:
            out["models"] = dict(sorted(model_b.items()))
        stats = self._backend_stats(jax)
        out.update(stats)
        if "bytes_in_use" in stats:
            out["bytes_held"] = (stats["bytes_in_use"]
                                 + stats.get("bytes_reserved", 0))
        # the allocator's figure where there is one: it holds the loaded
        # programs' reservations, which are no live array
        held = out.get("bytes_held", total)
        with self._lock:
            self.snapshots += 1
            if held > self.high_water:
                self.high_water = held
                self.high_water_ts = time.time()
            # MERGE, don't replace: a single-model reload snapshot must
            # not wipe sibling tenants' last-known bytes (eviction
            # removes its entry explicitly via drop_model)
            self._model_bytes.update(model_b)
            out["hwm_bytes"] = self.high_water
        frac = None
        limit = out.get("bytes_limit")
        if limit:
            frac = min(1.0, held / limit)
            out["devmem_frac"] = round(frac, 6)
        obs_journal.emit(event, plane=self.plane, worker=self.worker,
                         **out, **ctx)
        wd = obs_slo.active()
        if wd is not None and frac is not None:
            wd.observe("devmem_frac", frac)
        self._last = out
        self._set_gauges(out)
        return out

    @staticmethod
    def _backend_stats(jax) -> dict:
        """Allocator-view totals summed over the local devices that
        report them.  A figure is present only when every reporting
        device gives it: a device that does not report its peak or its
        limit leaves that signal absent, never an assumed zero.  {} when
        the backend doesn't implement memory_stats at all (CPU)."""
        per_device = [ms for d in jax.local_devices()
                      if (ms := d.memory_stats())]
        return {
            key: sum(int(ms[key]) for ms in per_device)
            for key in ("bytes_in_use", "bytes_reserved",
                        "peak_bytes_in_use", "bytes_limit")
            if per_device and all(key in ms for ms in per_device)
        }

    def _set_gauges(self, out: dict) -> None:
        r = self.registry
        for key in ("total_bytes", "params_bytes", "opt_bytes",
                    "infeed_bytes", "other_bytes", "hwm_bytes"):
            r.set_gauge(key, out.get(key, 0))
        if "exec_bytes" in out:
            r.set_gauge("exec_bytes", out["exec_bytes"])
        else:
            r.remove_gauge("exec_bytes")  # absent signal, not zero
        if "params_dev_bytes" in out:
            r.set_gauge("params_dev_bytes", out["params_dev_bytes"])
        else:
            r.remove_gauge("params_dev_bytes")  # absent signal, not zero
        for key in ("bytes_in_use", "bytes_held", "bytes_limit"):
            if key in out:
                r.set_gauge(f"backend_{key}", out[key])
        with self._lock:
            models = dict(self._model_bytes)
        for name, b in models.items():
            r.set_gauge(f"model_bytes_{_escape(name)}", b,
                        labels='{model="%s"}' % name)

    def drop_model(self, name: str) -> None:
        """Eviction: the tenant's device bytes leave the scrape instead
        of freezing at their last value (same contract as the SLO
        watchdog's untrack_serve_tenant)."""
        with self._lock:
            self._model_bytes.pop(name, None)
        self.registry.remove_gauge(f"model_bytes_{_escape(name)}")

    def model_bytes(self) -> dict[str, int]:
        """Last-known device bytes per admitted model (the tenancy
        store's budget dashboard reads this)."""
        with self._lock:
            return dict(self._model_bytes)

    def state(self) -> dict:
        with self._lock:
            return {
                "high_water": self.high_water,
                "snapshots": self.snapshots,
                "model_bytes": dict(self._model_bytes),
            }

    def render_prometheus(self) -> str:
        """``stpu_devmem_*`` gauges for the plane's scrape surface.
        Renders the full gauge set from the first scrape (zeros before
        the first snapshot) — a series that appears only after its
        first event breaks dashboards, the registry's own rule."""
        self._set_gauges(self._last)
        return self.registry.render_prometheus("stpu_devmem_")


# ---- process-global hook (mirrors obs.trace / obs.journal) ----

_active: MemoryAccountant | None = None


def install(accountant: MemoryAccountant) -> MemoryAccountant:
    global _active
    _active = accountant
    return accountant


def uninstall() -> None:
    global _active
    _active = None


def active() -> MemoryAccountant | None:
    return _active
