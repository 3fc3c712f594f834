"""``jax.shard_map`` behind the one wrapper every shard_map call site in
the framework shares.

Being the one chokepoint makes it the obs plane's collective seam:
every returned callable runs under an ``obs.fleet.comm_region`` —
``comm.shmap.<label>`` tracer span plus a PR-10 compile-attribution
frame, so an eager shard_map call's wall time lands in the epoch's span
budget and a compile fired inside is attributed to the collective, not
to "unattributed".  Calls from inside an enclosing jit trace attribute
to the observed step instead, which is the truth (the same rule the
Pallas seams follow).  Pass ``comm_label=None`` to skip the wrapper
(call sites that already run under their own comm region, e.g.
``ring_attention_sharded``).
"""

from __future__ import annotations

import jax


def shard_map(fn, mesh, in_specs, out_specs, *, check_replication=False,
              comm_label: str | None = "auto"):
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_replication,
    )
    if comm_label is None:
        return mapped
    if comm_label == "auto":
        comm_label = (getattr(fn, "__name__", None)
                      or getattr(getattr(fn, "func", None), "__name__",
                                 None)
                      or "fn")

    def instrumented(*args, **kwargs):
        from shifu_tensorflow_tpu.obs import fleet as obs_fleet

        with obs_fleet.comm_region(f"shmap.{comm_label}"):
            return mapped(*args, **kwargs)

    instrumented.__wrapped__ = mapped
    return instrumented
