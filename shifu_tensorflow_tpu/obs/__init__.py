"""Unified observability plane: metrics registry, span tracing, event journal.

The reference system's only window into a running job was YARN container
logs and heartbeat exit codes — the AM could say *that* a worker died,
never *why it was slow* (SURVEY.md §5).  By PR 3 this reproduction had
grown three private telemetry planes (serve counters, the coordinator
epoch board, ad-hoc trainer log lines) with no shared vocabulary.  This
package is the one instrumentation layer all three planes share:

- :mod:`~shifu_tensorflow_tpu.obs.registry` — thread-safe counters,
  gauges, and latency histograms with one Prometheus text renderer.
  ``serve/metrics.py`` is a thin wrapper over these types.
- :mod:`~shifu_tensorflow_tpu.obs.trace` — lightweight span timing for
  the per-step loop (infeed / host / dispatch / block), checkpoint
  save/restore, retry sleeps, and coordinator RPCs.  Spans carry the
  worker index so SPMD replicas compare.
- :mod:`~shifu_tensorflow_tpu.obs.journal` — append-only JSONL event
  journal (rotation + size cap, crash-safe line-at-a-time writes) that
  records structured lifecycle events from train, coordinator, and
  serve.  ``python -m shifu_tensorflow_tpu.obs tail|summary`` reads it.

Everything is off-by-default-cheap: with no ``shifu.tpu.obs-*`` key set,
the module-level hooks are a single ``is None`` check per call site.
stdlib-only by design: the observability plane must import in every
process (CLI ``--help`` included) without paying for jax.
"""

from __future__ import annotations

from shifu_tensorflow_tpu.obs.config import ObsConfig, resolve_obs_config
from shifu_tensorflow_tpu.obs.registry import (
    LatencyHistogram,
    MetricsRegistry,
)

__all__ = [
    "ObsConfig",
    "resolve_obs_config",
    "LatencyHistogram",
    "MetricsRegistry",
    "install_obs",
    "device_obs_text",
]


def device_obs_text() -> str:
    """The device/compiler leg's scrape suffix, shared by every
    ``/metrics`` surface (serve single-model, serve multi-tenant, the
    coordinator ``metrics`` op): ``stpu_compile_*`` (the executable
    registry + storm state) and ``stpu_devmem_*`` (the memory
    accountant's last snapshot) when the leg is installed, plus —
    always — the ``stpu_build_info`` identity gauge saying WHAT build
    answered the scrape."""
    from shifu_tensorflow_tpu.obs import compile as compile_mod
    from shifu_tensorflow_tpu.obs import cost as cost_mod
    from shifu_tensorflow_tpu.obs import datastats as datastats_mod
    from shifu_tensorflow_tpu.obs import memory as memory_mod
    from shifu_tensorflow_tpu.obs.registry import build_info_text

    text = ""
    rec = compile_mod.active()
    if rec is not None:
        text += rec.render_prometheus()
    mem = memory_mod.active()
    if mem is not None:
        text += mem.render_prometheus()
    mon = datastats_mod.active()
    if mon is not None:
        # stpu_data_* per-model drift gauges (the data leg)
        text += mon.render_prometheus()
    acct = cost_mod.active()
    if acct is not None:
        # stpu_cost_* per-tenant device-time counters + the device
        # lane's busy/idle headroom gauges (the cost leg)
        text += acct.render_prometheus()
    return text + build_info_text()


def install_obs(cfg: ObsConfig, *, worker_index: int | None = None,
                plane: str = "train", job: str | None = None):
    """Install the process-wide tracer + journal + SLO watchdog from a
    resolved :class:`ObsConfig`.  Returns ``(tracer, journal)`` (either
    may be None; the watchdog is reachable via ``obs.slo.active()``).
    Subprocess workers pass their ``worker_index`` so their journal
    lands beside the base path as ``<path>.w<index>`` (train fleets) or
    ``<path>.s<index>`` (``--serve-workers`` scoring processes) — one
    writer per file keeps the line-at-a-time crash-safety contract
    honest across a fleet (the CLI reader merges the set by ``(ts,
    writer, seq)``).  ``job`` is the fleet-wide correlation id every
    event from this writer carries — mint one per job at the submitting
    CLI (workers receive it via the register reply / ``--obs-job``), so
    one merged journal can tell two jobs' events apart.
    """
    from shifu_tensorflow_tpu.obs import compile as compile_mod
    from shifu_tensorflow_tpu.obs import cost as cost_mod
    from shifu_tensorflow_tpu.obs import datastats as datastats_mod
    from shifu_tensorflow_tpu.obs import fleet as fleet_mod
    from shifu_tensorflow_tpu.obs import journal as journal_mod
    from shifu_tensorflow_tpu.obs import memory as memory_mod
    from shifu_tensorflow_tpu.obs import profile as profile_mod
    from shifu_tensorflow_tpu.obs import registry as registry_mod
    from shifu_tensorflow_tpu.obs import rollup as rollup_mod
    from shifu_tensorflow_tpu.obs import slo as slo_mod
    from shifu_tensorflow_tpu.obs import trace as trace_mod

    # persistent compilation cache: placed for every plane, whether or
    # not observability itself is on.  shifu.tpu.compile-cache-dir rides
    # this config for the key-resolve + JSON bridge and only names the
    # directory when JAX_COMPILATION_CACHE_DIR does not
    compile_mod.apply_persistent_cache(cfg.compile_cache_dir)
    if not cfg.enabled:
        slo_mod.uninstall()
        compile_mod.uninstall()
        memory_mod.uninstall()
        fleet_mod.uninstall()
        datastats_mod.uninstall()
        datastats_mod.uninstall_train()
        cost_mod.uninstall()
        # drop the retired accountant's counter source too — the
        # process-global _sources dict would otherwise pin its object
        # graph for process lifetime (same leak the serve close path
        # guards against)
        rollup_mod.unregister_source("cost")
        rollup_mod.uninstall()
        rollup_mod.uninstall_regression()
        profile_mod.unconfigure()
        return None, None
    if cfg.hist_buckets:
        # scrape surfaces construct their registries AFTER the CLI
        # installs obs, so the configured ladder reaches them here
        registry_mod.set_default_bounds(cfg.hist_buckets)
    tracer = trace_mod.Tracer(
        worker_index=worker_index if worker_index is not None else 0,
        sample_every=cfg.trace_sample,
    )
    trace_mod.install(tracer)
    jrn = None
    if cfg.journal_path:
        # one writer per file: train fleets write .w<i>, serve scoring
        # processes .s<i>, the lifecycle controller .l<i> — the reader
        # merges the set by (ts, writer, seq)
        suffix = {"serve": "s", "lifecycle": "l"}.get(plane, "w")
        path = (
            cfg.journal_path
            if worker_index is None
            else f"{cfg.journal_path}.{suffix}{worker_index}"
        )
        jrn = journal_mod.Journal(
            path,
            max_bytes=cfg.journal_max_bytes,
            max_files=cfg.journal_max_files,
            plane=plane,
            worker=worker_index,
            job=job,
        )
        journal_mod.install(jrn)
    # the SLO watchdog installs whenever obs is on: with no slo-* target
    # configured it still feeds the stpu_slo_* gauges and the anomaly
    # detector — consumers (ScoringServer, Trainer) pick it up via
    # slo.active() the same way the trainer picks up the tracer
    slo_mod.install(slo_mod.from_config(cfg, plane=plane,
                                        worker=worker_index))
    # device/compiler leg (PR 10): the compile flight recorder and the
    # device-memory accountant install beside the watchdog — seams pick
    # them up via compile.active()/memory.active() exactly like the
    # tracer; the profiler trigger polls only when a journal exists (the
    # journal base is the operator's rendezvous point)
    analysis = getattr(cfg, "compile_analysis", "auto")
    if analysis == "auto":
        # full memory_analysis costs a second backend compile: fine on
        # the train plane (compiles are rare and off any request path),
        # not on serve, where a request-path compile runs under the
        # compute lock on the dispatch thread
        analysis = "cost" if plane == "serve" else "full"
    compile_mod.install(compile_mod.CompileRecorder(
        plane=plane, worker=worker_index,
        analysis=analysis,
        storm_window_s=cfg.slo_window_s,
        storm_threshold=getattr(cfg, "compile_storm", 8),
    ))
    memory_mod.install(memory_mod.MemoryAccountant(
        plane=plane, worker=worker_index))
    # fleet leg (PR 11): the coordinator feeds it from workers' epoch
    # reports (fleet.active() in report_epoch); on planes that never see
    # fleet traffic it idles at zero cost like the other legs
    fleet_mod.install(fleet_mod.FleetMonitor(
        skew_threshold=getattr(cfg, "fleet_skew_threshold", 1.5),
        hysteresis=cfg.slo_hysteresis,
        plane=plane,
    ))
    # data leg (PR 12): the drift monitor (serve batchers feed it per
    # dispatch, model stores register bundle baselines, the SLO tick
    # evaluates it) and the train-side sketch (ingest taps feed it
    # under the trace-sample discipline; export ships its snapshot as
    # the bundle's feature_stats.json)
    datastats_mod.install(datastats_mod.DataDriftMonitor(
        threshold=getattr(cfg, "data_drift_threshold", 1.0),
        hysteresis=cfg.slo_hysteresis,
        window_s=cfg.slo_window_s,
        plane=plane,
        worker=worker_index,
    ))
    datastats_mod.install_train(datastats_mod.TrainDataSketch(
        sample_every=cfg.trace_sample))
    # cost leg (PR 13): the device-time ledger every dispatch seam feeds
    # (batcher _dispatch_one, Trainer._obs_epoch) — registered as a
    # rollup counter source so per-tenant device-seconds survive journal
    # rotation
    acct = cost_mod.install(cost_mod.CostAccountant(
        plane=plane, worker=worker_index))
    rollup_mod.register_source("cost", acct.counters)
    # rollup compactor (PR 13): one per journal WRITER, tapping its emit
    # path and appending per-window aggregates to the rotation-exempt
    # <journal>.rollup.jsonl sidecar; the journal's close hook does the
    # final flush so a drained fleet's sidecar is complete
    if jrn is not None and getattr(cfg, "rollup", True):
        comp = rollup_mod.install(rollup_mod.RollupCompactor(
            rollup_mod.rollup_path(jrn.path),
            window_s=getattr(cfg, "rollup_window_s", 60.0),
            plane=plane, worker=worker_index, job=job,
        ))
        jrn.set_tap(comp.note_event)
        jrn.on_close(comp.close)
    else:
        rollup_mod.uninstall()
    # cross-run regression watchdog: live windowed digests vs the pinned
    # baseline rollup — both the target and the baseline must be set,
    # and an unreadable baseline degrades to a logged warning, never a
    # refused job (observability must not take down what it observes)
    baseline_path = getattr(cfg, "baseline_path", "")
    threshold = getattr(cfg, "slo_regression", 0.0)
    rollup_mod.uninstall_regression()
    if baseline_path and threshold > 1:
        baseline = rollup_mod.load_baseline(baseline_path)
        if baseline is None or not baseline.get("digests"):
            rollup_mod.log.warning(
                "obs-baseline %r has no readable rollup digests; "
                "regression watchdog disabled", baseline_path)
        else:
            rollup_mod.install_regression(rollup_mod.RegressionWatchdog(
                baseline, threshold=threshold,
                hysteresis=cfg.slo_hysteresis,
                plane=plane, worker=worker_index,
            ))
    profile_mod.configure(cfg.journal_path or None, plane=plane,
                          worker=worker_index)
    return tracer, jrn
