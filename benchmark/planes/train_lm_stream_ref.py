"""Plane ``train_lm_stream_ref``: ``train_lm_stream``'s epochs of the
streamed trainer over gzip shards of token rows, for a ``ModelType:
hybrid_lm`` configuration that names its own plain reference.

Everything but the reference is ``benchmark/planes/train_lm_stream.py``'s
and is imported from there: the CLI arguments and the settings they
resolve to, the reference a row at a time, Adam's first move and first
moment against the trainer's, the comparison and its limits.  That
plane's ``run`` imports ``benchmark.reference.hybrid_lm`` by name; this
one takes the module from the configuration's ``check.reference``
(``benchmark/reference/<name>.py``, with ``make_loss(cfg, precision,
with_grad)``), so a further decoder configuration brings its reference
and needs no further plane.

``prepare`` asks the program, before JAX is imported, whether its
``HybridLMConfig`` reads the configuration's keys: a program that does not
(the parent of the PR that added them) ends there, at once, with no
result.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys
import time

from benchmark import gen, gen_tokens
from benchmark.planes import train_stream
from benchmark.planes.train_lm_stream import (
    EXIT_NO_RESULT,
    STEP_PATTERN,
    by_rows,
    cli_argv,
    compare,
    first_moment,
    resolved_settings,
    update_errors,
)

_perf = time.perf_counter


def reference_of(config: dict):
    """The configuration's plain reference, ``benchmark/reference/<name>``
    of its ``check.reference``."""
    return importlib.import_module(
        "benchmark.reference." + config["check"]["reference"])


def _no_result(why: str):
    print(f"benchmark: no result: {why}", file=sys.stderr, flush=True)
    raise SystemExit(EXIT_NO_RESULT)


def prepare(ctx) -> None:
    """Shards and ModelConfig.json; no JAX yet."""
    from shifu_tensorflow_tpu.config import model_config

    family = getattr(model_config, "HybridLMConfig", None)
    if family is None:
        _no_result("this program has no ModelType hybrid_lm")
    try:
        family.from_json(ctx.config["model_config"]["train"]["params"])
    except (ValueError, KeyError, TypeError) as e:
        _no_result("this program's HybridLMConfig does not read the "
                   f"configuration ({type(e).__name__}: {e})")
    ctx.ensure_native()
    t = ctx.traffic
    if int(t["rows"]) % int(t["batch"]):
        raise SystemExit(f"{ctx.cell['name']}: {t['rows']} rows leave a "
                         f"remainder batch at batch {t['batch']}")
    paths, reused = gen_tokens.ensure_shards(
        os.path.join(ctx.work_dir, "data"), ctx.seed, int(t["rows"]),
        int(t["shards"]), ctx.config["data"])
    ctx.state["paths"], ctx.state["shards_reused"] = paths, reused
    mc_path = os.path.join(ctx.work_dir, "ModelConfig.json")
    with open(mc_path, "w") as f:
        json.dump(ctx.config["model_config"], f)
    ctx.state["model_config_path"] = mc_path


def run(ctx) -> dict:
    import jax
    from flax.core import meta

    from shifu_tensorflow_tpu.config import keys as K
    from shifu_tensorflow_tpu.data.autotune import install_ingest_autotuner
    from shifu_tensorflow_tpu.data.dataset import ShardStream
    from shifu_tensorflow_tpu.obs import install_obs
    from shifu_tensorflow_tpu.obs import trace as obs_trace
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train import __main__ as cli
    from shifu_tensorflow_tpu.train import make_trainer

    t, paths = ctx.traffic, ctx.state["paths"]
    batch_size = int(t["batch"])
    argv = cli_argv(ctx, os.path.dirname(paths[0]),
                    ctx.state["model_config_path"])
    args, conf, model_config, schema = train_stream.resolve(argv)

    # ---- as run_single builds it
    mesh_spec = conf.get(K.MESH_SHAPE, K.DEFAULT_MESH_SHAPE)
    mesh = make_mesh(mesh_spec) if mesh_spec != "none" else None
    install_obs(cli.resolve_obs(args, conf), plane="train", job="bench")
    tracer = None
    if ctx.trace:
        from benchmark.tracing import AnnotatingTracer

        tracer = obs_trace.install(AnnotatingTracer())
    extras = cli.trainer_extras(args, conf)
    extras.pop("dtype_name")
    trainer = make_trainer(
        model_config, schema.num_features,
        feature_columns=schema.feature_columns, mesh=mesh, seed=args.seed,
        **extras)
    settings = resolved_settings(args, conf, model_config)
    feature_dtype = settings["stream_feature_dtype"]
    if feature_dtype != "float32":
        raise SystemExit(f"{ctx.cell['name']}: the stream would carry "
                         f"{feature_dtype}: token ids need float32")
    ing = cli.resolve_ingest(args, conf)
    widths, stats_sink = install_ingest_autotuner(
        trainer, ing["readers"], ing["decode_workers"], ing["prefetch"],
        autotune=ing["autotune"], fallback_prefetch=trainer.prefetch_depth)
    cache_dir = conf.get(K.CACHE_DIR)

    def make_stream(epoch: int):
        return ShardStream(
            paths, schema, batch_size, valid_rate=0.0, emit="train",
            salt=args.seed, cache_dir=cache_dir, feature_dtype=feature_dtype,
            shuffle_rows=ing["shuffle_rows"],
            shuffle_seed=args.seed + epoch, stats_sink=stats_sink,
            **widths())

    epoch_no = [0]

    def one_epoch(source=make_stream):
        """One turn of ``Trainer.fit_stream``'s own loop."""
        e = epoch_no[0]
        epoch_no[0] += 1
        return trainer.fit_stream(source, None, epochs=e + 1,
                                  start_epoch=e)[0]

    # ---- the check: the reference on the system's parameters, step by step
    cfg = ctx.config["model_config"]["train"]["params"]
    check = ctx.config["check"]
    ref = reference_of(ctx.config)
    stated = check.get("stated_precision", "default")
    batches = gen.first_batches(paths, batch_size,
                                int(t.get("check_steps", 2)),
                                schema.num_features)
    # two reference programs, both of one row: the truth's loss, and the
    # stated precision's loss with its gradient (later steps drop the
    # gradient: a third program would cost more to compile than it saves)
    truth_loss = ref.make_loss(cfg, "highest")
    stated_grad = ref.make_loss(cfg, stated, with_grad=True)
    truth_losses, stated_losses, sys_losses, leaf_errors = [], [], [], {}
    ref_peak = None
    for i, batch in enumerate(batches):
        live = meta.unbox(trainer.state.params)
        truth_losses.append(by_rows(truth_loss, live, batch))
        if i == 0:
            loss, grads_host = by_rows(stated_grad, live, batch,
                                       with_grad=True)
            stated_losses.append(loss)
            before_host = jax.device_get(live)
            ref_peak = ctx.memory_peak()
        else:
            stated_losses.append(by_rows(stated_grad, live, batch))
        del live
        sys_losses.append(float(
            one_epoch(lambda _e, b=batch: [b]).training_loss))
        if i == 0:
            leaf_errors = update_errors(
                before_host, grads_host, meta.unbox(trainer.state.params),
                float(cfg["LearningRate"]),
                first_moment(trainer.state.opt_state))
            del before_host, grads_host
    ctx.memory_peak()
    checked = compare(truth_losses, stated_losses, sys_losses, leaf_errors,
                      check)

    # ---- warm-up: whole epochs; the first builds the shard cache
    warm = []
    for _ in range(int(t.get("warm_epochs", 1))):
        t0 = _perf()
        stats = one_epoch()
        warm.append({"epoch_s": _perf() - t0, "loss": stats.training_loss})
    jax.block_until_ready(trainer.state.params)
    ctx.memory_peak()
    if tracer is not None:
        tracer.totals.clear()

    # ---- the window
    steps_per_epoch = int(t["rows"]) // batch_size
    epochs, steps_done, bad_steps = [], 0, 0
    step0 = int(jax.device_get(trainer.state.step))
    with ctx.window() as win:
        while win.elapsed() < ctx.seconds:
            t0 = _perf()
            stats = one_epoch()
            epochs.append({"epoch_s": _perf() - t0,
                           "loss": stats.training_loss})
            steps_done += steps_per_epoch
            if not math.isfinite(stats.training_loss):
                bad_steps += steps_per_epoch
    if int(jax.device_get(trainer.state.step)) - step0 != steps_done:
        raise SystemExit(
            f"{ctx.cell['name']}: the optimizer counted "
            f"{int(trainer.state.step) - step0} updates in the window, "
            f"the stream should have given {steps_done}")

    spans = tracer.cumulative() if tracer is not None else {}
    counters = {k: [float(x) for x in v]
                for k, v in trainer.epoch_counters.items()}
    if counters:
        spans["@counters"] = counters
    if ctx.trace and ctx.state.get("trace_dir"):
        from shifu_tensorflow_tpu.obs import profile

        reduced = profile.phases(ctx.state["trace_dir"])
        if reduced:  # empty off the TPU: the readers then read nothing
            spans["@phases_ms"] = reduced["phases_ms"]
            spans["@step_ms"] = reduced["step_ms"]
    rows_done = steps_done * batch_size
    return {
        "attempted": steps_done,
        "failed": bad_steps,
        "correct": checked["ok"] and bad_steps == 0,
        "end_to_end": {
            "train_rows_per_s": rows_done / win.seconds
            / int(ctx.cell["chips"]),
        },
        "spans": spans,
        "span_names": sorted(tracer.names) if tracer is not None else [],
        "step_pattern": STEP_PATTERN,
        "info": {
            "check": checked, "warm": warm, "epochs": epochs[:12],
            "epochs_in_window": len(epochs),
            "rows": rows_done, "batch": batch_size,
            "tokens_per_row": schema.num_features,
            "shards_reused": ctx.state["shards_reused"],
            "memory_peak_after_reference": ref_peak,
            "ingest": widths(), "prefetch_depth": trainer.prefetch_depth,
            "settings": settings,
            "counters_last_epoch": {k: v[:4] for k, v in counters.items()},
            "phases_ms": spans.get("@phases_ms"),
        },
    }
