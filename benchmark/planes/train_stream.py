"""Plane ``train_stream``: epochs of the streamed trainer over gzip shards.

The path is the one ``python -m shifu_tensorflow_tpu.train --stream``
takes: the arguments a user would type go through the CLI's own parser
and resolvers (``load_conf``, ``resolve_schema``, ``trainer_extras``,
``resolve_ingest``, ``install_ingest_autotuner``, ``make_trainer``), and
each epoch is ``ShardStream`` + ``Trainer.train_epoch`` as
``Trainer.fit_stream`` runs them (checkpointing and validation are off:
the cell's file says so).  Nothing here reaches past those entry points.

Set-up: shards from the seed (forked writers, before JAX is touched); the
plain reference's first steps; the system's first steps (which compile the
one step shape the stream uses); one or more whole epochs that build the
program's shard cache.  The window then spans whole epochs over the warm
cache, opens on a synced state and closes on the value fetch
``train_epoch`` ends with.
"""

from __future__ import annotations

import json
import math
import os
import time

import numpy as np

from benchmark import gen

_perf = time.perf_counter
#: the jitted step's name in the device trace's module line
STEP_PATTERN = r"jit_train_step"


def _columns(data: dict) -> tuple[list[int], int, int]:
    """(feature columns, target column, weight column) of the shards."""
    n = int(data["numeric"]) + int(data["categorical"])
    return list(range(1, n + 1)), 0, n + 1


def cli_argv(ctx, shard_dir: str, model_config_path: str) -> list[str]:
    """What a user types for this cell (checkpoint, export and validation
    flags left out)."""
    features, target, weight = _columns(ctx.config["data"])
    argv = [
        "--training-data-path", shard_dir,
        "--model-config", model_config_path,
        "--feature-columns", ",".join(map(str, features)),
        "--target-column", str(target),
        "--weight-column", str(weight),
        "--delimiter", "|",
        "--stream",
        # inside the data slot, so a new seed's shards wipe the old cache
        "--cache-dir", os.path.join(ctx.work_dir, "data", "shard_cache"),
        "--batch-size", str(int(ctx.traffic["batch"])),
        "--seed", str(ctx.seed),
    ]
    if ctx.config.get("mesh"):
        argv += ["--mesh", ctx.config["mesh"]]
    if ctx.trace:
        argv += ["--obs"]
    return argv


def resolve(argv: list[str]):
    """The train CLI's own resolution of ``argv``: (args, conf,
    model_config, schema).  Imports no JAX."""
    from shifu_tensorflow_tpu.config import keys as K
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.train import __main__ as cli

    args = cli.build_parser().parse_args(argv)
    conf = cli.load_conf(args)
    model_config = ModelConfig.load(conf.get(K.MODEL_CONF))
    schema, _ = cli.resolve_schema(args, model_config)
    return args, conf, model_config, schema


def resolved_settings(args, conf, model_config) -> dict:
    """The settings that decide which path the trainer takes, as the CLI
    resolves them; the configuration's file records the same under
    ``settings`` and a test holds the two equal."""
    from shifu_tensorflow_tpu.config import keys as K
    from shifu_tensorflow_tpu.data.dataset import resolve_stream_feature_dtype
    from shifu_tensorflow_tpu.train import __main__ as cli

    extras = cli.trainer_extras(args, conf)
    ingest = cli.resolve_ingest(args, conf)
    return {
        "dtype": extras["dtype_name"],
        "prefetch_depth": extras["prefetch_depth"],
        "scan_steps": extras["scan_steps"],
        "accum_steps": extras["accum_steps"],
        "health_check_finite": bool(extras["health"].check_finite),
        "readers": ingest["readers"],
        "decode_workers": ingest["decode_workers"],
        "data_prefetch": ingest["prefetch"],
        "data_autotune": ingest["autotune"],
        "shuffle_rows": ingest["shuffle_rows"],
        "stream_feature_dtype": resolve_stream_feature_dtype(
            conf.get(K.STREAM_FEATURE_DTYPE, K.DEFAULT_STREAM_FEATURE_DTYPE),
            uses_feature_hashing=model_config.params.uses_feature_hashing,
            has_normalization_stats=False),
        "mesh": conf.get(K.MESH_SHAPE, K.DEFAULT_MESH_SHAPE),
        "valid_rate": cli.resolve_valid_rate(args, model_config),
    }


def prepare(ctx) -> None:
    """Everything that forks or needs no chip: the native parser, the
    shards, the ModelConfig.json."""
    ctx.ensure_native()
    t = ctx.traffic
    if int(t["rows"]) % int(t["batch"]):
        raise SystemExit(
            f"{ctx.cell['name']}: {t['rows']} rows leave a remainder batch "
            f"at batch {t['batch']}, which would compile inside the window")
    paths, reused = gen.ensure_shards(
        os.path.join(ctx.work_dir, "data"), ctx.seed, int(t["rows"]),
        int(t["shards"]), ctx.config["data"], workers=ctx.cpu_workers)
    ctx.state["paths"] = paths
    ctx.state["shards_reused"] = reused
    os.makedirs(ctx.work_dir, exist_ok=True)
    mc_path = os.path.join(ctx.work_dir, "ModelConfig.json")
    with open(mc_path, "w") as f:
        json.dump(ctx.config["model_config"], f)
    ctx.state["model_config_path"] = mc_path


def _probe_rows(model_params: dict, features, x: np.ndarray) -> dict:
    """For each table too large to compare whole, the rows the first
    batch touches (at most 4,096 of them)."""
    from benchmark.reference import tabular

    ids = tabular.ids_for(model_params, features, x)
    return {key: np.unique(v.reshape(-1))[:4096] for key, v in ids.items()}


def _probe(params, rows: dict) -> dict[str, np.ndarray]:
    """Small host copies of a parameter tree: small leaves whole, the two
    hashed tables at the probe rows."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("hashed_columns/table") and "emb" in rows:
            leaf = leaf[rows["emb"]]
        elif name.endswith("wide_cross/table") and "cross" in rows:
            leaf = leaf[rows["cross"]]
        out[name] = np.asarray(jax.device_get(leaf), np.float64)
    return out


def compare(truth_losses, stated_losses, sys_losses, init, stated, sys,
            check: dict) -> dict:
    """The comparison that decides ``correct`` for a training cell.

    The reference runs twice from the same parameters: at ``highest``
    matmul precision (the truth) and at the precision the configuration
    states for the program.  The system's first losses must be within
    ``loss_rtol`` of the truth (a floor the stated precision sets) and
    within ``stated_loss_rtol`` of the stated-precision reference, which is
    what fails a step computed in a lower precision than the file states.
    Updates: for each parameter (the hashed tables at the probe rows),
    ``|sys - stated| / |stated - init|`` (L2 norms) within
    ``update_rtol``: what the optimizer moved must be what the reference
    moved, which a dropped wide or cross term (zero-initialised, so
    invisible in a first loss), a wrong bucket or a wrong Adam moment
    fails outright.  A leaf of fewer than 64 elements (the output bias,
    the 3-wide linear kernel) gets ``small_leaf_update_rtol``: its
    gradient is one sum over the batch that can nearly cancel, and Adam's
    normalised step then magnifies rounding (0.075 seen once on the chip)
    while a dropped term is still an error of 1 and misplaced wide
    columns of 0.46."""
    def worst(ref_losses):
        return max(abs(a - b) / max(abs(b), 1e-12)
                   for a, b in zip(sys_losses, ref_losses))

    truth_err, stated_err = worst(truth_losses), worst(stated_losses)
    finite = all(map(math.isfinite, sys_losses))
    update_err, small_err, worst_leaf = 0.0, 0.0, None
    for name in stated:
        moved = float(np.linalg.norm(stated[name] - init[name]))
        off = float(np.linalg.norm(sys[name] - stated[name]))
        err = off / moved if moved > 0 else (0.0 if off == 0 else math.inf)
        if stated[name].size < 64:
            small_err = max(small_err, err)
        elif err > update_err:
            update_err, worst_leaf = err, name
    ok = (finite and truth_err <= float(check["loss_rtol"])
          and stated_err <= float(check["stated_loss_rtol"])
          and update_err <= float(check["update_rtol"])
          and small_err <= float(check["small_leaf_update_rtol"]))
    return {"ok": bool(ok), "loss_rel_err": truth_err,
            "stated_loss_rel_err": stated_err,
            "update_rel_err": update_err, "update_worst_leaf": worst_leaf,
            "small_leaf_update_rel_err": small_err,
            "truth_losses": list(truth_losses),
            "stated_losses": list(stated_losses),
            "sys_losses": list(sys_losses)}


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from benchmark.reference import tabular
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
    args, conf, model_config, schema = resolve(argv)

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
    if trainer.align_batch_size(batch_size) != batch_size:
        raise SystemExit(f"batch {batch_size} does not divide over {mesh}")
    settings = resolved_settings(args, conf, model_config)
    feature_dtype = settings["stream_feature_dtype"]
    if feature_dtype != "float32":
        raise SystemExit(f"{ctx.cell['name']}: the stream would carry "
                         f"{feature_dtype}; the reference is fed float32")
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
        """One turn of ``Trainer.fit_stream``'s own loop (health guard,
        ``train_epoch``, autotuner, the epoch's bookkeeping): returns its
        ``EpochStats``."""
        e = epoch_no[0]
        epoch_no[0] += 1
        return trainer.fit_stream(source, None, epochs=e + 1,
                                  start_epoch=e)[0]

    # ---- the reference first, then freed; then the system's first steps
    model_params = ctx.config["model_config"]["train"]["params"]
    steps = int(t.get("check_steps", 4))
    batches = gen.first_batches(paths, batch_size, steps,
                                schema.num_features)
    rows = _probe_rows(model_params, schema.feature_columns, batches[0]["x"])
    live = meta.unbox(trainer.state.params)
    init = _probe(live, rows)
    truth_losses = tabular.reference_steps(
        jax.tree.map(jnp.copy, live), model_params, schema.feature_columns,
        batches, "highest")[0]
    stated_losses, ref_params = tabular.reference_steps(
        jax.tree.map(jnp.copy, live), model_params, schema.feature_columns,
        batches, ctx.config["check"].get("stated_precision", "default"))
    stated = _probe(ref_params, rows)
    del ref_params, live
    ref_peak = ctx.memory_peak()
    sys_losses = [float(one_epoch(lambda _e, b=b: [b]).training_loss)
                  for b in batches]
    ctx.memory_peak()
    check = compare(truth_losses, stated_losses, sys_losses, init, stated,
                    _probe(meta.unbox(trainer.state.params), rows),
                    ctx.config["check"])

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
    rows_done = steps_done * batch_size
    chips = int(ctx.cell["chips"])
    return {
        "attempted": steps_done,
        "failed": bad_steps,
        "correct": check["ok"] and bad_steps == 0,
        "end_to_end": {
            "train_rows_per_s": rows_done / win.seconds / chips,
        },
        "spans": tracer.cumulative() if tracer is not None else {},
        "span_names": sorted(tracer.names) if tracer is not None else [],
        "step_pattern": STEP_PATTERN,
        "info": {
            "check": check, "warm": warm, "epochs": epochs[:12],
            "epochs_in_window": len(epochs),
            "rows": rows_done, "batch": batch_size,
            "shards_reused": ctx.state["shards_reused"],
            "memory_peak_after_reference": ref_peak,
            "ingest": widths(), "prefetch_depth": trainer.prefetch_depth,
            "settings": settings,
        },
    }
