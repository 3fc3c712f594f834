"""Plane ``train_lm_stream``: epochs of the streamed trainer over gzip
shards of token rows, for ``ModelType: hybrid_lm``.

The path is ``train_stream``'s — what ``python -m shifu_tensorflow_tpu.train
--stream`` takes: the CLI's own parser and resolvers, ``make_trainer``,
``ShardStream`` + ``Trainer.fit_stream`` one epoch at a time — with rows
that are sequences of token ids and a check against
``benchmark/reference/hybrid_lm.py``.

The check, sized to fit beside a trainer that rests at 8 GB of a 16 GB
chip (the reference's own moments would not): for each of the first
``check_steps`` batches the reference's loss on the SYSTEM's current
parameters, at ``highest`` matmul precision (the truth) and at the
precision the configuration states, against the loss the system's step
reports; and for the first step, per parameter leaf, from the reference's
gradient (stated precision): what Adam moved, against the first Adam move
in closed form, ``-lr g / (|g| + eps)`` — ``|(after - before) - move| /
|move|`` (L2, computed on the device a leaf at a time, the parameters
before and the gradient parked on the host meanwhile) is held to
``update_rtol`` (``small_leaf_update_rtol`` for a leaf of at most
``small_leaf`` elements) — and the gradient the step applied, read back
from Adam's first moment after that step (``(1 - b1) g`` exactly), against
the reference's: that move is about ``-lr sign(g)``, blind to a gradient
that is a positive multiple of the right one, so the moment's norm is held
to ``grad_norm_rtol`` of the reference's, leaf by leaf, and its distance
pooled over every element to ``pooled_grad_rtol``.  The embedding is
compared whole: a row no token of the batch touches has gradient 0 and
moves by 0 in both.

A traced run reduces its own capture with ``obs.profile.phases`` once the
window has closed and hands the per-phase medians and the step's counters
to the metric readers through the result's ``spans`` (keys ``@phases_ms``,
``@step_ms``, ``@counters``).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

from benchmark import gen, gen_tokens
from benchmark.planes import train_stream

_perf = time.perf_counter
STEP_PATTERN = train_stream.STEP_PATTERN
EXIT_NO_RESULT = 3


def cli_argv(ctx, shard_dir: str, model_config_path: str) -> list[str]:
    """What a user types for this cell: ``train_stream``'s arguments with
    the row's token ids for feature columns (target 0, ids 1 .. n, weight
    n + 1)."""
    columns = {"numeric": int(ctx.config["data"]["tokens_per_row"]),
               "categorical": 0}
    argv = train_stream.cli_argv(
        dataclasses.replace(ctx, config={**ctx.config, "data": columns}),
        shard_dir, model_config_path)
    if ctx.traffic.get("dtype"):  # lm_stream_s4096_b2_bf16, the control
        argv += ["--dtype", ctx.traffic["dtype"]]
    return argv


def resolved_settings(args, conf, model_config) -> dict:
    """``train_stream``'s settings, with the transport dtype resolved as
    the CLI resolves it for rows that carry ids."""
    from shifu_tensorflow_tpu.config import keys as K
    from shifu_tensorflow_tpu.data.dataset import resolve_stream_feature_dtype

    settings = train_stream.resolved_settings(args, conf, model_config)
    settings["stream_feature_dtype"] = resolve_stream_feature_dtype(
        conf.get(K.STREAM_FEATURE_DTYPE, K.DEFAULT_STREAM_FEATURE_DTYPE),
        uses_feature_hashing=model_config.params.features_carry_ids,
        has_normalization_stats=False)
    return settings


def prepare(ctx) -> None:
    """Shards and ModelConfig.json; no JAX yet.  A program without the
    family (the parent of the PR that added it) ends here, at once."""
    from shifu_tensorflow_tpu.config import model_config

    if not hasattr(model_config, "HybridLMConfig"):
        print("benchmark: no result: this program has no ModelType "
              "hybrid_lm", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_NO_RESULT)
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


def by_rows(fn, params, batch, with_grad: bool = False):
    """The reference on the whole batch, a row at a time: the loss is the
    mean over equally long rows, so the rows' losses and gradients
    average.  One row's backward pass fits beside the trainer; the
    batch's does not.  ``fn`` gives a loss, or ``(loss, gradients)`` of
    which ``with_grad`` keeps the second, summed on the host."""
    import jax

    rows = batch["x"].shape[0]
    loss, total = 0.0, None
    for r in range(rows):
        out = fn(params, {k: v[r:r + 1] for k, v in batch.items()})
        row_loss, grads = out if isinstance(out, tuple) else (out, None)
        loss += float(row_loss) / rows
        if with_grad:
            grads = jax.device_get(grads)
            total = (grads if total is None
                     else jax.tree.map(np.add, total, grads))
        del out, grads
    if not with_grad:
        return loss
    return loss, jax.tree.map(lambda g: g / np.float32(rows), total)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _ratio(part2: float, whole2: float) -> float:
    """sqrt(part2 / whole2); nothing over nothing is 0."""
    if whole2 > 0:
        return math.sqrt(part2 / whole2)
    return 0.0 if part2 == 0 else math.inf


#: one leaf's readings: the update error and its two squared norms; the
#: squared norms of (system's first moment - reference's), the reference's
#: and the system's
Leaf = collections.namedtuple(
    "Leaf", "err size off2 moved2 grad_off2 grad_ref2 grad_sys2",
    defaults=(0.0, 0.0, 0.0))


def first_moment(opt_state):
    """Adam's first moment in an optax state, shaped like the parameters:
    after the first step from zero it is ``(1 - b1)`` x the gradient that
    step applied, magnitude included."""
    import jax
    from flax.core import meta

    held = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if not held:
        raise SystemExit("the optimizer keeps no first moment to read the "
                         "applied gradient from")
    return meta.unbox(held[0].mu)


def update_errors(before_host, grads_host, after, lr: float,
                  moment) -> dict:
    """``{leaf: Leaf}``: ``|after - before - move| / |move|`` with ``move``
    the reference's first Adam move from its gradient, and the squared
    distance of the system's first ``moment`` after that step from the
    reference's ``(1 - b1) g`` with both squared norms; one leaf on the
    device at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import hybrid_lm as ref

    def sq(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    @jax.jit
    def norms(after, before, grad, mu):
        move = ref.adam_first_move(grad, lr)
        want = ref.adam_first_moment(grad)
        return (sq(after - before - move), sq(move),
                sq(mu - want), sq(want), sq(mu))

    out = {}
    flat = jax.tree_util.tree_flatten_with_path(after)[0]
    for (path, leaf), before, grad, mu in zip(
            flat, jax.tree.leaves(before_host), jax.tree.leaves(grads_host),
            jax.tree.leaves(moment)):
        off2, moved2, *grad2 = (float(v) for v in
                                norms(leaf, before, grad, mu))
        out[leaf_name(path)] = Leaf(_ratio(off2, moved2), int(leaf.size),
                                    off2, moved2, *grad2)
    return out


def compare(truth_losses, stated_losses, sys_losses, leaf_errors: dict,
            check: dict) -> dict:
    """``train_stream.compare``'s scheme on what this plane can hold: the
    losses against both references; the per-leaf update errors against
    ``update_rtol`` (``small_leaf_update_rtol`` at most ``small_leaf``
    elements), which a dropped term or a wrong mixer fails outright (an
    error of about 1 in the leaves it reaches); and the same error pooled
    over every parameter, ``sqrt(sum |off|^2 / sum |move|^2)``, against
    ``pooled_update_rtol``: over 667 M elements it hardly varies with the
    seed, where a single small leaf's error does.  Adam's first move is a
    sign, so the gradient's size is held apart: the norm of the system's
    first moment within ``grad_norm_rtol`` of the reference's on every
    leaf (a backward pass that loses a factor reads that factor), and
    their distance pooled over every element within ``pooled_grad_rtol``
    (what tells one matmul precision from the next).  ``stated_loss_rtol``
    is optional: a cell whose loss the precision hardly moves leaves it
    out (the error is still reported)."""
    def worst(ref_losses):
        return max(abs(a - b) / max(abs(b), 1e-12)
                   for a, b in zip(sys_losses, ref_losses))

    truth_err, stated_err = worst(truth_losses), worst(stated_losses)
    small = int(check.get("small_leaf", 0))
    leaves = leaf_errors.values()
    big = {n: v.err for n, v in leaf_errors.items() if v.size > small}
    little = [v.err for v in leaves if v.size <= small]
    worst_leaf = max(big, key=big.get)
    update_err, small_err = big[worst_leaf], max(little, default=0.0)
    moved2 = sum(v.moved2 for v in leaves)
    pooled = (_ratio(sum(v.off2 for v in leaves), moved2) if moved2 > 0
              else math.inf)
    norm_off = {n: abs(_ratio(v.grad_sys2, v.grad_ref2) - 1.0)
                if v.grad_ref2 > 0 else _ratio(v.grad_sys2, 0.0)
                for n, v in leaf_errors.items()}
    norm_leaf = max(norm_off, key=norm_off.get)
    pooled_grad = _ratio(sum(v.grad_off2 for v in leaves),
                         sum(v.grad_ref2 for v in leaves))
    ok = (all(map(math.isfinite, sys_losses))
          and truth_err <= float(check["loss_rtol"])
          and stated_err <= float(check.get("stated_loss_rtol", math.inf))
          and update_err <= float(check["update_rtol"])
          and small_err <= float(check["small_leaf_update_rtol"])
          and pooled <= float(check["pooled_update_rtol"])
          and norm_off[norm_leaf] <= float(check["grad_norm_rtol"])
          and pooled_grad <= float(check["pooled_grad_rtol"]))
    return {"ok": bool(ok), "loss_rel_err": truth_err,
            "stated_loss_rel_err": stated_err,
            "update_rel_err": update_err, "update_worst_leaf": worst_leaf,
            "small_leaf_update_rel_err": small_err,
            "pooled_update_rel_err": pooled,
            "grad_norm_rel_err": norm_off[norm_leaf],
            "grad_norm_worst_leaf": norm_leaf,
            "pooled_grad_rel_err": pooled_grad,
            "truth_losses": list(truth_losses),
            "stated_losses": list(stated_losses),
            "sys_losses": list(sys_losses),
            "leaf_update_rel_err": {n: round(v.err, 4) for n, v in
                                    sorted(leaf_errors.items(),
                                           key=lambda kv: -kv[1].err)},
            "leaf_grad_rel_err": {
                n: round(_ratio(v.grad_off2, v.grad_ref2), 4) for n, v in
                sorted(leaf_errors.items(), key=lambda kv: -_ratio(
                    kv[1].grad_off2, kv[1].grad_ref2))}}


def run(ctx) -> dict:
    import jax
    from flax.core import meta

    from benchmark.reference import hybrid_lm as ref
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
