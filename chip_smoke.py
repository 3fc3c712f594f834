"""Chip smoke: train -> export -> serve on the attached TPU, end to end.

The quickest proof that the system still starts on the chip.  It drives
the main path once through the entry points a user calls, at the full
width of the flagship tabular model (30 features, hidden 256/128/64, five
hashed embedding columns of dim 8 into a 1,048,576-row table, Adam, batch
16,384), from data it generates from ``--seed``:

  probe     the process that holds the chip names it (and must name a
            TPU), times one 20-matmul chain closed by
            ``jax.block_until_ready`` and by ``true_sync``, and reads
            ``memory_stats()`` and the AOT compile-environment fingerprint
  native    ``make -B -C cpp`` from source; the streamed path's parser
            must load (the run does not pass on the Python parser)
  data      gzip PSV shards, ``target|f0..f29|weight``
  train     ``python -m shifu_tensorflow_tpu.train --stream --cache-dir
            --checkpoint-dir`` for the first epochs
  resume    the same command again: restores the checkpoint, finishes the
            epochs, ``--export-dir --export-aot``; compiles the programs
            the first child compiled, so it must report cache hits
  serve     ``python -m shifu_tensorflow_tpu.serve --model-dir`` on that
            export, answering /score requests on three bucket rungs
  eval      ``EvalModel`` on the same rows; scores must agree, with zero
            AOT fallbacks on the machine that wrote the executables
  sequence  two train steps of the ModelType=sequence model (d_model 128,
            4 heads, 2 blocks, S=1024) under SeqAttention full, chunked
            and flash (the Pallas kernel)

``--chips 4`` runs the one path that exists only across chips, and what
it is compared with, and no other phase: the flagship for 50 steps on a
``data:2,model:2`` mesh and on a one-device mesh from the same seed.

One process per chip: this parent never imports JAX (it says so in its
``parent`` line); every phase that needs the chip is a child process that
exits before the next starts.  Every stdout line is one JSON object; the
last is the result.  Any phase that fails makes the script exit non-zero
without a result line.  ``--tiny`` is the rehearsal: small sizes, any
platform, Pallas in interpret mode off the TPU — its result says
``"ok": false`` and names the platform it truly ran on, and it exits 4.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
#: exit code of a rehearsal whose phases all passed: not a chip run
EXIT_REHEARSAL = 4

NUM_FEATURES = 30
EMBED_COLUMNS = 5  # the last five features are category codes


class Sizes:
    """The one place full and rehearsal sizes differ."""

    def __init__(self, tiny: bool):
        self.tiny = tiny
        self.hash_size = 4096 if tiny else 1_048_576
        self.batch = 256 if tiny else 16_384
        self.shards = 2 if tiny else 8
        self.rows_per_shard = 2_048 if tiny else 131_072
        self.first_epochs = 2          # train child; resume child runs
        self.total_epochs = 4          # the rest and exports
        self.score_rows = (1, 7, 40, 300)  # buckets 8, 8, 64, 512
        self.matmul_n = 256 if tiny else 8192
        self.seq_len = 64 if tiny else 1024
        self.seq_d_model = 32 if tiny else 128
        self.seq_batch = 4 if tiny else 32
        self.mesh_steps = 10 if tiny else 50


def flagship_model_config(sizes: Sizes, first_feature_column: int) -> dict:
    """``__graft_entry__._flagship_model_config`` at the real table size;
    ``first_feature_column`` is the data column of feature 0 (1 in the
    PSV shards, whose column 0 is the target; 0 for in-memory rows)."""
    last = first_feature_column + NUM_FEATURES
    return {"train": {
        "numTrainEpochs": sizes.total_epochs,
        "validSetRate": 0.1,
        "params": {
            "NumHiddenLayers": 3,
            "NumHiddenNodes": [256, 128, 64],
            "ActivationFunc": ["relu", "relu", "tanh"],
            "LearningRate": 0.01,
            "Optimizer": "adam",
            "EmbeddingColumnNums": list(range(last - EMBED_COLUMNS, last)),
            "EmbeddingHashSize": sizes.hash_size,
            "EmbeddingDim": 8,
        },
    }}


def synth_rows(rng, n: int):
    """(features (n, 30) float32, labels (n,) int): 25 numeric columns
    and five skewed category codes (a heavy head that an embedding can
    learn, a long tail that touches the table broadly); the label is a
    noisy logistic of both, so the loss has somewhere to fall.  A code
    travels as ``code * 1e-6``: the model hashes a column's float bits
    but also feeds the raw column to the dense net, which a value in
    the millions would saturate."""
    import numpy as np

    numeric = rng.normal(size=(n, NUM_FEATURES - EMBED_COLUMNS))
    codes = np.floor(2_000_000 * rng.random((n, EMBED_COLUMNS)) ** 4)
    w = np.linspace(-1.0, 1.0, NUM_FEATURES - EMBED_COLUMNS)
    logit = numeric @ w * 0.7 + ((codes % 5) - 2).sum(axis=1) * 0.4
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int64)
    x = np.concatenate([numeric, codes * 1e-6], axis=1).astype(np.float32)
    return x, y


def write_shard(path: str, seed: int, shard: int, rows: int) -> None:
    """One gzip PSV shard in bench.py's generator's format."""
    import gzip

    import numpy as np

    x, y = synth_rows(np.random.default_rng([seed, shard]), rows)
    fmt = ("%d|" + "%.5f|" * (NUM_FEATURES - EMBED_COLUMNS)
           + "%.6f|" * EMBED_COLUMNS + "1.0\n")
    body = "".join(
        fmt % (label, *feats) for label, feats in zip(y.tolist(), x.tolist()))
    with gzip.open(path, "wb", compresslevel=1) as f:
        f.write(body.encode())


# ------------------------------------------------------------------ parent


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class PhaseFailed(Exception):
    pass


def run_child(name: str, argv: list[str],
              timeout_s: float) -> tuple[list[str], float]:
    """Run one child to its end; returns (stdout lines, wall seconds).
    Its stderr passes through.  A non-zero exit or a timeout fails the
    phase — the child is gone either way before this returns."""
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, cwd=REPO,
                            text=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise PhaseFailed(f"{name}: no end after {timeout_s:.0f}s") from None
    if proc.returncode != 0:
        sys.stderr.write(out[-4000:])
        raise PhaseFailed(f"{name}: exit code {proc.returncode}")
    return out.splitlines(), time.monotonic() - t0


def self_child(name: str, args, timeout_s: float) -> tuple[dict, float]:
    """A phase of this script that needs the chip: run it as a child and
    return the one JSON object it prints last."""
    argv = [sys.executable, os.path.abspath(__file__), "--child", name,
            "--out", args.out, "--seed", str(args.seed)]
    if args.tiny:
        argv.append("--tiny")
    lines, wall = run_child(name, argv, timeout_s)
    return json.loads(lines[-1]), wall


def read_journal(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def compile_totals(events: list[dict]) -> dict:
    """What obs/compile.py journaled for one child: backend compiles,
    their seconds, and how many the persistent cache served."""
    compiles = [e for e in events if e["event"] == "compile"
                and e.get("kind") != "aot_load"]
    return {
        "compiles": sum(max(1, e.get("parts", 1)) for e in compiles),
        "compile_s": round(sum(e.get("compile_s", 0.0) for e in compiles), 3),
        "cache_hits": sum(e.get("cache_hits", 0) for e in compiles),
    }


_EPOCH = re.compile(r"^epoch (\d+): train_loss=(\S+) valid_loss=(\S+) .* "
                    r"step=(\d+)$")


def train_phase(name: str, args, sizes: Sizes, epochs: int,
                export: bool) -> dict:
    out = args.out
    journal = os.path.join(out, f"{name}.journal")
    argv = [
        sys.executable, "-m", "shifu_tensorflow_tpu.train",
        "--training-data-path", os.path.join(out, "data"),
        "--model-config", os.path.join(out, "ModelConfig.json"),
        "--feature-columns",
        ",".join(str(c) for c in range(1, NUM_FEATURES + 1)),
        "--target-column", "0", "--weight-column", str(NUM_FEATURES + 1),
        "--delimiter", "|", "--stream",
        "--cache-dir", os.path.join(out, "shard_cache"),
        "--checkpoint-dir", os.path.join(out, "checkpoints"),
        "--batch-size", str(sizes.batch), "--epochs", str(epochs),
        "--seed", str(args.seed), "--obs-journal", journal,
    ]
    if export:
        argv += ["--export-dir", os.path.join(out, "export"), "--export-aot"]
    lines, wall = run_child(name, argv, 600)
    epoch_lines = [m.groups() for m in map(_EPOCH.match, lines) if m]
    summary = json.loads(lines[-1])
    if summary.get("state") != "finished" or not epoch_lines:
        raise PhaseFailed(f"{name}: did not finish: {summary}")
    losses = [float(m[1]) for m in epoch_lines]
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise PhaseFailed(f"{name}: training loss not finite: {losses}")
    return {
        "phase": name, "wall_s": round(wall, 2),
        **compile_totals(read_journal(journal)),
        "epochs": [int(m[0]) for m in epoch_lines],
        "steps": int(epoch_lines[-1][3]),
        "first_loss": losses[0], "last_loss": losses[-1],
        "valid_loss": float(epoch_lines[-1][2]),
        "platform": summary["platform"],
        "device_kind": summary["device_kind"],
        "device_count": summary["device_count"],
    }


def serve_phase(args, sizes: Sizes) -> dict:
    """Start the serve CLI on the export, send the requests one after
    the other (each its own dispatch, so each lands on its own bucket),
    stop it, and read what it journaled."""
    import numpy as np

    journal = os.path.join(args.out, "serve.journal")
    rng = np.random.default_rng([args.seed, 99])
    requests = [synth_rows(rng, n)[0] for n in sizes.score_rows]
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "-m", "shifu_tensorflow_tpu.serve",
         "--model-dir", os.path.join(args.out, "export"),
         "--port", "0", "--obs-journal", journal],
        stdout=subprocess.PIPE, cwd=REPO, text=True)
    # warming the ladder comes before the line: a server that never gets
    # there is killed, which ends the readline below
    watchdog = threading.Timer(300, proc.kill)
    watchdog.start()
    try:
        ready = json.loads(proc.stdout.readline() or "{}")
        watchdog.cancel()
        if ready.get("state") != "listening":
            raise PhaseFailed(f"serve: never listened: {ready}")
        ready_s = time.monotonic() - t0
        url = f"http://{ready['host']}:{ready['port']}/score"
        scores = []
        for rows in requests:
            req = urllib.request.Request(
                url, data=json.dumps({"rows": rows.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                scores.append(json.load(resp)["scores"])
        proc.send_signal(signal.SIGTERM)
        stopped = json.loads(proc.communicate(timeout=120)[0]
                             .strip().splitlines()[-1])
    finally:
        watchdog.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    if proc.returncode != 0 or stopped.get("state") != "stopped":
        raise PhaseFailed(f"serve: exit {proc.returncode}: {stopped}")
    with open(os.path.join(args.out, "requests.json"), "w") as f:
        json.dump({"rows": [r.tolist() for r in requests],
                   "scores": scores}, f)
    events = read_journal(journal)
    kinds = [e.get("kind") for e in events if e["event"] == "compile"]
    rungs = sorted({e["bucket"] for e in events
                    if e["event"] == "serve_batch"})
    if len(rungs) < 3:
        raise PhaseFailed(f"serve: fewer than three rungs ran: {rungs}")
    if kinds.count("aot_fallback") or not kinds.count("aot_load"):
        raise PhaseFailed(f"serve: AOT executables not admitted: {kinds}")
    return {
        "phase": "serve", "wall_s": round(time.monotonic() - t0, 2),
        "ready_s": round(ready_s, 2), **compile_totals(events),
        "requests": len(requests), "rows": list(sizes.score_rows),
        "rungs": rungs, "aot_loads": kinds.count("aot_load"),
        "aot_fallbacks": kinds.count("aot_fallback"),
        "platform": next((e["backend"] for e in events
                          if e["event"] == "compile" and "backend" in e),
                         None),
    }


def native_phase() -> dict:
    t0 = time.monotonic()
    make = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "cpp")],
                          capture_output=True, text=True)
    if make.returncode != 0:
        sys.stderr.write(make.stderr[-2000:])
        raise PhaseFailed(
            "native: `make -B -C cpp` failed — no toolchain on this "
            "machine?  The streamed path's parser is on the main path, "
            "and this run does not pass on the Python parser")
    # ctypes only: neither this check nor the loader imports JAX
    run_child("native", [
        sys.executable, "-c",
        "import sys; from shifu_tensorflow_tpu import _native; "
        "sys.exit(0 if _native.load('stpu_data') is not None else 1)"], 180)
    return {"phase": "native", "wall_s": round(time.monotonic() - t0, 2),
            "built": True, "parser_loaded": True}


def data_phase(args, sizes: Sizes) -> dict:
    import multiprocessing

    t0 = time.monotonic()
    root = os.path.join(args.out, "data")
    os.makedirs(root)
    jobs = [(os.path.join(root, f"part-{s:05d}.gz"), args.seed, s,
             sizes.rows_per_shard) for s in range(sizes.shards)]
    with multiprocessing.get_context("spawn").Pool(
            min(sizes.shards, os.cpu_count() or 1)) as pool:
        pool.starmap(write_shard, jobs)
    with open(os.path.join(args.out, "ModelConfig.json"), "w") as f:
        json.dump(flagship_model_config(sizes, first_feature_column=1), f)
    return {"phase": "data", "wall_s": round(time.monotonic() - t0, 2),
            "shards": sizes.shards,
            "rows": sizes.shards * sizes.rows_per_shard,
            "bytes": sum(os.path.getsize(j[0]) for j in jobs)}


def parent(args) -> int:
    sizes = Sizes(args.tiny)
    for needed in ("shifu_tensorflow_tpu", "cpp"):
        if not os.path.isdir(os.path.join(REPO, needed)):
            print(f"chip_smoke: {needed}/ is not beside this script — "
                  f"it drives the repo it sits in", file=sys.stderr)
            return 2
    # nothing of an earlier run is read: the directory is emptied, but
    # only one this script made (it leaves a marker) or an empty one
    marker = os.path.join(args.out, ".chip_smoke_out")
    if os.path.isdir(args.out) and os.listdir(args.out):
        if not os.path.exists(marker):
            print(f"chip_smoke: --out {args.out} holds files this script "
                  f"did not write; give it a directory of its own",
                  file=sys.stderr)
            return 2
        import shutil

        shutil.rmtree(args.out)
    os.makedirs(args.out, exist_ok=True)
    open(marker, "w").close()

    device = None
    try:
        if args.chips == 4:
            mesh, wall = self_child("mesh", args, 900)
            emit({"phase": "mesh", "wall_s": round(wall, 2), **mesh})
            device = mesh["device"]
        else:
            # first, before any line is printed or any work done: a run
            # that finds no accelerator ends here with no result
            probe, wall = self_child("probe", args, 300)
            device = probe.pop("device")
            emit({"phase": "probe", "wall_s": round(wall, 2), **probe})
            emit(native_phase())
            emit(data_phase(args, sizes))
            first = train_phase("train", args, sizes, sizes.first_epochs,
                                export=False)
            emit(first)
            second = train_phase("resume", args, sizes, sizes.total_epochs,
                                 export=True)
            emit(second)
            if second["epochs"][0] != sizes.first_epochs:
                raise PhaseFailed(
                    f"resume: did not restore the checkpoint: ran epochs "
                    f"{second['epochs']}")
            if not second["last_loss"] < first["first_loss"]:
                raise PhaseFailed(
                    f"training loss did not fall: {first['first_loss']} "
                    f"-> {second['last_loss']}")
            if second["cache_hits"] < 1:
                raise PhaseFailed(
                    "resume: compiled what the first child compiled and "
                    "the persistent compile cache served none of it")
            emit(serve_phase(args, sizes))
            for name, limit in (("eval", 300), ("sequence", 600)):
                result, wall = self_child(name, args, limit)
                emit({"phase": name, "wall_s": round(wall, 2), **result})
            for phase in (first, second):
                seen = {"platform": phase["platform"],
                        "kind": phase["device_kind"],
                        "count": phase["device_count"]}
                if seen != device:
                    raise PhaseFailed(
                        f"{phase['phase']} ran on {seen}, probe on {device}")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    emit({"phase": "parent", "jax_imported": "jax" in sys.modules,
          "tiny": args.tiny, "out": args.out})
    ok = device["platform"] == "tpu" and not args.tiny
    emit({"ok": ok, "device": device})
    return 0 if ok else EXIT_REHEARSAL


# ---------------------------------------------------------------- children
# Each runs in its own process, which holds the chip until it exits.


def child_setup(args):
    """Common to every child: the compile cache placed like the CLIs
    place theirs, and the platform check before any work."""
    sys.path.insert(0, REPO)
    from shifu_tensorflow_tpu.obs.compile import apply_persistent_cache

    apply_persistent_cache()
    import jax

    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
            f"rehearse off the chip with --tiny")
    return jax, {"platform": dev.platform, "kind": dev.device_kind,
                 "count": len(jax.devices())}


class CompileCounter:
    """Backend compiles and persistent-cache hits of this process, from
    the same jax.monitoring events obs/compile.py listens to (its
    recorder attributes them to named calls; a child that runs no CLI
    only needs the totals)."""

    def __init__(self):
        import jax.monitoring as monitoring

        from shifu_tensorflow_tpu.obs import compile as obs_compile

        self._events = obs_compile
        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_kw):
        if name.endswith(self._events._COMPILE_EVENT_SUFFIX):
            self.compiles += 1
            self.compile_s += secs

    def _event(self, name, **_kw):
        if name == self._events._CACHE_HIT_EVENT:
            self.cache_hits += 1

    def totals(self) -> dict:
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "cache_hits": self.cache_hits}


def child_probe(args, sizes: Sizes) -> dict:
    jax, device = child_setup(args)
    import statistics

    import jax.numpy as jnp

    from shifu_tensorflow_tpu.export.aot import compile_env_fingerprint
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    n = sizes.matmul_n
    key = jax.random.key(args.seed)
    w = jax.random.normal(key, (n, n), jnp.bfloat16) * (n ** -0.5)
    x0 = jax.random.normal(key, (n, n), jnp.bfloat16)
    step = jax.jit(lambda x, w: x @ w)

    def chain(close) -> float:
        t0 = time.perf_counter()
        x = x0
        for _ in range(20):
            x = step(x, w)
        close(x)
        return time.perf_counter() - t0

    chain(true_sync)  # compile + warm
    by_block = [chain(jax.block_until_ready) for _ in range(5)]
    by_fetch = [chain(true_sync) for _ in range(5)]
    flops = 20 * 2 * n ** 3
    mem = jax.local_devices()[0].memory_stats()
    return {
        "device": device,
        "jax": jax.__version__,
        "sync": {
            "chain": f"20 x ({n}x{n} @ {n}x{n}) bf16, one dispatch each",
            "block_until_ready_s": statistics.median(by_block),
            "true_sync_s": statistics.median(by_fetch),
            "tflops_by_block_until_ready":
                flops / statistics.median(by_block) / 1e12,
            "tflops_by_true_sync": flops / statistics.median(by_fetch) / 1e12,
        },
        # absent (None) on a backend that reports none, never assumed
        "memory_stats": None if not mem else {
            k: mem.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                    "bytes_limit")},
        "aot_fingerprint": compile_env_fingerprint(),
    }


def child_eval(args, sizes: Sizes) -> dict:
    jax, device = child_setup(args)
    import numpy as np

    from shifu_tensorflow_tpu.export.eval_model import EvalModel

    counter = CompileCounter()
    with open(os.path.join(args.out, "requests.json")) as f:
        doc = json.load(f)
    worst = 0.0
    with EvalModel(os.path.join(args.out, "export")) as model:
        for rows, served in zip(doc["rows"], doc["scores"]):
            got = model.compute_batch(np.asarray(rows, np.float32))[:, 0]
            if not np.all(np.isfinite(got)) or got.shape != (len(rows),):
                raise SystemExit(f"eval: bad scores for {len(rows)} rows")
            worst = max(worst, float(np.max(np.abs(got - served))))
        aot = model.aot_stats
    # /score rounds to 6 decimals; the programs are the same executables
    tolerance = 2e-6
    if worst > tolerance:
        raise SystemExit(f"eval: serve and EvalModel disagree by {worst}")
    if aot["fallbacks"] or not aot["loads"] or aot["unusable"]:
        raise SystemExit(f"eval: AOT executables not admitted: {aot}")
    return {**counter.totals(), "requests": len(doc["rows"]),
            "max_abs_diff": worst, "tolerance": tolerance,
            "aot_loads": aot["loads"], "aot_fallbacks": aot["fallbacks"],
            "platform": device["platform"]}


def child_sequence(args, sizes: Sizes) -> dict:
    jax, device = child_setup(args)
    import numpy as np

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from jax.experimental.pallas import tpu as pltpu

    from shifu_tensorflow_tpu.train.trainer import Trainer

    # rehearsal only (child_setup refused a full run off the TPU): the
    # CPU has no Mosaic compiler, and the program never picks the
    # interpreter by itself
    interpret = device["platform"] != "tpu"
    pallas_mode = (pltpu.force_tpu_interpret_mode() if interpret
                   else contextlib.nullcontext())

    step_features = 4
    width = sizes.seq_len * step_features
    rng = np.random.default_rng([args.seed, 7])
    batches = []
    for _ in range(2):
        x = rng.normal(size=(sizes.seq_batch, width)).astype(np.float32)
        y = (x[:, ::step_features].mean(axis=1, keepdims=True) > 0)
        batches.append({"x": x, "y": y.astype(np.float32),
                        "w": np.ones((sizes.seq_batch, 1), np.float32)})
    out = {"seq_len": sizes.seq_len, "d_model": sizes.seq_d_model,
           "batch": sizes.seq_batch, "pallas_interpret": interpret,
           "platform": device["platform"], "attention": {}}
    with pallas_mode:
        for attention in ("full", "chunked", "flash"):
            counter = CompileCounter()
            t0 = time.perf_counter()
            mc = ModelConfig.from_json({"train": {"params": {
                "NumHiddenLayers": 1, "NumHiddenNodes": [8],
                "ActivationFunc": ["relu"], "LearningRate": 0.001,
                "Optimizer": "adam", "ModelType": "sequence",
                "SeqLen": sizes.seq_len, "SeqDModel": sizes.seq_d_model,
                "SeqHeads": 4, "SeqBlocks": 2, "SeqAttention": attention}}})
            trainer = Trainer(mc, width, seed=args.seed)
            losses = [trainer.train_epoch([b])[0] for b in batches]
            if not all(np.isfinite(losses)):
                raise SystemExit(f"sequence/{attention}: loss {losses}")
            out["attention"][attention] = {
                "steps": len(losses), "losses": [float(v) for v in losses],
                "wall_s": round(time.perf_counter() - t0, 2),
                **counter.totals()}
    # same seed, same batches: the three settings compute one function
    first = [v["losses"][0] for v in out["attention"].values()]
    if max(first) - min(first) > 1e-2 * max(abs(v) for v in first):
        raise SystemExit(f"sequence: settings disagree on step 1: {first}")
    return out


def child_mesh(args, sizes: Sizes) -> dict:
    """The four-chip path: data:2 x model:2 over the host's chips, the
    embedding table sharded on ``model``, against a one-device mesh."""
    jax, device = child_setup(args)
    import numpy as np

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.train.checkpoint import NpzCheckpointer
    from shifu_tensorflow_tpu.train.trainer import Trainer

    devices = jax.devices()
    if len(devices) != 4 and not (args.tiny and len(devices) > 4):
        raise SystemExit(f"mesh: needs the host's four chips, JAX has "
                         f"{len(devices)} device(s)")
    mc = ModelConfig.from_json(flagship_model_config(sizes, 0))
    rng = np.random.default_rng([args.seed, 4])
    batches = []
    for _ in range(sizes.mesh_steps):
        x, y = synth_rows(rng, sizes.batch)
        batches.append({"x": x, "y": y[:, None].astype(np.float32),
                        "w": np.ones((sizes.batch, 1), np.float32)})

    def make(spec: str, devs, seed: int) -> Trainer:
        return Trainer(mc, NUM_FEATURES,
                       feature_columns=tuple(range(NUM_FEATURES)),
                       mesh=make_mesh(spec, devices=list(devs)), seed=seed)

    def run(trainer: Trainer) -> list[float]:
        chunk = max(1, sizes.mesh_steps // 5)
        return [float(trainer.train_epoch(batches[i:i + chunk])[0])
                for i in range(0, sizes.mesh_steps, chunk)]

    def table_of(trainer: Trainer):
        flat = jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]
        # a sharded table sits in a flax Partitioned box: ['table'].value
        (leaf,) = [v for path, v in flat
                   if "['table']" in jax.tree_util.keystr(path)]
        return leaf

    t0 = time.perf_counter()
    sharded = make("data:2,model:2", devices[:4], args.seed)
    losses_mesh = run(sharded)
    mesh_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses_one = run(make("data:1", devices[:1], args.seed))
    one_s = time.perf_counter() - t0
    # reduction order differs (two data shards all-reduced, two table
    # halves gathered), and 50 Adam steps carry it forward
    tolerance = 1e-2
    diff = max(abs(a - b) / abs(b) for a, b in zip(losses_mesh, losses_one))
    if not all(np.isfinite(losses_mesh)) or diff > tolerance:
        raise SystemExit(f"mesh: losses {losses_mesh} vs one device "
                         f"{losses_one}: relative difference {diff}")
    if not losses_mesh[-1] < losses_mesh[0]:
        raise SystemExit(f"mesh: loss did not fall: {losses_mesh}")

    table = table_of(sharded)
    shards = [{"device": s.device.id, "rows": list(s.data.shape)[0],
               "start": s.index[0].start or 0}
              for s in table.addressable_shards]
    half = table.shape[0] // 2
    if (len({s["device"] for s in shards}) != 4
            or any(s["rows"] != half for s in shards)
            or sorted({s["start"] for s in shards}) != [0, half]):
        raise SystemExit(f"mesh: table not sharded on model: {shards}")
    placed = sharded._put(batches[0])["x"]
    batch_shards = [{"device": s.device.id, "rows": s.data.shape[0]}
                    for s in placed.addressable_shards]
    if (len({s["device"] for s in batch_shards}) != 4
            or any(s["rows"] != sizes.batch // 2 for s in batch_shards)):
        raise SystemExit(f"mesh: batch not on all four: {batch_shards}")
    state_bytes: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(sharded.state):
        for s in getattr(leaf, "addressable_shards", ()):
            state_bytes[s.device.id] = (state_bytes.get(s.device.id, 0)
                                        + s.data.nbytes)

    # per-shard checkpoint: save, restore onto the same mesh into a
    # trainer that started from other weights
    ckpt_dir = os.path.join(args.out, "mesh_checkpoints")
    with NpzCheckpointer(ckpt_dir) as ckpt:
        ckpt.save(0, sharded.state)
        files = sorted(os.listdir(ckpt_dir))
        other = make("data:2,model:2", devices[:4], args.seed + 1)
        # a copy: on the CPU np.asarray is a view of the device buffer,
        # which restore() frees and may hand to the restored table
        before = np.array(table_of(other))
        next_epoch = other.restore(ckpt)
    want, got = np.asarray(table), np.asarray(table_of(other))
    if (next_epoch != 1 or not np.array_equal(want, got)
            or np.array_equal(before, got)
            or not table_of(other).sharding.is_equivalent_to(
                table.sharding, table.ndim)):
        raise SystemExit(
            f"mesh: checkpoint did not restore the table: next epoch "
            f"{next_epoch}, equal to saved {np.array_equal(want, got)}, "
            f"equal to its own init {np.array_equal(before, got)}, "
            f"sharding {table_of(other).sharding} vs {table.sharding}")

    return {
        "device": device, "steps": sizes.mesh_steps, "batch": sizes.batch,
        "losses_mesh": losses_mesh, "losses_one_device": losses_one,
        "max_rel_diff": diff, "tolerance": tolerance,
        "mesh_s": round(mesh_s, 2), "one_device_s": round(one_s, 2),
        "table_sharding": str(table.sharding.spec),
        "table_shards": shards, "batch_sharding": str(placed.sharding.spec),
        "batch_shards": batch_shards,
        "state_bytes_per_device": state_bytes,
        "memory_in_use_per_device": {
            d.id: (d.memory_stats() or {}).get("bytes_in_use")
            for d in devices[:4]},
        "checkpoint": {"files": files, "restored_next_epoch": next_epoch,
                       "table_equal": True},
    }


CHILDREN = {"probe": child_probe, "eval": child_eval,
            "sequence": child_sequence, "mesh": child_mesh}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded-mesh phase and its one-device "
                         "comparison, and no other phase")
    ap.add_argument("--out", default=os.path.join(REPO, "chip_smoke_out"),
                    help="data, caches, checkpoints and exports go here "
                         "(emptied first)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal: small sizes, any platform; never ok")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.out = os.path.abspath(args.out)
    if args.child:
        emit(CHILDREN[args.child](args, Sizes(args.tiny)))
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
