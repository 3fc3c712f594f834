"""Bulk scoring benchmark: the batch plane vs HTTP /score, worker
scaling, and the exactly-once kill drill (ISSUE 17).

Three phases, one artifact (``BENCH_SCORE.json``):

- **bulk vs HTTP**: the same dataset scored end-to-end by the same
  bundle twice — once through the lease-driven batch plane (one scan,
  shard-sized dispatches, durable digest-sealed output), once through
  the serving plane's HTTP /score the way an operator would actually
  bulk-score with it: read + parse the input files, POST per-request
  batches, format and write the scored rows back out.  Admission is
  outside both windows (the batch arm gets pre-admitted stores, the
  HTTP arm a started + warmed server); the delta is the per-request
  JSON + HTTP + admission tax the batch plane exists to delete.
  Gate: bulk ≥ the HTTP path (``host_capped`` fallback below).
- **worker scaling**: the identical job at 1 vs 2 thread workers.
  On a wide host two scanners ≈ 2x; on this repo's 2-core CI host both
  workers and the driver contend for the same cores, so the measured
  ratio is reported honestly and the gate falls back to the kill-drill
  criterion (``host_capped: true``).
- **kill drill**: REAL scorer processes under
  ``score.read:slow300@1.0,score.commit:torn-write@3``; one scorer is
  SIGKILLed while it provably holds an uncommitted lease.  Gates (never
  host-capped): the job still seals with committed rows == input rows,
  zero duplicate commit tokens, at least one lease reclaim, and output
  BIT-IDENTICAL to an unkilled thread-mode control arm over the same
  drill dataset.

Output contract matches bench.py: every stdout line is a JSON object,
the last the most complete; artifact lands in ``BENCH_SCORE.json``.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import os
import signal
import sys
import tempfile
import threading
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

ARTIFACT = os.path.join(REPO_ROOT, "BENCH_SCORE.json")
N_FEATURES = 8
QUICK = "--quick" in sys.argv[1:]
N_FILES = 4 if QUICK else 8
# rows stay full-size even under --quick: the bulk-vs-HTTP comparison
# needs enough rows that marginal rate, not fixed job setup, decides it
ROWS_PER_FILE = 4000
BATCH_ROWS = 512
HTTP_BATCH = 64
HTTP_THREADS = 4
# the kill drill runs its own small dataset: slow300 drags every read
# check 300ms (that is what guarantees the SIGKILL lands mid-shard), so
# drill time scales with block count, not with the perf dataset
DRILL_FILES = 4
DRILL_ROWS_PER_FILE = 120
DRILL_BATCH_ROWS = 64


def _emit(result: dict, partial: bool = True) -> None:
    out = dict(result)
    if partial:
        out["partial"] = True
    print(json.dumps(out), flush=True)


def _gen_inputs(root: str, n_files: int, rows_per_file: int,
                seed: int = 3) -> int:
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(n_files):
        with open(os.path.join(root, f"in-{i:03d}.psv"), "w") as f:
            for _ in range(rows_per_file):
                f.write("|".join(f"{v:.5f}" for v in rng.random(N_FEATURES))
                        + "\n")
    return n_files * rows_per_file


def _export_bundle(path: str) -> str:
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.export.saved_model import export_native_bundle
    from shifu_tensorflow_tpu.train.trainer import Trainer

    mc = ModelConfig.from_json(
        {"train": {"params": {"NumHiddenLayers": 1, "NumHiddenNodes": [16],
                              "ActivationFunc": ["relu"],
                              "LearningRate": 0.05}}})
    t = Trainer(mc, N_FEATURES, seed=4)
    export_native_bundle(path, t.state.params, mc, N_FEATURES)
    return path


def _blob(out_dir: str) -> bytes:
    parts = sorted(n for n in os.listdir(out_dir)
                   if n.startswith("part-") and n.endswith(".psv"))
    return b"".join(
        open(os.path.join(out_dir, n), "rb").read() for n in parts)


def _bulk_phase(data_dir: str, models_dir: str, work: str) -> dict:
    from shifu_tensorflow_tpu.score.job import run_job
    from shifu_tensorflow_tpu.serve.tenancy.store import admit_batch_tenants

    out: dict = {}
    walls = {}
    # admission (load + verify + warm) happens ONCE, outside the timing
    # window — the HTTP arm's server is equally started + warmed before
    # its window, so both arms measure steady scoring
    stores = admit_batch_tenants(models_dir)
    try:
        # warm the scoring traces at the block shapes the scan will use
        # (the HTTP arm's warm request is the same courtesy)
        tail = ROWS_PER_FILE % BATCH_ROWS or BATCH_ROWS
        for store in stores.values():
            model = store.current().model
            for n in {BATCH_ROWS, tail}:
                model.compute_batch(np.zeros((n, N_FEATURES), np.float32))
        for workers in (1, 2):
            out_dir = os.path.join(work, f"bulk-{workers}w")
            t0 = time.monotonic()
            summary = run_job(data_dir, models_dir, out_dir,
                              workers=workers, batch_rows=BATCH_ROWS,
                              worker_mode="thread", stores=stores,
                              ttl_s=10.0, speculate_factor=0.0,
                              timeout_s=300.0)
            walls[workers] = time.monotonic() - t0
            out[f"bulk_{workers}w_rows"] = summary["rows"]
            out[f"bulk_{workers}w_wall_s"] = round(walls[workers], 3)
            out[f"bulk_{workers}w_rows_per_sec"] = round(
                summary["rows"] / walls[workers], 1)
    finally:
        for store in stores.values():
            store.close()
    out["scale_speedup_2w"] = round(walls[1] / walls[2], 2)
    out["bulk_blob_sha"] = hashlib.sha256(
        _blob(os.path.join(work, "bulk-1w"))).hexdigest()
    # 1w and 2w outputs must already be bit-identical (determinism)
    out["bulk_1w_2w_identical"] = (
        _blob(os.path.join(work, "bulk-1w"))
        == _blob(os.path.join(work, "bulk-2w")))
    return out


def _http_phase(data_dir: str, models_dir: str, work: str) -> dict:
    """Bulk scoring the way an operator would do it WITHOUT the batch
    plane: read + parse each input file, POST /score in per-request
    batches, format the scores, write the output file.  The timed window
    is the full ETL — exactly what the batch arm's window covers."""
    from shifu_tensorflow_tpu.serve.config import ServeConfig
    from shifu_tensorflow_tpu.serve.server import ScoringServer

    out_dir = os.path.join(work, "http-out")
    os.makedirs(out_dir, exist_ok=True)
    files = sorted(n for n in os.listdir(data_dir) if n.endswith(".psv"))
    cfg = ServeConfig(model_dir=models_dir, port=0, max_batch=HTTP_BATCH,
                      max_delay_ms=2.0,
                      max_queue_rows=max(1024, HTTP_BATCH * HTTP_THREADS * 4),
                      reload_poll_ms=0)
    served = [0]
    lock = threading.Lock()

    with ScoringServer(cfg) as srv:
        srv.start()

        def post(conn, rows: list) -> list:
            payload = json.dumps({"rows": rows}).encode()
            conn.request("POST", "/score", payload,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"/score -> {resp.status}")
            return json.loads(body)["scores"]

        def score_file(name: str) -> None:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60.0)
            try:
                with open(os.path.join(data_dir, name)) as f:
                    rows = [[float(v) for v in line.strip().split("|")]
                            for line in f if line.strip()]
                lines = []
                for i in range(0, len(rows), HTTP_BATCH):
                    for s in post(conn, rows[i:i + HTTP_BATCH]):
                        lines.append(format(float(s), ".9g"))
                with open(os.path.join(out_dir, name + ".scored"),
                          "w") as f:
                    f.write("\n".join(lines) + "\n")
                with lock:
                    served[0] += len(lines)
            finally:
                conn.close()

        # warm request (compile + connection path) outside the window
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60.0)
        post(conn, [[0.1] * N_FEATURES] * HTTP_BATCH)
        conn.close()

        idx = [0]

        def client():
            while True:
                with lock:
                    if idx[0] >= len(files):
                        return
                    name = files[idx[0]]
                    idx[0] += 1
                score_file(name)

        t0 = time.monotonic()
        threads = [threading.Thread(target=client)
                   for _ in range(HTTP_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
    return {
        "http_rows": served[0],
        "http_wall_s": round(wall, 3),
        "http_rows_per_sec": round(served[0] / wall, 1) if wall else 0.0,
        "http_batch": HTTP_BATCH,
        "http_threads": HTTP_THREADS,
    }


def _kill_drill(data_dir: str, models_dir: str, work: str,
                total_rows: int) -> dict:
    from shifu_tensorflow_tpu.obs import journal as obs_journal
    from shifu_tensorflow_tpu.score import committer
    from shifu_tensorflow_tpu.score.job import run_job

    out_dir = os.path.join(work, "drill")
    journal = os.path.join(work, "drill-journal.jsonl")
    obs_journal.uninstall()
    obs_journal.install(obs_journal.Journal(journal, plane="score"))
    procs: dict = {}
    killed = threading.Event()

    def victim_holds_live_lease() -> bool:
        try:
            events = obs_journal.read_events(journal)
        except OSError:
            return False
        held = None
        for e in events:
            kind = e.get("event")
            if (kind == "lease_grant"
                    and str(e.get("worker", "")).startswith("scorer-0")):
                held = e.get("shard")
            elif (kind in ("shard_commit", "lease_reclaim")
                    and e.get("shard") == held):
                held = None
        return held is not None

    def killer():
        deadline = time.monotonic() + 90.0
        while time.monotonic() < deadline:
            if not victim_holds_live_lease():
                time.sleep(0.05)
                continue
            time.sleep(0.7)  # mid-scan: every read check drags 300ms
            p = procs.get("scorer-0")
            if p is None or p.poll() is not None:
                return
            if not victim_holds_live_lease():
                continue
            p.send_signal(signal.SIGKILL)
            killed.set()
            return

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    t0 = time.monotonic()
    summary = run_job(
        data_dir, models_dir, out_dir,
        workers=2, ttl_s=1.5, speculate_factor=4.0,
        batch_rows=DRILL_BATCH_ROWS,
        worker_mode="process", timeout_s=300.0,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "STPU_FAULT_PLAN":
                "score.read:slow300@1.0,score.commit:torn-write@3",
            "STPU_FAULT_SEED": "11",
        },
        on_spawn=lambda wid, p: procs.__setitem__(wid, p),
    )
    wall = time.monotonic() - t0
    t.join(timeout=10.0)
    obs_journal.uninstall()

    success = committer.read_success(out_dir) or {}
    tokens = [s.get("token") for s in success.get("shards", [])]
    events = obs_journal.read_events(journal)
    names = [e.get("event") for e in events]
    return {
        "drill_wall_s": round(wall, 2),
        "drill_killed": killed.is_set(),
        "drill_rows": summary["rows"],
        "drill_missing_rows": total_rows - summary["rows"],
        "drill_duplicate_tokens": len(tokens) - len(set(tokens)),
        "drill_reclaims": summary["reclaims"],
        "drill_duplicates_discarded": summary["duplicates"],
        "drill_blob": _blob(out_dir),
        "drill_journal_sequence_ok": bool(
            "lease_expire" in names and "lease_reclaim" in names
            and "shard_commit" in names
            and names.index("lease_expire") < names.index("lease_reclaim")),
    }


def main() -> int:
    from shifu_tensorflow_tpu.utils.jaxenv import force_cpu_backend

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    force_cpu_backend()
    result: dict = {
        "bench": "score",
        "quick": QUICK,
        "n_files": N_FILES,
        "rows_per_file": ROWS_PER_FILE,
        "batch_rows": BATCH_ROWS,
    }
    with tempfile.TemporaryDirectory(prefix="bench-score-") as work:
        data_dir = os.path.join(work, "data")
        total_rows = _gen_inputs(data_dir, N_FILES, ROWS_PER_FILE)
        result["input_rows"] = total_rows
        models_dir = _export_bundle(os.path.join(work, "model"))

        result.update(_bulk_phase(data_dir, models_dir, work))
        _emit(result)
        result.update(_http_phase(data_dir, models_dir, work))
        result["bulk_vs_http_ratio"] = round(
            result["bulk_1w_rows_per_sec"]
            / max(result["http_rows_per_sec"], 0.001), 2)
        _emit(result)

        # the kill drill runs its own small slow-read dataset, with an
        # unkilled thread-mode control arm as the bit-identity baseline
        drill_data = os.path.join(work, "drill-data")
        drill_rows = _gen_inputs(drill_data, DRILL_FILES,
                                 DRILL_ROWS_PER_FILE, seed=13)
        result["drill_input_rows"] = drill_rows
        from shifu_tensorflow_tpu.score.job import run_job

        control_dir = os.path.join(work, "drill-control")
        run_job(drill_data, models_dir, control_dir, workers=1,
                batch_rows=DRILL_BATCH_ROWS, worker_mode="thread",
                ttl_s=10.0, speculate_factor=0.0, timeout_s=120.0)
        drill = _kill_drill(drill_data, models_dir, work, drill_rows)
        drill_blob = drill.pop("drill_blob")
        result.update(drill)
        result["drill_bit_identical_to_control"] = (
            drill_blob == _blob(control_dir))

    host_capped = (os.cpu_count() or 2) < 4
    result["host_capped"] = host_capped
    gates = {
        # the batch plane's reason to exist: bulk beats per-request HTTP
        "bulk_beats_http": result["bulk_vs_http_ratio"] >= 1.0,
        # 2 workers buy real wall-clock on a wide host; on a capped host
        # the ratio measures core contention — fall back, but the runs
        # must still be deterministic across fleet sizes
        "scale_speedup_ok": result["scale_speedup_2w"] >= 1.3,
        "fleet_size_deterministic": result["bulk_1w_2w_identical"],
        # the exactly-once gates are NEVER host-capped
        "drill_kill_landed": result["drill_killed"],
        "drill_zero_missing_rows": result["drill_missing_rows"] == 0,
        "drill_zero_duplicate_tokens":
            result["drill_duplicate_tokens"] == 0,
        "drill_reclaim_observed": result["drill_reclaims"] >= 1,
        "drill_bit_identical": result["drill_bit_identical_to_control"],
        "drill_journal_sequence_ok": result["drill_journal_sequence_ok"],
    }
    result["gates"] = gates
    hard = [k for k in gates if k.startswith("drill_")
            or k == "fleet_size_deterministic"]
    result["acceptance_ok"] = bool(
        all(gates[k] for k in hard)
        and (gates["bulk_beats_http"] or host_capped)
        and (gates["scale_speedup_ok"] or host_capped))
    _emit(result, partial=False)
    with open(ARTIFACT, "w") as f:
        json.dump(result, f, indent=2, default=str)
        f.write("\n")
    return 0 if result["acceptance_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
