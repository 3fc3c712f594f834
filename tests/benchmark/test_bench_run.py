"""Each plane end to end at a tiny size on the CPU, through ``run.py``'s
own discovery: cells of the tests' own (``conftest.py``'s ``tiny_root``:
configuration, traffic mix and ``BENCHMARK.json`` entries in new files
only — what a later PR does), the four-chip plane on a ``data:2,model:2``
mesh of virtual CPU devices, the stamp and exit code of a run off the
TPU, and no result without one."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(tiny_root, cell, *extra, devices=1, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "11", "--seconds", "1",
         "--trace", str(trace),
         "--benchmark-json", str(tiny_root / "BENCHMARK.json"), *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, lines


def test_one_chip_cell_untraced(tiny_root):
    proc, lines = run(tiny_root, "tiny_wdl_cell", "--rehearse")
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert RESULT_KEYS <= set(last) and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 16  # whole epochs of 16 steps
    assert set(last["metrics"]) == {"train_rows_per_s", "setup_s"}
    for m in last["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    note = next(json.loads(ln)["note"] for ln in lines[:-1]
                if '"compiles_in_window"' in ln)
    assert note["compiles_in_window"] == 0
    assert note["info"]["check"]["loss_rel_err"] < 1e-4
    # the same seed again finds its shards
    proc, lines = run(tiny_root, "tiny_wdl_cell", "--rehearse")
    assert proc.returncode == 4
    assert '"shards_reused": true' in proc.stdout


def test_four_chip_cell_traced_on_a_virtual_mesh(tiny_root):
    proc, lines = run(tiny_root, "tiny_wdl_x4_cell", "--rehearse",
                      devices=4, trace=1)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert RESULT_KEYS | {"breakdown"} <= set(last)
    assert last["correct"] is True and last["device"]["count"] == 4
    assert {"busy_s", "window_s"} <= set(last["device"])
    # the program's spans were read; device-trace readers found no TPU
    # plane on this machine and left their metrics out
    assert {"infeed_wait_pct", "dispatch_ms"} <= set(last["metrics"])
    assert "step_device_ms" not in last["metrics"]
    assert "setup_s" not in last["metrics"]


def test_another_mesh_on_four_chips_needs_no_edit(tiny_root):
    """PERF.md's open cell ``wdl_criteo_x4_m4`` in small: ``data:1,
    model:4`` runs through the same plane from new files alone."""
    proc, lines = run(tiny_root, "tiny_wdl_m4_cell", "--rehearse", devices=4)
    assert proc.returncode == 4, proc.stderr[-3000:]
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["device"]["count"] == 4
    assert '"mesh": "data:1,model:4"' in proc.stdout


def test_too_few_chips_gives_no_result(tiny_root):
    proc, lines = run(tiny_root, "tiny_wdl_x4_cell", "--rehearse", devices=2)
    assert proc.returncode == 3
    assert "asks for 4 chips" in proc.stderr
    assert not any('"correct"' in ln for ln in lines)


def test_without_a_tpu_no_result(tiny_root):
    proc, lines = run(tiny_root, "tiny_wdl_cell")
    assert proc.returncode == 3
    assert "no TPU" in proc.stderr
    assert not any('"correct"' in ln for ln in lines)


def test_unknown_cell_gives_no_result(tiny_root):
    proc, lines = run(tiny_root, "no_such_cell", "--rehearse")
    assert proc.returncode == 3 and not lines


def test_without_the_program_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files: exit 3, nothing on stdout."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "wdl_criteo_stream", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=60)
    assert proc.returncode == 3 and proc.stdout == ""
    assert "is not here" in proc.stderr
