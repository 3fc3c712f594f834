"""chip_smoke.py, rehearsed at a tiny size on the CPU.

The script's real run is on the chip (the driver repeats it there); here
its control flow, its children and its output contract are held in place:
every phase runs, every stdout line is one JSON object, the last line names
the platform it truly ran on — the CPU, so never ``"ok": true`` and never
exit code 0 — and the parent never imported JAX.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(tmp_path, **extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # the conftest keeps the persistent cache off for every other
        # test; this one checks that the second train child hits it
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
    })
    env.update(extra)
    return env


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("smoke")
    proc = subprocess.run(
        [sys.executable, SMOKE, "--tiny", "--out", str(tmp / "out")],
        capture_output=True, text=True, timeout=600, env=_env(tmp), cwd=tmp)
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    return proc, lines, tmp


def test_rehearsal_runs_every_phase(rehearsal):
    proc, lines, _ = rehearsal
    assert proc.returncode == 4, proc.stderr[-3000:]
    phases = [line["phase"] for line in lines[:-1]]
    assert phases == ["probe", "native", "data", "train", "resume", "serve",
                      "eval", "sequence", "parent"]
    by_phase = {line["phase"]: line for line in lines[:-1]}
    assert by_phase["native"]["parser_loaded"] is True
    assert by_phase["train"]["epochs"] == [0, 1]
    assert by_phase["resume"]["epochs"] == [2, 3]
    assert by_phase["resume"]["last_loss"] < by_phase["train"]["first_loss"]
    assert by_phase["resume"]["steps"] > by_phase["train"]["steps"] > 0
    assert by_phase["serve"]["rungs"] == [8, 64, 512]
    assert by_phase["serve"]["aot_fallbacks"] == 0
    assert by_phase["eval"]["aot_fallbacks"] == 0
    assert by_phase["eval"]["max_abs_diff"] <= by_phase["eval"]["tolerance"]
    assert set(by_phase["sequence"]["attention"]) == {"full", "chunked",
                                                      "flash"}
    assert by_phase["sequence"]["pallas_interpret"] is True
    sync = by_phase["probe"]["sync"]
    assert sync["block_until_ready_s"] > 0 and sync["true_sync_s"] > 0


def test_rehearsal_never_claims_the_chip(rehearsal):
    proc, lines, _ = rehearsal
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert '"platform": "tpu"' not in proc.stdout
    # one process per chip: the parent stayed off JAX
    assert lines[-2] == {"phase": "parent", "jax_imported": False,
                         "tiny": True, "out": lines[-2]["out"]}


def test_second_train_child_hits_the_placed_cache(rehearsal):
    _, lines, tmp = rehearsal
    by_phase = {line["phase"]: line for line in lines[:-1]}
    assert by_phase["train"]["compiles"] > 0
    assert by_phase["resume"]["cache_hits"] > 0
    # JAX_COMPILATION_CACHE_DIR was set: the cache is there and nowhere
    # the script chose
    assert any((tmp / "jax_cache").iterdir())
    assert not (tmp / "out" / ".jax_cache").exists()


def test_full_size_run_without_a_chip_prints_no_result(tmp_path):
    """No ``--tiny``: the first child finds no TPU, and the script ends
    there — non-zero, before any phase line, with no result."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=300, env=_env(tmp_path),
        cwd=tmp_path)
    assert proc.returncode not in (0, 4)
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_script_alone_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo it must fail, not find the program somewhere else."""
    import shutil

    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py"), "--tiny"],
        capture_output=True, text=True, timeout=120, env=_env(tmp_path),
        cwd=tmp_path)
    assert proc.returncode not in (0, 4)
    assert proc.stdout == ""
