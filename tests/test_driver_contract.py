"""Driver-contract tests: the two root-level files the round driver
executes must keep their contracts — bench.py prints only JSON lines whose
LAST line carries the required keys (earlier lines are incremental partial
results, flushed so a killed bench still leaves evidence), and
__graft_entry__.entry() returns a jittable fn + args.  (dryrun_multichip
is exercised by the driver itself and manually; running the full
multi-mesh dryrun here would double the suite's wall time.)"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_env(**extra):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "BENCH_BATCH": "512",
        "BENCH_SECONDS": "0.2",
        "BENCH_TOTAL_BUDGET_S": "400",
        "BENCH_TPU_TIMEOUT": "180",
    })
    env.update(extra)
    return env


def _reject(tok):  # json.loads accepts NaN/Infinity by default
    raise ValueError(f"non-standard JSON token {tok} in bench line")


def test_bench_emits_json_lines_with_contract_keys():
    # one retry: on a loaded 1-CPU host the timed child can blow its
    # internal budget and bench (correctly) reports value 0 with
    # diagnostics and a non-zero exit — bench working as designed, not a
    # contract break, so give it one quiet second chance before failing
    # the suite
    for attempt in (1, 2):
        # outer timeout exceeds bench's own worst-case internal budget
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, timeout=500, env=_bench_env(), cwd=REPO,
        )
        lines = [l for l in proc.stdout.decode().splitlines() if l.strip()]
        assert lines, "bench printed nothing"
        # EVERY line must parse — a caller that truncates the stream at
        # any point still holds a valid artifact
        parsed = [json.loads(l, parse_constant=_reject) for l in lines]
        d = parsed[-1]
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in d, f"contract key {k} missing"
        assert d["metric"] == "training_rows_per_sec_per_chip"
        assert "partial" not in d, "final line must not be partial"
        # the primary metric must appear EARLY (incremental emission):
        # the first parsed line already carries it
        assert parsed[0].get("value", 0) > 0 or d["value"] == 0
        # the exit code says whether anything was measured
        assert (proc.returncode == 0) == (d["value"] > 0), \
            proc.stderr.decode()[-2000:]
        if d["value"] > 0 or attempt == 2:
            break
    assert d["value"] > 0, f"bench measured nothing twice: {d}"
    assert np.isfinite(d["vs_baseline"])
    # the platform it ran on is the one the test pinned: no re-run
    # somewhere else
    assert d["platform"] == "cpu"


def test_bench_that_measures_nothing_exits_nonzero():
    """A child that cannot even start (here: a platform JAX does not
    have) leaves nothing measured: the error stub is printed, the exit
    code is non-zero, and there is no second attempt on the CPU."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, timeout=300, cwd=REPO,
        env=_bench_env(JAX_PLATFORMS="no_such_platform"),
    )
    assert proc.returncode != 0
    d = json.loads(proc.stdout.decode().strip().splitlines()[-1],
                   parse_constant=_reject)
    assert d["value"] == 0.0 and d["error"] == "nothing was measured"
    assert len(d["diagnostics"]) == 1, "one attempt, no fallback"


def test_bench_sigterm_flushes_partial_artifact():
    """The round-3 failure mode: the driver killed the bench and got an
    empty tail.  Now SIGTERM at ANY point must still end with a parseable
    JSON line on stdout (rc 0 from the parent's flush handler)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "bench.py")],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=_bench_env(), cwd=REPO,
    )
    time.sleep(3.0)  # mid-startup: before any measurement finishes
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 0
    lines = [l for l in out.decode().splitlines() if l.strip()]
    assert lines, "killed bench left an empty tail"
    d = json.loads(lines[-1], parse_constant=_reject)
    for k in ("metric", "value", "unit", "vs_baseline"):
        assert k in d, f"contract key {k} missing from flushed artifact"
    assert "diagnostics" in d


def test_graft_entry_is_jittable_with_example_args():
    import jax

    import __graft_entry__ as g  # conftest puts the repo root on sys.path

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    out = np.asarray(jax.device_get(out))
    assert out.ndim == 2 and out.shape[1] == 1
    assert np.all(np.isfinite(out))
    # dryrun contract: callable with an int (driver passes the device count)
    assert callable(g.dryrun_multichip)
