"""JAX backend-environment helpers: the CPU pin shared by the test
conftest and the CPU-only drill scripts, and the one-process-per-chip
check of the entry points that start local worker processes."""

from __future__ import annotations

import os


def force_cpu_backend(device_count: int | None = None) -> None:
    """Pin JAX to the CPU backend, whatever accelerator the host has.

    ``JAX_PLATFORMS`` / ``XLA_FLAGS`` are read when the backend
    initializes, so call this before the first ``jax.devices()`` / jit
    use; the ``jax.config`` update re-pins a jax module that a plugin
    imported earlier.  ``device_count`` additionally requests a virtual
    multi-device CPU (only effective before the backend initializes).
    """
    if device_count is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{device_count}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


def refuse_processes_sharing_a_chip(n_local: int, what: str) -> None:
    """Exit with the cause when ``n_local`` JAX processes of this host
    would have to share its accelerator.

    A chip belongs to the one process that opened it: every other
    process dies inside backend start-up ("The TPU is already in use by
    process with pid …" on the v5e, PR 21) — after the fleet was launched,
    and under a supervisor again at every restart.  One process drives
    all local chips (``shifu.tpu.mesh-shape``), so more than one process
    per host only works on the CPU backend.  The platform is asked of a short-lived
    child, so the caller — a supervisor or submitter that must stay off
    JAX — never opens the chip itself; a ``JAX_PLATFORMS=cpu`` pin
    answers without asking.  Call before any worker is started.
    """
    import subprocess
    import sys

    if n_local <= 1 or os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.local_devices(); "
         "print(d[0].platform, len(d))"],
        capture_output=True, text=True, timeout=300,
    )
    if probe.returncode != 0:
        raise SystemExit(
            f"{what}: cannot tell which platform {n_local} local "
            f"processes would share — JAX failed to start in a probe "
            f"process: {probe.stderr.strip().splitlines()[-1:]}")
    platform, count = probe.stdout.split()[-2:]
    if platform != "cpu":
        raise SystemExit(
            f"{what}: {n_local} processes on this host cannot share its "
            f"{count} {platform} chip(s) — a chip belongs to the one "
            f"process that opens it, and every other process fails at "
            f"backend start-up.  One process drives all local "
            f"chips (shifu.tpu.mesh-shape); more than one process per "
            f"host runs on the CPU backend only (JAX_PLATFORMS=cpu).")
