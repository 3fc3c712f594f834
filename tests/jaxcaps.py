"""Environment-capability gates for tests, shared across files.

The cross-process SPMD drills (test_spmd, test_cli multi-worker,
test_convergence, test_eval_cli fleet, test_netns_spmd) run each worker as
its own jax process, with gradients all-reduced over loopback by the CPU
backend's cross-process collectives — which the one installation there is
(jax/jaxlib 0.9.0) has, so they carry no gate.

In-process SPMD (the conftest's 8-device virtual CPU mesh) runs
everywhere.
"""

from __future__ import annotations

import os

import pytest

# The ssh-launcher drills additionally bind the jax coordination service
# to this machine's non-loopback interface, and the containerized CI
# network cannot route worker<->chief traffic over it (verified failing
# identically on a pristine seed checkout, PR 4 notes).  These tests run
# only when the operator asserts the network can route the non-loopback
# plane by setting STPU_NONLOOPBACK_SPMD_TESTS=1.  Tier-1 then reads
# green-or-real-regression instead of known-red.
needs_nonloopback_spmd = pytest.mark.skipif(
    not os.environ.get("STPU_NONLOOPBACK_SPMD_TESTS"),
    reason=(
        "non-loopback cross-process SPMD needs a network that routes "
        "the non-loopback coordination plane — opt in with "
        "STPU_NONLOOPBACK_SPMD_TESTS=1 (container failure pre-existing "
        "at seed, see CHANGES.md PR 4)"
    ),
)
