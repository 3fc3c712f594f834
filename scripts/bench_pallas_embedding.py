"""Micro-benchmark: Pallas one-hot-matmul embedding lookup vs XLA gather.

Substantiates (or refutes) models/embeddings.py's auto-impl cutover
(PALLAS_MAX_HASH_SIZE): sweeps table sizes 4K -> 256K and batch sizes,
timing forward and forward+backward for both implementations on the
current backend, and writes the artifact JSON the docstring claims cite
(SURVEY.md §7.1 item 8; round-2 verdict task 6).

Run on the TPU host:   python scripts/bench_pallas_embedding.py
Output artifact:       BENCH_PALLAS_EMBEDDING.json (repo root)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from shifu_tensorflow_tpu.ops import hashing
from shifu_tensorflow_tpu.ops.pallas.embedding import hashed_embedding_lookup

DIM = 16
N_COLS = 5
TABLE_SIZES = [4096, 16384, 65536, 262144]
BATCH_SIZES = [4096, 16384]
REPS = 30


def _xla_lookup(table, cats, hash_size):
    ids = hashing.salted_bucket_ids(cats, hash_size)
    b, c = cats.shape
    return jnp.take(table, ids.reshape(-1), axis=0).reshape(b, -1)


def _time(fn, *args) -> float:
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    out = fn(*args)
    true_sync(out)
    # chain one element of every rep's output into an accumulator and
    # fetch THAT: each dispatch's whole program must execute before its
    # output can be sliced, so one final round trip proves all REPS ran
    # inside the window (see utils/profiling.true_sync)
    acc = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
        first = jax.tree_util.tree_leaves(out)[0]
        acc = acc + first.reshape(-1)[0].astype(jnp.float32)
    true_sync(acc)
    return (time.perf_counter() - t0) / REPS * 1e6  # us


def probe_overhead_us() -> float:
    """Cost of the slice+accumulate probe itself: time the same REPS loop
    around an identity dispatch on a tiny array.  The probe adds one fixed
    dispatch per rep inside the timed window, which inflates ABSOLUTE
    us/call for microsecond-scale lookups (the pallas-vs-xla ratio is
    unaffected — both sides carry it).  The artifact reports this baseline
    so readers can net it out of the absolute numbers."""
    tiny = jnp.zeros((8,), jnp.float32)
    ident = jax.jit(lambda x: x)
    return _time(ident, tiny)


def bench_case(hash_size: int, batch: int) -> dict:
    rng = np.random.default_rng(0)
    table = jnp.asarray(
        rng.normal(size=(hash_size, DIM)).astype(np.float32)
    )
    cats = jnp.asarray(
        rng.integers(0, 10_000_000, size=(batch, N_COLS)).astype(np.float32)
    )
    fwd_pallas = jax.jit(lambda t, x: hashed_embedding_lookup(x, t))
    fwd_xla = jax.jit(lambda t, x: _xla_lookup(t, x, hash_size))

    def loss_pallas(t, x):
        return jnp.sum(hashed_embedding_lookup(x, t) ** 2)

    def loss_xla(t, x):
        return jnp.sum(_xla_lookup(t, x, hash_size) ** 2)

    grad_pallas = jax.jit(jax.grad(loss_pallas))
    grad_xla = jax.jit(jax.grad(loss_xla))

    # parity check before timing — a fast wrong kernel is worthless
    np.testing.assert_array_equal(
        np.asarray(fwd_pallas(table, cats)), np.asarray(fwd_xla(table, cats))
    )
    np.testing.assert_allclose(
        np.asarray(grad_pallas(table, cats)),
        np.asarray(grad_xla(table, cats)), rtol=1e-5, atol=1e-5,
    )

    case = {
        "hash_size": hash_size,
        "batch": batch,
        "fwd_pallas_us": round(_time(fwd_pallas, table, cats), 1),
        "fwd_xla_us": round(_time(fwd_xla, table, cats), 1),
        "fwdbwd_pallas_us": round(_time(grad_pallas, table, cats), 1),
        "fwdbwd_xla_us": round(_time(grad_xla, table, cats), 1),
    }
    case["fwd_speedup"] = round(case["fwd_xla_us"] / case["fwd_pallas_us"], 2)
    case["fwdbwd_speedup"] = round(
        case["fwdbwd_xla_us"] / case["fwdbwd_pallas_us"], 2
    )
    return case


def main() -> None:
    dev = jax.devices()[0]
    results = []
    for hs in TABLE_SIZES:
        for b in BATCH_SIZES:
            case = bench_case(hs, b)
            print(json.dumps(case), flush=True)
            results.append(case)
    # the cutover the auto-impl should use: largest table where pallas wins
    # fwd+bwd at every batch size
    winning = [
        hs for hs in TABLE_SIZES
        if all(c["fwdbwd_speedup"] >= 1.0 for c in results
               if c["hash_size"] == hs)
    ]
    artifact = {
        "platform": dev.platform,
        "device": str(dev.device_kind),
        "dim": DIM,
        "n_cols": N_COLS,
        "reps": REPS,
        # fixed per-rep probe dispatch cost, measured with an identity jit:
        # subtract from any absolute us/call; ratios are unaffected
        "probe_overhead_us": round(probe_overhead_us(), 1),
        "cases": results,
        "pallas_wins_up_to_hash_size": max(winning) if winning else 0,
    }
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_PALLAS_EMBEDDING.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"wrote {out}", flush=True)


if __name__ == "__main__":
    main()
