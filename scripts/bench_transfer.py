"""Host->device transfer micro-bench: fp32 vs bf16 vs uint16-view+bitcast.

bf16 halves the bytes of a streamed batch, and host-side memmap drains
show bf16 1.5x FASTER (BENCH_INGEST_HOST.json), but an earlier
end-to-end run saw bf16 streaming slower than fp32.  The suspect is the
transfer path for ml_dtypes bfloat16 numpy arrays; if so, shipping the
same bits as a uint16 view and bitcasting on device is the fix, and this
artifact is the evidence for (or against) building it.  Not measured on
the attached chip.

Run on the TPU host:
    python scripts/bench_transfer.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

ROWS = int(os.environ.get("BENCH_TRANSFER_ROWS", 65536))
COLS = 30
REPS = 30


def _rate(fn) -> float:
    """Calls/sec -> rows/sec, completion proven by value fetch.

    Transfers are enqueued back-to-back (overlapping, as training's
    prefetch does); one element of each result is chained into an
    on-device accumulator, and ONE final fetch of the accumulator proves
    every transfer landed inside the elapsed window — a single round
    trip, not REPS serialized ones (utils/profiling.true_sync)."""
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    true_sync(fn())
    t0 = time.perf_counter()
    acc = None
    for _ in range(REPS):
        probe = fn().reshape(-1)[0].astype(jnp.float32)
        acc = probe if acc is None else acc + probe
    true_sync(acc)
    return REPS * ROWS / (time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    a32 = rng.normal(size=(ROWS, COLS)).astype(np.float32)
    a16 = a32.astype(ml_dtypes.bfloat16)
    a16u = a16.view(np.uint16)

    bitcast = jax.jit(
        lambda u: jax.lax.bitcast_convert_type(u, jnp.bfloat16)
    )
    out = {
        "bench": "transfer",
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0].device_kind),
        "rows": ROWS,
        "cols": COLS,
        "date": time.strftime("%Y-%m-%d"),
        "device_put_f32_rows_s": round(_rate(lambda: jax.device_put(a32))),
        "device_put_bf16_rows_s": round(_rate(lambda: jax.device_put(a16))),
        "device_put_u16_bitcast_rows_s": round(
            _rate(lambda: bitcast(jax.device_put(a16u)))
        ),
    }
    out["bf16_vs_f32"] = round(
        out["device_put_bf16_rows_s"] / out["device_put_f32_rows_s"], 2
    )
    out["u16_vs_bf16"] = round(
        out["device_put_u16_bitcast_rows_s"] / out["device_put_bf16_rows_s"],
        2,
    )
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
