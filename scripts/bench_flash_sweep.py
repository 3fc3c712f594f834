"""Flash-vs-chunked attention sweep: fwd+bwd at long S, block sizes.

r04 verdict item 5: flash lost to chunked at every measured S with its
backward running through the chunked path anyway.  r05 lands a true
Pallas FlashAttention-2 backward; this sweep measures, on-chip, the full
fwd+bwd gradient step for:

- ``chunked``          — the XLA online-softmax scan (current default)
- ``flash-bN``         — Pallas fwd + Pallas bwd at block N (128/256/512)
- ``flash-b128-xbwd``  — Pallas fwd + chunked XLA bwd (the r04 shape),
                         isolating how much the new backward contributes

at S in {4096, 8192, 16384} and a fixed token budget per step.  Each case
runs in a SUBPROCESS (bench_sequence.py lesson: a failed case leaks
device buffers into the next in-process) and the artifact is flushed
after every case.  The verdict field names the winner per S — the data
that either flips SeqAttention=auto to flash in a measured regime or
formally demotes the kernels to reference status.

Run: python scripts/bench_flash_sweep.py
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(REPO, ".jax_cache"))

SEQ_LENS = tuple(int(s) for s in os.environ.get(
    "FLASH_SWEEP_LENS", "4096,8192,16384").split(","))
TOKENS = int(os.environ.get("FLASH_SWEEP_TOKENS", 65536))
REPS = int(os.environ.get("FLASH_SWEEP_REPS", 10))
HEADS = 4
DIM = 32

VARIANTS = {
    "chunked": {},
    "flash-b128": {"blocks": 128},
    "flash-b256": {"blocks": 256},
    "flash-b512": {"blocks": 512},
    "flash-b128-xbwd": {"blocks": 128, "env": {"STPU_FLASH_BWD": "chunked"}},
}


def run_case(seq_len: int, variant: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shifu_tensorflow_tpu.ops.pallas.flash_attention import (
        flash_attention,
    )
    from shifu_tensorflow_tpu.parallel.ring import chunked_attention
    from shifu_tensorflow_tpu.utils.profiling import true_sync

    spec = VARIANTS[variant]
    batch = max(1, TOKENS // seq_len)
    rng = np.random.default_rng(seq_len)
    q, k, v = (jnp.asarray(
        rng.normal(size=(batch, seq_len, HEADS, DIM)), jnp.bfloat16)
        for _ in range(3))

    if variant == "chunked":
        attn = lambda q, k, v: chunked_attention(  # noqa: E731
            q, k, v, causal=True, block_size=512)
    else:
        blocks = spec["blocks"]
        attn = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, True, blocks, blocks)

    @jax.jit
    def grad_step(q, k, v):
        return jax.grad(
            lambda q, k, v: jnp.sum(
                attn(q, k, v).astype(jnp.float32) ** 2),
            (0, 1, 2))(q, k, v)

    gq, gk, gv = grad_step(q, k, v)
    true_sync(gq)
    # value-fetch sync (utils/profiling.true_sync):
    # chain one element per rep so one final fetch proves all executed
    acc = jnp.zeros((), jnp.float32)
    t0 = time.perf_counter()
    for _ in range(REPS):
        gq, gk, gv = grad_step(q, k, v)
        acc = acc + gq.reshape(-1)[0].astype(jnp.float32)
    true_sync(acc)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    return {
        "seq_len": seq_len,
        "variant": variant,
        "batch": batch,
        "fwdbwd_per_sec": round(REPS / dt, 3),
        "tokens_per_sec": round(REPS * batch * seq_len / dt),
        "platform": dev.platform,
        "device": str(dev.device_kind),
    }


def case_or_error(seq_len: int, variant: str) -> dict:
    env = dict(os.environ)
    env["FLASH_SWEEP_SINGLE"] = f"{seq_len}:{variant}"
    env.update(VARIANTS[variant].get("env", {}))
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=300, env=env,
        )
        for raw in reversed(proc.stdout.strip().splitlines()):
            if raw.startswith("{"):
                return json.loads(raw)
        tail = proc.stderr.strip().splitlines()[-1:] or ["no output"]
        return {"seq_len": seq_len, "variant": variant,
                "error": f"rc={proc.returncode}: {tail[0][:300]}"}
    except subprocess.TimeoutExpired:
        return {"seq_len": seq_len, "variant": variant,
                "error": "timeout after 300s"}


def main() -> None:
    single = os.environ.get("FLASH_SWEEP_SINGLE")
    if single:
        s, variant = single.split(":")
        print(json.dumps(run_case(int(s), variant)), flush=True)
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=os.path.join(REPO, "BENCH_FLASH_SWEEP.json"))
    args = ap.parse_args()

    # the parent NEVER touches the device: a chip belongs to one
    # process, and acquiring it here would starve every case
    # subprocess.  platform/device come from the first successful case.
    artifact: dict = {
        "platform": "unknown",
        "device": "unknown",
        "tokens_per_step": TOKENS,
        "heads": HEADS, "dim": DIM, "reps": REPS,
        "cases": [],
    }

    def flush() -> None:
        # winner per S, from completed cases
        verdict = {}
        for s in SEQ_LENS:
            done = [c for c in artifact["cases"]
                    if c["seq_len"] == s and "tokens_per_sec" in c]
            if done:
                best = max(done, key=lambda c: c["tokens_per_sec"])
                chunk = next((c for c in done if c["variant"] == "chunked"),
                             None)
                verdict[str(s)] = {
                    "winner": best["variant"],
                    "flash_over_chunked": round(
                        best["tokens_per_sec"] / chunk["tokens_per_sec"], 3)
                    if chunk and best["variant"] != "chunked" else None,
                }
        artifact["verdict_per_seq_len"] = verdict
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)

    for s in SEQ_LENS:
        for variant in VARIANTS:
            case = case_or_error(s, variant)
            if artifact["platform"] == "unknown" and case.get("platform"):
                artifact["platform"] = case["platform"]
                artifact["device"] = case.get("device", "unknown")
            print(json.dumps(case), flush=True)
            artifact["cases"].append(case)
            flush()
    print(f"wrote {args.out}", flush=True)


if __name__ == "__main__":
    main()
