"""Sequence-family train-step throughput across sequence lengths.

The sequence transformer (models/sequence.py, ModelType=sequence) is the
framework's beyond-parity long-context family (SURVEY.md §5.7); its ring
and Ulysses attention paths need a multi-device 'seq' mesh axis and are
exercised on the 8-device CPU mesh (tests/test_ring.py) and in the
driver's dryrun.  What a single chip CAN measure — and what this script
does — is the on-chip full-attention step across sequence lengths at a
fixed token budget per step, which is the compute baseline the ring path
trades collectives against.

Model: SequenceClassifier d_model=128, 4 heads, 2 blocks, F=4 features
per step, bf16 compute / fp32 params.  Per seq length S the batch is
TOKENS_PER_STEP / S so every case runs the same token count per step;
reported are steps/s, rows/s and tokens/s for a full fwd+bwd+adam update.

Run on the TPU host (the watcher battery does):
    python scripts/bench_sequence.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# share bench.py's persistent compile cache: a case that compiled once
# need not compile again
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache"),
)

import jax
import jax.numpy as jnp
import numpy as np
import optax

from shifu_tensorflow_tpu.models.sequence import SequenceClassifier

SEQ_LENS = tuple(
    int(s.strip()) for s in os.environ.get(
        "BENCH_SEQ_LENS", "256,1024,4096").split(",")
)
TOKENS_PER_STEP = int(os.environ.get("BENCH_SEQ_TOKENS", 131072))
F_PER_STEP = 4
D_MODEL = 128
HEADS = 4
BLOCKS = 2
REPS = int(os.environ.get("BENCH_SEQ_REPS", 20))
IMPLS = tuple(s.strip() for s in os.environ.get(
    "BENCH_SEQ_IMPLS", "full,chunked,flash").split(","))


def _case(seq_len: int, impl: str = "full") -> dict:
    from shifu_tensorflow_tpu.models.sequence import make_attention

    batch = max(1, TOKENS_PER_STEP // seq_len)
    model = SequenceClassifier(
        seq_len=seq_len, d_model=D_MODEL, num_heads=HEADS,
        num_blocks=BLOCKS,
        # one dispatch table: the bench measures exactly what a
        # SeqAttention=<impl> user gets, defaults included
        attention=make_attention(impl, None, seq_len=seq_len,
                                 num_heads=HEADS),
        dtype=jnp.bfloat16,
    )
    rng = np.random.default_rng(seq_len)
    x = jnp.asarray(
        rng.normal(size=(batch, seq_len * F_PER_STEP)).astype(np.float32)
    )
    y = jnp.asarray(
        (rng.random(size=(batch, 1)) < 0.5).astype(np.float32)
    )
    params = model.init(jax.random.PRNGKey(0), x)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    def loss_fn(p, xb, yb):
        pred = model.apply(p, xb)
        return jnp.mean((pred.astype(jnp.float32) - yb) ** 2)

    @jax.jit
    def step(p, s, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(p, xb, yb)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    from shifu_tensorflow_tpu.utils.profiling import true_sync

    params, opt_state, loss = step(params, opt_state, x, y)
    true_sync(loss)
    # value-fetch sync: the final loss depends on every step through the
    # params chain, so one fetch proves all REPS executed in the window
    # (utils/profiling.true_sync)
    t0 = time.perf_counter()
    for _ in range(REPS):
        params, opt_state, loss = step(params, opt_state, x, y)
    true_sync(loss)
    dt = time.perf_counter() - t0
    return {
        "seq_len": seq_len,
        "attention": impl,
        "batch": batch,
        "steps_per_sec": round(REPS / dt, 2),
        "rows_per_sec": round(REPS * batch / dt),
        "tokens_per_sec": round(REPS * batch * seq_len / dt),
        "final_loss": round(float(loss), 4),
    }


def _case_or_error(seq_len: int, impl: str) -> dict:
    """One case in a SUBPROCESS: a flaky remote-compile failure or an
    OOM poisons only itself, and no device buffers leak into the next
    case (measured 2026-07-31: an S=8192 chunked case that runs clean in
    a fresh process hit ResourceExhausted when it followed a failed
    full-attention case in the same process)."""
    import subprocess

    env = dict(os.environ)
    env["BENCH_SEQ_SINGLE"] = f"{seq_len}:{impl}"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            capture_output=True, text=True, timeout=300, env=env,
        )
        for raw in reversed(proc.stdout.strip().splitlines()):
            if raw.startswith("{"):
                return json.loads(raw)
        tail = proc.stderr.strip().splitlines()[-1:] or ["no output"]
        return {"seq_len": seq_len, "attention": impl,
                "error": f"rc={proc.returncode}: {tail[0][:300]}"}
    except subprocess.TimeoutExpired:
        return {"seq_len": seq_len, "attention": impl,
                "error": "timeout after 300s"}


def main() -> None:
    single = os.environ.get("BENCH_SEQ_SINGLE")
    if single:
        s, impl = single.split(":")
        try:
            case = _case(int(s), impl)
            case["platform"] = jax.devices()[0].platform
            case["device"] = str(jax.devices()[0].device_kind)
        except Exception as e:  # noqa: BLE001 — the parent records it
            msg = str(e)
            # keep the compiler's memory verdict intact: it is the
            # feasibility EVIDENCE (e.g. "Used 24.29G of 15.75G hbm")
            i = msg.lower().find("ran out of memory")
            if i >= 0:
                detail = msg[i:i + 400]
            else:
                detail = msg[:300]
            case = {"seq_len": int(s), "attention": impl,
                    "error": f"{type(e).__name__}: {detail}"}
        print(json.dumps(case), flush=True)
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # the parent NEVER touches the device: on a stock single-process
    # libtpu TPU VM, acquiring it here would starve every case
    # subprocess.  platform/device come from the first successful case.
    out = {
        "bench": "sequence_family",
        "platform": "unknown",
        "device": "unknown",
        "date": time.strftime("%Y-%m-%d"),
        "d_model": D_MODEL,
        "heads": HEADS,
        "blocks": BLOCKS,
        "tokens_per_step": TOKENS_PER_STEP,
        "note": ("single device; ring/ulysses need a seq mesh. "
                 "Each case is a full fwd+bwd+adam train step; the "
                 "attention impl sweep sets STPU_CHUNKED_MIN_SEQ "
                 "(models/sequence.py auto cutover) from data."),
        "cases": [],
    }

    def flush() -> str:
        line = json.dumps(out)
        if args.out:  # written after EVERY case: a hung case or an
            with open(args.out, "w") as f:  # outer timeout keeps what
                f.write(line + "\n")        # already completed
        return line

    for s in SEQ_LENS:
        for impl in IMPLS:
            case = _case_or_error(s, impl)
            if out["platform"] == "unknown" and case.get("platform"):
                out["platform"] = case.pop("platform")
                out["device"] = case.pop("device", "unknown")
            else:
                case.pop("platform", None)
                case.pop("device", None)
            out["cases"].append(case)
            flush()
    print(flush(), flush=True)


if __name__ == "__main__":
    main()
