#!/usr/bin/env bash
# Wheel proof (round-3 verdict, next-round item 6): packaging must be
# executable fact, not config.  Builds the wheel, installs it into a CLEAN
# venv (no repo on sys.path), and drives it: entry-point --help, native-lib
# presence, and a real 1-epoch training run exporting a scoreable model.
#
# Fully offline: --no-index everywhere; the venv sees the system
# site-packages only for the heavy deps (jax, flax, optax, orbax, numpy)
# the wheel itself does not vendor.  Reference anchor: package-shifu.sh:4-48
# (the reference's tarball injection this replaces).
#
# Run: bash scripts/prove_wheel.sh   (writes WHEEL_PROOF.json at repo root)
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
WORK="$(mktemp -d /tmp/stpu-wheel-XXXXXX)"
trap 'rm -rf "$WORK"' EXIT

echo "[1/5] build wheel (native libs compile in the build_py hook)"
cd "$REPO"
python -m pip wheel . --no-deps --no-build-isolation --no-index \
    -w "$WORK/dist" >"$WORK/build.log" 2>&1
WHEEL="$(ls "$WORK"/dist/*.whl)"

echo "[2/5] wheel carries the native libs (built from source by the hook)"
python - "$WHEEL" <<'EOF'
import sys, zipfile
names = zipfile.ZipFile(sys.argv[1]).namelist()
need = ["shifu_tensorflow_tpu/_native/libstpu_data.so",
        "shifu_tensorflow_tpu/_native/libstpu_scorer.so"]
missing = [n for n in need if n not in names]
assert not missing, f"wheel is missing native libs: {missing}"
print("   native libs present:", need)
EOF

echo "[3/5] clean venv + install (deps resolve from the invoking env)"
python -m venv "$WORK/venv"
# the invoking interpreter may itself be a venv, in which case
# --system-site-packages would skip over it to the bare system python —
# link the heavy deps (jax/flax/optax/orbax/numpy) explicitly via a .pth;
# it sorts AFTER the venv's own site-packages, so the wheel always wins
DEPS_SITE="$(python -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])')"
VENV_SITE="$("$WORK/venv/bin/python" -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])')"
echo "$DEPS_SITE" > "$VENV_SITE/zz_deps.pth"
"$WORK/venv/bin/pip" install --no-deps --no-index "$WHEEL" \
    >"$WORK/install.log" 2>&1

echo "[4/5] entry points respond"
cd "$WORK"   # OUT of the repo: imports must resolve from the wheel
"$WORK/venv/bin/stpu-train" --help >/dev/null
"$WORK/venv/bin/stpu-eval" --help >/dev/null
"$WORK/venv/bin/stpu-data" --help >/dev/null

echo "[5/5] 1-epoch smoke train + score through the installed wheel"
export WHEEL_PROOF_OUT="$REPO/WHEEL_PROOF.json"
JAX_PLATFORMS=cpu "$WORK/venv/bin/python" - <<'EOF'
import gzip, json, os, subprocess, sys, tempfile, time

import shifu_tensorflow_tpu as pkg
assert pkg.__file__.startswith(sys.prefix), (
    f"package resolved OUTSIDE the venv: {pkg.__file__}")

import numpy as np
work = tempfile.mkdtemp()
rng = np.random.default_rng(0)
n, f = 2000, 6
x = rng.normal(size=(n, f)).astype(np.float32)
y = (x[:, 0] + 0.5 * x[:, 1] + 0.2 * rng.normal(size=n) > 0).astype(int)
path = os.path.join(work, "part-00000.gz")
with gzip.open(path, "wt") as fh:
    for i in range(n):
        fh.write("|".join([str(y[i])] + [f"{v:.5f}" for v in x[i]] + ["1.0"]) + "\n")
mc = {"train": {"numTrainEpochs": 1, "validSetRate": 0.2,
                "params": {"NumHiddenLayers": 1, "NumHiddenNodes": [8],
                           "ActivationFunc": ["relu"], "LearningRate": 0.05,
                           "Optimizer": "adam"}}}
mcp = os.path.join(work, "ModelConfig.json")
open(mcp, "w").write(json.dumps(mc))
export_dir = os.path.join(work, "export")
venv_bin = os.path.dirname(sys.executable)
t0 = time.time()
proc = subprocess.run(
    [os.path.join(venv_bin, "stpu-train"),
     "--training-data-path", work, "--model-config", mcp,
     "--feature-columns", ",".join(str(i) for i in range(1, f + 1)),
     "--target-column", "0", "--weight-column", str(f + 1),
     "--batch-size", "200", "--export-dir", export_dir, "--seed", "1"],
    capture_output=True, text=True, timeout=300,
    env={**os.environ, "JAX_PLATFORMS": "cpu"},
)
assert proc.returncode == 0, proc.stderr[-2000:]
tail = json.loads(proc.stdout.strip().splitlines()[-1])
assert tail["state"] == "finished", tail
train_s = time.time() - t0

from shifu_tensorflow_tpu.export.eval_model import EvalModel
with EvalModel(export_dir, backend="native") as em:
    scores = em.compute_batch(x[:100])
assert scores.shape == (100, 1) and ((scores >= 0) & (scores <= 1)).all()

out = {
    "bench": "wheel_proof",
    "date": time.strftime("%Y-%m-%d"),
    "package_file": pkg.__file__,
    "train_state": tail["state"],
    "epochs_run": tail.get("epochs_run"),
    "smoke_train_s": round(train_s, 1),
    "scored_rows": 100,
}
print(json.dumps(out))
open(os.environ["WHEEL_PROOF_OUT"], "w").write(json.dumps(out) + "\n")
EOF
echo "wheel proof OK"
