"""Batch-scoring CLI — the eval entry point.

Parity surface: the reference's eval module plugs the exported model into
Shifu's Java batch-eval pipeline (`TensorflowModel implements Computable`,
TensorflowModel.java:32) — rows in, scores out, KS/AUC computed downstream.
Here the same operation is one command against any exported bundle:

    python -m shifu_tensorflow_tpu.export \
        --model-dir ./model-export --data-path /data/eval \
        --target-column 0 --output scores.txt

Backends: ``native`` (flax, default), ``cpp`` (the C++ scorer — DNN family,
zero Python-ML runtime), ``saved_model`` (TensorFlow — the exact signature
the Java evaluator consumes).  When the data carries a target column the
summary line includes KS and AUC.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from shifu_tensorflow_tpu.data.dataset import ShardStream
from shifu_tensorflow_tpu.data.reader import RecordSchema
from shifu_tensorflow_tpu.data.splitter import list_data_files
from shifu_tensorflow_tpu.export.eval_model import EvalModel
from shifu_tensorflow_tpu.ops import metrics as M


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m shifu_tensorflow_tpu.export",
        description="Score PSV(.gz) rows against an exported model bundle.",
    )
    p.add_argument("--model-dir", required=True)
    p.add_argument("--data-path", required=True,
                   help="file/dir of delimited rows to score")
    p.add_argument("--backend", default="native",
                   choices=["native", "cpp", "saved_model"])
    p.add_argument("--feature-columns", default=None,
                   help="comma-separated; default: 1..num_features in order")
    p.add_argument("--target-column", type=int, default=None,
                   help="label column for KS/AUC (omit to skip metrics)")
    p.add_argument("--weight-column", type=int, default=None)
    p.add_argument("--delimiter", default="|")
    p.add_argument("--batch-size", type=int, default=4096)
    p.add_argument("--output", default=None,
                   help="write one score per line here (default: no file)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    # after parse_args: --help must not pay for it
    from shifu_tensorflow_tpu.obs.compile import apply_persistent_cache

    apply_persistent_cache()
    paths = list_data_files(args.data_path)
    if not paths:
        print(f"no data files under {args.data_path}", file=sys.stderr)
        return 2

    with EvalModel(args.model_dir, backend=args.backend) as em:
        if args.feature_columns:
            features = tuple(
                int(c) for c in args.feature_columns.split(",")
            )
        else:
            # the reference layout: target first, then the feature vector
            features = tuple(range(1, em.num_features + 1))
        if len(features) != em.num_features:
            print(
                f"model expects {em.num_features} features, schema has "
                f"{len(features)}",
                file=sys.stderr,
            )
            return 2
        has_target = args.target_column is not None
        schema = RecordSchema(
            feature_columns=features,
            # scoring-only data may have no label; reuse a feature column as
            # a stand-in target so the row parser has a full wanted set
            target_column=args.target_column if has_target else features[0],
            weight_column=(
                args.weight_column if args.weight_column is not None else -1
            ),
            delimiter=args.delimiter,
        )
        stream = ShardStream(paths, schema, args.batch_size, valid_rate=0.0)
        out_f = open(args.output, "w") if args.output else None
        scores, labels, weights = [], [], []
        n_rows = 0
        try:
            for batch in stream:
                mask = batch["w"][:, 0] > 0  # padding rows carry weight 0
                x = batch["x"][mask]
                if x.shape[0] == 0:
                    continue
                s = em.compute_batch(x)[:, 0]
                n_rows += x.shape[0]
                if out_f is not None:
                    out_f.write("\n".join(f"{v:.6f}" for v in s) + "\n")
                if has_target:
                    scores.append(s)
                    labels.append(batch["y"][mask][:, 0])
                    weights.append(batch["w"][mask][:, 0])
        finally:
            if out_f is not None:
                out_f.close()

    summary = {"rows": n_rows, "backend": args.backend}
    if has_target and scores:
        s = np.concatenate(scores)
        y = np.concatenate(labels)
        w = np.concatenate(weights)
        summary["ks"] = round(float(M.ks_statistic(s, y, w)), 6)
        summary["auc"] = round(float(M.auc(s, y, w)), 6)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
