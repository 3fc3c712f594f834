"""Operations and bytes of one training step, from shapes alone.

The least the algorithm needs (forward + backward + the optimizer the
configuration names), not what a compiler happens to emit:

FLOPs   every dense kernel ``(in, out)`` costs ``2*B*in*out`` forward and
        twice that backward (input gradient and weight gradient; the first
        layer's input gradient is needed too, because the embeddings are
        part of its input), so ``6 * B * sum(in*out)``.  Element-wise work
        (activations, hashing, Adam's arithmetic) is not counted.
bytes   Adam as optax defines it is dense: every parameter's gradient is
        written once (4 B) and then p, m, v, g are read and p, m, v written
        (28 B): 32 B a parameter, tables included.  The batch is read once
        (features, target, weight).  Each gathered embedding row is read
        forward and its gradient row read-modify-written backward
        (3 x 4 B an element).  Each layer's activations (the input of the
        first layer included) are written forward and read backward
        (2 x 4 B an element).

All sizes are float32, as the configurations state their dtype.
"""

from __future__ import annotations


def widths(model_config: dict, num_features: int) -> dict:
    p = model_config["train"]["params"]
    n = int(p["NumHiddenLayers"])
    hidden = [int(h) for h in p["NumHiddenNodes"]][:n]
    emb_cols = len(p.get("EmbeddingColumnNums") or [])
    emb_rows = int(p.get("EmbeddingHashSize", 0)) if emb_cols else 0
    emb_dim = int(p.get("EmbeddingDim", 8)) if emb_rows else 0
    wide_deep = str(p.get("ModelType", "dnn")).lower() == "wide_deep"
    wide_cols = len(p.get("WideColumnNums") or []) if wide_deep else 0
    cross_rows = int(p.get("CrossHashSize", 0)) if wide_cols else 0
    first = num_features + emb_cols * emb_dim
    layers = list(zip([first] + hidden, hidden + [1]))
    if wide_deep:  # the wide linear part: its columns (all, if none named)
        layers.append((wide_cols or first, 1))
    return {"layers": layers, "first": first, "hidden": hidden,
            "emb_cols": emb_cols, "emb_rows": emb_rows, "emb_dim": emb_dim,
            "cross_rows": cross_rows, "wide_deep": wide_deep,
            "wide_cols": wide_cols}


def parameter_count(w: dict) -> int:
    dense = sum(i * o for i, o in w["layers"])
    # biases: every layer but the wide linear one (use_bias=False)
    biases = sum(o for _, o in w["layers"]) - (1 if w["wide_deep"] else 0)
    return (dense + biases + w["emb_rows"] * w["emb_dim"] + w["cross_rows"])


def train_step_flops(model_config: dict, num_features: int,
                     batch: int) -> float:
    w = widths(model_config, num_features)
    return 6.0 * batch * sum(i * o for i, o in w["layers"])


def train_step_bytes(model_config: dict, num_features: int,
                     batch: int) -> float:
    w = widths(model_config, num_features)
    optimizer = 32.0 * parameter_count(w)
    inputs = 4.0 * batch * (num_features + 2)
    gathered = 12.0 * batch * (w["emb_cols"] * w["emb_dim"]
                               + (1 if w["cross_rows"] else 0))
    activations = 8.0 * batch * (w["first"] + sum(w["hidden"]) + 1)
    return optimizer + inputs + gathered + activations


def roofline(flops: float, nbytes: float, peaks: dict) -> dict:
    """The least seconds the chip could take, and which bound binds."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "flops_s": t_flops, "bytes_s": t_bytes}
