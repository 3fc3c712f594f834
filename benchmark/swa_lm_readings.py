"""What the readers of the sliding-window + gated-expert cells share: the
configuration's shapes, the pairs the counter saw, a kernel's share of its
roofline.  A phase's time is ``lm_readings.phase_ms``."""

from benchmark.lm_readings import phase_ms
from benchmark.shapes_lm import least_seconds


def swa_shapes(r):
    """(params block, tokens a step, tokens a row) of a reading whose
    configuration is a ``hybrid_lm`` under the ``layer_types`` keys, else
    ``None`` (another family's cell, or the pattern-string decoder's)."""
    cfg = r["config"].get("model_config", {}).get("train", {}).get(
        "params", {})
    if (str(cfg.get("ModelType", "")).lower() != "hybrid_lm"
            or "layer_types" not in cfg):
        return None
    seq = int(r["config"]["data"]["tokens_per_row"])
    return cfg, int(r["traffic"]["batch"]) * seq, seq


def swa_phase_ms(r, scope):
    """``phase_ms`` in a cell of this configuration's kind only: the other
    decoder's cell has an ``attn.core`` and a ``moe.route`` of its own."""
    return phase_ms(r, scope) if swa_shapes(r) is not None else None


def held_pairs_a_layer(r):
    """(token, choice) pairs on the held experts of one expert layer, a
    step: the mean of the step's ``moe_held_pairs`` counter (summed over
    the expert layers) over the last epoch; ``None`` without it."""
    pairs = (r["spans"].get("@counters") or {}).get("moe_held_pairs")
    shapes = swa_shapes(r)
    if not pairs or shapes is None:
        return None
    return sum(pairs) / len(pairs) / len(shapes[0]["layer_types"])


def roofline_pct(r, scope, layers, flops, nbytes):
    """100 x ``layers`` x (the least seconds the chip could take for one
    layer's kernel, forward + backward) / (the phase's seconds a step,
    recomputation included)."""
    ms = swa_phase_ms(r, scope)
    if not ms or r["peaks"] is None:
        return None
    least = layers * least_seconds(flops, nbytes, r["peaks"])
    return 100.0 * least * 1e3 / ms
