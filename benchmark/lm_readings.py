"""What the language-model readers share: a phase's time from the plane's
reduction, and the configuration's shapes."""

def phase_ms(r, scope):
    """Forward + backward ms a step of one ``jax.named_scope`` phase, from
    the medians the plane reduced with ``obs.profile.phases``; ``None``
    where the reading has no such phase (another family's cell, a program
    without the scope, a capture off the TPU)."""
    phases = r["spans"].get("@phases_ms")
    if not phases:
        return None
    found = [phases[k] for k in (scope + ".fwd", scope + ".bwd") if k in phases]
    return sum(found) if found else None


def lm_shapes(r):
    """(params block, tokens a step, tokens a row) of a hybrid_lm cell's
    reading, else ``None``."""
    cfg = r["config"].get("model_config", {}).get("train", {}).get(
        "params", {})
    if str(cfg.get("ModelType", "")).lower() != "hybrid_lm":
        return None
    seq = int(r["config"]["data"]["tokens_per_row"])
    return cfg, int(r["traffic"]["batch"]) * seq, seq


def roofline_pct(r, scope, kind, flops_of, bytes_of, load=None):
    """100 x (the least seconds the chip could take for every ``kind``
    layer's kernel, forward + backward) / (the phase's seconds a step,
    recomputation included).  ``load`` is what one layer's kernel worked
    on in a step; the step's tokens where the caller gives none."""
    from benchmark import shapes_lm

    ms, shapes = phase_ms(r, scope), lm_shapes(r)
    if not ms or shapes is None or r["peaks"] is None:
        return None
    cfg, tokens, _ = shapes
    load = tokens if load is None else load
    least = shapes_lm.layers_of(cfg, kind) * shapes_lm.least_seconds(
        flops_of(cfg, load), bytes_of(cfg, load), r["peaks"])
    return 100.0 * least * 1e3 / ms


def held_pairs_a_layer(r):
    """(token, choice) pairs on the held experts of one expert layer, a
    step: the mean of the step's ``moe_held_pairs`` counter (summed over
    the expert layers) over the last epoch; ``None`` without it."""
    from benchmark import shapes_lm

    pairs = (r["spans"].get("@counters") or {}).get("moe_held_pairs")
    shapes = lm_shapes(r)
    if not pairs or shapes is None:
        return None
    return sum(pairs) / len(pairs) / shapes_lm.layers_of(shapes[0], "E")
