"""What the readers of the cells of the mixed-head decoder (``hybrid_lm``
under the ``laguna`` keys) share: the configuration's shapes and the pairs the
counter saw.  A phase's time is ``lm_readings.phase_ms``; a kernel's share
of its roofline is ``swa_lm_readings.roofline_pct``, whose gate (a
``hybrid_lm`` under ``layer_types``) holds here too: the readers ask
:func:`mixed_shapes` first."""

from benchmark.lm_readings import phase_ms


def mixed_shapes(r):
    """(params block, tokens a step, tokens a row) of a reading whose
    configuration is a ``hybrid_lm`` with heads by layer
    (``num_attention_heads_per_layer``), else ``None``: another family's
    cell, or another decoder's, whose phases carry the same names."""
    cfg = r["config"].get("model_config", {}).get("train", {}).get(
        "params", {})
    if (str(cfg.get("ModelType", "")).lower() != "hybrid_lm"
            or "num_attention_heads_per_layer" not in cfg):
        return None
    seq = int(r["config"]["data"]["tokens_per_row"])
    return cfg, int(r["traffic"]["batch"]) * seq, seq


def mixed_phase_ms(r, scope):
    """``phase_ms`` in a cell of this configuration's kind only."""
    return phase_ms(r, scope) if mixed_shapes(r) is not None else None


def counters(r):
    """The step's counters over the last epoch (``moe_held_pairs`` summed
    over the expert layers, ``moe_held_max`` the largest held expert's),
    or ``None`` without them or in a cell of another kind."""
    found = r["spans"].get("@counters") or {}
    if mixed_shapes(r) is None or not found.get("moe_held_pairs"):
        return None
    return found


def held_pairs_a_layer(r):
    """(token, choice) pairs on the held experts of one expert layer, a
    step: the mean of ``moe_held_pairs`` over the last epoch's steps."""
    from benchmark import shapes_mixed_lm

    found = counters(r)
    if found is None:
        return None
    pairs = found["moe_held_pairs"]
    return sum(pairs) / len(pairs) / shapes_mixed_lm.blocks_of(
        mixed_shapes(r)[0], "sparse")
