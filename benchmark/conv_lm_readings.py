"""What the readers of the cells of the short-convolution decoder
(``hybrid_lm`` under the ``lfm2_moe`` keys) share: the configuration's
shapes, a phase's time and the pairs the counter saw.  A kernel's share of
its roofline is ``swa_lm_readings.roofline_pct``, whose gate (a
``hybrid_lm`` under ``layer_types``) holds here too: each reader asks
:func:`conv_shapes` first, because another decoder's cell has an
``attn.core``, a ``moe.route``, an ``mlp.dense`` and counters of its
own."""

from benchmark.lm_readings import lm_shapes, phase_ms


def conv_shapes(r):
    """(params block, tokens a step, tokens a row) of a reading whose
    configuration is a ``hybrid_lm`` with ``conv`` among its
    ``layer_types``, else ``None``."""
    shapes = lm_shapes(r)
    if not shapes or "conv" not in shapes[0].get("layer_types", ()):
        return None
    return shapes


def conv_phase_ms(r, *scopes):
    """The scopes' ``phase_ms`` summed, in a cell of this configuration's
    kind only; ``None`` where the reading has none of them (the parent of
    the PR that added a scope, a capture off the TPU)."""
    if conv_shapes(r) is None:
        return None
    found = [ms for ms in (phase_ms(r, s) for s in scopes) if ms is not None]
    return sum(found) if found else None


def counters(r):
    """The step's counters over the last epoch (``moe_held_pairs`` summed
    over the sparse layers, ``moe_held_max`` the largest held expert's),
    or ``None`` without them or in a cell of another kind."""
    found = r["spans"].get("@counters") or {}
    if conv_shapes(r) is None or not found.get("moe_held_pairs"):
        return None
    return found


def held_pairs_a_layer(r):
    """(token, choice) pairs on the held experts of one sparse layer, a
    step: the mean of ``moe_held_pairs`` over the last epoch's steps."""
    from benchmark import shapes_conv_lm

    found = counters(r)
    if found is None:
        return None
    pairs = found["moe_held_pairs"]
    return sum(pairs) / len(pairs) / shapes_conv_lm.sparse_layers(
        conv_shapes(r)[0])
