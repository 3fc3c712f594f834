"""What the readers of the cells of the latent-attention decoder
(``hybrid_lm`` under the ``glm4_moe_lite`` keys) share: the configuration's
shapes, a phase's time, the pairs the counter saw, a kernel's share of its
roofline.  Each asks :func:`mla_shapes` first: another decoder's cell has
an ``attn.core``, a ``moe.route`` and counters of its own."""

from benchmark.lm_readings import lm_shapes, phase_ms
from benchmark.shapes_lm import least_seconds


def mla_shapes(r):
    """(params block, tokens a step, tokens a row) of a reading whose
    configuration is a ``hybrid_lm`` with latent attention
    (``kv_lora_rank``), else ``None``."""
    shapes = lm_shapes(r)
    return shapes if shapes and "kv_lora_rank" in shapes[0] else None


def mla_phase_ms(r, *scopes):
    """The scopes' ``phase_ms`` summed, in a cell of this configuration's
    kind only; ``None`` where the reading has none of them (the parent of
    the PR that added a scope, a capture off the TPU)."""
    if mla_shapes(r) is None:
        return None
    found = [ms for ms in (phase_ms(r, s) for s in scopes) if ms is not None]
    return sum(found) if found else None


def counters(r):
    """The step's counters over the last epoch (``moe_held_pairs`` summed
    over the sparse layers, the module's among them, ``moe_held_max`` the
    largest held expert's), or ``None`` without them or in a cell of
    another kind."""
    found = r["spans"].get("@counters") or {}
    if mla_shapes(r) is None or not found.get("moe_held_pairs"):
        return None
    return found


def held_pairs_a_layer(r):
    """(token, choice) pairs on the held experts of one sparse layer, a
    step: the mean of ``moe_held_pairs`` over the last epoch's steps."""
    from benchmark import shapes_mla_lm

    found = counters(r)
    if found is None:
        return None
    pairs = found["moe_held_pairs"]
    return sum(pairs) / len(pairs) / shapes_mla_lm.sparse_layers(
        mla_shapes(r)[0])


def roofline_pct(r, scope, layers, flops, nbytes):
    """100 x ``layers`` x (the least seconds the chip could take for one
    layer's kernel, forward + backward) / (the phase's seconds a step,
    recomputation included)."""
    ms = mla_phase_ms(r, scope)
    if not ms or r["peaks"] is None:
        return None
    return 100.0 * layers * least_seconds(flops, nbytes, r["peaks"]) * 1e3 / ms
