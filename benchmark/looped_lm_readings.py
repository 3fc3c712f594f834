"""What the readers of the looped decoder's cell (``hybrid_lm`` under the
``ouro`` keys) share: the configuration's shapes and a phase's time.  Each
reader asks :func:`looped_shapes` first, because another decoder's cell has
an ``attn.core``, an ``mlp.dense`` and an ``lm.head`` of its own."""

from benchmark.lm_readings import lm_shapes, phase_ms


def looped_shapes(r):
    """(params block, tokens a step, tokens a row) of a reading whose
    configuration is a ``hybrid_lm`` with ``total_ut_steps`` among its
    keys, else ``None``."""
    shapes = lm_shapes(r)
    if not shapes or "total_ut_steps" not in shapes[0]:
        return None
    return shapes


def looped_phase_ms(r, scope):
    """The scope's ``phase_ms``, in a cell of this configuration's kind
    only; ``None`` where the reading has no such phase (the parent of the PR
    that added the scope, a capture off the TPU)."""
    return phase_ms(r, scope) if looped_shapes(r) is not None else None


def counter_mean(r, name):
    """The mean of a step counter over the last epoch's steps, in a cell of
    this kind; ``None`` without it."""
    found = (r["spans"].get("@counters") or {}).get(name)
    if looped_shapes(r) is None or not found:
        return None
    return sum(found) / len(found)
