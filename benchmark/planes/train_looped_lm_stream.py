"""Plane ``train_looped_lm_stream``: ``train_lm_stream_ref``'s epochs and
check for a configuration whose stack runs several times a forward pass
(``total_ut_steps`` among its keys), and nothing else of its own.

``prepare`` first asks the program's ``HybridLMConfig`` for the loop: a
program whose reader has no ``total_ut_steps`` field (the parent of the PR
that added it) would drop the key and every other key of the family it
does not know, and read the configuration as one unlooped pass of blocks
whose feed-forwards are experts; it ends here, at once, with no result.
A reader that has the field is held to the configuration's value.  Then
``train_lm_stream_ref.prepare`` and its ``run``.
"""

from __future__ import annotations

from benchmark.planes import train_lm_stream_ref
from benchmark.planes.train_lm_stream_ref import _no_result, run  # noqa: F401


def prepare(ctx) -> None:
    """The loop's field before anything else; then the shards and
    ModelConfig.json, with no JAX."""
    from shifu_tensorflow_tpu.config import model_config

    family = getattr(model_config, "HybridLMConfig", None)
    fields = getattr(family, "__dataclass_fields__", {})
    if "total_ut_steps" not in fields:
        _no_result("this program's HybridLMConfig has no total_ut_steps: it "
                   "would run the stack once")
    params = ctx.config["model_config"]["train"]["params"]
    try:
        steps = family.from_json(params).total_ut_steps
    except (ValueError, KeyError, TypeError) as e:
        _no_result("this program's HybridLMConfig does not read the "
                   f"configuration ({type(e).__name__}: {e})")
    if steps != int(params["total_ut_steps"]):
        _no_result(f"this program reads total_ut_steps={steps} where the "
                   f"configuration says {params['total_ut_steps']}")
    train_lm_stream_ref.prepare(ctx)
