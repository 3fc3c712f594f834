"""The scan kernels (``ops/pallas/ssd_scan.py``), asked of the chip's
compiler without the chip, as tests/test_tpu_compile.py asks the other
kernels (its fixtures and helpers, its rules: shapes only, nothing runs):
the path ``models/hybrid_lm.py`` ``chunked_scan`` selects at the Nemotron
cell's shape is one forward and one backward kernel and holds no (chunk,
chunk) square of a head and chunk; every corner ``ssd_pays`` admits lowers;
the whole Nemotron step holds the kernels 8 + 4 times.  That the kernels'
numbers are the expression's is tests/test_ssd_kernel.py's.

A file of its own beside that one, not more tests in it: the driver's run
hands whole files to its six workers, tests/test_tpu_compile.py is the
longest file of the run and among the last to start, and these six
tests (80 s, 60 of them the step's compile) would all lengthen the run's
tail by as much.
"""

import functools
import json
import operator
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (  # noqa: F401  (the fixtures, by their names)
    _kernels,
    _on,
    _step_and_shapes,
    no_persistent_cache,
    topo,
)


def _scan_shapes(one_chip):
    """x as the convolution leaves it (token-major, a group's heads side
    by side), ``dt``, ``a``, B and C at the Nemotron cell's widths."""
    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    return (on(2, 4096, 64 * 64), on(2, 4096, 64), on(64),
            on(2, 4096, 8 * 128), on(2, 4096, 8 * 128))


def _scan_calls(text) -> dict:
    """How often a compiled program holds each scan kernel: the name a
    ``pallas_call`` was given ends its op's path in the metadata."""
    return {name: len(re.findall(
        r"custom-call\([^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*op_name=\"[^\"]*\b" + name + r"\)*/pallas_call\"", text))
        for name in ("ssd_scan_fwd", "ssd_scan_bwd")}


#: elements of x, and of the states that entered the chunks, at that shape
_SCAN_X = 2 * 4096 * 64 * 64


def test_the_selected_scan_is_two_kernels_at_the_lm_cells_shape(topo):
    """``models/hybrid_lm.py`` ``chunked_scan`` at the shape of
    ``test_chunked_scan_lowers_at_the_lm_cells_shape``, lowered for the
    TPU: forward + backward hold the forward and the backward kernel once
    each, no (chunk, chunk) square of a head and chunk (the expression's
    ``f32[2,32,8,8,128,128]``, 268 MB) and nothing larger than x or the
    entering states, and 0.504 GB of temporaries (the expression's are
    0.888): the entering states, ``dy`` as the loss hands it over, and the
    kernels' outputs before their sums."""
    from shifu_tensorflow_tpu.models import hybrid_lm

    one_chip = SingleDeviceSharding(topo.devices[0])

    def scan(x, dt, a, b, c):
        return hybrid_lm.chunked_scan(
            x.reshape(2, 4096, 64, 64), dt, a, b.reshape(2, 4096, 8, 128),
            c.reshape(2, 4096, 8, 128), 128)

    def loss(*args):
        return jnp.sum(scan(*args) ** 2)

    shapes = _scan_shapes(one_chip)
    forward = jax.jit(scan).lower(*shapes).compile()
    assert _scan_calls(forward.as_text()) == {"ssd_scan_fwd": 1,
                                              "ssd_scan_bwd": 0}
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        *shapes).compile()
    text = compiled.as_text()
    assert _scan_calls(text) == {"ssd_scan_fwd": 1, "ssd_scan_bwd": 1}
    assert _kernels(compiled) == 2
    for dims in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text):
        dims = [int(d) for d in dims.split(",")]
        assert dims[-2:] != [128, 128] or len(dims) < 5, dims
        assert functools.reduce(operator.mul, dims, 1) <= _SCAN_X, dims
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("chunk,dim,in_group,state", [
    (256, 128, 1, 256), (128, 8, 16, 128), (128, 256, 1, 128),
    (256, 64, 4, 128)], ids=["chunk256-state256", "sixteen-heads-of-8",
                             "a-head-of-256", "chunk256-heads-of-64"])
def test_every_corner_of_the_scans_rule_lowers(topo, chunk, dim, in_group,
                                                state):
    """``ssd_pays`` admits more than the Nemotron cell's shape: a chunk or
    a state of two registers, sixteen heads to a register, a head of two.
    What the rule admits has to lower, or a configuration that picks it
    would fail where the expression ran."""
    from shifu_tensorflow_tpu.ops.pallas import ssd_scan

    one_chip = SingleDeviceSharding(topo.devices[0])
    groups, seq = 2, 4 * chunk
    assert ssd_scan.ssd_pays(chunk, dim, in_group, state, jnp.float32, seq)

    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(*args):
        return jnp.sum(ssd_scan.ssd_scan(*args, chunk) ** 2)

    heads = groups * in_group
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        on(2, seq, heads, dim), on(2, seq, heads), on(heads),
        on(2, seq, groups, state), on(2, seq, groups, state)).compile()
    assert _scan_calls(compiled.as_text()) == {"ssd_scan_fwd": 1,
                                               "ssd_scan_bwd": 1}


def _shipped_lm_step_and_shapes(name):
    """:func:`_step_and_shapes` over one chip's share of a shipped
    language-model configuration (``benchmark/configs/<name>.json``), with
    the attention a program on the TPU gets."""
    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models import hybrid_lm
    from shifu_tensorflow_tpu.models.sequence import make_attention

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           name + ".json")) as f:
        cell = json.load(f)
    mc = ModelConfig.from_json(cell["model_config"])
    cfg = mc.params.hybrid_lm
    model = hybrid_lm.HybridLM(
        cfg=cfg, attention=make_attention("flash", None, causal=True),
        window_attention=(
            make_attention("flash", None, causal=True,
                           window=cfg.sliding_window)
            if "W" in cfg.hybrid_override_pattern else None))
    return _step_and_shapes(mc, range(cell["data"]["tokens_per_row"]),
                            with_grad_norm=True, model=model)


def test_the_nemotron_step_holds_the_scan_kernels_twelve_times(topo):
    """The whole step of ``nemotron3_nano_ep16`` (2 rows of 4,096 tokens,
    9 layers, every layer rematerialised) for one v5e chip: its four ``M``
    layers each run the forward kernel twice (forward, rematerialised
    forward) and the backward kernel once.  How often the kernels engage is
    static, so this count is their counter.  8.0 GB of arguments and 3.58
    GB of temporaries (the parent's step: 3.75)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("nemotron3_nano_ep16")
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    text = compiled.as_text()
    assert _scan_calls(text) == {"ssd_scan_fwd": 8, "ssd_scan_bwd": 4}
    # ... beside the causal flash kernels of its one attention layer
    assert _kernels(compiled) == 12 + 4
    assert "f32[2,32,8,8,128,128]" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 3.7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
