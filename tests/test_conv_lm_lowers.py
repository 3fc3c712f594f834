"""The short-convolution cell (``benchmark/configs/lfm2_24b_ep8.json``),
asked of the chip's compiler without the chip, as tests/test_tpu_compile.py
asks the other cells' kernels (its fixtures and helpers, its rules: shapes
only, nothing runs): the three causal flash kernels at 32 query heads of 64,
which they pad to 128 lanes, the whole step, which has to fit one v5e chip
beside nothing else, and the head's row maximum, a plain reduce at every
width (``models/hybrid_lm.py`` ``log_softmax``).

A file of its own beside that one (tests/test_ssd_kernel_lowers.py says
why): the step's compile takes most of a minute.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from shifu_tensorflow_tpu.models import hybrid_lm

from test_ssd_kernel_lowers import _shipped_lm_step_and_shapes
from test_tpu_compile import (  # noqa: F401  (the fixtures, by their names)
    _kernels,
    _on,
    no_persistent_cache,
    topo,
)


def test_flash_lowers_at_a_head_of_64(topo):
    """32 heads of 64 over the folded triangle at S 8,192 in 512 x 512
    tiles, two rows: forward, dQ and dK/dV kernels within the chip's VMEM;
    the kernels pad the head to 128 lanes themselves."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True)
    head = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        head, head, head).compile()
    assert _kernels(compiled) == 3
    assert "f32[2,32,8192,128]" in compiled.as_text()  # the padded heads
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_the_short_convolution_cells_step_lowers_for_one_v5e_chip(topo):
    """The whole step of ``lfm2_24b_ep8`` (2 rows of 8,192 tokens, five
    blocks, every layer and the tied head rematerialised): four flash
    kernel calls on its one attention layer (forward, recomputed forward,
    dQ, dK/dV) and no kernel for the rotation (a head of 64 is under the
    128 lanes ``ops/pallas/rope.py`` ``lanes_pay`` asks for); 5.63 GB of
    arguments (weights and two moments of 469.3 M parameters) and 3.34 GB
    of temporaries (3.41 before the head's maximum went plain).  The tied
    head's row of 8,192 columns is reduced plainly: its maximum twice
    (forward, recomputed forward) as ``f32[2,8191]``, and the only
    ``reduce-window``s left are the expert layers' running counts over
    the 8 held experts, ``s32[8,1]``."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("lfm2_24b_ep8")
    assert sum(x.size for x in jax.tree.leaves(state.params)) == (
        469_284_992 + 4 * 64)
    assert "lm_head" not in state.params
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    assert _kernels(compiled) == 4
    text = compiled.as_text()
    assert "rope_lanes" not in text
    windows = re.findall(r"= (\S+) reduce-window\(", text)
    assert windows and all(w.startswith("s32[8,1]") for w in windows)
    assert len(re.findall(
        r"= f32\[2,8191\]\S* reduce\(.*reduce_max", text)) == 2
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes < 5.7e9
    assert mem.temp_size_in_bytes < 3.37e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9


#: the sweep of the head alone (PR 39): the powers of two from 128 to
#: 32,768, the first width past the window and the accepted cells' slices
HEAD_WIDTHS = [1 << n for n in range(7, 16)] + [8193, 8320, 12544, 19360,
                                                24576]


def _lowered_head(topo, vocab):
    """The tied head's loss and gradient (forward, recomputed forward,
    backward) over 2 x 511 rows at hidden 256, lowered for one v5e chip:
    whether the row maximum windows hangs on the width alone (the sweep
    read 16 to 16,382 rows and hidden 64 to 2,048 alike)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    head = nn.remat(hybrid_lm.LMHead)(vocab, 256, 0.02, tied=True)

    def loss(table, h, ids, live):
        return head.apply({}, h, ids, live, table)[0]

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        on((vocab, 256)), on((2, 512, 256)), on((2, 512), jnp.int32),
        on((2,)))


@pytest.mark.parametrize("vocab", HEAD_WIDTHS)
def test_the_heads_row_maximum_is_a_plain_reduce_at_every_width(
        topo, vocab, monkeypatch):
    """No width compiles the log-softmax's row maximum to a
    ``reduce-window``.  Up to ``WINDOWED_ROW`` columns jax's own
    expression does, and the head takes its maximum through a barrier;
    past it the head lowers to the text jax's expression lowers to."""
    mine = _lowered_head(topo, vocab)
    assert "reduce-window" not in mine.compile().as_text()
    monkeypatch.setattr(hybrid_lm, "log_softmax",
                        lambda x: jax.nn.log_softmax(x, axis=-1))
    plain = _lowered_head(topo, vocab)
    if vocab > hybrid_lm.WINDOWED_ROW:
        assert (mine.as_text(debug_info=False)
                == plain.as_text(debug_info=False))
    else:
        assert "reduce-window" in plain.compile().as_text()
