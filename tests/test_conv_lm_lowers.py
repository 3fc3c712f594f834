"""The short-convolution cell (``benchmark/configs/lfm2_24b_ep8.json``),
asked of the chip's compiler without the chip, as tests/test_tpu_compile.py
asks the other cells' kernels (its fixtures and helpers, its rules: shapes
only, nothing runs): the three causal flash kernels at 32 query heads of 64,
which they pad to 128 lanes, and the whole step, which has to fit one v5e
chip beside nothing else.

A file of its own beside that one (tests/test_ssd_kernel_lowers.py says
why): the step's compile takes most of a minute.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

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
    arguments (weights and two moments of 469.3 M parameters)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("lfm2_24b_ep8")
    assert sum(x.size for x in jax.tree.leaves(state.params)) == (
        469_284_992 + 4 * 64)
    assert "lm_head" not in state.params
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    assert _kernels(compiled) == 4
    assert "rope_lanes" not in compiled.as_text()
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes < 5.7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
