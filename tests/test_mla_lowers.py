"""The latent-attention cell (``benchmark/configs/glm47_flash_ep8.json``),
asked of the chip's compiler without the chip, as tests/test_tpu_compile.py
asks the other cells' kernels (its fixtures and helpers, its rules: shapes
only, nothing runs): the three causal flash kernels at 20 heads of 256 and
tiles of 512, and the whole step with the multi-token prediction module in
its loss, which has to fit one v5e chip beside nothing else.

A file of its own beside that one (tests/test_ssd_kernel_lowers.py says
why): the step's compile takes 45 s.
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


def test_flash_lowers_at_the_latent_cells_shape(topo):
    """20 heads of 256 (192 + 64 for queries and keys, 256 for values: two
    lane registers, nothing padded), one key and value a head, over the
    folded triangle at S 8,192 in 512 x 512 tiles: forward, dQ and dK/dV
    kernels within the chip's VMEM."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True)
    head = jax.ShapeDtypeStruct((1, 8192, 20, 256), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        head, head, head).compile()
    assert _kernels(compiled) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_the_latent_cells_step_lowers_for_one_v5e_chip(topo):
    """The whole step of ``glm47_flash_ep8`` (1 row of 8,192 tokens, five
    blocks and the module, every layer and both head passes
    rematerialised): four flash kernel calls on each of six attention
    layers; 8.48 GB of arguments (weights and two moments of 706.5 M
    parameters) and 5.6 GB of temporaries."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("glm47_flash_ep8")
    assert sum(x.size for x in jax.tree.leaves(state.params)) == (
        706_518_528 + 5 * 64)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    assert _kernels(compiled) == 6 * 4
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 8.5e9
    assert mem.temp_size_in_bytes < 5.8e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.3e9
