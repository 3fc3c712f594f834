"""The looped cell (``benchmark/configs/ouro_2p6b_pp8.json``), asked of the
chip's compiler without the chip, as tests/test_tpu_compile.py asks the
other cells' kernels (its fixtures and helpers, its rules: shapes only,
nothing runs): the three causal flash kernels at 16 query and 16 key/value
heads of 128 over 4,096 positions, and the whole step, which has to fit one
v5e chip with 2.1 GB to spare.

A file of its own beside that one (tests/test_ssd_kernel_lowers.py says
why): the step's compile takes a minute.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from test_ssd_kernel_lowers import _shipped_lm_step_and_shapes
from test_tpu_compile import (  # noqa: F401  (the fixtures, by their names)
    V5E_HBM_BYTES,
    _flash_forwards,
    _kernels,
    _on,
    no_persistent_cache,
    topo,
)


def test_flash_lowers_at_16_heads_of_128(topo):
    """Multi-head attention at the cell's shape, one row of 4,096 in 512 x
    512 tiles: forward, dQ and dK/dV kernels within the chip's VMEM, the
    heads at whole lane registers (nothing padded)."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True)
    head = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.float32,
                                sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        head, head, head).compile()
    assert _kernels(compiled) == 3
    lse = _flash_forwards(compiled.as_text())
    assert len(lse) == 1 and lse[0].startswith("f32[16,1,4096]")
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_looped_cells_step_lowers_for_one_v5e_chip(topo):
    """The whole step of ``ouro_2p6b_pp8`` (one row of 4,096 tokens, six
    blocks run four times, every layer and every head pass rematerialised):
    a flash forward, dQ and dK/dV kernel for each of the 24 block
    applications (the rematerialisation keeps each core's output and
    log-sum-exp, so no forward runs twice) and six rotary kernel passes
    (q and k forward, again in the recomputed forward, and their
    transposes); 6.12 GB of arguments (weights and two moments of 509.7 M
    parameters) and 7.07 GB of temporaries, 3.7 GB under the chip: the
    four head passes run one after the other (``passes_nll``), where side
    by side their 4,095 x 49,152 scores held 3.2 GB at once and left 2.06
    GB.  The only ``reduce-window`` is the exit distribution's sum over
    the three gated passes."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("ouro_2p6b_pp8")
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 509_661_185
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    text = compiled.as_text()
    lse = _flash_forwards(text)
    assert len(lse) == 24 and all(s.startswith("f32[16,1,4096]") for s in lse)
    assert _kernels(compiled) == 24 * 3 + 24 * 6
    windows = re.findall(r"= (\S+) reduce-window\(", text)
    assert windows and all(w.startswith("f32[3,1,4095]") for w in windows)
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes < 6.2e9
    assert mem.temp_size_in_bytes < 7.2e9
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES - 2.1e9)
