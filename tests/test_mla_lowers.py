"""The latent-attention cell (``benchmark/configs/glm47_flash_ep8.json``),
asked of the chip's compiler without the chip, as tests/test_tpu_compile.py
asks the other cells' kernels (its fixtures and helpers, its rules: shapes
only, nothing runs): the three causal flash kernels at 20 heads of 256 and
tiles of 512, the passes that lay q, k and v out for them
(``ops/pallas/rope.py``: ``rope_lanes`` at an offset, ``latent_lanes``) with
nothing between the two, and the whole step with the multi-token prediction
module in its loss, which has to fit one v5e chip beside nothing else.

A file of its own beside that one (tests/test_ssd_kernel_lowers.py says
why): the step's compile takes 45 s.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from test_ssd_kernel_lowers import _shipped_lm_step_and_shapes
from test_tpu_compile import (  # noqa: F401  (the fixtures, by their names)
    _instructions,
    _kernels,
    _on,
    _through_bitcasts,
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


def _layout_calls(text) -> dict:
    """``instruction -> kernel`` of the calls that lay q, k and v out (the
    name a ``pallas_call`` was given ends its op's path in the metadata)."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = (?:\(.*?\)|\S+) custom-call\(.*op_name=\"[^\"]*"
        r"\b((?:rope|latent)_lanes(?:_t)?)\)*/pallas_call\"", text)}


def test_the_flash_kernels_read_the_latent_heads_where_they_lie(topo):
    """Expansion -> rotation -> attention -> ``o_proj`` as
    ``LatentAttentionMixer`` orders them, at the cell's shape (1 row of
    8,192, 20 heads of 192 + 64 | 256), forward and gradient, compiled
    together: one pass lays q out and one k and v, nothing but bitcasts
    stands between them and the flash kernels (nor between the dQ and
    dK/dV kernels and the transposed passes), and no concatenation, pad or
    slice of a tensor with a head axis is left anywhere in the program."""
    from shifu_tensorflow_tpu.models import hybrid_lm
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True)
    n, d_n, d_r, d_v, seq = 20, 192, 64, 256, 8192

    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def mixed(c_q, c_kv, k_r, w_q, w_kv, w_o):
        q = (c_q @ w_q).reshape(1, seq, n, d_n + d_r)
        kv = (c_kv @ w_kv).reshape(1, seq, n, d_n + d_v)
        cos, sin = hybrid_lm.rope_tables(
            hybrid_lm.RopeParameters("default", 1e6), seq, d_r)
        q = hybrid_lm.rotate(q, cos, sin, d_n)
        k, v = hybrid_lm.latent_heads(
            kv, k_r.reshape(1, seq, 1, d_r), cos, sin, d_n)
        return attention(q, k, v).reshape(1, seq, n * d_v) @ w_o

    def loss(*args):
        return jnp.sum(mixed(*args) ** 2)

    shapes = (on(1, seq, 768), on(1, seq, 512), on(1, seq, d_r),
              on(768, n * (d_n + d_r)), on(512, n * (d_n + d_v)),
              on(n * d_v, 2048))
    for fn, grad in ((mixed, False), (jax.grad(loss, range(6)), True)):
        text = jax.jit(fn).lower(*shapes).compile().as_text()
        entry = _instructions(text[text.rindex("ENTRY"):])
        calls = _layout_calls(text)
        assert sorted(calls.values()) == sorted(
            ["latent_lanes", "rope_lanes"]
            + (["latent_lanes_t", "rope_lanes_t"] if grad else []))

        def flash(met):
            return [m for m in met if m.startswith("custom-call:")
                    and m.split(":")[1] not in calls]

        for name, kernel in calls.items():
            tensors = 2 if kernel.startswith("latent") else 1  # k and v
            if kernel.endswith("_t"):
                # what the dQ, or the dK/dV, kernel wrote and nothing else
                made = [m for operand in entry[name][1][:tensors]
                        for m in _through_bitcasts(
                            {**entry, "_": ("bitcast", [operand], "")}, "_",
                            to_users=False)]
                assert made == flash(made) and len(made) == tensors, made
                continue
            # the forward kernel and, with the gradient, dQ and dK/dV
            met = _through_bitcasts(entry, name, to_users=True)
            assert met == flash(met), met
            assert len(met) == tensors * (3 if grad else 1)
        assert text.count("tpu_custom_call") == len(calls) + (
            3 if grad else 1)
        heads = re.findall(
            r"= f32\[(?:1,)?(?:8192,20|20,8192),\d\d+\]\S* "
            r"(concatenate|pad|slice|dynamic-slice|copy|transpose)\(", text)
        assert not heads, heads


def test_the_latent_cells_step_lowers_for_one_v5e_chip(topo):
    """The whole step of ``glm47_flash_ep8`` (1 row of 8,192 tokens, five
    blocks and the module, every layer and both head passes
    rematerialised): on each of six attention layers four flash kernel
    calls and six of the passes that lay q, k and v out for them (forward,
    recomputed forward and transpose of each of two); 8.48 GB of arguments
    (weights and two moments of 706.5 M parameters) and 5.2 GB of
    temporaries, 0.44 under what the concatenations, slices and padded
    sums of the parent's form took (5.594)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _shipped_lm_step_and_shapes("glm47_flash_ep8")
    assert sum(x.size for x in jax.tree.leaves(state.params)) == (
        706_518_528 + 5 * 64)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 6 * (4 + 6)
    assert sorted(_layout_calls(text).values()) == sorted(
        6 * (2 * ["latent_lanes", "rope_lanes"]
             + ["latent_lanes_t", "rope_lanes_t"]))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 8.5e9
    assert mem.temp_size_in_bytes < 5.594e9 + 0.2e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.3e9
