"""The chip's compiler, asked without the chip.

libtpu is installed in the sandbox and compiles for a TPU that is
described (``v5e:2x2``) and not attached, so what Mosaic or XLA:TPU would
refuse on the machine is refused here, at no chip time: a block shape
Mosaic cannot tile, a kernel that wants too much VMEM, a program that does
not fit HBM.  Interpret mode shows none of that — the flash kernel passed
every interpret-mode test while its logsumexp output could not lower.

Nothing runs, so a pass says nothing about results or times; that the same
programs RUN on the chip is ``chip_smoke.py``'s job.  Shapes only
(``jax.ShapeDtypeStruct``): there is no device to hold an array.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from shifu_tensorflow_tpu.ops.pallas.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host: nothing to ask
        pytest.skip(f"cannot describe a v5e:2x2 topology here: "
                    f"{type(e).__name__}: {e}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described device can be written to the persistent
    cache but not read back without the chip (the next one warns and
    compiles again), so keep the cache out of these compiles whatever an
    earlier test left configured."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------- kernels


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("head_dim", [32, 128])
@pytest.mark.parametrize("seq_len", [1024, 4096])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_lowers_for_v5e(topo, grad, seq_len, head_dim,
                                        dtype):
    """Forward (one kernel) and forward+backward (three: fwd, dQ, dK/dV)
    at the repo's largest sequence setting and at a long one."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    batch = 8 if seq_len == 1024 else 2
    x = jax.ShapeDtypeStruct((batch, seq_len, 4, head_dim), dtype,
                             sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, (0, 1, 2)) if grad else fwd
    compiled = jax.jit(fn).lower(x, x, x).compile()
    assert _kernels(compiled) == (3 if grad else 1)


# the language-model cell's kernels at its published widths
# (benchmark/configs/nemotron3_nano_ep16.json): 2 rows x 4,096 tokens


@pytest.mark.parametrize("seq_len,kv_heads", [(4096, 2), (8192, 4)],
                         ids=["nemotron-s4k", "mellum-s8k"])
def test_causal_grouped_query_flash_lowers_at_the_lm_cells_shape(
        topo, seq_len, kv_heads):
    """32 query heads of 128 over 2 KV heads at S 4,096 and over 4 at
    S 8,192 (the Mellum cell's full layer), through
    ``make_attention("flash", causal=True)`` with its 512-row tiles:
    forward, dQ and dK/dV kernels over the folded triangle, the KV heads
    repeated outside."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True)
    q = jax.ShapeDtypeStruct((2, seq_len, 32, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, seq_len, kv_heads, 128), jnp.float32,
                              sharding=one_chip)

    def loss(q, k, v):
        k, v = (jnp.repeat(x, 32 // kv_heads, axis=2) for x in (k, v))
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_chunked_scan_lowers_at_the_lm_cells_shape(topo):
    """64 heads of 64, state 128, 8 groups, chunk 128, forward + backward:
    no (S, S) anything, under 3 GB of temporaries."""
    from shifu_tensorflow_tpu.ops.ssm_scan import ssm_scan_chunked

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(x, dt, a, b, c):
        return jnp.sum(ssm_scan_chunked(x, dt, a, b, c, 128) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        on(2, 4096, 64, 64), on(2, 4096, 64), on(64),
        on(2, 4096, 8, 128), on(2, 4096, 8, 128)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_grouped_experts_lower_at_the_lm_cells_shape(topo):
    """8 held experts of 2688 x 1856 over the 49,152 (token, choice) pairs
    of a step: the tile walk is a ``while`` in both directions and the
    buffers are the tokens', not the worst case's."""
    from shifu_tensorflow_tpu.ops import grouped

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, up, down, weights, ids):
        pair, tile_expert, n_tiles, _ = grouped.plan_tiles(ids, 0, 8, 512)
        token = jnp.where(pair < ids.size, pair // 6, h.shape[0])
        gate = jnp.where(pair < ids.size,
                         jnp.take(weights.reshape(-1), pair, mode="clip"), 0.)
        return jnp.sum(grouped.expert_mlp(h, up, down, token, gate,
                                          tile_expert, n_tiles, 512) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
        on((8192, 2688)), on((8, 2688, 1856)), on((8, 1856, 2688)),
        on((8192, 6)), on((8192, 6), jnp.int32)).compile()
    assert compiled.as_text().count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# the sliding-window + gated-expert cell's kernels at its published widths
# (benchmark/configs/mellum2_ep4.json): 2 rows x 8,192 tokens


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_banded_flash_lowers_at_the_swa_cells_shape(topo, window):
    """32 query heads over 4 KV heads of 128 at S 8,192 with 512-row
    tiles: forward, dQ and dK/dV kernels, inside a 1,024 window (3 of 16
    blocks a grid row) and over the whole square."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True, window=window)
    q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.float32,
                              sharding=one_chip)

    def loss(q, k, v):
        k, v = (jnp.repeat(x, 8, axis=2) for x in (k, v))
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_gated_experts_lower_at_the_swa_cells_shape(topo):
    """16 held gated experts of 2304 x 896 over the 131,072 (token,
    choice) pairs of a step, the cell's 1,280-row tiles: a ``while`` in both
    directions, three weight gradients."""
    from shifu_tensorflow_tpu.ops import grouped

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, w_gate, up, down, weights, ids):
        pair, tile_expert, n_tiles, _ = grouped.plan_tiles(ids, 0, 16, 1280)
        token = jnp.where(pair < ids.size, pair // 8, h.shape[0])
        gate = jnp.where(pair < ids.size,
                         jnp.take(weights.reshape(-1), pair, mode="clip"), 0.)
        return jnp.sum(grouped.gated_expert_mlp(
            h, w_gate, up, down, token, gate, tile_expert, n_tiles,
            1280) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        on((16384, 2304)), on((16, 2304, 896)), on((16, 2304, 896)),
        on((16, 896, 2304)), on((16384, 8)),
        on((16384, 8), jnp.int32)).compile()
    assert compiled.as_text().count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# the mixed-head cell's kernels at its published widths
# (benchmark/configs/laguna_xs2_ep8.json): 1 row x 8,192 tokens


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)],
                         ids=["window-64-heads", "full-48-heads"])
def test_flash_lowers_at_the_mixed_head_cells_shapes(topo, heads, window):
    """64 query heads over 8 KV heads of 128 inside a 512 window (one
    512-row tile: a query block walks 2 key blocks), and 48 over the same 8
    on the folded triangle, at S 8,192: forward, dQ and dK/dV kernels."""
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True, window=window)
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.float32,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.float32,
                              sharding=one_chip)

    def loss(q, k, v):
        k, v = (jnp.repeat(x, heads // 8, axis=2) for x in (k, v))
        return jnp.sum(attention(q, k, v) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert _kernels(compiled) == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_small_gated_experts_lower_at_the_mixed_head_cells_shape(topo):
    """32 held gated experts of 2048 x 512 of a router's 256 over the
    65,536 (token, choice) pairs of a step, the cell's 384-row tiles: a
    ``while`` in both directions, three weight gradients."""
    from shifu_tensorflow_tpu.ops import grouped

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(h, w_gate, up, down, weights, ids):
        pair, tile_expert, n_tiles, _ = grouped.plan_tiles(ids, 0, 32, 384)
        token = jnp.where(pair < ids.size, pair // 8, h.shape[0])
        gate = jnp.where(pair < ids.size,
                         jnp.take(weights.reshape(-1), pair, mode="clip"), 0.)
        return jnp.sum(grouped.gated_expert_mlp(
            h, w_gate, up, down, token, gate, tile_expert, n_tiles,
            384) ** 2)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(
        on((8192, 2048)), on((32, 2048, 512)), on((32, 2048, 512)),
        on((32, 512, 2048)), on((8192, 8)),
        on((8192, 8), jnp.int32)).compile()
    assert compiled.as_text().count(" while(") >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# the rotary kernel (ops/pallas/rope.py) at the shapes the two cells with
# rotary positions run, alone and in front of the flash kernels

#: (rows, query heads, KV heads, dimensions that turn, window)
ROTARY_SHAPES = [
    pytest.param(2, 32, 4, 128, 1024, id="mellum-window-2x32-whole"),
    pytest.param(1, 64, 8, 128, 512, id="laguna-window-64-whole"),
    pytest.param(1, 48, 8, 64, None, id="laguna-full-48-half"),
]


def _rotary_shapes(one_chip, rows, heads, kv_heads, turning):
    def on(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    return (on(rows, 8192, heads, 128), on(rows, 8192, kv_heads, 128),
            on(8192, turning // 2))


@pytest.mark.parametrize("rows,heads,kv_heads,turning,window", ROTARY_SHAPES)
def test_rotary_kernel_lowers_at_the_cells_shapes(topo, rows, heads,
                                                  kv_heads, turning, window):
    """The rotation of q and of k and their transposes through Mosaic: two
    kernels a tensor, the forward's named ``rope_lanes`` and the
    backward's ``rope_lanes_t``."""
    from shifu_tensorflow_tpu.ops.pallas import rope

    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, table = _rotary_shapes(one_chip, rows, heads, kv_heads, turning)
    assert rope.lanes_pay(128, turning, jnp.float32)

    def loss(q, k, cos, sin):
        return sum(jnp.sum(rope.rope_lanes(u, cos, sin) ** 2)
                   for u in (q, k))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        q, k, table, table).compile().as_text()
    assert sorted(_rotary_calls(text).values()) == (
        ["rope_lanes"] * 2 + ["rope_lanes_t"] * 2)


def _rotary_calls(text) -> dict:
    """``instruction -> kernel`` of the rotary kernel's calls: the name a
    ``pallas_call`` was given ends its op's path in the metadata."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"%([\w.\-]+) = \S+ custom-call\(.*op_name=\"[^\"]*"
        r"\b(rope_lanes(?:_t)?)\)*/pallas_call\"", text)}


def _instructions(text) -> dict:
    """``name -> (opcode, operand names, result type)`` of a compiled
    module's text."""
    found = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\((.*?)\)(?:, |$)", line)
        if m:
            found[m.group(1)] = (m.group(3),
                                 re.findall(r"%([\w.\-]+)", m.group(4)),
                                 m.group(2))
    return found


def _through_bitcasts(instructions, name, to_users: bool) -> list[str]:
    """The opcodes the array meets first, bitcasts and tuple reads seen
    through: of its readers, or of the instruction that made it."""
    def step(n):
        if to_users:
            return [u for u, (_, ops, _) in instructions.items()
                    if n in ops]
        return instructions[n][1][:1]

    met, walk = [], step(name)
    while walk:
        n = walk.pop()
        op = instructions[n][0]
        if op in ("bitcast", "get-tuple-element"):
            walk.extend(step(n))
        else:
            met.append(f"{op}:{n}" if op == "custom-call" else op)
    return met


@pytest.mark.parametrize("rows,heads,kv_heads,turning,window", ROTARY_SHAPES)
def test_the_flash_kernels_read_the_rotated_heads_where_they_lie(
        topo, rows, heads, kv_heads, turning, window):
    """rope -> repeat -> banded / folded flash, as ``AttentionMixer``
    orders them, compiled together: nothing stands between q's rotation
    and the flash kernels (their ``(B, S, H, D) -> (B·H, S, D)`` is the
    inverse of the transposition the rotary kernel hands its output back
    through, and XLA folds the pair), nor between the dQ kernel and the
    transposed rotation; k's rotation is read by the repeat's broadcast
    and its transpose reads the repeat's sum, with no copy on the way."""
    from shifu_tensorflow_tpu.models import hybrid_lm
    from shifu_tensorflow_tpu.models.sequence import make_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    attention = make_attention("flash", None, causal=True, window=window)
    q, kv, table = _rotary_shapes(one_chip, rows, heads, kv_heads, turning)

    def attended(q, k, v, cos, sin):
        q, k = hybrid_lm.rotate(q, cos, sin), hybrid_lm.rotate(k, cos, sin)
        k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
        return attention(q, k, v)

    def loss(q, k, v, cos, sin):
        return jnp.sum(attended(q, k, v, cos, sin) ** 2)

    def flash_calls(met):
        return [m for m in met if m.startswith("custom-call:")
                and m.split(":")[1] not in calls]

    for fn, grad in ((attended, False), (jax.grad(loss, (0, 1, 2)), True)):
        text = jax.jit(fn).lower(q, kv, kv, table, table).compile().as_text()
        entry = _instructions(text[text.rindex("ENTRY"):])
        calls = _rotary_calls(text)
        assert sorted(calls.values()) == ["rope_lanes"] * 2 + (
            ["rope_lanes_t"] * 2 if grad else [])
        for n, kernel in calls.items():
            forward = kernel == "rope_lanes"
            met = _through_bitcasts(entry, n, to_users=forward)
            of_q = entry[n][2].startswith(
                f"f32[{rows},{heads},8192,128]" if forward
                else f"f32[{rows},8192,{heads * 128}]")
            if of_q:
                # the forward kernel and, with the gradient, dQ and dK/dV
                # read q; the dQ kernel alone writes its cotangent
                assert met == flash_calls(met), met
                assert len(met) == (3 if grad and forward else 1)
            else:
                assert met == (["broadcast"] if forward else ["reduce"]), met


# ----------------------------------------------------- the flagship step


def _step_and_shapes(mc, columns, mesh=None, with_grad_norm=False,
                     model=None):
    """The trainer's own step body and the abstract TrainState / batch it
    takes at ``mc.batch_size`` rows — built the way ``Trainer.__init__``
    builds them, minus everything that needs a device.  ``model`` where
    ``build_model`` would pick by ``jax.default_backend()``, which is the
    CPU here."""
    from shifu_tensorflow_tpu.models.factory import build_model, family_loss
    from shifu_tensorflow_tpu.train.optimizers import make_optimizer
    from shifu_tensorflow_tpu.train.trainer import (
        TrainState,
        make_train_step_body,
    )

    sharded = mesh is not None and mesh.shape.get("model", 1) > 1
    if model is None:
        model = build_model(mc, columns, shard_embeddings=sharded,
                            mesh=mesh)
    tx = make_optimizer(mc.params)

    def init():
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, len(columns))))["params"]
        state = TrainState.create(apply_fn=model.apply, params=params,
                                  tx=tx)
        return state.replace(step=jnp.asarray(state.step, jnp.int32))

    rows = mc.batch_size
    batch = {"x": jax.ShapeDtypeStruct((rows, len(columns)), jnp.float32),
             "y": jax.ShapeDtypeStruct((rows, 1), jnp.float32),
             "w": jax.ShapeDtypeStruct((rows, 1), jnp.float32)}
    body = make_train_step_body(model.apply, "mse", mc.params.l2_reg,
                                with_grad_norm=with_grad_norm,
                                batch_loss=family_loss(model))
    return body, jax.eval_shape(init), batch


def _flagship_step_and_shapes(mesh):
    """The flagship of ``__graft_entry__`` over a 1,048,576-row table, at
    batch 16,384."""
    import dataclasses

    import __graft_entry__ as g  # conftest puts the repo root on sys.path

    mc = dataclasses.replace(
        g._flagship_model_config(embedding_hash=1_048_576),
        batch_size=16_384)
    return _step_and_shapes(mc, tuple(range(g.NUM_FEATURES)), mesh)


def test_flagship_train_step_lowers_for_one_v5e_chip(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    body, state, batch = _flagship_step_and_shapes(None)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    mem = compiled.memory_analysis()
    # table + Adam mirrors is ~100 MB; a step that asked for gigabytes
    # would mean the gather or its gradient densified somewhere
    assert mem.temp_size_in_bytes < 2 << 30


def test_table_step_keeps_its_resting_layout_on_one_v5e_chip(topo):
    """The benchmark cell's step (``benchmark/configs/wdl_criteo.json``:
    wide_deep, 4,194,304 x 32 hashed table, batch 16,384, the health
    guard's gradient norm) must update the table in the layout it rests
    in.  A ``lax.cond`` around the update makes XLA copy table, moments
    and gradient into the branch's row-major layout (the 32-wide minor
    dimension padded to 128 lanes) and back: seven copies and 9.67 GB of
    temporaries a step.  The guard's select leaves the dense gradient's
    0.54 GB and no such copy."""
    import json

    from shifu_tensorflow_tpu.config.model_config import ModelConfig

    cell = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "configs", "wdl_criteo.json")
    with open(cell) as f:
        config = json.load(f)
    mc = ModelConfig.from_json(config["model_config"])
    table = (mc.params.embedding_hash_size, mc.params.embedding_dim)
    assert (mc.batch_size, table) == (16_384, (4_194_304, 32))
    features = config["data"]["numeric"] + config["data"]["categorical"]
    body, state, batch = _step_and_shapes(
        mc, tuple(range(1, features + 1)), with_grad_norm=True)
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        _on(one_chip, state), _on(one_chip, batch)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30
    text = compiled.as_text()
    assert "conditional" not in text
    assert not re.findall(r"= f32\[%d,%d\]\{1,0[^}]*\} copy\(" % table, text)
    _assert_the_gradient_is_scattered_in_lines(text, table)
    _assert_the_rows_are_read_by_lines(text, table)
    assert _kernels(compiled) == 3  # the two turns and the forward's pick


def _assert_the_gradient_is_scattered_in_lines(text, table):
    """``ops/embedding_grad.py``: no scatter into the table's own shape
    (rows on the lanes: 126-147 ns a lookup); the one into lines of
    ``128 // dim`` rows is told that its indices are sorted, and one
    kernel turns the lines into the table's layout."""
    rows, dim = table
    assert not re.findall(r"= f32\[%d,%d\][^ ]* scatter\(" % table, text)
    scatters = re.findall(
        r"= f32\[%d,128\]\{1,0[^ ]* scatter\(.*" % (rows * dim // 128), text)
    assert len(scatters) == 1, scatters
    assert "indices_are_sorted=true" in scatters[0], scatters[0]
    assert len(re.findall(r"= f32\[%d,%d\]\{1,0[^ ]* custom-call\(.*"
                          r"tpu_custom_call" % (dim, rows), text)) == 1


def _gather_operands(text) -> list[str]:
    """The shape (with its layout) of what every gather reads."""
    shape_of = dict(re.findall(r"%(\S+) = (\S+) ", text))
    return [shape_of[operand]
            for operand in re.findall(r" gather\(%([^,)]+)", text)]


def _assert_the_rows_are_read_by_lines(text, table):
    """``ops/embedding_grad.py`` ``rows_by_lines``: no gather out of the
    table's own shape (rows on the lanes: 38 ns a lookup); one kernel
    turns the table into lines, one gather fetches a line a lookup, one
    kernel picks the rows off them and writes them rows-on-lanes."""
    rows, dim = table
    read = _gather_operands(text)
    assert not [s for s in read if s.startswith("f32[%d,%d]" % table)], read
    assert len([s for s in read if s.startswith(
        "f32[%d,128]{1,0" % (rows * dim // 128))]) == 1, read
    assert len(re.findall(r"= f32\[%d,128\]\{1,0[^ ]* custom-call\(.*"
                          r"tpu_custom_call" % (rows * dim // 128),
                          text)) == 1
    assert len(re.findall(r"= f32\[%d,\d+\]\{1,0[^ ]* custom-call\(.*"
                          r"tpu_custom_call" % dim, text)) == 2


def test_a_scoring_forward_of_the_table_model_keeps_the_plain_gather(topo):
    """512 rows are 13,312 lookups into 4,194,304 rows: turning the whole
    table into lines (1.7 ms) would cost three times what XLA's gather of
    them does (0.58 ms), so the forward ``EvalModel``, the AOT ladder and
    the SavedModel run reads the table as it rests and holds no kernel."""
    import json

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.models.factory import build_model

    cell = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "configs", "wdl_criteo.json")
    with open(cell) as f:
        config = json.load(f)
    mc = ModelConfig.from_json(config["model_config"])
    table = (mc.params.embedding_hash_size, mc.params.embedding_dim)
    features = config["data"]["numeric"] + config["data"]["categorical"]
    model = build_model(mc, tuple(range(1, features + 1)))
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, features)))["params"])
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(
        lambda params, x: model.apply({"params": params}, x)).lower(
            _on(one_chip, params),
            jax.ShapeDtypeStruct((512, features), jnp.float32,
                                 sharding=one_chip)).compile()
    assert _kernels(compiled) == 0
    read = _gather_operands(compiled.as_text())
    assert len([s for s in read
                if s.startswith("f32[%d,%d]" % table)]) == 1, read
    assert not [s for s in read if ",128]" in s], read


def _placed_on(mesh, state):
    """What ``shard_params`` does, on shapes."""
    from shifu_tensorflow_tpu.parallel.sharding import (
        DEFAULT_PARTITION_RULES,
        _is_partitioned,
        params_shardings,
    )

    shardings = params_shardings(state, mesh, rules=DEFAULT_PARTITION_RULES)

    def place(leaf, sh):
        if _is_partitioned(leaf):
            return leaf.replace(value=place(leaf.value, sh))
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

    return jax.tree_util.tree_map(place, state, shardings,
                                  is_leaf=_is_partitioned), shardings


def test_table_step_runs_its_backward_per_device_on_the_2x2_mesh(topo):
    """The four-chip cell's step (``benchmark/configs/wdl_criteo_x4.json``:
    8,388,608 x 32 rows over ``model:2``, global batch 32,768 over
    ``data:2``): every device sorts its own 425,984 lookups and scatters
    into its own 4,194,304 rows.  A sort along the sharded batch under
    the partitioner would gather every data shard's gradient rows first.
    The forward is per device too: a device turns its own shard into
    lines and reads its data shard's lookups from them, and the parts'
    sum over ``model`` stays the partitioner's ``all-reduce``."""
    import dataclasses
    import json

    from shifu_tensorflow_tpu.config.model_config import ModelConfig
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.parallel.sharding import batch_sharding

    cell = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                        "configs", "wdl_criteo_x4.json")
    with open(cell) as f:
        config = json.load(f)
    assert config["mesh"] == "data:2,model:2"
    mesh = make_mesh(config["mesh"], devices=list(topo.devices))
    mc = ModelConfig.from_json(config["model_config"])
    mc = dataclasses.replace(mc, batch_size=2 * mc.batch_size)
    rows, dim = mc.params.embedding_hash_size, mc.params.embedding_dim
    assert (mc.batch_size, rows, dim) == (32_768, 8_388_608, 32)
    features = config["data"]["numeric"] + config["data"]["categorical"]
    body, state, batch = _step_and_shapes(
        mc, tuple(range(1, features + 1)), mesh, with_grad_norm=True)
    state, _ = _placed_on(mesh, state)
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        state, _on(batch_sharding(mesh), batch)).compile()
    text = compiled.as_text()
    _assert_the_gradient_is_scattered_in_lines(text, (rows // 2, dim))
    _assert_the_rows_are_read_by_lines(text, (rows // 2, dim))
    assert re.search(r"f32\[%d,%d\][^ ]* all-reduce\(" % (rows // 2, dim),
                     text), "the sum over data is the dense all-reduce"
    assert re.search(r"f32\[16384,%d\][^ ]* all-reduce\(" % (26 * dim),
                     text), "the sum of the model shards' rows, as it was"
    assert not re.findall(r"f32\[\d+,%d\][^ ]* all-gather" % dim, text)


def test_flagship_train_step_lowers_for_the_2x2_mesh(topo):
    """The four-chip path of ``chip_smoke.py --chips 4``: data:2 x model:2
    over the host's chips, the table (and its Adam mirrors) sharded
    row-wise on ``model``, the batch on ``data``."""
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.parallel.sharding import batch_sharding

    mesh = make_mesh("data:2,model:2", devices=list(topo.devices))
    body, state, batch = _flagship_step_and_shapes(mesh)
    state, shardings = _placed_on(mesh, state)
    tables = [sh for sh in jax.tree_util.tree_leaves(shardings)
              if isinstance(sh, NamedSharding) and "model" in sh.spec]
    assert len(tables) == 3, "table + Adam mu/nu shard on the model axis"
    compiled = jax.jit(body, donate_argnums=(0,)).lower(
        state, _on(batch_sharding(mesh), batch)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text, "the data axis must all-reduce gradients"
