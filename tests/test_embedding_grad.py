"""The embedding lookup's own backward (``models/embeddings.py``
``take_rows``, ``ops/embedding_grad.py``) against ``jax.grad`` through a
plain ``jnp.take``.

The weights on the looked-up rows are multiples of 1/64 in [-2, 2], so a
float32 sum of any 2^17 of them is exact in every order: what the two
backwards may differ by is then a fault and no rounding of either.

Tables of ``DIM`` 8 take the path the benchmark's table takes: 16 rows to
a 128-lane line, whole blocks of 2,048 rows, the lines turned into rows at
the end.  A table whose rows are not whole blocks is built a block longer
and cut; one whose ``dim`` does not tile a line keeps XLA's transpose.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from shifu_tensorflow_tpu.models.embeddings import take_rows
from shifu_tensorflow_tpu.ops import embedding_grad

DIM = 8


def _weights(rng, *shape):
    return jnp.asarray(rng.integers(-128, 129, size=shape) / 64.0,
                       jnp.float32)


def _zipf_ids(rng, n, hash_size, s=1.05):
    """A bounded power law over ``hash_size`` ranks, scattered over the
    table by a fixed odd multiplier (as a hash would)."""
    u = rng.random(n)
    rank = ((hash_size ** (1.0 - s) - 1.0) * u + 1.0) ** (1.0 / (1.0 - s))
    rank = np.minimum(np.floor(rank) - 1, hash_size - 1).astype(np.int64)
    return (rank * 2654435761 % hash_size).astype(np.int32)


def _loss(lookup):
    def loss(table, ids, w):
        return jnp.sum(lookup(table, ids).astype(jnp.float32) * w)
    return loss


_plain = _loss(lambda table, ids: jnp.take(table, ids, axis=0))
_ours = _loss(take_rows)


def _case(name):
    rng = np.random.default_rng(27)
    dtype, dim, transform = jnp.float32, DIM, "none"
    if name == "distinct":
        hash_size, ids = 4096, rng.permutation(4096)[:1024]
    elif name == "all_equal":
        hash_size, ids = 2048, np.full(768, 1717)
    elif name == "zipf_runs_of_thousands":
        hash_size, ids = 262_144, _zipf_ids(rng, 425_984 // 16, 262_144)
        runs = np.unique(ids, return_counts=True)[1]
        assert runs.max() > 1000 and runs.size > ids.size // 8
    elif name == "first_and_last_row":
        hash_size = 4096
        ids = np.concatenate([[0, 4095, 4095, 0], rng.integers(0, 4096, 60)])
    elif name == "first_and_last_row_of_a_ragged_table":
        hash_size = 1000
        ids = np.concatenate([[0, 999, 999, 0], rng.integers(0, 1000, 60)])
    elif name == "lookups_not_a_multiple_of_128":
        hash_size, ids = 2048, rng.integers(0, 2048, 845) // 8
    elif name == "bfloat16_table":
        hash_size, ids = 2048, rng.integers(0, 32, 512) * 67
        dtype = jnp.bfloat16
    elif name == "dim_4_keeps_xlas_transpose":
        hash_size, ids, dim = 2048, rng.integers(0, 2048, 512) // 8, 4
    else:
        hash_size, ids = 2048, rng.integers(0, 300, (4, 517))
        transform = name
    assert bool(embedding_grad.rows_a_line(dim)) == (dim == DIM)
    ids = jnp.asarray(ids, jnp.int32)
    table = jnp.asarray(rng.normal(size=(hash_size, dim)), dtype)
    return table, ids, _weights(rng, *ids.shape, dim), transform


def _grad(loss, table, ids, w, transform):
    grad = jax.grad(loss)
    if transform == "vmap":
        return jax.jit(jax.vmap(grad, (None, 0, 0)))(table, ids, w)
    if transform == "scan":  # the scan and accumulation epoch paths
        def step(acc, batch):
            return acc + grad(table, *batch), None
        return jax.jit(lambda: lax.scan(
            step, jnp.zeros_like(table), (ids, w))[0])()
    return jax.jit(grad)(table, ids, w)


@pytest.mark.parametrize("name", [
    "distinct", "all_equal", "zipf_runs_of_thousands", "first_and_last_row",
    "first_and_last_row_of_a_ragged_table", "lookups_not_a_multiple_of_128",
    "bfloat16_table", "dim_4_keeps_xlas_transpose", "vmap", "scan"])
def test_backward_equals_the_transpose_of_take(name):
    table, ids, w, transform = _case(name)
    ours = _grad(_ours, table, ids, w, transform)
    # bfloat16: the sums in float32, cast once (XLA's transpose adds in
    # bfloat16, row by row, and is further from the truth)
    plain = _grad(_plain, table.astype(jnp.float32), ids, w, transform)
    assert ours.dtype == table.dtype and ours.shape == plain.shape
    assert float(jnp.abs(plain).max()) > 1.0
    want = plain.astype(table.dtype).astype(jnp.float32)
    diff = float(jnp.abs(ours.astype(jnp.float32) - want).max())
    assert diff <= 1e-6 * float(jnp.abs(want).max())
    np.testing.assert_array_equal(
        np.asarray(_ours(table, ids, w)), np.asarray(_plain(table, ids, w)))


def test_backward_of_arbitrary_floats_is_within_float32_rounding():
    """Normal weights: against the sums in float64."""
    rng = np.random.default_rng(5)
    ids = _zipf_ids(rng, 20_000, 4096)
    w = rng.normal(size=(ids.size, DIM)).astype(np.float32)
    want = np.zeros((4096, DIM))
    np.add.at(want, ids, w.astype(np.float64))
    ours = jax.jit(jax.grad(_ours))(jnp.zeros((4096, DIM)),
                                    jnp.asarray(ids), jnp.asarray(w))
    assert np.abs(np.asarray(ours) - want).max() <= 1e-6 * np.abs(want).max()


@pytest.mark.parametrize("name,lines", [
    ("distinct", 4096 // 16), ("first_and_last_row_of_a_ragged_table", 128),
    ("dim_4_keeps_xlas_transpose", 0)])
def test_the_one_scatter_is_into_lines_and_told_sorted(name, lines):
    import re

    table, ids, w, _ = _case(name)
    text = jax.jit(jax.grad(_ours)).lower(table, ids, w).compile().as_text()
    scatters = re.findall(r"= (\S+) scatter\((.*)", text)
    assert len(scatters) == 1, text
    shape, attributes = scatters[0]
    assert shape.startswith(f"f32[{lines},128]" if lines else "f32[2048,4]")
    assert ("indices_are_sorted=true" in attributes) == bool(lines)


@pytest.mark.parametrize("dim", [8, 16, 32, 64])
def test_the_kernel_turns_lines_into_rows_as_the_expression_does(
        pallas_interpret, dim):
    """A program lowered for the TPU turns the lines with a Pallas kernel
    (here interpreted), any other with an expression."""
    rng = np.random.default_rng(8)
    pack = 128 // dim
    lines = jnp.asarray(rng.normal(size=(3 * 128, 128)), jnp.float32)
    want = embedding_grad._rows_from_lines(lines, dim)
    assert want.shape == (3 * 128 * pack, dim)
    # row (q * pack + j) * 128 + l rests in line q * 128 + l, lanes j * dim
    j = pack - 1
    np.testing.assert_array_equal(
        np.asarray(want[(2 * pack + j) * 128 + 5]),
        np.asarray(lines[2 * 128 + 5, j * dim:(j + 1) * dim]))
    got = embedding_grad._turned_by_the_kernel(lines, dim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _mesh_case(spec):
    """``"data:2,model:2 ragged"`` -> (the mesh over the first devices,
    ``{"data": "2", "model": "2"}``, ``"ragged"``); skips where the host
    has too few devices."""
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh

    spec, _, kind = spec.partition(" ")
    sizes = dict(part.split(":") for part in spec.split(","))
    count = int(np.prod([int(v) for v in sizes.values()]))
    if len(jax.devices()) < count:
        pytest.skip(f"needs {count} devices")
    return make_mesh(spec, devices=jax.devices()[:count]), sizes, kind


@pytest.mark.parametrize("spec,table_spec", [
    ("data:2,model:2", P("model", None)),
    ("data:4", P(None, None)),
    ("model:2", P("model", None)),
    ("data:2,model:2 ragged", P("model", None)),
    ("data:2,model:2 whole", P(None, None)),  # shard_table=False
])
def test_backward_on_a_mesh_equals_one_device(spec, table_spec):
    """Each device sorts its own lookups and keeps its own rows; the sum
    over ``data`` is the dense all-reduce."""
    import re

    mesh, sizes, kind = _mesh_case(spec)
    ragged, shard_table = kind == "ragged", kind != "whole"
    rng = np.random.default_rng(3)
    # 2,048 rows a model shard are one block of lines; 500 are part of one
    hash_size, n = 1000 if ragged else 4096, 1536
    ids = jnp.asarray(_zipf_ids(rng, n, hash_size))
    table = jnp.asarray(rng.normal(size=(hash_size, DIM)), jnp.float32)
    w = _weights(rng, n, DIM)
    want = jax.jit(jax.grad(_plain))(table, ids, w)

    batch = "data" if "data" in sizes else None
    place = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    grad = jax.jit(
        jax.grad(_loss(lambda t, i: take_rows(t, i, mesh, shard_table))),
        in_shardings=(place(table_spec), place(P(batch)),
                      place(P(batch, None))))
    got = grad(table, ids, w)
    assert got.sharding.is_equivalent_to(place(table_spec), got.ndim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    text = grad.lower(table, ids, w).compile().as_text()
    assert not re.findall(r"f32\[\d+,%d\][^ ]* all-gather" % DIM, text)


def test_lookups_that_do_not_divide_over_data_stay_whole():
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh("data:2,model:2", devices=jax.devices()[:4])
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(0, 64, 77), jnp.int32)
    table, w = jnp.zeros((64, DIM)), _weights(rng, 77, DIM)
    got = jax.jit(jax.grad(_loss(lambda t, i: take_rows(t, i, mesh))))(
        table, ids, w)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jax.grad(_plain)(table, ids, w)))


def test_ids_of_another_shard_are_dropped_at_either_end():
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.integers(0, 3 * 2048, 521), jnp.int32)
    rows = _weights(rng, ids.size, DIM)
    whole = embedding_grad.dense_row_grad(ids, rows, 3 * 2048)
    middle = embedding_grad.dense_row_grad(ids, rows, 2048, 2048)
    assert float(jnp.abs(middle).max()) > 0
    np.testing.assert_array_equal(np.asarray(middle),
                                  np.asarray(whole[2048:4096]))


# ---- the forward: the table read through lines of whole lanes
# (``embedding_grad.rows_by_lines``), against ``jnp.take`` bit for bit


def _bits(x):
    x = np.asarray(x)
    return x.view({2: np.int16, 4: np.int32}[x.dtype.itemsize])


def _rule(monkeypatch, side):
    """Put a lookup of any size on one side of ``lines_pay``'s rule: the
    lines from the first lookup and from rows of 8 floats, or never."""
    monkeypatch.setattr(embedding_grad, "ROWS_TURNED_FOR_A_LOOKUP",
                        1 << 30 if side == "lines" else 0)
    monkeypatch.setattr(embedding_grad, "LINES_FROM_DIM", DIM)


def _reads_lines(lookup, *args) -> bool:
    """Whether the lowered lookup gathers 128-lane lines (and then no
    table rows) or table rows (and then no lines)."""
    import re

    sizes = re.findall(r"slice_sizes = array<i64: 1, (\d+)>",
                       lookup.lower(*args).as_text())
    assert len(sizes) == 1, sizes
    return sizes == ["128"]


@pytest.mark.parametrize("side", ["lines", "take"])
@pytest.mark.parametrize("name", [
    "distinct", "all_equal", "zipf_runs_of_thousands", "first_and_last_row",
    "first_and_last_row_of_a_ragged_table", "lookups_not_a_multiple_of_128",
    "bfloat16_table", "dim_4_keeps_xlas_transpose", "vmap", "scan"])
def test_forward_equals_take_bit_for_bit(monkeypatch, name, side):
    """On both sides of the rule, a row of ``-0.0`` and one with a NaN
    among those looked up; ``vmap`` and ``scan`` are the ``(4, 517)`` ids
    of a caller that did not flatten them."""
    _rule(monkeypatch, side)
    table, ids, _, _ = _case(name)
    flat = np.asarray(ids).reshape(-1)
    table = table.at[flat[0], ::2].set(-0.0).at[flat[-1], 1].set(jnp.nan)
    lookup = jax.jit(lambda table, ids: take_rows(table, ids))
    got, want = lookup(table, ids), jnp.take(table, ids, axis=0)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.signbit(np.asarray(want, np.float32)[..., 0]).any()
    assert np.isnan(np.asarray(want, np.float32)).any()
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert _reads_lines(lookup, table, ids) == (
        side == "lines"
        and name not in ("bfloat16_table", "dim_4_keeps_xlas_transpose"))


@pytest.mark.parametrize("rows,dim,lookups,lines", [
    (4_194_304, 32, 425_984, True),   # the benchmark cell's step
    (4_194_304, 32, 65_536, True),    # a lookup for every 64 rows
    (4_194_304, 32, 65_535, False),
    (4_194_304, 32, 13_312, False),   # 512 rows scored
    (4_194_304, 32, 26, False),       # one row scored
    (4_194_304, 64, 425_984, True),
    (4_194_304, 16, 425_984, True),
    (1_048_576, 8, 425_984, False),   # the flagship: a row lies in one tile
    (4_194_304, 24, 425_984, False),  # tiles no line
])
def test_the_rule_engages_the_lines_where_lookups_are_many(rows, dim,
                                                            lookups, lines):
    assert embedding_grad.lines_pay(rows, dim, jnp.float32, lookups) == lines
    assert not embedding_grad.lines_pay(rows, dim, jnp.bfloat16, lookups)


@pytest.mark.parametrize("rows", ["whole_blocks", "ragged"])
@pytest.mark.parametrize("dim", [8, 16, 32, 64])
def test_the_kernel_turns_rows_into_lines_as_the_expression_does(
        pallas_interpret, dim, rows):
    """... and ``_rows_from_lines`` turns them back.  A ragged table's
    last block holds what the kernel read beyond the rows there: nothing
    looks that up, so only the rows are compared."""
    rng = np.random.default_rng(9)
    pack = 128 // dim
    rows = 3 * 128 * pack - (0 if rows == "whole_blocks" else 128 * pack - 5)
    table = jnp.asarray(rng.normal(size=(rows, dim)), jnp.float32)
    want = embedding_grad._lines_from_rows(table, dim)
    assert want.shape == (-(-rows // (128 * pack)) * 128, 128)
    # row (q * pack + j) * 128 + l rests in line q * 128 + l, lanes j * dim
    j = pack - 1
    np.testing.assert_array_equal(
        np.asarray(want[128 + 5, j * dim:(j + 1) * dim]),
        np.asarray(table[(pack + j) * 128 + 5]))
    got = embedding_grad._lined_by_the_kernel(table, dim)
    assert got.shape == want.shape
    for lines in (want, got):
        np.testing.assert_array_equal(
            np.asarray(embedding_grad._rows_from_lines(lines, dim)[:rows]),
            np.asarray(table))


@pytest.mark.parametrize("lookups", [1, 845, 2048, 5000])
@pytest.mark.parametrize("dim", [8, 16, 32, 64])
def test_the_kernel_picks_rows_off_lines_as_the_expression_does(
        pallas_interpret, dim, lookups):
    """Blocks of 2,048 lookups, the last one part of a block; place -1
    (another shard's row) reads zeros."""
    rng = np.random.default_rng(10)
    taken = jnp.asarray(rng.normal(size=(lookups, 128)), jnp.float32)
    place = jnp.asarray(rng.integers(-1, 128 // dim, lookups), jnp.int32)
    want = embedding_grad._picked_by_xla(taken, place, dim)
    assert want.shape == (lookups, dim)
    at = int(place[0])
    np.testing.assert_array_equal(
        np.asarray(want[0]), np.zeros(dim) if at < 0
        else np.asarray(taken[0, at * dim:(at + 1) * dim]))
    got = embedding_grad._picked_by_the_kernel(taken, place, dim)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_the_forward_through_both_kernels_equals_take(pallas_interpret):
    rng = np.random.default_rng(11)
    table = jnp.asarray(rng.normal(size=(5000, 32)), jnp.float32)
    ids = jnp.asarray(_zipf_ids(rng, 3000, 5000))
    lines = embedding_grad._lined_by_the_kernel(table, 32)
    got = embedding_grad._picked_by_the_kernel(
        lines[embedding_grad._line_of(ids, 4)],
        embedding_grad._place_of(ids, 4), 32)
    np.testing.assert_array_equal(_bits(got), _bits(table[ids]))


@pytest.mark.parametrize("rows", [2048, 1000])
def test_rows_of_another_shard_read_zeros_at_either_end(rows):
    rng = np.random.default_rng(14)
    ids = jnp.asarray(rng.integers(0, 3 * rows, 521), jnp.int32)
    table = jnp.asarray(rng.normal(size=(3 * rows, DIM)), jnp.float32)
    middle = embedding_grad.rows_by_lines(table[rows:2 * rows], ids, rows)
    mine = (ids >= rows) & (ids < 2 * rows)
    assert 100 < int(mine.sum()) < 421
    np.testing.assert_array_equal(
        _bits(middle), _bits(jnp.where(mine[:, None], table[ids], 0)))


@pytest.mark.parametrize("spec,table_spec", [
    ("data:2,model:2", P("model", None)),
    ("data:4", P(None, None)),
    ("model:2", P("model", None)),
    ("data:2,model:2 ragged", P("model", None)),
    ("data:2,model:2 whole", P(None, None)),  # shard_table=False
    ("data:2,model:2 undivided", P("model", None)),  # 77 lookups
])
def test_forward_on_a_mesh_equals_one_device(monkeypatch, spec, table_spec):
    """Each device turns its own shard and reads the lookups of its data
    shard that fall in its rows; the parts' sum over ``model`` is the
    partitioner's all-reduce, and no table row is all-gathered."""
    import re

    _rule(monkeypatch, "lines")
    mesh, sizes, kind = _mesh_case(spec)
    rng = np.random.default_rng(3)
    hash_size = 1000 if kind == "ragged" else 4096
    n = 77 if kind == "undivided" else 1536
    ids = jnp.asarray(_zipf_ids(rng, n, hash_size))
    table = jnp.asarray(rng.normal(size=(hash_size, DIM)), jnp.float32)
    table = table.at[ids[0], 1].set(jnp.nan)
    batch = "data" if "data" in sizes and kind != "undivided" else None
    place = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    lookup = jax.jit(
        lambda table, ids: take_rows(table, ids, mesh, kind != "whole"),
        in_shardings=(place(table_spec), place(P(batch))))
    got = lookup(table, ids)
    assert got.sharding.is_equivalent_to(place(P(batch, None)), got.ndim)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table[ids]))
    assert _reads_lines(lookup, table, ids)
    text = lookup.lower(table, ids).compile().as_text()
    assert not re.findall(r"f32\[\d+,%d\][^ ]* all-gather" % DIM, text)
    assert not re.findall(r"psum", text)
    assert bool(re.findall(r"f32\[\d+,%d\][^ ]* all-reduce" % DIM, text)) \
        == ("model" in sizes and kind != "whole")


def test_forward_inside_a_shard_map_runs_as_on_one_device(monkeypatch):
    """SAGN traces its step per device already (manual axes): the lookup
    opens no ``shard_map`` of its own there, and the shard's lookups read
    the whole table through lines."""
    from shifu_tensorflow_tpu.parallel.mesh import make_mesh
    from shifu_tensorflow_tpu.parallel.shmap import shard_map

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    _rule(monkeypatch, "lines")
    mesh = make_mesh("data:4", devices=jax.devices()[:4])
    rng = np.random.default_rng(12)
    table = jnp.asarray(rng.normal(size=(4096, DIM)), jnp.float32)
    ids = jnp.asarray(_zipf_ids(rng, 1536, 4096))
    lookup = jax.jit(shard_map(
        lambda table, ids: take_rows(table, ids, mesh), mesh,
        in_specs=(P(), P("data")), out_specs=P("data", None),
        comm_label=None))
    np.testing.assert_array_equal(np.asarray(lookup(table, ids)),
                                  np.asarray(table[ids]))
    assert _reads_lines(lookup, table, ids)
    assert lookup.lower(table, ids).as_text().count("shard_map") \
        == jax.jit(shard_map(
            lambda table, ids: jnp.take(table, ids, axis=0), mesh,
            in_specs=(P(), P("data")), out_specs=P("data", None),
            comm_label=None)).lower(table, ids).as_text().count("shard_map")


@pytest.mark.parametrize("through", ["take_rows", "HashedEmbedding"])
def test_an_exported_lookup_of_a_symbolic_batch_keeps_the_plain_gather(
        monkeypatch, through):
    """jax2tf exports the scoring forward with a polymorphic batch
    (``export/saved_model.py``): a count of lookups that is no integer
    cannot be held against the table's rows, whatever the rule's
    constants, and the program holds ``jnp.take``'s gather."""
    from jax import export

    from shifu_tensorflow_tpu.models.embeddings import HashedEmbedding

    _rule(monkeypatch, "lines")
    b, = export.symbolic_shape("b")
    table = jnp.asarray(np.random.default_rng(13).normal(size=(4096, DIM)),
                        jnp.float32)
    if through == "take_rows":
        fn = lambda ids: take_rows(table, ids.reshape(-1))  # noqa: E731
        arg = jax.ShapeDtypeStruct((b, 3), jnp.int32)
        x = jnp.asarray([[5, 9, 4095], [0, 5, 77]], jnp.int32)
        want = table[x.reshape(-1)]
    else:
        module = HashedEmbedding(hash_size=4096, features=DIM)
        params = {"params": {"table": table}}
        fn = lambda x: module.apply(params, x)  # noqa: E731
        arg = jax.ShapeDtypeStruct((b, 3), jnp.float32)
        x = jnp.asarray([[5e-6, 9e-6, 1.0], [0.0, 5e-6, 3.0]], jnp.float32)
        want = module.apply(params, x)
        assert _reads_lines(jax.jit(fn), x)  # a concrete batch reads lines
    exported = export.export(jax.jit(fn))(arg)
    text = exported.mlir_module()
    assert "slice_sizes = array<i64: 1, %d>" % DIM in text
    assert "slice_sizes = array<i64: 1, 128>" not in text
    np.testing.assert_array_equal(np.asarray(exported.call(x)),
                                  np.asarray(want))
