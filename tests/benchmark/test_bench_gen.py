"""The seeded generator: same seed same data, skew, one-slot reuse, and
the first batches read back from the shard files."""

import gzip
import json
import os

import numpy as np
import pytest

from benchmark import gen

DATA = {"numeric": 3, "categorical": 4, "zipf_s": 1.05,
        "cardinality_min": 10, "cardinality_max": 10_000_000,
        "code_scale": 1e-6}


def test_same_seed_same_rows_and_other_seed_other_rows():
    a = gen.synth_rows(np.random.default_rng([1, 0]), 1000, DATA)
    b = gen.synth_rows(np.random.default_rng([1, 0]), 1000, DATA)
    c = gen.synth_rows(np.random.default_rng([2, 0]), 1000, DATA)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_codes_are_skewed_bounded_and_exact_in_float32():
    card = gen.cardinalities(DATA)
    assert card[0] == 10 and card[-1] == 10_000_000
    codes = gen.zipf_codes(np.random.default_rng(0), 200_000, card, 1.05)
    assert codes.min() == 0 and (codes.max(axis=0) < card).all()
    # heavy head: the first 1% of ranks takes most draws; long tail: the
    # widest column still reaches far
    wide = codes[:, -1]
    assert (wide < 100_000).mean() > 0.5
    assert len(np.unique(wide)) > 50_000
    carried = (wide * 1e-6).astype(np.float32)
    np.testing.assert_array_equal(np.round(carried.astype(np.float64) * 1e6)
                                  .astype(np.int64), wide)


def test_labels_follow_the_features():
    x, y = gen.synth_rows(np.random.default_rng(4), 50_000, DATA)
    w = np.linspace(-1.0, 1.0, 3)
    assert np.corrcoef(x[:, :3] @ w, y)[0, 1] > 0.2
    assert 0.3 < y.mean() < 0.7


@pytest.mark.parametrize("workers", [1, 3])
def test_shards_one_slot_reuse_and_first_batches(tmp_path, workers):
    work = str(tmp_path / "data")
    paths, reused = gen.ensure_shards(work, 5, 2048, 2, DATA, workers)
    assert not reused and len(paths) == 2
    with gzip.open(paths[0], "rt") as f:
        first = f.readline().rstrip("\n").split("|")
    assert len(first) == 1 + 7 + 1 and first[-1] == "1.0"
    again, reused = gen.ensure_shards(work, 5, 2048, 2, DATA, workers)
    assert reused and again == paths
    before = os.path.getmtime(paths[0])
    other, reused = gen.ensure_shards(work, 6, 2048, 2, DATA, workers)
    assert not reused and os.path.getmtime(other[0]) >= before
    with open(os.path.join(work, "stamp.json")) as f:
        assert json.load(f)["seed"] == 6
    bs = gen.first_batches(other, 512, 3, 7)
    assert [b["x"].shape for b in bs] == [(512, 7)] * 3
    x, y = gen.synth_rows(np.random.default_rng([6, 0]), 1024, DATA)
    np.testing.assert_allclose(bs[0]["x"], x[:512], atol=6e-6)
    np.testing.assert_array_equal(bs[1]["y"][:, 0], y[512:1024])
    # 3 x 512 rows cross into the second shard
    x1, _ = gen.synth_rows(np.random.default_rng([6, 1]), 1024, DATA)
    np.testing.assert_allclose(bs[2]["x"], x1[:512], atol=6e-6)
    with pytest.raises(ValueError):
        gen.first_batches(other, 512, 5, 7)
