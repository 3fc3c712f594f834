"""The shapes functions (FLOPs, bytes) against counts made by hand."""

import json
import os

import pytest

from benchmark import peaks, shapes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


#: the reference's own 30-feature DNN (PERF.md section 7, `dnn30_stream`)
DNN30 = {"model_config": {"train": {"params": {
    "NumHiddenLayers": 3, "NumHiddenNodes": [256, 128, 64],
    "ActivationFunc": ["relu", "relu", "tanh"], "LearningRate": 0.01,
    "Optimizer": "adam"}}}}


def config(name):
    if name == "dnn30":
        return DNN30
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


B = 16_384
# dnn30: 30 -> 256 -> 128 -> 64 -> 1, biases on every layer
DNN_KERNELS = 30 * 256 + 256 * 128 + 128 * 64 + 64 * 1            # 48,704
DNN_PARAMS = DNN_KERNELS + 256 + 128 + 64 + 1                     # 49,153
# wdl_criteo: input 39 + 26*32 = 871 -> 1024 -> 512 -> 256 -> 1, plus the
# wide linear part over its 3 columns (no bias), a 2^22 x 32 table and a
# 2^20 x 1 cross table
WDL_FIRST = 39 + 26 * 32
WDL_KERNELS = (WDL_FIRST * 1024 + 1024 * 512 + 512 * 256 + 256 * 1
               + 3 * 1)                                           # 1,547,523
WDL_PARAMS = (WDL_KERNELS + 1024 + 512 + 256 + 1
              + 4_194_304 * 32 + 1_048_576)


@pytest.mark.parametrize("name,nf,kernels,params,first,hidden,gathered", [
    ("dnn30", 30, DNN_KERNELS, DNN_PARAMS, 30, 256 + 128 + 64, 0),
    ("wdl_criteo", 39, WDL_KERNELS, WDL_PARAMS, WDL_FIRST,
     1024 + 512 + 256, 26 * 32 + 1),
])
def test_hand_counts(name, nf, kernels, params, first, hidden, gathered):
    mc = config(name)["model_config"]
    w = shapes.widths(mc, nf)
    assert WDL_FIRST == 871
    assert sum(i * o for i, o in w["layers"]) == kernels
    assert shapes.parameter_count(w) == params
    assert shapes.train_step_flops(mc, nf, B) == 6.0 * B * kernels
    by_hand = (32.0 * params            # dense Adam: g w; p m v g r; p m v w
               + 4.0 * B * (nf + 2)     # the batch, once
               + 12.0 * B * gathered    # rows gathered, grads scattered
               + 8.0 * B * (first + hidden + 1))  # activations w + r
    assert shapes.train_step_bytes(mc, nf, B) == by_hand


def test_wdl_is_bytes_bound_and_table_dominated():
    mc = config("wdl_criteo")["model_config"]
    flops = shapes.train_step_flops(mc, 39, B)
    nbytes = shapes.train_step_bytes(mc, 39, B)
    assert flops == pytest.approx(152.1e9, rel=1e-3)
    assert nbytes == pytest.approx(4.93e9, rel=1e-2)
    least = shapes.roofline(flops, nbytes, peaks.lookup("TPU v5 lite"))
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(nbytes / 819e9)
    assert least["flops_s"] == pytest.approx(flops / 197e12)


def test_x4_per_chip_share_equals_one_chip_cell():
    from benchmark.metrics import train_step_roofline as m

    one = m.per_chip(config("wdl_criteo"), {"batch": 16_384}, 1)
    four = m.per_chip(config("wdl_criteo_x4"), {"batch": 32_768}, 4)
    assert four[1] == one[1] == 16_384
    p1, p4 = (c[0]["train"]["params"] for c in (one, four))
    assert p4["EmbeddingHashSize"] == p1["EmbeddingHashSize"] == 4_194_304
    # the cross table is split over model too
    assert p4["CrossHashSize"] == p1["CrossHashSize"] // 2


def test_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="no peaks for device kind 'cpu'"):
        peaks.lookup("cpu")
