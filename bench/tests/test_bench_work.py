"""Least operations and bytes from shapes and precision, against hand
counts; each configuration's work from its own sizes."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import cim  # noqa: E402
import harness  # noqa: E402


def _config(name):
    spec = harness.load_spec()
    entry = {c["name"]: c for c in spec["configs"]}[name]
    cfg, path = harness.config_files(entry)
    return cfg, harness.load_module(path)


def test_lenet_conv2_by_hand():
    # LeNet-5's C3: 5x5 over 6 maps to 16, a 10x10 output an image
    m, k, n = 10000 * 100, 150, 16
    ops, nbytes = cim.gemm_work(m, k, n, (8, 4, 8))
    assert ops == 2 * 1_000_000 * 150 * 16 == 4_800_000_000
    # weights 150*16 at 4 bits, inputs 1e6*150 at 8, codes 1e6*16 at 8
    assert nbytes == 1200 + 150_000_000 + 16_000_000 == 166_001_200
    _, nbytes1 = cim.gemm_work(m, k, n, (1, 1, 8))
    assert nbytes1 == 300 + 18_750_000 + 16_000_000


def test_olmo_gate_projection_by_hand():
    ops, nbytes = cim.gemm_work(64, 2048, 8192, (8, 4, 8))
    assert ops == 2 * 64 * 2048 * 8192
    assert nbytes == 2048 * 8192 // 2 + 64 * 2048 + 64 * 8192


def test_least_seconds_names_the_bound():
    peaks = harness.peaks_for("TPU v5 lite")
    t, bound = cim.least_seconds(393e12, 1.0, peaks)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = cim.least_seconds(1.0, 819e9, peaks)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_lenet_layers_give_the_stated_macs():
    cfg, model = _config("lenet5-mnist")
    assert model.gemms(cfg, 10000) == [
        (10000 * 784, 25, 6), (10000 * 100, 150, 16), (10000, 400, 120),
        (10000, 120, 84), (10000, 84, 10)]
    # 117600 + 240000 + 48000 + 10080 + 840
    assert sum(m * k * n for m, k, n in model.gemms(cfg, 1)) \
        == cfg["macs_per_image"] == 416_520


def test_olmo_operations_per_token():
    cfg, model = _config("olmo-1b")
    assert len(model.gemms(cfg, 1)) == 7 * 16
    # projections plus the tied head: 2.354 GOP a token, no context
    assert model.model_ops(cfg, 1, 0, 1) == 2 * (16 * (4 * 2048 * 2048
                                                      + 3 * 2048 * 8192)
                                                + 2048 * 50304)
    # attention adds 2 (QK and PV) * d_model MACs a layer per position
    assert model.model_ops(cfg, 0, 10, 0) == 2 * 2 * 2048 * 16 * 10


def test_row_tiles_split_k_evenly():
    assert cim.row_tiles(1568) == [(0, 784), (784, 784)]
    assert cim.row_tiles(8192) == [(i * 1024, 1024) for i in range(8)]
    assert cim.row_tiles(144) == [(0, 144)]
    # one olmo-1b layer: 64 output channels per tile at r_w=4 gives the
    # 1024 kernel launches a layer that the compiled program holds
    cfg, model = _config("olmo-1b")
    assert sum(len(cim.row_tiles(k)) * -(-n // 64)
               for _, k, n in model.gemms(cfg, 1)[:7]) == 1024
