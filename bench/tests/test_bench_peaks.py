"""The peak table: known device kinds only."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import harness  # noqa: E402


def test_v5e_peaks():
    p = harness.peaks_for("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")
