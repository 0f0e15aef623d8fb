"""The peaks table and the bytes-moved function of the extract program."""

import json

import pytest

from benchmark import kernels


def test_peaks_table_has_its_source_and_the_v5e():
    with open(kernels.PEAKS_FILE) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    p = kernels.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    assert p["bf16_flop_per_s"] == 197e12 and p["ici_bits_per_s"] == 1600e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        kernels.peaks("cpu")


def test_extract_bytes_is_one_pass_over_the_corpus():
    assert kernels.extract_bytes(4 * (128 << 20)) == 536870912
    # 512 MiB at 819 GB/s is 0.6555 ms; a program that took 65.55 ms is at 1 %
    ideal = 536870912 / 819e9
    assert kernels.hbm_share(536870912, 100 * ideal, "TPU v5 lite") == \
        pytest.approx(1.0)
    with pytest.raises(ValueError):
        kernels.hbm_share(1.0, 0.0, "TPU v5 lite")
