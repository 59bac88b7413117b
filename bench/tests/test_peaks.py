"""The peaks table: the v5e's numbers, and an error for a device it
does not hold."""
import pytest

from bench import harness


def test_peaks_table_and_unknown_device():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
