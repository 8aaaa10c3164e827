import pytest

from harness import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.for_kind("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("cpu")
