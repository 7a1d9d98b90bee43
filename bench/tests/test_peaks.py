"""The peaks table: keyed by device_kind, an unknown device is an error."""
import pytest

from bench.lib import peaks


def test_v5e_peaks_from_the_published_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "tpu v5 lite", ""])
def test_unknown_device_raises(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks(kind)
