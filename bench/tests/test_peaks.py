"""bench/peaks.json: the published peaks, keyed by device kind."""

import pytest

from bench import harness


def test_v5e_peaks():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        harness.load_peaks(kind)
