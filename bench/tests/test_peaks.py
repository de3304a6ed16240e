"""The table of published peaks, keyed by `device_kind`."""
from __future__ import annotations

import pytest

from bench.harness.peaks import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks("TPU v5 lite")
    assert p.bf16_flops == 197e12
    assert p.int8_ops == 393e12
    assert p.hbm_bytes_per_s == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_an_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        peaks(kind)
