"""The peak table: known chips only."""
import pytest

import bench_testkit  # noqa: F401  (puts bench/ on the path)
import peaks


def test_v5e_peaks_are_the_published_ones():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "NVIDIA A100-SXM4-80GB"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)
