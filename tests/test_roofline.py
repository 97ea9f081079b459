"""Roofline peaks: keyed by ``device_kind``, no default for other chips."""
import pytest

from repro.roofline import hw
from repro.roofline.analysis import RooflineTerms


def test_v5e_peaks_are_the_published_ones():
    v5e = hw.peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.ops_int8, v5e.hbm_bw) == (197e12, 393e12,
                                                           819e9)
    assert v5e.hbm_bytes == 16e9
    assert v5e.ici_bw * 4 * 8 == 1600e9      # 4 links, 1,600 Gbit/s


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "cpu", ""])
def test_unlisted_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        hw.peaks(kind)


def test_roofline_times_use_the_given_chip():
    chip = hw.peaks(hw.V5E)
    t = RooflineTerms(chip=chip, flops=197e12, bytes_hbm=819e9,
                      bytes_coll=50e9, coll_breakdown={}, int_ops=393e12)
    assert (t.t_compute, t.t_memory, t.t_collective, t.t_int) == (
        1.0, 1.0, 1.0, 1.0)
