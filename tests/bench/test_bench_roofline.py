"""The roofline yardstick: the algorithm's bytes, and the peak table."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import roofline  # noqa: E402
from repro.kernels.dict_decode.dict_decode import ONEHOT_MAX  # noqa: E402


@pytest.mark.parametrize("d", [2, ONEHOT_MAX - 1, ONEHOT_MAX,
                               ONEHOT_MAX + 1, 1_500_000])
def test_dict_decode_counts_a_gather_on_either_route(d):
    """Below ONEHOT_MAX the one-hot grid runs, above it XLA's gather: the
    count is the gather's bytes on both, with no padding and no FLOPs."""
    n = 1_677_721
    assert roofline.dict_decode_bytes(n, d) == 4 * n + 4 * n + 4 * d


def test_dict_decode_count_is_the_same_across_the_route_boundary():
    n = 100_000
    below = roofline.dict_decode_bytes(n, ONEHOT_MAX)
    above = roofline.dict_decode_bytes(n, ONEHOT_MAX + 1)
    assert above - below == 4


def test_pack_counts_values_mask_and_kept_values():
    assert roofline.pack_bytes(1000, 100) == 4000 + 1000 + 400
    assert roofline.pack_bytes(1000, 0) == 5000


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes == 819e9


def test_roofline_share_is_least_time_over_measured_time():
    chip = roofline.peaks("TPU v5 lite")
    # 819 MB at 819 GB/s is 1 ms; measured 4 ms -> 25%
    assert roofline.roofline_pct(seconds=4e-3, hbm_bytes=819e6,
                                 chip=chip) == pytest.approx(25.0)
    # the larger of the two bounds wins
    assert roofline.roofline_pct(seconds=1.0, hbm_bytes=819e9,
                                 flops=2 * 197e12, chip=chip) \
        == pytest.approx(200.0)
    assert roofline.roofline_pct(seconds=0.0, hbm_bytes=1, chip=chip) \
        is None
