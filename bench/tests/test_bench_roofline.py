"""Compulsory bytes and FLOPs of the kernels against hand counts, and the
peak table."""
import pytest

from bench import roofline

NELL2 = (12_092, 9_184, 28_818)
UBER = (183, 24, 1_140, 1_717)


def test_mttkrp_nell2_mode0_by_hand():
    # stream: 76,879,419 x (2 words + 1 value) x 4 B = 922,553,028
    # factors read: (9,184 + 28,818) x 16 x 4 = 2,432,128
    # output written: 12,092 x 16 x 4 = 773,888
    assert roofline.stream_bytes(76_879_419, 2) == 922_553_028
    assert roofline.mttkrp_bytes(NELL2, 76_879_419, 2, 16, 0) == 925_759_044


def test_phi_uber_mode3_by_hand():
    # stream 3,309,490 x 12 = 39,713,880; other factors (183 + 24 + 1,140)
    # x 64 = 86,208; B read and Φ written 2 x 1,717 x 64 = 219,776
    assert roofline.phi_bytes(UBER, 3_309_490, 2, 16, 3) == 40_019_864
    assert roofline.phi_flops(UBER, 3_309_490, 16) == 317_711_040


def test_bound_is_bytes_on_v5e():
    nbytes = roofline.phi_bytes(UBER, 3_309_490, 2, 16, 3)
    flops = roofline.phi_flops(UBER, 3_309_490, 16)
    t = roofline.bound_s(flops, nbytes, "TPU v5 lite")
    assert t == pytest.approx(40_019_864 / 819e9)
    assert flops / nbytes < 10         # far below the ridge (~240)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.bound_s(1.0, 1.0, "cpu")
