"""Compulsory work of one matvec and the peak table."""

import pytest

from harness import roofline


def test_peak_table_has_the_v5e_and_refuses_unknown_kinds():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_cube_96_matvec_is_bound_by_its_compulsory_bytes():
    n_dof, n_elem = 3 * 97 ** 3, 96 ** 3
    w = roofline.matvec_work(n_dof, [(24, n_elem)])
    assert w["bytes"] == 2 * n_dof * 4 + n_elem * 4       # 25.4 MB
    assert w["flops"] == 2 * 24 * 24 * n_elem
    t = roofline.least_time_s(w, roofline.peaks("TPU v5 lite"))
    assert t == pytest.approx(w["bytes"] / 819e9)
    assert 30e-6 < t < 32e-6


def test_flops_sum_over_element_types():
    w = roofline.matvec_work(1000, [(24, 10), (30, 5)])
    assert w["flops"] == 2 * 24 * 24 * 10 + 2 * 30 * 30 * 5
    assert w["bytes"] == 2 * 1000 * 4 + 15 * 4
    fast = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    assert roofline.least_time_s(w, fast) == w["flops"]
