"""Peak table and stage work at sift1m shapes."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from squashbench import work  # noqa: E402


def test_v5e_peaks_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_keep_survivors_matches_h_perc():
    assert work.keep_survivors(105000, 10.0, 64) == 10500
    assert work.keep_survivors(300, 10.0, 64) == 64
    assert work.keep_survivors(40, 10.0, 64) == 40


def test_stage_work_at_sift1m_shapes():
    # Q = 16, P = 10, n_max = 105,000, d = 128, keep_s = 10,500.
    ham = work.hamming_work(16, 10, 105000, 128)
    assert ham.bytes == 10 * 105000 * 16 + 4 * 16 * 10 * 105000
    assert ham.bytes == 84_000_000
    assert ham.ops == 2 * 16 * 10 * 105000 * 4
    adc = work.adc_work(16, 10, 10500, 128, 129)
    assert adc.ops == 16 * 10 * 10500 * 128 == 215_040_000
    assert adc.bytes == (4 * 215_040_000 + 4 * 16 * 10 * 129 * 128
                         + 4 * 16 * 10 * 10500)
    peak = work.peaks("TPU v5 lite")
    t, bound = work.roofline_seconds(ham, peak)
    assert bound == "bytes" and t == pytest.approx(84e6 / 819e9)
    t, bound = work.roofline_seconds(adc, peak)
    assert bound == "bytes" and t == pytest.approx(adc.bytes / 819e9)
    t, bound = work.roofline_seconds(work.Work(ops=1e12, bytes=1.0), peak)
    assert bound == "ops" and t == pytest.approx(1e12 / 197e12)
