"""``bench/work.py`` against counts made by hand."""
import json
import os

import pytest

from bench import work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def test_sift_counts_by_hand():
    w = work.search_work(_config("sift1m-mcam3-l2-d2d"), 256)
    K = 7813 * 128                       # 1,000,000 rows in 128-row banks
    assert w["K"] == K == 1_000_064
    assert w["ops"] == 2 * 256 * K * 128
    # noisy cells are f32: 4 bytes each; f32 queries; (value, index) out
    assert w["bytes"] == K * 128 * 4 + 256 * 128 * 4 + 256 * 10 * 8
    assert w["peak_ops"] == "bf16_flops_per_s"
    t, bound = work.least_time(w, work.load_peaks("TPU v5 lite"))
    assert bound == "memory"
    assert t == pytest.approx((K * 512 + 131072 + 20480) / 819e9)


def test_glove_counts_by_hand():
    w = work.search_work(_config("glove100-mcam3-dot"), 256)
    K = 9247 * 128                       # 1,183,514 rows in 128-row banks
    assert w["K"] == K == 1_183_616
    assert w["N"] == 100                 # the query dims, not the 128 cols
    assert w["ops"] == 2 * 256 * K * 100
    # noise-free 3-bit codes: 3/8 byte each
    assert w["bytes"] == K * 100 * 3 / 8 + 256 * 100 * 4 + 256 * 10 * 8
    assert w["peak_ops"] == "int8_ops_per_s"
    t, bound = work.least_time(w, work.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    assert t == pytest.approx(2 * 256 * K * 100 / 393e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("TPU v9 imaginary")
