"""Work counts against counts made by hand, and the table of peaks."""

import json

import pytest

from harness import device
from work import preempt_kernel


def test_ops_per_pair_by_hand():
    # R = 2: mask 2+4+1+1+3 = 11; binpack 2+4+2+4+2+1+1+2 = 18;
    # least 4+4+6+4+4+6+1+2 = 31; balanced 4+2+2+4 = 12; total 2+2+1+3 = 8
    assert preempt_kernel.ops_per_pair(2) == 11 + 18 + 31 + 12 + 8 == 80


def test_preempt_count_small_shape():
    assert preempt_kernel.ops_per_victim(2) == 5 + 4 + 2 + 4 + 1 == 16
    got = preempt_kernel.count(preemptors=2, nodes=3, victims=5, resources=2)
    # ops 2 x (3 x 80 + 5 x 16); bytes read 2x4x4 + 3x8x4 + 5x5x4, written 5 + 2x4
    assert got == {"ops": 2 * (240 + 80), "bytes": 32 + 96 + 100 + 5 + 8}


def test_peaks_known_kind():
    p = device.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_peaks_missing_kind_raises(tmp_path):
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        device.peaks("TPU v5 lite", str(table))
