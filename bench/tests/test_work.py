"""Work counts and roofline shares, checked by hand at a small shape."""
import json
from pathlib import Path

import pytest

from bench import harness, work

PEAKS = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_interact_work_by_hand():
    # n=4 rows, k=2 neighbours, f=3 charges: 2*4*2*3 flops; 4*2 values
    # + 4*3 charges in + 4*3 answers out, 4 bytes each
    assert work.interact_work(4, 2, 3) == (48, 4 * (8 + 12 + 12))


def test_roofline_takes_the_binding_bound():
    # 128 bytes at 10 B/s = 12.8 s; 48 flops at 100 /s = 0.48 s
    assert work.roofline_seconds(48, 128, PEAKS) == pytest.approx(12.8)
    assert work.roofline_share(48, 128, 25.6, PEAKS) == pytest.approx(50.0)
    assert work.roofline_seconds(4800, 1, PEAKS) == pytest.approx(48.0)


def test_ell_entries():
    assert work.ell_entries(n_rb=4, max_nbr=3, bs=2) == 48


def test_peaks_refuse_an_unknown_device():
    root = Path(__file__).resolve().parents[2]
    peaks = harness.load_peaks(root, "TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.load_peaks(root, "TPU v99")
    assert json.loads((root / "bench" / "peaks.json").read_text())["source"]
