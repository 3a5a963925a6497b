"""The transform's work counts, the roofline share and the peak table."""
import json
import math

import pytest

from benchmarks.chip import work

V5E = "TPU v5 lite"


def test_counts_at_2e26_one_chip():
    w = work.transform_work(1 << 16, 1 << 10, 8)
    assert w["flops"] == 5 * (1 << 26) * 10
    assert w["bytes"] == 2 * (1 << 26) * 8
    peaks = work.load_peaks(V5E)
    t, bound = work.least_time(w, peaks)
    assert bound == "hbm"
    assert t == pytest.approx(1.3110e-3, rel=1e-3)
    assert w["flops"] / peaks["flops_per_s"] == pytest.approx(17.03e-6,
                                                               rel=1e-3)


def test_counts_pencil_over_four_chips():
    w = work.transform_work(16, 1 << 26, 8)
    assert w["flops"] == 5 * 16 * (1 << 26) * 26
    assert w["bytes"] == 2 * 16 * (1 << 26) * 8
    t, bound = work.least_time(w, work.load_peaks(V5E), chips=4)
    assert bound == "hbm"
    assert t == pytest.approx(2 * 4 * (1 << 26) * 8 / 819e9)


@pytest.mark.parametrize("b,n,chips", [(1 << 16, 1 << 10, 1),
                                       (8, 1 << 23, 1), (16, 1 << 26, 4)])
@pytest.mark.parametrize("factor", [1.0, 1.0001, 2.0, 1e3])
def test_roofline_never_over_100(b, n, chips, factor):
    w = work.transform_work(b, n, 8)
    peaks = work.load_peaks(V5E)
    t, _ = work.least_time(w, peaks, chips)
    pct = work.roofline_pct(w, peaks, t * factor, chips)
    assert 0 < pct <= 100.0 + 1e-9
    assert pct == pytest.approx(100.0 / factor)


def test_unknown_device_raises():
    with pytest.raises(KeyError):
        work.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.load_peaks("cpu")


def test_peak_table_names_its_source():
    table = json.loads(work.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    p = table["devices"][V5E]
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_work_needs_a_transform():
    with pytest.raises(ValueError):
        work.transform_work(0, 1024, 8)
    assert math.isclose(work.transform_work(1, 2, 8)["flops"], 10.0)
