"""A traced run of the batch cell, in process on the CPU at a tiny size,
reads the scan's program counters: the share of tiles the sweep visited
and the share of evaluated pairs that were useful."""

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def test_traced_cell_reads_the_scan_counters(root):
    cell = "copydays-sift.batch"
    result, lines = tiny.measure(root, cell, trace=True)
    assert result["correct"], lines
    metrics = result["metrics"]
    for name in ("scan_wave_share_pct.batch", "scan_pair_yield_pct.batch"):
        assert metrics[name]["unit"] == "%"
        assert 0 < metrics[name]["value"] <= 100
