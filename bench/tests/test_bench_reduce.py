"""The trace reducer on hand-made planes and on a small trace recorded on
a TPU v5e (``record_trace.py``)."""

import os

import pytest

import profile_reduce as pr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "reduce_trace.xplane.pb")


def _planes():
    ms = 1_000_000
    host = [("bench.window", 0, 100 * ms), ("bench.call", 0, 40 * ms),
            ("bench.pace", 40 * ms, 30 * ms), ("bench.call", 70 * ms, 30 * ms),
            ("not.ours", 0, 100 * ms)]
    ops = [("fusion", 5 * ms, 20 * ms), ("fusion", 20 * ms, 10 * ms),
           ("copy", 75 * ms, 10 * ms), ("late", 95 * ms, 20 * ms)]
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", [("jit_f", 0, 100 * ms)]),
                               ("XLA Ops", ops)])]


def test_busy_is_the_union_of_ops_inside_the_window():
    r = pr.reduce_planes(_planes())
    # [5, 30] + [75, 85] + [95, 100] ms: overlap merged, tail clipped
    assert r.busy_s == pytest.approx(0.040)
    assert r.window_s == pytest.approx(0.100)
    assert r.n_devices == 1
    assert r.device_ops[0] == ["fusion", pytest.approx(0.030)]
    assert [n for n, _ in r.device_ops] == ["fusion", "copy", "late"]


def test_idle_gaps_are_named_by_the_host_annotation():
    r = pr.reduce_planes(_planes())
    # gaps [30, 75] (mid 52.5: pacing), [0, 5], [85, 95] (calls)
    assert r.idle_gaps[0] == ["bench.pace", pytest.approx(0.045)]
    assert sorted(g for _, g in r.idle_gaps) == pytest.approx(
        [0.005, 0.010, 0.045])
    assert {n for n, _ in r.idle_gaps} == {"bench.pace", "bench.call"}


def test_no_window_or_no_device_reads_nothing():
    planes = _planes()
    assert pr.reduce_planes([planes[1]]) is None
    assert pr.reduce_planes([planes[0]]) is None


def test_recorded_v5e_trace():
    r = pr.reduce_planes(pr.load_planes(DATA))
    assert r is not None and r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.device_ops and r.idle_gaps
    names = {n for n, _ in r.idle_gaps}
    assert names <= {"bench.call", "bench.pace",
                     "outside any bench annotation"}
