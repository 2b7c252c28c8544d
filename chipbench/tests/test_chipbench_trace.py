"""The trace reduction: interval arithmetic, and a small recorded trace."""
from pathlib import Path

import pytest

from chipbench import trace

HERE = Path(__file__).resolve().parent
RECORDED = HERE.parent / "testdata" / "admission_v5e.xplane.pb"


def test_union_merges_overlaps_and_clips_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45), (100, 200)]
    assert trace.union_s(iv, 0, 50) == pytest.approx((20 + 15) / 1e9)
    assert trace.union_s(iv, 10, 35) == pytest.approx((10 + 5) / 1e9)
    assert trace.union_s([], 0, 50) == 0.0


def test_gaps_are_the_complement_inside_the_window():
    iv = [(10, 20), (15, 30), (40, 50)]
    assert trace.idle_gaps(iv, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert trace.idle_gaps(iv, 12, 45) == [(30, 40)]
    assert trace.idle_gaps([], 0, 5) == [(0, 5)]


def test_gaps_take_the_host_span_that_overlaps_them_most():
    spans = [("tick", 0, 25), ("idle", 25, 60)]
    got = trace.label_gaps([(0, 10), (20, 40), (70, 80)], spans)
    assert got == [("tick", 1e-8), ("idle", 2e-8), ("none", 1e-8)]


def test_self_times_take_nested_ops_out_of_their_parents():
    ev = [("while", 0, 100), ("fusion", 10, 30), ("dot", 40, 90),
          ("fusion", 50, 60), ("copy", 120, 130)]
    got = trace.self_times(ev)
    assert got == pytest.approx({"while": 30e-9, "fusion": 30e-9,
                                 "dot": 40e-9, "copy": 10e-9})


def test_recorded_trace_reduces_to_consistent_numbers():
    red = trace.reduce_trace(str(RECORDED), ("submit", "tick"))
    assert red.n_devices == 1
    assert 0.0 < red.busy_s < red.window_s
    assert any(k.startswith("jit_dfts_scan") for k in red.programs)
    assert sum(red.ops.values()) == pytest.approx(red.busy_s, rel=1e-3)
    idle = sum(s for _, s in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)
