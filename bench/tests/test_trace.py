"""The trace reduction, on plain events and on a trace recorded on a v5e."""
import glob
import os

import pytest

from bench import readout, trace
from bench.tests import record_trace

MS = 1_000_000  # nanoseconds


def test_reduction_by_hand():
    # Window 0-100 ms.  Device ops at 10-30 and 25-50 (overlapping: busy
    # 40 ms) and 90-120 (10 ms inside).  Host: a segment span over 0-60 and
    # a harvest span over 60-95.
    dev = [{trace.OPS_LINE: [("fusion", 10 * MS, 20 * MS),
                             ("_lln_decode_kernel", 25 * MS, 25 * MS),
                             ("fusion", 90 * MS, 30 * MS)],
            trace.MODULES_LINE: [("jit__segment", 10 * MS, 110 * MS)]}]
    host = [("bench.window", 0, 100 * MS), ("bench.segment", 0, 60 * MS),
            ("bench.harvest", 60 * MS, 35 * MS), ("python", 0, 5)]
    s = trace.reduce_events(dev, host)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.05)
    assert s["ops"]["fusion"]["count"] == 2
    assert s["ops"]["fusion"]["seconds"] == pytest.approx(0.03)
    assert s["programs"]["jit__segment"]["seconds"] == pytest.approx(0.09)
    # Gaps: 0-10 (segment open), 50-90 (harvest open at its middle, 70).
    assert s["idle"]["bench.segment"] == pytest.approx(0.01)
    assert s["idle"]["bench.harvest"] == pytest.approx(0.04)
    assert s["breakdown"]["idle_gaps"][0] == ["bench.harvest",
                                              pytest.approx(0.04)]
    assert s["breakdown"]["device_ops"][0][0] == "fusion"


def test_two_devices_average():
    one = {trace.OPS_LINE: [("a", 0, 50 * MS)]}
    two = {trace.OPS_LINE: [("a", 0, 100 * MS)]}
    s = trace.reduce_events([one, two], [("bench.window", 0, 100 * MS)])
    assert s["busy_s"] == pytest.approx(0.075)
    assert s["ops"]["a"]["seconds"] == pytest.approx(0.075)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events([], [("bench.segment", 0, 1)])


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.json.gz")))


def test_a_recorded_trace_is_kept():
    assert RECORDED


@pytest.mark.parametrize("path", RECORDED)
def test_recorded_v5e_trace(path):
    # The 2-layer, 4-slot pool of record_trace.py: the decode kernel runs
    # over 4 slots x 32 heads, T padded to 16, head size 64.
    s = trace.reduce_events(*record_trace.load_extract(path))
    assert 0 < s["busy_s"] <= s["window_s"]
    assert any("_segment" in name for name in s["programs"])
    assert sum(s["idle"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
    rows, state = 128, [(128, 64, 64), (128, 1, 64)]
    calls = [(n, sec) for ins, outs, n, sec in
             readout.kernel_calls({"trace": s})
             if ins == [(rows, 16, 64)] * 3 + state
             and outs == [(rows, 16, 64)] + state]
    assert len(calls) == 1 and calls[0][0] > 0 and calls[0][1] > 0
